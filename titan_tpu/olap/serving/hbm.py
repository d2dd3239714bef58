"""Device-memory (HBM) accounting for graph images — admission's ledger.

The serving scheduler admits jobs against it before building a
snapshot's images on device. The ledger holds, each under its own key,
the images a job kind's row lists (serving/kinds.py: the forward
chunked CSR under ``id(snap)``, every other image and a run's working
set under ``(name, id(snap))``) and the interactive lane's reversed
layout for ``out()`` (``("interactive-rev", id(snap))``); the byte
models are the functions below. The forward image's: the transposed
8-aligned ``dstT`` [8, q_total] int32 plus three [n+1] int32 side
arrays (colstart/degc/deg) — models/bfs_hybrid.build_chunked_csr's
exact footprint. Eviction is largest-first over unpinned entries;
pinned entries (graphs under a running batch) are never evicted.

Every size derives from ``n`` and three counts: two of columns,
sum(ceil(deg/8)) + 1 over the out-degrees (``"out"``) and over the
in-degrees (``"in"``), and the lanes of CDLP's row image (``"cdlp"``:
its rows hold whole vertices, so how full they pack follows from the
in-degrees one by one and from no sum over them:
models/cdlp.row_plan). Each is one pass over a degree array, paid once
a snapshot: ``_columns`` keeps it ON the snapshot (``_q_out``,
``_q_in``, ``_cdlp_lanes``), and
``GraphSnapshot._invalidate_layout_caches`` drops it
with the layouts it sizes, so a refreshed or mutated snapshot is
re-priced before its next reservation. ``price`` tells an admission how
many passes it paid (0 on a priced snapshot).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import numpy as np

#: default budget: 12 GB of a 16 GB v5e HBM (leaving headroom for
#: kernel state/temporaries)
DEFAULT_BUDGET_BYTES = 12.0e9

#: counter of passes over a degree array that pricing ran, by
#: ``{image="out"|"in"|"cdlp"}``
SIZING_PASSES = "serving.hbm.sizing_passes"

#: image -> (the attribute its count is kept under, the built layout
#: that carries the same count, and under what name)
_KEPT = {"out": ("_q_out", "_hybrid_csr", "q_total"),
         "in": ("_q_in", "_hybrid_csr_rev", "q_total"),
         "cdlp": ("_cdlp_lanes", "_cdlp_csr", "lanes")}


def _columns(snap, image: str, metrics=None) -> tuple:
    """``(count, passes paid)`` of a snapshot's forward (``"out"``) or
    reversed (``"in"``) chunked layout, in columns (sum(ceil(deg/8)) +
    1 pad column), or of its CDLP row image (``"cdlp"``), in lanes
    (models/cdlp.image_lanes: the packing planned, nothing built),
    computable BEFORE any build (admission must not pay the upload to
    learn it doesn't fit). The one place that reads a degree
    array: once a snapshot, then the kept integer; a layout already
    built is asked for its own count instead. A pass is counted on
    ``metrics`` (the process-wide registry without one). Two threads
    that price one snapshot at once may each run the pass: both count
    it, and both keep the same integer."""
    attr, built, name = _KEPT[image]
    q = getattr(snap, attr, None)
    if q is not None:
        return q, 0
    layout = getattr(snap, built, None)
    if layout is not None:
        q, paid = int(layout[name]), 0
    else:
        if image == "cdlp":
            from titan_tpu.models.cdlp import image_lanes
            q = image_lanes(snap)
        else:
            deg = snap.out_degree if image == "out" \
                else np.diff(snap.indptr_in[:snap.n + 1])
            q = int((-(-deg.astype(np.int64) // 8)).sum()) + 1
        paid = 1
        if metrics is None:
            from titan_tpu.utils.metrics import MetricManager
            metrics = MetricManager.instance()
        metrics.counter(SIZING_PASSES, labels={"image": image}).inc()
    setattr(snap, attr, q)
    return q, paid


def price(snap, images, metrics=None) -> int:
    """Make sure the counts of ``images`` (``"out"``, ``"in"``,
    ``"cdlp"``) are kept on ``snap``, so that the byte functions below
    read integers: returns the passes over a degree array this admission
    paid for it, 0 on a priced snapshot."""
    return sum(_columns(snap, image, metrics)[1] for image in images)


def _pull_columns(snap) -> int:
    """Columns of the pull image (models/pagerank_pull.pull_columns):
    the reversed layout's, rounded up to a whole block."""
    from titan_tpu.ops.vmem_gather import padded_columns
    return padded_columns(_columns(snap, "in")[0])


def chunked_csr_bytes(n: int, q_total: int) -> int:
    """Device bytes of a chunked CSR: dstT [8, q_total] int32 + 3 x
    [n+1] int32 (colstart/degc/deg)."""
    return q_total * 8 * 4 + 3 * 4 * (n + 1)


def snapshot_csr_bytes(snap) -> int:
    """Predicted device bytes for a GraphSnapshot's chunked CSR, from
    its kept ``"out"`` column count."""
    return chunked_csr_bytes(snap.n, _columns(snap, "out")[0])


def snapshot_rev_csr_bytes(snap) -> int:
    """Predicted device bytes of the REVERSED chunked CSR (the
    interactive lane's ``out()`` orientation,
    interactive/compile.reversed_chunked_csr), from the kept ``"in"``
    column count."""
    return chunked_csr_bytes(snap.n, _columns(snap, "in")[0])


def snapshot_pull_bytes(snap) -> int:
    """Predicted device bytes of a snapshot's PageRank pull image
    (models/pagerank_pull.pull_image: the in-edge ``srcT``, a flag a
    column, a few words a vertex), sized
    from the in-degrees BEFORE the build. A ``pagerank`` job reserves it
    beside the forward image."""
    from titan_tpu.models.pagerank_pull import pull_image_bytes
    return pull_image_bytes(snap.n, _pull_columns(snap))


def snapshot_cdlp_image_bytes(snap) -> int:
    """Predicted device bytes of a snapshot's CDLP row image
    (models/cdlp.cdlp_image: a neighbour id and the key's owner part a
    lane, a few bytes a vertex), from the kept ``"cdlp"`` lane count,
    BEFORE the build. A ``cdlp`` job reserves it beside the forward
    image; it reads no pull image."""
    from titan_tpu.models.cdlp import image_bytes
    return image_bytes(snap.n, _columns(snap, "cdlp")[0])


def snapshot_cdlp_bytes(snap) -> int:
    """Predicted device bytes a ``cdlp`` job's rounds work on beside its
    image (models/cdlp.work_bytes: the gathered labels, the sort's
    operand and what it is split into, the vote's temporaries, each as
    wide as the image's lanes), from the same lane count."""
    from titan_tpu.models.cdlp import work_bytes
    return work_bytes(snap.n, _columns(snap, "cdlp")[0])


def snapshot_lcc_bytes(snap) -> int:
    """Predicted device bytes of what an ``lcc`` job keeps resident
    beside the forward image (models/lcc.image_bytes: the hub bit table
    and what is built with it; the job reads no pull image, whose
    columns only bound its edges), from ``n``, the kept ``"in"`` column
    count and the module's hub count: no pass over a degree array."""
    from titan_tpu.models import lcc
    return lcc.image_bytes(snap.n, _pull_columns(snap), lcc.HUBS)


def snapshot_lcc_work_bytes(snap) -> int:
    """Predicted device bytes an ``lcc`` job's tiles and finish work on
    (models/lcc.work_bytes), from the same integers."""
    from titan_tpu.models import lcc
    return lcc.work_bytes(snap.n, _pull_columns(snap), lcc.HUBS)


def snapshot_bc_work_bytes(snap) -> int:
    """Predicted device bytes a ``bc`` job's levels work on beside the
    forward and pull images (models/bc.work_bytes: a level of the
    widest group of roots that shares a pull at this ``n``, a few
    n-vectors and the column-wide temporaries a root of it, and a delta
    kept a root, priced at the most roots a job may name), from ``n``
    and the kept ``"in"`` column count."""
    from titan_tpu.models import bc
    return bc.work_bytes(snap.n, _pull_columns(snap))


def bfs_plane_bytes(n: int, k: int, num_devices: int = 1) -> int:
    """Device bytes of ONE ``[K, n+1]`` int32 plane of the batched BFS's
    state (models/bfs_hybrid: ``dist``; ``par``, the BFS tree, for a
    group that asked for parents), a device's share where the cohort is
    mesh-placed (the vertex axis shards over the mesh)."""
    return -(-4 * int(k) * (int(n) + 1) // max(int(num_devices), 1))


def meshed_snapshot_csr_bytes(snap, num_devices: int) -> int:
    """PER-DEVICE bytes of a MESH-PLACED chunked CSR (ISSUE 13,
    ``parallel/partition.place_batched_csr``): the ``dstT`` edge image
    shards its chunk columns over the mesh — each device holds ~1/D of
    it — while the per-vertex side arrays replicate. The ledger models
    ONE device's HBM, so a mesh-placed cohort charges this, not the
    whole image; that reduction is the memory half of why batching and
    sharding compose."""
    total = snapshot_csr_bytes(snap)
    n = getattr(snap, "n", 0)
    vert = 3 * 4 * (n + 1)                    # colstart/degc/deg
    edges = max(total - vert, 0)
    return int(vert + -(-edges // max(int(num_devices), 1)))


class AdmissionError(RuntimeError):
    """The job's graph image cannot fit the HBM budget even after
    evicting every unpinned resident graph."""


class HBMLedger:
    """Budgeted accounting of device-resident graph images.

    ``reserve(key, nbytes)`` charges an entry, evicting largest-first
    among unpinned entries until it fits (``on_evict(key)`` lets the
    owner drop the device arrays — actual frees happen when the last
    jax reference dies). Raises AdmissionError when even a full sweep
    cannot make room. Entries are pinned while reserved; ``unpin``
    leaves them resident-but-evictable (the warm-cache state),
    ``release`` drops them entirely."""

    def __init__(self, budget_bytes: float = DEFAULT_BUDGET_BYTES,
                 on_evict: Optional[Callable[[object], None]] = None):
        self.budget_bytes = float(budget_bytes)
        self._on_evict = on_evict
        self._bytes: dict = {}
        self._pins: dict = {}
        self._lock = threading.Lock()

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(self._bytes.values())

    def pinned_bytes(self) -> int:
        """Bytes held by PINNED entries (graphs under a running batch)
        — the unevictable share of ``resident_bytes``; exported as the
        ``serving.hbm.pinned_bytes`` gauge."""
        with self._lock:
            return sum(b for k, b in self._bytes.items()
                       if self._pins.get(k, 0) > 0)

    def reserve(self, key, nbytes: int) -> None:
        evicted = []
        with self._lock:
            if key in self._bytes:
                self._pins[key] = self._pins.get(key, 0) + 1
                return
            pinned = sum(self._bytes[k] for k, c in self._pins.items()
                         if c > 0)
            if pinned + nbytes > self.budget_bytes:
                raise AdmissionError(
                    f"admission: graph image needs {nbytes/1e9:.2f}GB "
                    f"but only {max(self.budget_bytes - pinned, 0)/1e9:.2f}"
                    f"GB of the {self.budget_bytes/1e9:.2f}GB HBM budget "
                    "is free of pinned (in-use) graphs")
            # evict largest unpinned until the new entry fits
            while sum(self._bytes.values()) + nbytes > self.budget_bytes:
                victims = {k: b for k, b in self._bytes.items()
                           if self._pins.get(k, 0) == 0}
                if not victims:
                    raise AdmissionError(
                        "admission: HBM budget exhausted by pinned "
                        "graphs")
                victim = max(victims, key=victims.get)
                self._bytes.pop(victim)
                self._pins.pop(victim, None)
                evicted.append(victim)
            self._bytes[key] = int(nbytes)
            self._pins[key] = 1
        for k in evicted:
            if self._on_evict is not None:
                self._on_evict(k)

    def unpin(self, key) -> None:
        with self._lock:
            if key in self._pins and self._pins[key] > 0:
                self._pins[key] -= 1

    def release(self, key) -> None:
        with self._lock:
            self._bytes.pop(key, None)
            self._pins.pop(key, None)

