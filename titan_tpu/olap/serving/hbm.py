"""Device-memory (HBM) accounting for graph images — admission's ledger.

The serving scheduler admits jobs against it before building a
snapshot's chunked CSR on device. The byte model matches what the
kernels actually upload: the transposed 8-aligned ``dstT`` [8, q_total]
int32 plus three [n+1] int32 side arrays (colstart/degc/deg) —
models/bfs_hybrid.build_chunked_csr's exact footprint. Eviction is
largest-first over unpinned entries; pinned entries (graphs under a
running batch) are never evicted.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

#: default budget: 12 GB of a 16 GB v5e HBM (leaving headroom for
#: kernel state/temporaries)
DEFAULT_BUDGET_BYTES = 12.0e9


def chunked_csr_bytes(n: int, q_total: int) -> int:
    """Device bytes of a chunked CSR: dstT [8, q_total] int32 + 3 x
    [n+1] int32 (colstart/degc/deg)."""
    return q_total * 8 * 4 + 3 * 4 * (n + 1)


def snapshot_csr_bytes(snap) -> int:
    """Predicted device bytes for a GraphSnapshot's chunked CSR,
    computable BEFORE the build (admission must not pay the upload to
    learn it doesn't fit): q_total = sum(ceil(deg/8)) + 1 pad column."""
    deg = snap.out_degree
    q_total = int((-(-deg.astype("int64") // 8)).sum()) + 1
    return chunked_csr_bytes(snap.n, q_total)


def snapshot_pull_bytes(snap) -> int:
    """Predicted device bytes of a snapshot's PageRank pull image
    (models/pagerank_pull.pull_image: the in-edge ``srcT``, a flag a
    column, a few words a vertex), sized
    from the in-degrees BEFORE the build. A ``pagerank`` job reserves it
    beside the forward image."""
    from titan_tpu.models.pagerank_pull import (pull_columns,
                                                pull_image_bytes)
    return pull_image_bytes(snap.n, pull_columns(snap.indptr_in, snap.n))


def snapshot_cdlp_bytes(snap) -> int:
    """Predicted device bytes a ``cdlp`` job's rounds work on beside the
    pull image (models/cdlp.work_bytes: the gathered labels, the sort's
    operands and the vote's temporaries, each as wide as the image's
    lanes), sized from the in-degrees like the image itself."""
    from titan_tpu.models.cdlp import work_bytes
    from titan_tpu.models.pagerank_pull import pull_columns
    return work_bytes(snap.n, pull_columns(snap.indptr_in, snap.n))


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

