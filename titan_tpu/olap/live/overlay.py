"""DeltaOverlay: device-resident COO add-buffer + base-edge tombstones.

The freshness half of the live plane's cost model: applying a delta to
the HOST snapshot (``GraphSnapshot.apply_changes``) invalidates every
device-layout cache and forces the next run to re-upload the full
chunked CSR (11.6 GB for a Twitter-2010-size graph) host→device.  The
overlay instead keeps the base CSR device arrays UNTOUCHED and layers
the delta next to them:

* **adds** — a padded COO buffer ``(src, dst)`` of dense indices (pad =
  ``n+1``, the kernels' scatter-drop sentinel), sized in power-of-two
  capacity buckets so appends never change the compiled kernel shapes
  (no recompile on append — the same discipline as the frontier list
  caps);
* **tombstones** — a bitmap over base edge SLOTS in the chunked-CSR
  layout (slot = column*8 + lane, exactly the id ``frontier.py`` hashes
  for SSSP weights): masked slots stop counting as parents/targets in
  the overlay-aware kernels. The bitmap is updated by scattering only
  the touched bytes, so a removal costs O(changed bytes) H2D, not a
  re-upload.

Delta-page uploads (ISSUE 9): ``view()`` ships only the CHANGED device
bytes — the appended row range (plus any in-place-killed rows) is
scattered into the resident add buffers, and only the dirtied tombstone
bytes hit the bitmap. Buffer establishment and capacity growth are
device-side pad fills (``jnp.full`` / pad-extension), so they cost no
H2D at all. Every byte that does go host→device — scatter payloads
AND the int32 index words the scatters ship — is counted on
``serving.live.upload_bytes`` when a ``metrics`` manager is attached,
so the H2D cost of freshness is directly observable
(docs/monitoring.md, the ``live_refresh`` bench stage).

Views are immutable: :meth:`view` freezes the current device arrays +
counters into an :class:`OverlayView`; a running job keeps reading its
leased view while the plane appends to fresh arrays (jax arrays are
immutable, so the old view stays consistent — the "(snapshot, overlay)
pair at a consistent epoch" lease contract).

HBM accounting: the overlay's device bytes (2·4·cap + q_total tomb
bytes) are reserved through the serving ``HBMLedger`` when one is
attached, so admission sees the delta as resident state, not free
lunch.

Thread safety: the overlay is owned and locked by the LiveGraphPlane;
methods here assume external synchronization.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

#: smallest add-buffer capacity bucket (power of two)
MIN_CAP = 1024


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 1).bit_length()


class OverlayView:
    """Immutable device-side view of the overlay at one delta seq."""

    __slots__ = ("n", "cap", "count", "src_dev", "dst_dev", "tomb_dev",
                 "tomb_count", "seq", "slot_base")

    def __init__(self, n, cap, count, src_dev, dst_dev, tomb_dev,
                 tomb_count, seq, slot_base):
        self.n = n
        self.cap = cap
        self.count = count
        self.src_dev = src_dev
        self.dst_dev = dst_dev
        self.tomb_dev = tomb_dev
        self.tomb_count = tomb_count
        self.seq = seq
        self.slot_base = slot_base

    @property
    def empty(self) -> bool:
        return self.count == 0 and self.tomb_count == 0

    @property
    def has_tombstones(self) -> bool:
        return self.tomb_count > 0


class DeltaOverlay:
    """See module doc. Built against ONE base snapshot epoch; the
    compactor folds it into the base and starts a fresh overlay."""

    def __init__(self, snapshot, *, min_cap: int = MIN_CAP,
                 ledger=None, ledger_key=None, metrics=None):
        self.snap = snapshot
        self.n = int(snapshot.n)
        deg = snapshot.out_degree.astype(np.int64)
        degc = -(-deg // 8)
        colstart = np.zeros(self.n + 1, np.int64)
        np.cumsum(degc, out=colstart[1:])
        # q_total matches models/bfs_hybrid.build_chunked_csr exactly —
        # slot ids must agree with the device layout (+1 pad column)
        self.q_total = int(colstart[-1]) + 1
        self._colstart = colstart
        self._deg = deg
        # out-CSR host view for slot lookup on removals
        self._dst_by_src, self._indptr_out = snapshot.out_csr()
        self._labels_by_src: Optional[np.ndarray] = None
        # add buffer (host mirror; device arrays built lazily per view)
        self.cap = int(min_cap)
        self._min_cap = int(min_cap)
        self._h_src = np.full(self.cap, self.n + 1, np.int32)
        self._h_dst = np.full(self.cap, self.n + 1, np.int32)
        self._h_lab = np.zeros(self.cap, np.int32)
        self.count = 0
        self.dead_adds = 0             # appended rows later tombstoned
        # tombstone state: slot bitmap (device mirror) + per-base-ROW
        # mask (host only — the compactor filters snapshot rows with it)
        self._h_tomb = np.zeros(self.q_total, np.uint8)
        self.tomb_row_mask = np.zeros(snapshot.num_edges, bool)
        self.tomb_count = 0
        self.seq = 0                   # bumps on every mutation
        # device state: rows [0, _clean_rows) of the add buffers are
        # already device-resident and accurate; rows the writer killed
        # IN PLACE below that watermark collect in _dirty_add_rows.
        # view() scatters only (watermark tail + dirty rows) — the
        # delta pages; buffer establishment and capacity growth are
        # device-side pad fills (jnp.full / concatenate), so they cost
        # ZERO H2D — only changed rows/bytes ever go host→device.
        self._d_src = None
        self._d_dst = None
        self._d_tomb = None
        self._clean_rows = 0
        self._dirty_add_rows: set = set()
        self._dirty_tomb_bytes: set = set()
        self._metrics = metrics
        self._ledger = ledger
        self._ledger_key = ledger_key if ledger_key is not None \
            else ("live-overlay", id(self))
        self._reserved = 0
        self._reserve()

    # -- HBM accounting ------------------------------------------------------

    def device_bytes(self) -> int:
        return 2 * 4 * self.cap + self.q_total

    def _reserve(self) -> None:
        if self._ledger is None:
            return
        need = self.device_bytes()
        if need == self._reserved:
            return
        self._ledger.release(self._ledger_key)
        self._ledger.reserve(self._ledger_key, need)  # stays pinned
        self._reserved = need

    def close(self) -> None:
        if self._ledger is not None:
            self._ledger.release(self._ledger_key)
            self._reserved = 0

    # -- mutation ------------------------------------------------------------

    def _grow(self, need: int) -> None:
        new_cap = _next_pow2(max(need, self._min_cap))
        if new_cap <= self.cap:
            return
        for name in ("_h_src", "_h_dst", "_h_lab"):
            old = getattr(self, name)
            fill = self.n + 1 if name != "_h_lab" else 0
            fresh = np.full(new_cap, fill, np.int32)
            fresh[:self.count] = old[:self.count]
            setattr(self, name, fresh)
        self.cap = new_cap    # device buffers pad-extend at next view()
        self._reserve()       # raises AdmissionError when HBM is tight
                              # — the plane responds by compacting

    def append_edges(self, src_dense, dst_dense, labs) -> int:
        """Append dense-index edge rows (caller symmetrizes for
        undirected snapshots). Returns rows appended."""
        src_dense = np.asarray(src_dense, np.int32)
        dst_dense = np.asarray(dst_dense, np.int32)
        labs = np.asarray(labs, np.int32)
        k = len(src_dense)
        if k == 0:
            return 0
        if self.count + k > self.cap:
            self._grow(self.count + k)
        sl = slice(self.count, self.count + k)
        self._h_src[sl] = src_dense
        self._h_dst[sl] = dst_dense
        self._h_lab[sl] = labs
        self.count += k          # the [_clean_rows, count) tail is the
        self.seq += 1            # delta page view() scatters — no flag
        return k

    def _labels_src_order(self) -> Optional[np.ndarray]:
        if self.snap.labels is None:
            return None
        if self._labels_by_src is None:
            self._labels_by_src = self.snap.labels[self._base_order()]
        return self._labels_by_src

    def _base_order(self) -> np.ndarray:
        """src-order permutation of the base rows (slot → dst-order
        row). The snapshot caches it beside its out-CSR — ``__init__``
        already forced that build — and ``merge_delta`` carries both
        across epoch merges incrementally, so this is a read, not an
        O(E log E) argsort re-paid per epoch (ROADMAP #5 residual)."""
        if getattr(self, "_order", None) is None:
            order = getattr(self.snap, "_out_csr_order", None)
            if order is None:
                self.snap.out_csr()
                order = getattr(self.snap, "_out_csr_order", None)
            self._order = order if order is not None \
                else np.argsort(self.snap.src, kind="stable")
        return self._order

    def remove_edge(self, u: int, v: int, lab: Optional[int]) -> bool:
        """Tombstone ONE live row (u→v[, label]) — first a base-CSR
        slot, else a live overlay add. Returns False when no live row
        matches (caller may ignore: a rebuild would not see the edge
        either)."""
        labs_src = self._labels_src_order()
        p0 = int(self._indptr_out[u])
        p1 = p0 + int(self._deg[u])
        for p in range(p0, p1):
            if int(self._dst_by_src[p]) != v:
                continue
            if lab is not None and labs_src is not None \
                    and int(labs_src[p]) != lab:
                continue
            slot = int(self._colstart[u]) * 8 + (p - p0)
            byte, bit = slot >> 3, slot & 7
            if self._h_tomb[byte] & (1 << bit):
                continue               # this row is already dead
            self._h_tomb[byte] |= (1 << bit)
            self._dirty_tomb_bytes.add(byte)
            self.tomb_row_mask[self._base_order()[p]] = True
            self.tomb_count += 1
            self.seq += 1
            return True
        # not in the base: kill a live overlay add
        for i in range(self.count):
            if int(self._h_src[i]) == u and int(self._h_dst[i]) == v \
                    and (lab is None or int(self._h_lab[i]) == lab):
                self._h_src[i] = self.n + 1
                self._h_dst[i] = self.n + 1
                self.dead_adds += 1
                if i < self._clean_rows:
                    self._dirty_add_rows.add(i)
                self.seq += 1
                return True
        return False

    # -- observation ---------------------------------------------------------

    def fill_fraction(self) -> float:
        return self.count / max(self.cap, 1)

    def tombstone_fraction(self) -> float:
        return self.tomb_count / max(self.snap.num_edges, 1)

    def live_adds(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(src, dst, lab) dense host arrays of the LIVE appended rows
        (killed rows excluded) — the compactor's merge input."""
        s = self._h_src[:self.count]
        alive = s <= self.n
        return (s[alive].copy(), self._h_dst[:self.count][alive].copy(),
                self._h_lab[:self.count][alive].copy())

    def stats(self) -> dict:
        return {"capacity": self.cap, "adds": self.count,
                "dead_adds": self.dead_adds,
                "tombstones": self.tomb_count,
                "fill": round(self.fill_fraction(), 4),
                "tombstone_fraction":
                    round(self.tombstone_fraction(), 6),
                "device_bytes": self.device_bytes(), "seq": self.seq}

    # -- device sync / views -------------------------------------------------

    def _count_upload(self, nbytes: int) -> None:
        if self._metrics is not None and nbytes:
            self._metrics.counter("serving.live.upload_bytes") \
                .inc(int(nbytes))
        # device-cost mirror (obs/devprof, ISSUE 10): the same delta
        # pages on the process-wide device.xfer.h2d_bytes family, so
        # the profiler's transfer story includes live-plane traffic
        from titan_tpu.obs import devprof
        devprof.count_h2d("overlay.delta", int(nbytes))

    def view(self) -> OverlayView:
        """Freeze the current state into an immutable device view.
        ONLY delta pages go host→device: the appended tail (plus any
        in-place-killed rows) scatters into the resident add buffers,
        and only dirtied bytes hit the tombstone bitmap. Buffer
        establishment and capacity growth are device-side pad fills —
        never an upload. Every byte that does ship counts on
        ``serving.live.upload_bytes``."""
        import jax.numpy as jnp

        pad = jnp.int32(self.n + 1)
        if self._d_src is None:
            # device-side constant fill: 0 bytes H2D; the scatter
            # below ships rows [0, count) — the actual delta
            self._d_src = jnp.full((self.cap,), pad, jnp.int32)
            self._d_dst = jnp.full((self.cap,), pad, jnp.int32)
            self._clean_rows = 0
        elif self._d_src.shape[0] != self.cap:
            # capacity bucket grew: pad-extend ON DEVICE (device-to-
            # device copy, 0 bytes H2D); resident rows stay valid —
            # in-place kills are tracked in _dirty_add_rows
            ext = jnp.full((self.cap - self._d_src.shape[0],), pad,
                           jnp.int32)
            self._d_src = jnp.concatenate([self._d_src, ext])
            self._d_dst = jnp.concatenate([self._d_dst, ext])
        if self._dirty_add_rows or self._clean_rows < self.count:
            rows = sorted(self._dirty_add_rows)
            rows.extend(range(self._clean_rows, self.count))
            idx = jnp.asarray(np.asarray(rows, np.int32))
            # .at[].set returns NEW arrays — frozen views keep theirs
            self._d_src = self._d_src.at[idx].set(
                jnp.asarray(self._h_src[rows]))
            self._d_dst = self._d_dst.at[idx].set(
                jnp.asarray(self._h_dst[rows]))
            self._clean_rows = self.count
            self._dirty_add_rows.clear()
            # 2 int32 payloads + the int32 scatter-index array (shipped
            # once, reused by both scatters) — index words are H2D too
            self._count_upload((2 * 4 + 4) * len(rows))
        if self._d_tomb is None:
            # all-zero bitmap: device-side fill, 0 bytes H2D (every
            # set byte since construction is in _dirty_tomb_bytes)
            self._d_tomb = jnp.zeros((self.q_total,), jnp.uint8)
        if self._dirty_tomb_bytes:
            idx = np.fromiter(self._dirty_tomb_bytes, np.int64,
                              len(self._dirty_tomb_bytes))
            self._d_tomb = self._d_tomb.at[
                jnp.asarray(idx.astype(np.int32))].set(
                jnp.asarray(self._h_tomb[idx]))
            self._dirty_tomb_bytes.clear()
            # 1 payload byte + 4 index bytes per dirtied bitmap byte
            self._count_upload(5 * len(idx))
        return OverlayView(self.n, self.cap, self.count, self._d_src,
                           self._d_dst, self._d_tomb, self.tomb_count,
                           self.seq, slot_base=self.q_total * 8)
