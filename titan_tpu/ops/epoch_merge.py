"""Device-side epoch merge: overlay + base chunked CSR → next-epoch CSR.

The live plane's epoch boundary (olap/live/compactor.py) used to be the
old Titan-style full rebuild in disguise: merge the overlay into the
base on the HOST (``np.concatenate`` + a full dst-stable sort) and
re-upload the merged chunked CSR whole — ~11.6 GB of H2D per epoch for
a Twitter-2010-size graph, which caps sustainable write throughput at whatever
the host→device link will carry. But every input of the merge is ALREADY resident
in HBM: the base ``dstT`` (models/bfs_hybrid.build_chunked_csr), the
overlay's COO add-buffer and the tombstone bitmap (olap/live/overlay).
This module computes the next epoch's chunked CSR from them entirely on
device, so the per-epoch H2D cost is the overlay delta (already paid
incrementally by ``OverlayView``), not the graph.

Shape of the problem: within one source vertex ``u`` the merged segment
is a two-way merge of two dst-sorted runs — the surviving base slots
(base order is dst-ascending within ``u``; tombstone removal preserves
it) and ``u``'s overlay adds (sorted by (dst, append order)), with base
rows winning dst ties. That is exactly what the host oracle
(``EpochCompactor.merge`` + ``from_arrays`` + ``build_chunked_csr``)
produces via one global stable sort; here it falls out of three
p-scale passes with NO sort over the base:

1. **survivor compaction** — one ``alive`` mask (non-pad, non-tombstone)
   cumsum feeds ``ops.compaction.scatter_compact``: the kept base
   values land in a dense ``[E_base]`` list that is, by construction,
   globally ordered by (vertex, dst);
2. **add placement** — each live add's slot in the NEW layout is
   ``colstart'[u]*8 + rank_among_u's_adds + #kept(u, dst<=d)``; the
   kept-count is a 32-step vectorized binary search over ``u``'s OLD
   padded segment (dst-ascending with trailing ``n+1`` pads, so no
   segment extraction is needed) composed with the alive prefix sum —
   cap-scale work, the only per-edge "random" access of the pass;
3. **complement fill** — adds scatter into the new flat array, and the
   kept survivors fill the remaining valid (non-pad) slots of each
   segment IN ORDER: one free-slot cumsum gives every merged slot its
   kept-rank, one gather pulls the survivor value. No branch, no sort,
   no dependence on where the writes landed.

Everything is ``jnp`` traceable and int32-safe without x64 (slot ids
stay below 2**31 — callers must check :func:`fits_int32` and fall back
to the host merge otherwise, the same discipline as
``build_chunked_csr``'s column guard). n-wide ``jnp.nonzero`` is banned
here as in every round-loop module (tests/test_compaction.py op-scan).

Bit-equality contract (pinned by tests/test_live_compact_device.py):
:func:`merge_chunked_csr` output == ``build_chunked_csr`` of the host
oracle's merged snapshot, array for array, across adds-only /
tombstones-only / mixed / labeled shapes.
"""

from __future__ import annotations

import numpy as np

from titan_tpu.ops.compaction import scatter_compact

#: binary-search depth: covers any segment below 2**31 slots (the
#: int32 guard bounds every slot id under that anyway)
_BSEARCH_ITERS = 32


def fits_int32(q_total: int) -> bool:
    """True when a chunked CSR of ``q_total`` columns is addressable
    with int32 slot ids (slot = column*8 + lane)."""
    return q_total * 8 < (1 << 31)


class LazyHostMirror:
    """``_host`` mirrors of a DEVICE-merged chunked CSR, built on first
    access instead of downloaded.

    ``build_chunked_csr`` keeps host copies of dstT/colstart/degc for
    shard slicing (parallel/multihost, bfs_hybrid_sharded) because a
    D2H readback of the image is a multi-gigabyte transfer. A device-merged
    epoch has no host dstT yet — and downloading it would pay exactly
    the per-epoch transfer the device merge exists to kill. The side
    arrays are free (the merge's host bookkeeping already produced
    them); the flat dstT is recomputed from the merged snapshot's
    out-CSR on FIRST ``["dstT"]`` access only, so single-device serving
    (which never slices on host) pays nothing.
    """

    def __init__(self, snapshot, colstart: np.ndarray,
                 degc: np.ndarray):
        self._snap = snapshot
        self._built = {"colstart": colstart, "degc": degc}

    def __getitem__(self, key: str):
        if key == "dstT" and "dstT" not in self._built:
            self._built["dstT"] = self._build_dstT()
        return self._built[key]

    def _build_dstT(self) -> np.ndarray:
        # same layout math as models/bfs_hybrid.build_chunked_csr
        snap = self._snap
        n = snap.n
        dst_by_src, indptr_out = snap.out_csr()
        deg = snap.out_degree.astype(np.int64)
        colstart = self._built["colstart"].astype(np.int64)
        q_total = int(colstart[-1]) + 1
        flat = np.full(q_total * 8, n + 1, np.int32)
        starts8 = colstart[:n] * 8
        pos = np.repeat(starts8 - indptr_out[:n], deg[:n]) \
            + np.arange(len(dst_by_src), dtype=np.int64)
        flat[pos] = dst_by_src
        return np.ascontiguousarray(flat.reshape(q_total, 8).T)


def merged_degrees_host(snapshot, overlay):
    """Host-side O(n + delta) bookkeeping for the merged layout:
    ``(deg, degc, colstart, q_total)`` of the NEXT epoch, as numpy.

    This is the only host math the device merge needs (the output
    allocation wants a static ``q_total``); the device kernel
    recomputes the same arrays in HBM and tests pin the two equal.
    """
    n = int(snapshot.n)
    tombs_per_src = np.zeros(n, np.int64)
    if overlay.tomb_count:
        np.add.at(tombs_per_src,
                  snapshot.src[overlay.tomb_row_mask].astype(np.int64), 1)
    adds_per_src = np.zeros(n, np.int64)
    a_src, _, _ = overlay.live_adds()
    if len(a_src):
        np.add.at(adds_per_src, a_src.astype(np.int64), 1)
    deg = snapshot.out_degree.astype(np.int64) - tombs_per_src \
        + adds_per_src
    degc = -(-deg // 8)
    colstart = np.zeros(n + 1, np.int64)
    np.cumsum(degc, out=colstart[1:])
    q_total = int(colstart[-1]) + 1
    return (np.concatenate([deg, [0]]).astype(np.int32),
            np.concatenate([degc, [0]]).astype(np.int32),
            colstart.astype(np.int32), q_total)


def _bitmap_bits(tomb_dev, q_total: int):
    """Expand the [q_total]-byte tombstone bitmap to a [q_total*8] bool
    vector in slot order (slot s → byte s>>3, bit s&7)."""
    import jax.numpy as jnp

    lanes = jnp.arange(8, dtype=jnp.uint8)
    return ((tomb_dev[:, None] >> lanes) & jnp.uint8(1)) \
        .astype(bool).reshape(q_total * 8)


def _upper_bound_segmented(flat, lo, hi, needle):
    """Vectorized per-query binary search: for each query i, the number
    of entries <= needle[i] within ``flat[lo[i]:hi[i]]`` (each segment
    ascending), returned as the absolute upper-bound position. All
    int32; ``lo==hi`` (empty segment) answers ``lo``."""
    import jax.numpy as jnp

    size = flat.shape[0]
    for _ in range(_BSEARCH_ITERS):
        mid = lo + (hi - lo) // 2          # no lo+hi int32 overflow
        v = flat[jnp.clip(mid, 0, max(size - 1, 0))]
        active = lo < hi
        take = active & (v <= needle)
        lo = jnp.where(take, mid + 1, lo)
        hi = jnp.where(active & ~take, mid, hi)
    return lo


def merge_chunked_csr(csr: dict, view, *, q_total_new: int,
                      e_base: int) -> dict:
    """Merge ``csr`` (a ``build_chunked_csr`` dict, device-resident)
    with an ``OverlayView`` into the next epoch's chunked CSR, entirely
    in HBM. ``q_total_new`` is the host-precomputed output column count
    (:func:`merged_degrees_host`); ``e_base`` the base edge count.

    Returns the device half of a ``build_chunked_csr`` dict (``dstT`` /
    ``colstart`` / ``degc`` / ``deg`` / ``q_total`` / ``n`` — the
    caller attaches the ``_host`` mirrors via the delta-page sync).
    Raises ``ValueError`` on inputs the int32 layout cannot express —
    callers catch and take the host path.

    Routed through the device-cost profiler (obs/devprof, ISSUE 10):
    the merge is an eager device-op sequence, so its per-epoch wall and
    any eager-op compiles land on the ``device.exec.* / device.compile
    .*`` families under kernel ``ops.epoch_merge``.
    """
    from titan_tpu.obs import devprof
    return devprof.profiled("ops.epoch_merge", _merge_chunked_csr,
                            csr, view, q_total_new=q_total_new,
                            e_base=e_base)


def _merge_chunked_csr(csr: dict, view, *, q_total_new: int,
                       e_base: int) -> dict:
    import jax.numpy as jnp

    n = int(csr["n"])
    q_old = int(csr["q_total"])
    if e_base <= 0:
        raise ValueError("device merge needs a non-empty base CSR")
    if not (fits_int32(q_old) and fits_int32(q_total_new)):
        raise ValueError("chunked CSR exceeds int32 slot ids")
    if int(view.tomb_dev.shape[0]) != q_old:
        raise ValueError("overlay tombstone bitmap does not match the "
                         "base CSR layout (stale epoch?)")
    s_old = q_old * 8
    s_new = q_total_new * 8
    pad = jnp.int32(n + 1)

    # ---- survivors of the base (pass 1) --------------------------------
    flat = csr["dstT"].T.reshape(s_old)          # slot order
    alive = (flat <= n) & ~_bitmap_bits(view.tomb_dev, q_old)
    # inclusive prefix with a leading 0: css[k] = #alive slots in [0, k)
    css = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(alive.astype(jnp.int32))])
    colstart8 = csr["colstart"] * 8              # [n+1] int32 (guarded)
    kept_before = css[colstart8]                 # [n+1]; [:n] = kept cumsum
    kept_per_u = kept_before[1:] - kept_before[:-1]   # [n]
    _, (kfv,) = scatter_compact(alive, (flat,), e_base, (pad,))

    # ---- add placement (pass 2) ----------------------------------------
    a_src, a_dst = view.src_dev, view.dst_dev    # [cap], pad n+1
    alive_add = a_src <= n
    adds_per_u = jnp.zeros(n, jnp.int32) \
        .at[a_src].add(alive_add.astype(jnp.int32), mode="drop")
    deg_new_n = kept_per_u + adds_per_u
    degc_new_n = (deg_new_n + 7) // 8
    colstart_new = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(degc_new_n)]) \
        .astype(jnp.int32)
    colstart8_new = colstart_new * 8
    # stable (src, dst, append-order) sort of the cap-sized buffer:
    # dead/pad rows (n+1, n+1) sink to the tail and stay masked
    o1 = jnp.argsort(a_dst)
    order = o1[jnp.argsort(a_src[o1])]
    sa_src = a_src[order]
    sa_dst = a_dst[order]
    sa_alive = sa_src <= n
    u_clip = jnp.clip(sa_src, 0, n)
    acs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(adds_per_u)])[u_clip]
    rank = jnp.arange(a_src.shape[0], dtype=jnp.int32) - acs
    lo = colstart8[u_clip]
    hi = lo + csr["degc"][u_clip] * 8
    ub = _upper_bound_segmented(flat, lo, hi, sa_dst)
    kept_le = css[ub] - css[lo]                  # tombstones excluded
    t_add = jnp.where(sa_alive,
                      colstart8_new[u_clip] + kept_le + rank,
                      jnp.int32(s_new))          # masked rows drop
    out = jnp.full((s_new,), pad, jnp.int32) \
        .at[t_add].set(sa_dst, mode="drop")
    occ = jnp.zeros(s_new, bool).at[t_add].set(True, mode="drop")

    # ---- complement fill (pass 3) --------------------------------------
    cols = jnp.arange(q_total_new, dtype=jnp.int32)
    owner_col = jnp.clip(
        jnp.searchsorted(colstart_new, cols, side="right") - 1, 0, n)
    owner = jnp.broadcast_to(owner_col[:, None],
                             (q_total_new, 8)).reshape(s_new)
    deg_new = jnp.concatenate([deg_new_n, jnp.zeros(1, jnp.int32)])
    pos = jnp.arange(s_new, dtype=jnp.int32) - colstart8_new[owner]
    valid = pos < deg_new[owner]
    free = valid & ~occ
    cfs = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           jnp.cumsum(free.astype(jnp.int32))])
    kept_rank = cfs[:-1] - cfs[colstart8_new][owner]
    src_idx = jnp.clip(kept_before[owner] + kept_rank, 0, e_base - 1)
    out = jnp.where(free, kfv[src_idx], out)

    return {"dstT": out.reshape(q_total_new, 8).T,
            "colstart": colstart_new,
            "degc": jnp.concatenate([degc_new_n,
                                     jnp.zeros(1, jnp.int32)]),
            "deg": deg_new,
            "q_total": q_total_new,
            "n": n}
