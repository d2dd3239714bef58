"""Pallas TPU kernel: fused bottom-up frontier fetch+test+compact.

The bottom-up BFS wall is fetch WIDTH, not tests: the XLA chain in
models/bfs_hybrid.py (``_bu_startL``/``_bu_finish_chunk0``/``_bu_more``)
materializes full 8-lane chunk fetches from the 9GB ``dstT`` to HBM
before the frontier-bitmap hit test sees them, and the split-lane
opener's narrow-first economics (fetch+test 0.427s -> 0.268s per 4.2M
candidates at 4 lanes — experiments/lane_split_probe.py) only apply at
the level opener because the refetch needs a host-sized second dispatch.
This kernel fuses one whole chunk round on-chip instead: a sequential
grid streams candidate blocks through VMEM and, per block,

* gathers the LEADING ``lanes`` lanes of each candidate's chunk column
  (the narrow fetch — leading row slices ``dstT[:lanes]`` fuse; offset
  slices do not, see ``_bu_finish_chunk0``),
* tests them against the frontier bitmap(s) (and the tombstone/label
  slot bitmap when masked — the olap/live and level_masks seams),
* refetches ONLY the still-undecided candidates at the full 8-lane
  width (decided candidates fetch the all-pad sink column, so the
  ladder's fetched-byte saving survives the fusion; the economics are
  pinned by tests/test_lane_economics.py),
* emits the per-(job, candidate) found flags, and
* compacts the surviving (candidate, next-chunk-cursor) pairs IN ORDER
  into the output list through an SMEM survivor-cursor carry (TPU grids
  run sequentially on a core, so the scalar persists across blocks —
  the same carry pattern as ops/pallas_segment.py).

Bit-equality: the ladder never changes results — a candidate that
misses the narrow lanes is re-tested at full width, so the found set
equals the XLA all-8-lane test exactly, and the in-order compaction
matches ``ops.compaction.scatter_compact``'s stable order. Interpreter-
mode property tests (tests/test_pallas_frontier.py) pin this on CPU
across the plain / batched / sharded callers and the overlay and
level-mask seams.

Kept behind ``TITAN_TPU_FRONTIER_KERNEL=pallas`` (or the explicit
``frontier_round`` call) and, as it stands, an interpreter-mode kernel
only: the chip's compiler refuses it (``TPU_REFUSAL`` below), so the
opt-in raises on a TPU backend. CPU-proxy caveats, honestly:
interpreter mode emulates the kernel with XLA ops, so CPU wall times
say NOTHING about the chip; and this first cut keeps ``dstT`` as a
whole-array VMEM input — valid at test shapes only; the port (ROADMAP
S5) moves it to ANY/HBM space with per-block DMA.
"""

from __future__ import annotations

import functools
import os

import numpy as np

#: candidate-axis block width streamed through VMEM per grid step
DEFAULT_BLOCK = 1024


#: what the chip's compiler says of ``frontier_round`` at
#: ``interpret=False`` (Mosaic lowering, libtpu 0.0.34 / TPU v5 lite,
#: K=8, C=2^17, the scale-20 ``dstT``, blocks 1024/512/128 alike;
#: pinned by tests/test_chip_compile.py). The in-kernel ``jnp.take``
#: gathers have no Mosaic lowering at these shapes — ROADMAP S5's port
#: (streamed ``dstT``, aligned stores) replaces them.
TPU_REFUSAL = ("ValueError: Shape mismatch in input, indices and output "
               "(jax/_src/pallas/mosaic/lowering.py, "
               "_gather_lowering_rule)")


def frontier_kernel_mode() -> str:
    """``TITAN_TPU_FRONTIER_KERNEL`` — ``xla`` (default: the chain in
    models/bfs_hybrid.py) or ``pallas`` (this kernel, interpreter mode,
    off-TPU only). Raises on junk rather than silently falling back,
    and raises on a TPU backend: the chip's compiler refuses the kernel
    (``TPU_REFUSAL``), so the opt-in fails here, at the gate, instead of
    at job time."""
    mode = os.environ.get("TITAN_TPU_FRONTIER_KERNEL", "xla")
    if mode not in ("xla", "pallas"):
        raise ValueError(
            f"TITAN_TPU_FRONTIER_KERNEL={mode!r}: expected xla|pallas")
    if mode == "pallas":
        import jax

        if jax.default_backend() == "tpu":
            raise RuntimeError(
                "TITAN_TPU_FRONTIER_KERNEL=pallas on a TPU backend: the "
                "chip's compiler refuses ops/pallas_frontier."
                f"frontier_round — {TPU_REFUSAL}")
    return mode


def frontier_interpret() -> bool:
    """The kernel's static ``interpret`` argument for the five callers
    in models/: true off-TPU (the CPU parity tests run the kernel in
    interpreter mode), never true on a TPU backend — and there
    ``frontier_kernel_mode`` has already refused the opt-in."""
    import jax

    return jax.default_backend() != "tpu"


def _frontier_round_kernel(cols_ref, undec_ref, more_ref, pay0_ref,
                           pay1_ref, fbits_ref, tbits_ref, dstT_ref,
                           found_ref, pay0_out, pay1_out, nsur_ref,
                           cursor_ref, *, block: int, lanes: int,
                           masked: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cursor_ref[0] = jnp.int32(0)

    cols = cols_ref[...][0]              # (B,) chunk column per candidate
    undec = undec_ref[...] > 0           # (K, B) job still wants candidate
    dstT = dstT_ref[...]                 # (8, Q) whole transposed CSR
    fbits = fbits_ref[...]               # (K, NB) bitmap bytes, widened
    q_pad = dstT.shape[1] - 1

    def hit_any(par, pcols):
        """(l, B) gathered parents -> (K, B) any-lane bitmap hit, with
        tombstoned slots (col*8 + lane) masked out when ``masked``."""
        byte = par >> 3
        bit = (par & 7).astype(jnp.int32)
        w = jnp.take(fbits, byte.reshape(-1), axis=1) \
            .reshape(fbits.shape[0], *par.shape)        # (K, l, B)
        h = ((w >> bit[None]) & 1) > 0
        if masked:
            tb = tbits_ref[...][0]                      # (TB,) widened
            lane = jax.lax.broadcasted_iota(jnp.int32, par.shape, 0)
            slot = pcols[None, :] * 8 + lane
            tw = jnp.take(tb, (slot >> 3).reshape(-1)) \
                .reshape(par.shape)
            tomb = ((tw >> (slot & 7)) & 1) > 0
            h = h & ~tomb[None]
        return h.any(axis=1)                            # (K, B)

    # narrow fetch: leading lanes only, everyone
    par_n = jnp.take(dstT[:lanes], cols, axis=1)        # (lanes, B)
    hit = hit_any(par_n, cols)
    if lanes < 8:
        # refetch survivors wide: candidates some undecided job still
        # missed fetch all 8 lanes; decided ones fetch the all-pad sink
        # column (pad bits are never set, so they stay misses)
        need_w = (undec & ~hit).any(axis=0)             # (B,)
        wcols = jnp.where(need_w, cols, q_pad)
        par_w = jnp.take(dstT, wcols, axis=1)           # (8, B)
        hit = hit | (hit_any(par_w, wcols) & need_w[None])

    found = undec & hit
    found_ref[...] = found.astype(jnp.int32)

    # in-order survivor compaction through the SMEM cursor carry
    surv = (undec & ~hit).any(axis=0) & (more_ref[...][0] > 0)
    s32 = surv.astype(jnp.int32)
    pos = jnp.cumsum(s32) - 1                           # (B,) stable
    cnt = s32.sum()
    cur = cursor_ref[0]
    tgt = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    sel = (pos[:, None] == tgt) & surv[:, None]         # (B, B) one-hot
    slab0 = jnp.where(sel, pay0_ref[...][0][:, None], 0).sum(axis=0)
    slab1 = jnp.where(sel, pay1_ref[...][0][:, None], 0).sum(axis=0)
    pay0_out[pl.ds(0, 1), pl.ds(cur, block)] = slab0[None, :]
    pay1_out[pl.ds(0, 1), pl.ds(cur, block)] = slab1[None, :]
    cursor_ref[0] = cur + cnt
    nsur_ref[0, 0] = cur + cnt


def _pad_lanes(a, mult: int = 128):
    import jax.numpy as jnp

    pad = (-a.shape[-1]) % mult
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad)))
    return a


def frontier_round(cols, undec, has_more, pay0, pay1, fbits, tbits,
                   dstT, *, lanes: int, fill0: int, fill1: int,
                   block: int = DEFAULT_BLOCK, interpret: bool = False):
    """One fused chunk round: gather+test+compact for ``C`` candidates.

    ``cols`` [C] int32 — each candidate's chunk column (dead lanes at
    ``q_pad``); ``undec`` [K, C] bool/int — job k still wants candidate
    j decided (fold the alive mask in); ``has_more`` [C] — candidate
    has chunks beyond this one (folds the survivor condition);
    ``pay0``/``pay1`` [C] int32 — the payloads to compact for survivors
    (candidate id and next chunk cursor); ``fbits`` [K, nbytes] uint8
    frontier bitmaps; ``tbits`` — edge-slot tombstone/label bitmap
    (uint8 [tbytes]) or None; ``dstT`` [8, Q] the transposed CSR.

    Returns ``(found [K, C] bool, pay0c [C], pay1c [C], nsur scalar)``
    with ``pay*c`` the survivors compacted in candidate order and
    padded with ``fill0``/``fill1`` — exactly
    ``ops.compaction.scatter_compact``'s contract."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C = cols.shape[0]
    K = undec.shape[0]
    q_pad = dstT.shape[1] - 1
    blk = min(block, C)
    pad = (-C) % blk
    grid = (C + pad) // blk

    def padded(a, val):
        if pad:
            a = jnp.concatenate(
                [a, jnp.full((pad,), val, a.dtype)])
        return a[None, :]

    cols2 = padded(jnp.clip(cols, 0, q_pad).astype(jnp.int32), q_pad)
    und2 = undec.astype(jnp.int32)
    if pad:
        und2 = jnp.concatenate(
            [und2, jnp.zeros((K, pad), jnp.int32)], axis=1)
    more2 = padded(has_more.astype(jnp.int32), 0)
    pay0_2 = padded(pay0.astype(jnp.int32), fill0)
    pay1_2 = padded(pay1.astype(jnp.int32), fill1)
    fb = _pad_lanes(fbits.astype(jnp.int32))
    masked = tbits is not None
    tb = _pad_lanes(tbits[None, :].astype(jnp.int32)) if masked \
        else jnp.zeros((1, 128), jnp.int32)
    cp = C + pad

    kern = functools.partial(_frontier_round_kernel, block=blk,
                             lanes=lanes, masked=masked)
    found, p0c, p1c, nsur = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, blk), lambda i: (0, i)),
                  pl.BlockSpec((K, blk), lambda i: (0, i)),
                  pl.BlockSpec((1, blk), lambda i: (0, i)),
                  pl.BlockSpec((1, blk), lambda i: (0, i)),
                  pl.BlockSpec((1, blk), lambda i: (0, i)),
                  pl.BlockSpec(fb.shape, lambda i: (0, 0)),
                  pl.BlockSpec(tb.shape, lambda i: (0, 0)),
                  pl.BlockSpec(dstT.shape, lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((K, blk), lambda i: (0, i)),
                   pl.BlockSpec((1, cp), lambda i: (0, 0)),
                   pl.BlockSpec((1, cp), lambda i: (0, 0)),
                   pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((K, cp), jnp.int32),
                   jax.ShapeDtypeStruct((1, cp), jnp.int32),
                   jax.ShapeDtypeStruct((1, cp), jnp.int32),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(cols2, und2, more2, pay0_2, pay1_2, fb, tb, dstT)
    nc = nsur[0, 0]
    # mask the unwritten tail (the last block's slab overhang and any
    # never-reached region of the full-width output)
    j = jnp.arange(C, dtype=jnp.int32)
    pay0c = jnp.where(j < nc, p0c[0, :C], fill0)
    pay1c = jnp.where(j < nc, p1c[0, :C], fill1)
    return found[:, :C] > 0, pay0c, pay1c, nc


def ladder_fetch_counts(cols, fbits, dstT, lanes: int, tbits=None):
    """The ladder's fetched-byte cost model, host-side:
    ``(narrow_bytes, wide_bytes, baseline_bytes)`` for one chunk round
    over candidate chunk columns ``cols`` — the deterministic form of
    experiments/lane_split_probe.py's measurement. 4 bytes per fetched
    lane entry; every candidate pays the ``lanes`` narrow rows, only
    the narrow-round misses pay the 8-lane wide refetch (decided
    candidates refetch the single all-pad sink column — charged 0, it
    is one VMEM-resident column); the baseline is the XLA chain's flat
    8-lane fetch. tests/test_lane_economics.py pins narrow + wide <
    baseline on a hub-frontier graph, so the economics claim behind
    SPLIT_LANES (PERF_NOTES r5) is tested, not folklore."""
    cols = np.asarray(cols)
    fb = np.asarray(fbits)
    dstT = np.asarray(dstT)

    def hit_any(par):
        h = (fb[par >> 3] >> (par & 7)) & 1
        if tbits is not None:
            lane = np.arange(par.shape[0], dtype=np.int64)[:, None]
            slot = cols[None, :] * 8 + lane
            h = h & ~((np.asarray(tbits)[slot >> 3] >> (slot & 7)) & 1)
        return h.any(axis=0)

    narrow_b = int(cols.size) * 4 * lanes
    missed = ~hit_any(dstT[:lanes][:, cols])
    wide_b = int(missed.sum()) * 4 * 8
    return narrow_b, wide_b, int(cols.size) * 4 * 8
