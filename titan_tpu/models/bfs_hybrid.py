"""Direction-optimizing (top-down/bottom-up) frontier BFS on TPU.

The reference executes BFS-style traversals by scanning every row through a
vertex-program superstep (FulgoraGraphComputer.java:151-189); the TPU cost
model is entirely different: XLA lowers *random* single-element gathers and
scatters at ~113M elem/s into cache-resident tables but only ~67M elem/s
into 100MB+ tables (HBM-latency-bound — measured, experiments/
gather_table_size.py), while *coalesced* fetches — columns of a [8, E/8]
array — are 5-50x cheaper per edge. Kernel design rules:

* at most ONE random-access op per *examined* edge;
* direction optimization (Beamer et al., SC'12) cuts examined edges
  ~5-10x below E;
* the bottom-up hit test reads a per-level FRONTIER BITMAP (n/8 bytes —
  8.4MB at scale 26, the fast-gather regime) instead of the 4-byte dist
  array (268MB, the slow regime): measured 1.9x on the hit test;
* a heavy level's first lanes are tested on the VERTEX SET, n-wide in
  vertex order over the leading-lane image, not on a list of the
  candidates (``_bu_startL``): a list there is the vertex set written
  out again, and every operation on it a random one (graph500-24's
  heavy level: 947 ms on the list, 116 ms n-wide: PERF.md 6, PR 39);
* work that is usually wasted runs under ``lax.cond``: survivor
  compaction only when survivors exist, the level-end wrap only when the
  level is already decided (at scale 26's heavy level ALL 27M candidates
  resolve on their first chunk — the unconditional compaction alone cost
  ~2.5s);
* every host↔device round trip stalls the level loop, so the cheap
  levels fuse into on-device ``lax.while_loop``s: the
  HEAD loop runs the early small top-down levels in one dispatch, and the
  ENDGAME loop finishes ALL trailing small levels (either mode would be
  sub-second; bottom-up form needs no frontier list) in one dispatch.

Layout: the out-CSR is stored transposed and 8-aligned —
``dstT[j, q] = neighbor j of chunk q`` with every vertex's edge segment
padded to a multiple of 8 columns (pad = ``n+1``, out of range for the
[n+1]-sized state arrays: pad scatters drop, pad gathers clamp to the
never-written ``dist[n]``; pad BITS are never set).

SYMMETRIC GRAPHS ONLY: bottom-up treats a vertex's out-neighbors as its
potential parents, which holds iff every edge has its reverse present
(Graph500 BFS runs on the symmetrized graph). For directed graphs use
``titan_tpu.models.bfs`` or symmetrize first.

The host drives only the HEAVY middle levels (one stats readback each);
all graph state stays on device, and the returned ``dist`` is a device
array (a full readback is an O(n) D2H transfer — callers
that need numpy convert explicitly).
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.models.bfs import INF, _next_pow2
from titan_tpu.ops.compaction import (CLAIM_SENTINEL, claim_dedup,
                                      claim_reset, compact_ids,
                                      scatter_compact)

# mode-switch thresholds (Beamer-style, tuned on v5e):
# td->bu when the frontier's (chunked) edge mass exceeds 1/ALPHA of the
# remaining unvisited edge mass; bu->td when the next frontier's edge mass
# falls back below it. Kernels use the integer form m8_f > m8_unvis // 8
# (m8 * 8 would overflow int32 at scale 26).
ALPHA = 8.0
# after this many 8-edge chunks checked per candidate, survivors go to the
# exhaustive sweep
BU_CHUNK_ROUNDS = 8
# split-lane bottom-up opener: at heavy levels, test the first
# SPLIT_LANES lanes of chunk 0 for everyone (cuts the bitmap-gather and
# fetch width; measured fetch+test 0.427s -> 0.268s per 4.2M candidates
# at 4 lanes, experiments/lane_split_probe.py) and refetch the remaining
# lanes only for the minority that miss. Misses that can still hit a
# later lane are RARE (scale-26 heavy level, 27M candidates: untested
# after 2 lanes ~0.2M, after 4 lanes ~2k — adjacency lists are
# id-sorted and the heavy-level frontier covers the low-id hubs), so
# fewer leading lanes win: measured scale-26 BFS 7.72s (lanes=2) vs
# 8.51s (lanes=4) vs 11.5s (r4 4-lane two-gather opener). Below
# SPLIT_LANE_MIN candidates the extra dispatch+readback outweighs the
# gather saving (tuned while the first lanes ran over a list of the
# candidates; since PR 39 they run n-wide, _bu_startL, at a cost that
# does not fall with the candidates: at graph500-24 and 2^21 slots
# 95 ms against that list's 198 ms, PERF.md 6).
SPLIT_LANES = 2
SPLIT_LANE_MIN = 1 << 21
# head loop caps: early top-down levels fused into one dispatch while the
# frontier stays under these
HEAD_F_CAP = 1 << 12
HEAD_P_CAP = 1 << 18
# endgame entry: remaining unvisited vertex / chunk mass caps (one fused
# dispatch finishes every trailing level)
END_C_CAP = 1 << 21
END_P_CAP = 1 << 22
# batched top-down (push) step, frontier_bfs_batched: the ladder of its
# chunk-column caps (``f_cap`` = ``p_cap``; a pair with no chunk is not
# listed, so pairs never outnumber chunks), as right shifts of the top
# rung, which is the largest power of two at or below HALF the layout's
# chunk columns (``_td_caps``): the ladder scales with the graph. A
# FIXED ladder, not a power of two of the level's mass: every cap is an
# executable of its own, and a cap minted inside a served window is a
# 7-25 s stall (PERF.md 5, PR 25). A level whose frontier chunk mass
# passes the top rung goes bottom-up. A push costs its RUNG, not its
# frontier (one v5e, K = 1, a list in hand, PERF.md 6, PRs 29 and 31):
# 2.5 ms on 2^12, 16.9 on 2^17, 31.8 on 2^18, 61.5 on 2^19, 122.4 on
# 2^20, 248.4 on 2^21, linear in ``p_cap`` from the middle rung up. So
# from the middle to the top the rungs stand a factor of two apart: no
# such level pays for more than twice its mass. Below the middle the
# dearest rounding-up is 14 ms, less than a query's fixed 19, and a
# rung is five executables (one a padded batch size), so there the gap
# stays. At scale 20 (4.56 M / 4.65 M columns; CPU count, PR 31, the
# second level of the benchmark's 256 starts a cell): 2^12 holds every
# Urand level, every L1 and 165 Kron starts; 2^17 holds 80 (the p95
# start weighs 77,627 columns); 2^18 ten (150,732-193,702) and 2^20
# one (892,911), which all paid for 2^21 before; 2^19 none; 2^21 the
# heaviest 16-query Kron batch (1.47 M chunks).
TD_RUNG_SHIFTS = (9, 4, 3, 2, 1, 0)
# batched bottom-up (pull) step: the ladders of its caps, as right
# shifts of a top rung the layout states (``_bu_caps``), for the reason
# the push has one: a cap that is the power of two of a count read back
# from the device is an executable a source, and a new source then
# builds inside a served window. A count takes the lowest rung that
# holds it; the lanes above it are dead ones the programs mask already,
# so ``dist`` is bit-equal whatever the rung. ``bstep``'s ``c_cap``
# (the level's candidates): BU_RUNG_SHIFTS of the power of two at or
# above n, which holds every list. A pulled level costs its RUNG, not
# its candidates, and linearly (one v5e, K = 1, graph500-22, PERF.md 6,
# PR 49: 0.16 us a lane of ``c_cap`` a chunk round: 1.3 s for the eight
# rounds on 2^20, 2.7 on 2^21, 5.5 on 2^22), so from the middle up the
# rungs stand a factor of two apart; a source's tail levels hold 6-150
# thousand candidates, so below it a factor of four (a first ladder that
# stood 2^12 and 2^18 with nothing between cost every job 300 ms).
# Under the lowest rung a sweep is the dispatch's cost. ``bex`` (the
# stragglers' sweep: survivors of the eight chunk rounds; on an
# undirected graph a source of this size met none) is rare, so its
# ladders are coarse: its ``c_cap`` the lowest and the top candidate
# rung, its ``p_cap`` (their remaining chunk columns) EX_RUNG_SHIFTS of
# the power of two at or above the layout's chunk columns.
BU_RUNG_SHIFTS = (10, 8, 6, 4, 3, 2, 1, 0)
EX_RUNG_SHIFTS = (9, 4, 0)
# direction rule (e): a level goes top-down while
#   mass * TD_BU_COST <= rounds * c_count
# — one pushed chunk column against one candidate-round of the
# bottom-up sweep, ``rounds`` the chunk rounds the pull's first
# dispatch runs over ALL its candidates (``_bu_fuse``): the eight of a
# hop's level, ONE of a BFS level since its rounds run a dispatch each
# and the first decides nearly every candidate (the rule had weighed a
# BFS pull at eight, so a frontier of up to eight times the candidates'
# chunk mass was pushed on the top rungs: 0.5-1 s a level where the
# pull is 0.3-0.6, PERF.md 6, PR 50). Chip measurement that set the
# constant: PERF.md 6, PR 26.
TD_BU_COST = 1
# hand-on rule of a pushed level (``_td_lists``): the push dedups its
# scatter targets into the next level's pair list only while
#   8 * p_cap * TD_DEDUP_COST < n
# — a deduped lane (a claim scatter-min and its gather, a degc gather,
# a two-payload compaction: five random ops) against a vertex of the
# n-wide listing that would find the next frontier instead (one
# streaming compaction). Chip measurement that set it: PERF.md 6, PR 29
# (33 ns a lane on rung 2^17 against 5.5 ns a vertex). At scale 20 the
# lowest rung hands on and the two above it do not.
TD_DEDUP_COST = 6


def layout_slot_positions(indptr, deg, n: int):
    """Edge → slot index (``col*8 + lane``) in the 8-aligned transposed
    chunk layout, in payload order: vertex v's edge k lands at
    ``colstart[v]*8 + k``. The ONE definition of the slot arithmetic —
    ``chunked_layout`` scatters payloads through it and the interactive
    lane's per-hop label masks (compile.hop_label_masks) index the same
    slots, so the mask packing can never skew from the device layout.
    Returns ``(pos int64 [E], colstart int64 [n+1], degc int64 [n])``."""
    degc = -(-deg // 8)
    colstart = np.zeros(n + 1, np.int64)
    np.cumsum(degc, out=colstart[1:])
    total = int(indptr[n])
    pos = np.repeat(colstart[:n] * 8 - indptr[:n], deg[:n]) \
        + np.arange(total, dtype=np.int64)
    return pos, colstart, degc


def chunked_layout(payload, indptr, deg, n: int):
    """The 8-aligned transposed chunk layout shared by the forward
    chunked CSR below and the interactive lane's REVERSED orientation
    (olap/serving/interactive/compile.reversed_chunked_csr) — one
    definition of the pad convention and the int32 column guard.
    Returns ``(dstT [8, Q] int32 host, colstart int64 [n+1], degc
    int64 [n], q_total)``."""
    pos, colstart, degc = layout_slot_positions(indptr, deg, n)
    q_total = int(colstart[-1]) + 1          # +1 all-pad column for the sink
    if q_total >= (1 << 31):
        raise NotImplementedError(
            "chunked CSR uses int32 COLUMN indices; shard below 2^31 chunks")
    # pad = n+1: OUT of range for dist[0..n], so pad-lane scatters are
    # dropped and pad-lane gathers clamp to dist[n], which is never
    # written and stays INF (writing the in-range sink n instead would
    # leak level values into later bottom-up hit tests)
    flat = np.full(q_total * 8, n + 1, np.int32)
    flat[pos] = payload
    dstT = np.ascontiguousarray(flat.reshape(q_total, 8).T)
    return dstT, colstart, degc, q_total


def build_chunked_csr(snap):
    """Host-side (cached): transposed 8-aligned out-CSR device arrays.

    Returns dict with ``dstT`` [8, Q] int32 (pad = n+1, see module doc),
    ``colstart`` [n+1] int32 (first column of each vertex), ``degc``
    [n+1] int32 (chunk count; 0 for the sink), ``deg`` [n+1] int32, all
    on device.
    """
    import jax.numpy as jnp

    cached = getattr(snap, "_hybrid_csr", None)
    if cached is not None:
        return cached
    n = snap.n
    dst_by_src, indptr_out = snap.out_csr()
    deg = snap.out_degree.astype(np.int64)
    dstT, colstart, degc, q_total = chunked_layout(
        dst_by_src, indptr_out, deg, n)
    # device-cost seam (obs/devprof): the chunked-CSR upload is the
    # dominant H2D cost of a cold snapshot — count it once per build
    from titan_tpu.obs import devprof
    devprof.count_h2d("bfs.chunked_csr",
                      dstT.nbytes + 3 * (n + 1) * 4)
    out = {
        "dstT": jnp.asarray(dstT),
        "colstart": jnp.asarray(colstart.astype(np.int32)),
        "degc": jnp.asarray(np.concatenate(
            [degc, [0]]).astype(np.int32)),
        "deg": jnp.asarray(np.concatenate(
            [deg, [0]]).astype(np.int32)),
        "q_total": q_total,
        "n": n,
        # host copies retained for shard slicing: reading the device
        # arrays back would be a multi-gigabyte D2H transfer
        "_host": {"dstT": dstT,
                  "colstart": colstart.astype(np.int32),
                  "degc": np.concatenate([degc, [0]]).astype(np.int32)},
    }
    snap._hybrid_csr = out
    return out


# --------------------------------------------------------------------------
# jitted level steps (module-level so (cap) buckets compile once per process)
# --------------------------------------------------------------------------

from titan_tpu.utils.jitcache import jit_once as _get  # noqa: E402


def enumerate_chunk_pairs(valid, counts, colstarts, p_cap: int, q_pad: int,
                          with_owner: bool = False):
    """Enumerate (item, chunk) pairs with the delta-scatter+cumsum trick.

    ``valid`` [f_cap] bool, ``counts`` [f_cap] chunks per item (0 where
    invalid), ``colstarts`` [f_cap] each item's first column. Pair i of
    item j maps to column ``colstarts[j] + i - first_pair(j)``. Returns
    (cols [p_cap] int32 clipped to q_pad with dead pairs = q_pad,
    p_total, owner [p_cap] = owning item slot if ``with_owner``).

    Colliding starts of empty items sum their deltas, so the net base
    offset stays right; starts at/after p_cap are DROPPED (a clamped
    delta would corrupt the last live pair's column)."""
    import jax.numpy as jnp

    f_cap = valid.shape[0]
    counts = jnp.where(valid, counts, 0).astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    p_total = ends[-1]
    base = jnp.where(valid, colstarts, 0) - starts
    delta = jnp.diff(base, prepend=0)
    acc = jnp.zeros((p_cap,), jnp.int32).at[starts].add(delta, mode="drop")
    j = jnp.arange(p_cap, dtype=jnp.int32)
    cols = jnp.cumsum(acc) + j
    cols = jnp.where(j < p_total, jnp.clip(cols, 0, q_pad), q_pad)
    if not with_owner:
        return cols, p_total, None
    oacc = jnp.zeros((p_cap,), jnp.int32).at[starts].add(
        jnp.diff(jnp.arange(f_cap, dtype=jnp.int32), prepend=0),
        mode="drop")
    owner = jnp.clip(jnp.cumsum(oacc), 0, f_cap - 1)
    return cols, p_total, owner


#: the frontier bitmap's plane width is a multiple of this many bytes,
#: so each plane starts on a tile of the chip's memory layout
FBITS_ALIGN = 4096


def _fbits_width(n_: int) -> int:
    return -(-(n_ + 2) // (8 * FBITS_ALIGN)) * FBITS_ALIGN


def _pack_bits(dist, level, n_: int):
    """Frontier bitmap over vertices 0 .. n_+1 (the pad vertex, always
    0), in PLANES: with W = ``_fbits_width(n_)`` bytes, vertex v is bit
    v // W of byte v % W, so byte w is built from eight CONTIGUOUS
    slices of the mask — elementwise, nothing moves across lanes. Read
    it with ``_fbit_of`` alone (``_bit_of`` reads the byte-major layout
    of the host's tombstone bits).

    Why not ``jnp.packbits``: its ``[nbytes, 8]`` reshape takes the
    chip's compiler 100 s at n = 8.9 M in every executable that packs,
    which made a cold replica's first graph500-24 job outlast a 300 s
    timeout; the same byte-major bits from eight STRIDED slices compile
    in a second and run 86 ms a pack, where a plane pack runs 2 ms
    (PERF.md 6, PR 36)."""
    import jax.numpy as jnp

    w = _fbits_width(n_)
    mask = jnp.concatenate(
        [dist == level, jnp.zeros((8 * w - dist.shape[0],), bool)])
    planes = mask.reshape(8, w).astype(jnp.uint8)
    out = planes[0]
    for k in range(1, 8):
        out = out | (planes[k] << jnp.uint8(k))
    return out


def _fbit_of(fbits, idx):
    """Test ``_pack_bits``' bitmap at int32 vertex ids 0 .. n_+1 (any
    shape). The plane is seven compares, not a division: beside the
    gather they cost nothing."""
    import jax.numpy as jnp

    w = fbits.shape[0]
    plane = sum((idx >= k * w).astype(jnp.int32) for k in range(1, 8))
    byte = jnp.take(fbits, idx - plane * w)
    return ((byte >> plane.astype(jnp.uint8)) & jnp.uint8(1)).astype(bool)


def _frontier_road(impl: str, columns: int) -> str:
    """What serves the bottom-up frontier test of a block ``columns``
    wide under ``impl`` (``vmem_gather.gather_impl``: the backend and
    the table's size): ``"vmem"`` where the kernel can take the table
    and the block is whole grid steps, else ``"xla"``. The host loop
    hands each program this road as its static ``impl``, so a
    ``kernel`` span says what served its test."""
    from titan_tpu.ops.vmem_gather import BLOCK

    if impl == "vmem" and columns % BLOCK == 0:
        return "vmem"
    return "xla"


def _frontier_test(dist, level, n_: int, impl: str, columns: int,
                   fbits=None):
    """The bottom-up frontier test of the single-source family, written
    once: returns ``hit(parents)`` for blocks ``parents`` int32
    [rows, columns] of vertex ids 0 .. n_+1 (row after row as
    ``jnp.take(dstT, cols, axis=1)`` gives them; n_ the sink and n_+1
    the pad, neither ever on a frontier), giving bool [columns]: does
    any of a column's ``rows`` parents sit on the frontier ``dist ==
    level``. The frontier's image is made here, once a call of this
    function (before a program's rounds, not inside them):

    - ``"vmem"`` (``_frontier_road``): the frontier as a 0/1 float32
      table in VMEM under the Pallas gather of ``ops/vmem_gather.py``
      (a column's ``rows`` values sum exactly in float32; ``rows`` a
      power of two). XLA's gather serves these reads an element at a
      time: 8 x 2^20 of them 68.8 ms on a v5e against 12.3 ms here,
      and the table wins from one grid step up (0.067 against 0.059 ms
      at 1,024 columns, the table's one pass 0.10 ms where the
      bitmap's is 1.05: experiments/endgame_probe.py, PERF.md 6, PR
      43);
    - ``"xla"``: ``_fbit_of`` over the plane bitmap ``fbits`` (the
      level's opener hands it on; packed here where there is none)."""
    import jax.numpy as jnp

    from titan_tpu.ops import vmem_gather

    if _frontier_road(impl, columns) == "vmem":
        table = vmem_gather.as_table((dist == level).astype(jnp.float32))
        return lambda parents: vmem_gather.colsum_vmem(
            parents.reshape(-1), table, rows=parents.shape[0]) > 0
    if fbits is None:
        fbits = _pack_bits(dist, level, n_)
    return lambda parents: _fbit_of(fbits, parents).any(axis=0)


def _bit_of(fbits, idx):
    """Test bitmap bits at int32 indices (any shape)."""
    import jax.numpy as jnp

    w = jnp.take(fbits, idx >> 3)
    return ((w >> (idx & 7).astype(jnp.uint8)) & jnp.uint8(1)) \
        .astype(bool)


def _level_stats(dist, degc, level, n_: int):
    """[nf, m8_next, m8_unvis, n_unvis] after a level's writes landed
    (frontier now at dist == level+1)."""
    import jax.numpy as jnp

    changed = dist[:n_] == level + 1
    nf = changed.sum().astype(jnp.int32)
    m8_next = jnp.where(changed, degc[:n_], 0).sum(dtype=jnp.int32)
    unvis = dist[:n_] >= INF
    m8_unvis = jnp.where(unvis, degc[:n_], 0).sum(dtype=jnp.int32)
    n_unvis = (unvis & (degc[:n_] > 0)).sum().astype(jnp.int32)
    return jnp.stack([nf, m8_next, m8_unvis, n_unvis])


def _head_loop():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("f_cap", "p_cap", "n_"))
        def head(source, max_lv, dstT, colstart, degc, f_cap: int,
                 p_cap: int, n_: int):
            """Fused early top-down levels: run levels from the source
            while the frontier stays within (f_cap, p_cap) and top-down
            stays the right mode; ONE dispatch, one stats readback.

            NO n-scale work per iteration: the next frontier is deduped
            from the scatter targets with a CLAIM array
            (ops.compaction.claim_dedup — first lane to claim a
            newly-found vertex wins; every op is p_cap-scale — the old
            per-iteration n-wide nonzero + n-wide stats cost ~1.1s of
            the 1.41s head at scale 26), and the unvisited-mass stats
            are maintained as running differences. claim_reset
            re-scatters sentinels at the SAME p_cap positions, so the
            claim array stays clean without an n-pass."""
            q_pad = dstT.shape[1] - 1
            lanes = 8 * p_cap

            def cond(s):
                _, _, _, f_count, m8_f, m8_unvis, n_unvis, level, \
                    going = s
                return going & (level < max_lv)

            def body(s):
                (dist, claim, frontier, f_count, m8_f, m8_unvis,
                 n_unvis, level, _) = s
                valid = jnp.arange(f_cap) < f_count
                v = jnp.minimum(frontier, n_)
                cols, _, _ = enumerate_chunk_pairs(
                    valid, degc[v], colstart[v], p_cap, q_pad)
                nbr = jnp.take(dstT, cols, axis=1)      # [8, p_cap]
                # the dist gather reads PRE-scatter state: duplicates of
                # one new vertex all see INF and race on the claim,
                # where exactly one lane wins
                newly = jnp.where(dist[nbr] >= INF, nbr, n_ + 1)
                dist = dist.at[nbr].min(level + 1, mode="drop")
                lane_id = jnp.arange(lanes, dtype=jnp.int32) \
                    .reshape(8, p_cap)
                claim, won = claim_dedup(claim, newly, lane_id)
                winner = won & (newly <= n_)
                nf = winner.sum().astype(jnp.int32)
                degn = degc[jnp.minimum(newly, n_)]
                m8_next = jnp.where(winner, degn, 0).sum(dtype=jnp.int32)
                # compact the winners: p-scale scatter compaction
                _, (nxt,) = scatter_compact(
                    winner.ravel(), (newly.ravel(),), f_cap, (n_,))
                # reset the claim entries this level touched
                claim = claim_reset(claim, newly)
                m8_unvis2 = m8_unvis - m8_next
                n_unvis2 = n_unvis - jnp.where(winner & (degn > 0),
                                               1, 0).sum(dtype=jnp.int32)
                going = (nf > 0) & (nf <= f_cap) & (m8_next <= p_cap) \
                    & ~((m8_next > m8_unvis2 // 8) & (nf > 1))
                return (dist, claim, nxt, nf, m8_next, m8_unvis2,
                        n_unvis2, level + 1, going)

            dist = jnp.full((n_ + 1,), INF, jnp.int32).at[source].set(0)
            claim = jnp.full((n_ + 2,), 2**31 - 1, jnp.int32)
            frontier = jnp.full((f_cap,), n_, jnp.int32) \
                .at[0].set(source)
            m8_f = degc[source]
            m8_unvis = jnp.where(dist[:n_] >= INF, degc[:n_], 0) \
                .sum(dtype=jnp.int32)
            n_unvis0 = ((dist[:n_] >= INF) & (degc[:n_] > 0)) \
                .sum().astype(jnp.int32)
            state = (dist, claim, frontier, jnp.int32(1), m8_f,
                     m8_unvis, n_unvis0, jnp.int32(0),
                     (m8_f <= p_cap) & (m8_f > 0))
            (dist, claim, frontier, f_count, m8_f, m8_unvis, n_unvis,
             level, _) = jax.lax.while_loop(cond, body, state)
            return dist, frontier, jnp.stack(
                [f_count, m8_f, m8_unvis, n_unvis, level])
        return head
    return _get("hybrid_head", build)


def _td_step():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("f_cap", "p_cap", "n_"),
                           donate_argnums=(0,))
        def td(dist, frontier, stats, level, dstT, colstart, degc,
               f_cap: int, p_cap: int, n_: int):
            # frontier count arrives as the previous step's DEVICE stats
            # vector — shipping it back as a scalar would cost a host↔device
            # round trip per level.
            # The NEXT frontier list is NOT built here: the n-wide
            # nonzero cost ~0.9s at scale 26 and the next level is
            # usually bottom-up (which never reads it) — the driver
            # dispatches _frontier_of lazily only when the next level
            # stays top-down, same total compute in that case.
            f_count = stats[0]
            valid = jnp.arange(f_cap) < f_count
            v = jnp.minimum(frontier, n_)
            cols, _, _ = enumerate_chunk_pairs(
                valid, degc[v], colstart[v], p_cap, dstT.shape[1] - 1)
            nbr = jnp.take(dstT, cols, axis=1)   # [8, p_cap], pad = n+1
            dist = dist.at[nbr].min(level + 1, mode="drop")
            return dist, _level_stats(dist, degc, level, n_)
        return td
    return _get("hybrid_td", build)


def _bu_start():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "impl"),
                           donate_argnums=(0,))
        def bu0(dist, level, dstT, colstart, degc, c_cap: int, n_: int,
                impl: str):
            """Bottom-up level opener, fully fused: build the candidate
            list from dist (the old separate all_unvis dispatch), check
            chunk 0 of every candidate against the frontier BITMAP, then
            - survivors > 0: compact them (lax.cond — skipped at heavy
              levels where chunk 0 decides everyone);
            - survivors == 0: level done — emit the level-end stats
              (lax.cond, so it costs nothing when survivors remain).
            Caller guarantee: count(unvisited & deg>0) <= c_cap."""
            q_pad = dstT.shape[1] - 1
            fbits = _pack_bits(dist, level, n_)
            unvis = (dist[:n_] >= INF) & (degc[:n_] > 0)
            c_count, cand = compact_ids(unvis, c_cap, n_)

            alive = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            cols = jnp.where(alive, colstart[v], q_pad)
            parents = jnp.take(dstT, jnp.clip(cols, 0, q_pad), axis=1)
            found = alive & _frontier_test(dist, level, n_, impl, c_cap,
                                           fbits)(parents)
            dist = dist.at[jnp.where(found, v, n_ + 1)].set(
                level + 1, mode="drop")
            # gathered once: a branch of lax.cond would gather it again
            chunks = degc[v]
            surv = alive & ~found & (chunks > 1)
            nc = surv.sum().astype(jnp.int32)

            def compact(_):
                _, (cand2,) = scatter_compact(surv, (cand,), c_cap,
                                              (n_,))
                rem8 = jnp.where(surv, chunks - 1, 0) \
                    .sum(dtype=jnp.int32)
                return cand2, rem8

            def no_compact(_):
                return jnp.full((c_cap,), n_, jnp.int32), jnp.int32(0)

            cand2, rem8 = jax.lax.cond(nc > 0, compact, no_compact, None)
            st = jax.lax.cond(
                nc == 0,
                lambda _: _level_stats(dist, degc, level, n_),
                lambda _: jnp.zeros((4,), jnp.int32), None)
            return dist, fbits, cand2, jnp.stack([nc, rem8]), st
        return bu0
    return _get("hybrid_bu_start", build)


def _lead_image():
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.vmem_gather import padded_columns

        @functools.partial(jax.jit, static_argnames=("lanes",))
        def lead(dstT, colstart, deg, lanes: int):
            n1 = colstart.shape[0]                          # n + 1
            first = jnp.take(dstT[:lanes], colstart, axis=1)
            k = jnp.arange(lanes, dtype=jnp.int32)[:, None]
            first = jnp.where(deg[None, :] > k, first, n1)
            width = padded_columns(n1)
            return jnp.pad(first, ((0, 0), (0, width - n1)),
                           constant_values=n1).reshape(-1)
        return lead
    return _get("hybrid_lead", build)


def leading_lanes(g, lanes: int):
    """Per-graph cache: the LEADING-LANE image the split-lane opener
    reads, ``lead[k, v] = dstT[k, colstart[v]]`` for k < ``lanes`` and
    v = 0 .. n, the pad vertex n + 1 where ``deg[v] <= k`` (a vertex
    without an edge shares its column with the next vertex, so the
    degree masks it; the sink has none) and in the columns past n that
    round the width up to whole blocks (``vmem_gather.padded_columns``).
    Stored FLAT, row after row: the layout the table-in-VMEM gather
    reads, and one reshape from what XLA's gather reads. With it a
    vertex's first lanes are a contiguous read in vertex order. Built
    once a graph and lane width (ONE random gather, ``lanes`` x (n + 1)
    reads) and kept in the graph dict: 2 x 4 x 8.87 M = 71 MB at
    graph500-24."""
    key = f"_lead{lanes}"
    got = g.get(key)
    if got is None:
        got = g[key] = _lead_image()(g["dstT"], g["colstart"], g["deg"],
                                     lanes=lanes)
    return got


def _bu_startL():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "lanes",
                                            "impl"),
                           donate_argnums=(0,))
        def bu0a(dist, level, lead, deg, degc, c_cap: int, n_: int,
                 lanes: int, impl: str):
            """Split-lane bottom-up opener: a ``lanes``-wide chunk-0
            bitmap test of every candidate (unvisited, with an edge),
            run on the VERTEX SET: n-wide, in vertex order, over
            ``lead`` = leading_lanes(g, lanes). No list going in: the
            lanes are a contiguous read, ``dist`` is written
            elementwise, and the one compaction is that of the
            UNTESTED — candidates that miss the tested lanes AND have
            deg > lanes, ids ascending, at most ``c_cap`` of them
            (their remaining lanes may still hit: _bu_finish_chunk0
            decides them at a host-sized cap); deg <= lanes misses are
            decided (pad lanes never hit). Level-end stats under
            lax.cond when no untested remain (then no bu_more survivors
            can exist either, since degc > 1 implies deg > 8).

            Its cost is n's, whatever the candidates' count. Testing a
            LIST of the candidates instead (an n-wide compaction, then
            a slot a column gather and a bitmap gather a lane, a
            scatter into dist and a second compaction) lost at every
            point measured on the chip (experiments/bu_dense_probe.py,
            graph500-24's heavy level, n = 8.87 M, 2 lanes, ms a call,
            the candidates thinned by hand to 2^24, 2^23, 2^22, 2^21
            slots: 946.8, 547.0, 330.1, 198.3 against 223.4, 220.0,
            223.5, 203.2 under ``impl="xla"`` and 115.7, 115.6, 115.7,
            95.3 under ``"vmem"``: PERF.md 6, PR 39); below 2^21
            candidates ``bu0`` opens.

            ``impl`` says what serves the frontier test's random reads
            (``_frontier_test``)."""
            fbits = _pack_bits(dist, level, n_)
            unvis = (dist >= INF) & (degc > 0)              # [n + 1]
            first = lead.reshape(lanes, -1)
            hit = _frontier_test(dist, level, n_, impl, first.shape[1],
                                 fbits)(first)[:n_ + 1]
            found = unvis & hit
            dist = jnp.where(found, level + 1, dist)
            untested = unvis & ~found & (deg > lanes)
            nu = untested.sum().astype(jnp.int32)

            def compact(_):
                return compact_ids(untested, c_cap, n_)[1]

            def no_compact(_):
                return jnp.full((c_cap,), n_, jnp.int32)

            cand2 = jax.lax.cond(nu > 0, compact, no_compact, None)
            st = jax.lax.cond(
                nu == 0,
                lambda _: _level_stats(dist, degc, level, n_),
                lambda _: jnp.zeros((4,), jnp.int32), None)
            return dist, fbits, cand2, jnp.stack([nu]), st
        return bu0a
    return _get("hybrid_bu_startL", build)


def _bu_finish_chunk0():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "impl"),
                           donate_argnums=(0,))
        def bu0b(dist, fbits, cand, level, dstT, colstart, degc,
                 c_cap: int, n_: int, impl: str):
            """Finish chunk 0 for the split-lane opener's untested
            candidates: fetch the FULL chunk (all 8 lanes — an
            offset row slice like ``dstT[lo:]`` does NOT fuse into the
            gather: XLA materializes it as a row-count/8 copy of the
            whole 9GB edge array, measured as an 8.4G HLO-temp OOM at
            scale 26; only leading slices ``dstT[:k]`` fuse. The
            already-tested lanes re-test as guaranteed misses at a few
            percent extra lane work on a small cap), scatter the hits,
            compact the full-chunk-0 misses with degc > 1 for the
            bu_more rounds (off starts at 1 — chunk 0 is consumed).
            ``bu0a`` wrote only ``level + 1`` into ``dist``, so ``dist
            == level`` is still the frontier ``fbits`` was packed
            from."""
            q_pad = dstT.shape[1] - 1
            c_count = (cand < n_).sum().astype(jnp.int32)
            alive = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            cols = jnp.where(alive, colstart[v], q_pad)
            parents_hi = jnp.take(dstT, jnp.clip(cols, 0, q_pad),
                                  axis=1)
            found = alive & _frontier_test(dist, level, n_, impl, c_cap,
                                           fbits)(parents_hi)
            dist = dist.at[jnp.where(found, v, n_ + 1)].set(
                level + 1, mode="drop")
            # gathered once: a branch of lax.cond would gather it again
            chunks = degc[v]
            surv = alive & ~found & (chunks > 1)
            nc = surv.sum().astype(jnp.int32)

            def compact(_):
                _, (cand2,) = scatter_compact(surv, (cand,), c_cap,
                                              (n_,))
                rem8 = jnp.where(surv, chunks - 1, 0) \
                    .sum(dtype=jnp.int32)
                return cand2, rem8

            def no_compact(_):
                return jnp.full((c_cap,), n_, jnp.int32), jnp.int32(0)

            cand2, rem8 = jax.lax.cond(nc > 0, compact, no_compact, None)
            st = jax.lax.cond(
                nc == 0,
                lambda _: _level_stats(dist, degc, level, n_),
                lambda _: jnp.zeros((4,), jnp.int32), None)
            return dist, cand2, jnp.stack([nc, rem8]), st
        return bu0b
    return _get("hybrid_bu_finish0", build)


def _bu_more():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "fuse",
                                            "impl"),
                           donate_argnums=(0,))
        def bu(dist, fbits, cand, off, prog, level, dstT, colstart,
               degc, c_cap: int, n_: int, fuse: int, impl: str):
            """``fuse`` chunk-check rounds over the compacted survivor
            list (frontier hit test), with the level-end stats under
            lax.cond when the survivors die out inside."""
            c_count = prog[0]      # survivor count from the DEVICE
            q_pad = dstT.shape[1] - 1      # progress vector (no put)
            # the rounds write level + 1 alone: one image serves them all
            hit_of = _frontier_test(dist, level, n_, impl, c_cap, fbits)

            def round_(state, _):
                dist, cand, off, c_count = state
                alive = jnp.arange(c_cap) < c_count
                v = jnp.minimum(cand, n_)
                cols = jnp.where(alive, colstart[v] + off, q_pad)
                parents = jnp.take(dstT, jnp.clip(cols, 0, q_pad),
                                   axis=1)
                found = alive & hit_of(parents)
                dist = dist.at[jnp.where(found, v, n_ + 1)].set(
                    level + 1, mode="drop")
                surv = alive & ~found & (off + 1 < degc[v])
                nc = surv.sum().astype(jnp.int32)
                # survivor list + its chunk cursor compacted through
                # ONE shared index (scatter_compact fuses the pair)
                _, (cand, off) = scatter_compact(
                    surv, (cand, off + 1), c_cap, (n_, 0))
                return (dist, cand, off, nc), None

            (dist, cand, off, c_count), _ = jax.lax.scan(
                round_, (dist, cand, off, c_count), None, length=fuse)
            alive = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            rem = jnp.where(alive, jnp.maximum(degc[v] - off, 0), 0) \
                .sum(dtype=jnp.int32)
            st = jax.lax.cond(
                c_count == 0,
                lambda _: _level_stats(dist, degc, level, n_),
                lambda _: jnp.zeros((4,), jnp.int32), None)
            return dist, cand, off, jnp.stack([c_count, rem]), st
        return bu
    return _get("hybrid_bu_more", build)


def _bu_exhaust():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "p_cap", "n_",
                                            "impl"),
                           donate_argnums=(0,))
        def ex(dist, fbits, cand, off, prog, level, dstT, colstart,
               degc, c_cap: int, p_cap: int, n_: int, impl: str):
            """One masked sweep over ALL remaining chunks of the surviving
            candidates (rare: frontier-less hubs / small components), then
            the level-end stats (always needed here)."""
            c_count = prog[0]
            valid = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            rem = jnp.maximum(degc[v] - off, 0)
            cols, p_total, owner = enumerate_chunk_pairs(
                valid, rem, colstart[v] + off, p_cap, dstT.shape[1] - 1,
                with_owner=True)
            parents = jnp.take(dstT, cols, axis=1)       # [8, p_cap]
            hit = _frontier_test(dist, level, n_, impl, p_cap,
                                 fbits)(parents)          # [p_cap]
            # per-candidate any-hit: scatter-max of hit through the
            # pair -> candidate mapping
            j = jnp.arange(p_cap, dtype=jnp.int32)
            found_per = jnp.zeros((c_cap,), jnp.int32) \
                .at[jnp.where(j < p_total, owner, c_cap - 1)] \
                .max(hit.astype(jnp.int32), mode="drop")
            found = valid & (found_per > 0)
            dist = dist.at[jnp.where(found, v, n_ + 1)].set(
                level + 1, mode="drop")
            return dist, _level_stats(dist, degc, level, n_)
        return ex
    return _get("hybrid_ex", build)


def _endgame():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "p_cap", "n_",
                                            "impl"),
                           donate_argnums=(0,))
        def end(dist, level0, max_lv, dstT, colstart, degc, c_cap: int,
                p_cap: int, n_: int, impl: str):
            """Finish the BFS: run EVERY remaining level in one dispatch.
            Each iteration is a full bottom-up level over the (shrinking)
            unvisited set — candidate count and chunk mass are bounded by
            the entry caps, so shapes are static and the loop needs no
            host round trips. The candidate list is built ONCE (one
            n-scale scatter compaction) and re-compacted at c_cap
            width between
            iterations. Terminates when a level finds nothing.
            Caller guarantee: n_unvis <= c_cap and m8_unvis <= p_cap."""
            q_pad = dstT.shape[1] - 1

            def cond(s):
                _, _, _, level, found, _ = s
                return (found > 0) & (level < max_lv)

            def body(s):
                dist, cand, c_count, level, _, iters = s
                valid = jnp.arange(c_cap) < c_count
                v = jnp.minimum(cand, n_)
                cols, p_total, owner = enumerate_chunk_pairs(
                    valid, degc[v], colstart[v], p_cap, q_pad,
                    with_owner=True)
                parents = jnp.take(dstT, cols, axis=1)
                # the frontier's image is this level's own: made in
                # the body, an n-wide elementwise pass
                hit = _frontier_test(dist, level, n_, impl,
                                     p_cap)(parents)
                j = jnp.arange(p_cap, dtype=jnp.int32)
                found_per = jnp.zeros((c_cap,), jnp.int32) \
                    .at[jnp.where(j < p_total, owner, c_cap - 1)] \
                    .max(hit.astype(jnp.int32), mode="drop")
                found = valid & (found_per > 0)
                dist = dist.at[jnp.where(found, v, n_ + 1)].set(
                    level + 1, mode="drop")
                nfound = found.sum().astype(jnp.int32)
                # compact survivors at c_cap width (no n-scale pass)
                surv = valid & ~found
                nc = surv.sum().astype(jnp.int32)
                _, (cand,) = scatter_compact(surv, (v,), c_cap, (n_,))
                return (dist, cand, nc, level + 1, nfound,
                        iters + (nfound > 0).astype(jnp.int32))

            unvis = (dist[:n_] >= INF) & (degc[:n_] > 0)
            c0, cand0 = compact_ids(unvis, c_cap, n_)
            state = (dist, cand0, c0, level0, jnp.int32(1), jnp.int32(0))
            dist, _, _, _, _, iters = jax.lax.while_loop(cond, body,
                                                         state)
            return dist, iters
        return end
    return _get("hybrid_endgame", build)


def _frontier_of():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def fr(dist, level, n_: int):
            # scatter compaction, not nonzero: the n-wide nonzero here
            # measured ~0.9s at scale 26 (see ops/compaction.py)
            changed = dist[:n_] == level
            return compact_ids(changed, n_, n_)[1]
        return fr
    return _get("hybrid_frontier_of", build)


# --------------------------------------------------------------------------
# batched multi-source BFS: K concurrent jobs share one device run
# --------------------------------------------------------------------------
#
# The serving layer (olap/serving) fuses K same-snapshot BFS jobs into one
# batched run with state widened to [K, n+1]: the per-level n-scale plan
# (candidate compaction + per-job frontier stats) runs ONCE for all K jobs
# instead of once per job, and every edge-chunk gather from the
# HBM-resident dstT is read once and tested against all K frontier
# bitmaps (each n/8 bytes — the cache-resident fast-gather regime). That
# amortizes the per-round plan floor K-fold (PERF_NOTES "K-way
# plan-amortization model"). Each level runs in one of two directions,
# chosen for the whole batch from the counts the plan reads back
# (``_td_cap``): bottom-up (level-synchronous pull over the shared
# candidate list: n-wide rounds whatever the frontier) or top-down
# (``_batched_td``: a push from the (job, vertex) pairs of the frontier,
# which costs the frontier's chunks). BFS distances and hop sets are
# canonical, so dist[k] is bit-equal whichever direction a level took.
# SYMMETRIC graphs only (module contract above); a layout that says it
# holds one orientation of a directed graph (``"directed": True``)
# pulls at every level.


def _with_parents(dist, par):
    """The state the batched programs take and hand back in their first
    position: ``dist`` alone, or the pair ``(dist, par)`` where the run
    keeps the BFS tree (``par`` [K, n+1] int32: the vertex a job's search
    reached a vertex FROM, the source its own, -1 where none yet). The
    form is part of a program's signature as a static flag would be, so
    a run without parents traces, builds and donates exactly what it did
    before the plane existed."""
    return dist if par is None else (dist, par)


def _split_state(state) -> tuple:
    """``(dist, par)`` of a program's state, ``par`` None where the run
    keeps no parents."""
    return state if isinstance(state, tuple) else (state, None)


def _fbits_bytes(n_: int) -> int:
    """Bytes of one job's frontier bitmap (``_pack_bits_batched``)."""
    return (n_ + 2 + 7) // 8


def _pack_bits_batched(dist, active, level, n_: int):
    """[K, nbytes] frontier bitmaps: bit v of row k = (dist[k, v] ==
    level and job k is active). Inactive jobs get an all-zero row, so
    the hit tests below can never find anything for them — the per-job
    early-exit/cancellation mask is exactly this zeroing."""
    import jax.numpy as jnp

    K = dist.shape[0]
    nbytes = _fbits_bytes(n_)
    mask = (dist == level) & active[:, None]
    mask = jnp.concatenate([mask, jnp.zeros((K, 8), bool)], axis=1)
    return jnp.packbits(mask[:, :nbytes * 8], axis=1, bitorder="little")


def _bit_of_batched(fbits, idx):
    """Test all K bitmaps at shared int32 indices: fbits [K, nbytes],
    idx [...] -> bool [K, *idx.shape]. One index expression serves every
    job (the byte gather fans out along the job axis only)."""
    import jax.numpy as jnp

    w = jnp.take(fbits, idx >> 3, axis=1)
    return ((w >> (idx & 7).astype(jnp.uint8)) & jnp.uint8(1)) \
        .astype(bool)


def _batched_plan():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "expand"))
        def bplan(dist, active, level, degc, c_cap: int, n_: int,
                  expand: bool = False):
            """ONE n-scale pass serving all K jobs: the per-job frontier
            counts (early-exit decisions), the SHARED candidate list
            (vertices unvisited in ANY active job, deg > 0 — one
            compaction amortized over K), and the per-job frontier
            bitmaps for the bottom-up hit tests. Stats read back:
            ``[c_count, nf[K], mass[K]]``.

            ``expand`` (hops mode, olap/serving/interactive): every
            vertex of an active job is a candidate every level — the
            sweep computes the exact next-hop frontier SET instead of
            BFS levels, so already-stamped vertices stay reachable
            again at later hops."""
            fbits = _pack_bits_batched(dist, active, level, n_)
            if expand:
                unvis = jnp.broadcast_to(active[:, None],
                                         (dist.shape[0], n_))
            else:
                unvis = (dist[:, :n_] >= INF) & active[:, None]
            front = (dist[:, :n_] == level) & active[:, None]
            nf = front.sum(axis=1).astype(jnp.int32)
            # the frontier's chunk mass per job: what a push would
            # touch (the direction rule's input, _td_cap)
            mass = jnp.where(front, degc[:n_], 0).sum(
                axis=1, dtype=jnp.int32)
            cand_mask = unvis.any(axis=0) & (degc[:n_] > 0)
            c_count, cand = compact_ids(cand_mask, c_cap, n_ + 1)
            return fbits, cand, jnp.concatenate(
                [c_count[None], nf, mass])
        return bplan
    return _get("batched_plan", build)


def _batched_bu():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "n_", "fuse",
                                            "masked", "expand"),
                           donate_argnums=(0,))
        def bstep(state, fbits, cand, off, prog, level, dstT, colstart,
                  degc, tbits, c_cap: int, n_: int, fuse: int,
                  masked: bool = False, expand: bool = False):
            """``fuse`` chunk-check rounds over the shared candidate
            list (``cand``, ``off``: any width from ``c_cap`` up, the
            first ``c_cap`` read here and the lists handed back at the
            width they came in, so the host slices and pads nothing):
            chunk ``off`` of each candidate is gathered ONCE and
            tested against all K bitmaps; per-job finds scatter into
            dist rows; a candidate survives while it has chunks left
            AND some job still has it undecided. With ``masked``,
            ``tbits`` is the live overlay's tombstone bitmap over edge
            SLOTS (col*8 + lane): a tombstoned slot never counts as a
            parent — the expansion seam that keeps the base device CSR
            valid under edge removals (olap/live).

            ``expand`` (hops mode): no visited mask — every alive
            candidate with a chunk neighbor in a job's frontier joins
            that job's next hop, stamped ``level + 1`` via max-scatter
            (monotone in level, so re-reached vertices re-stamp; the
            0 scatter for misses is the max-identity no-op). A
            candidate retires once every LIVE job (nonzero frontier
            bitmap — deactivated/pad rows never hit and must not pin
            candidates through all their chunks) has stamped it this
            level.

            ``state`` (``_with_parents``): with a parent plane, the lane
            that hit names the parent: of a chunk's lanes in a job's
            frontier the largest id (a max over the eight the test
            already holds: any of them is a valid parent, and the rounds
            still stop at the first chunk that hits), scattered where
            the depth is."""
            dist, par = _split_state(state)
            c_count = prog[0]
            q_pad = dstT.shape[1] - 1
            live = (fbits != 0).any(axis=1) if expand else None  # [K]
            room = (0, cand.shape[0] - c_cap)
            cand, off = cand[:c_cap], off[:c_cap]

            def round_(state, _):
                dist, par, cand, off, c_count = state
                alive = jnp.arange(c_cap) < c_count
                v = jnp.minimum(cand, n_)
                cols = jnp.where(alive & (off < degc[v]),
                                 colstart[v] + off, q_pad)
                parents = jnp.take(dstT, jnp.clip(cols, 0, q_pad),
                                   axis=1)                 # [8, c_cap]
                hitl = _bit_of_batched(fbits, parents)     # [K, 8, c_cap]
                if masked:
                    lane = jnp.arange(8, dtype=jnp.int32)[:, None]
                    slot = jnp.clip(cols, 0, q_pad)[None, :] * 8 + lane
                    hitl = hitl & ~_bit_of(tbits, slot)[None]
                hit = hitl.any(axis=1)                     # [K, c_cap]
                if expand:
                    undec = (dist[:, v] != level + 1) & live[:, None]
                    found = undec & hit & alive[None, :]
                    dist = dist.at[:, jnp.where(alive, v, n_ + 1)].max(
                        jnp.where(found, level + 1, 0), mode="drop")
                else:
                    undec = dist[:, v] >= INF
                    found = undec & hit & alive[None, :]
                    to = jnp.where(alive, v, n_ + 1)
                    dist = dist.at[:, to].min(
                        jnp.where(found, level + 1, INF), mode="drop")
                    if par is not None:
                        via = jnp.where(hitl, parents[None], -1) \
                            .max(axis=1)                   # [K, c_cap]
                        par = par.at[:, to].max(
                            jnp.where(found, via, -1), mode="drop")
                rem = (undec & ~hit).any(axis=0)
                surv = alive & rem & (off + 1 < degc[v])
                nc = surv.sum().astype(jnp.int32)
                _, (cand2, off2) = scatter_compact(
                    surv, (cand, off + 1), c_cap, (n_ + 1, 0))
                return (dist, par, cand2, off2, nc), None

            (dist, par, cand, off, c_count), _ = jax.lax.scan(
                round_, (dist, par, cand, off, c_count), None,
                length=fuse)
            alive = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            rem8 = jnp.where(alive, jnp.maximum(degc[v] - off, 0), 0) \
                .sum(dtype=jnp.int32)
            return (_with_parents(dist, par),
                    jnp.pad(cand, room, constant_values=n_ + 1),
                    jnp.pad(off, room), jnp.stack([c_count, rem8]))
        return bstep
    return _get("batched_bu", build)


def _batched_seed():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("n_", "cap", "expand",
                                            "parents"))
        def bseed(src, start, n_: int, cap: int, expand: bool = False,
                  parents: bool = False):
            """The whole start of a single-start batch in ONE program:
            the ``[K, n+1]`` state (bfs: INF with 0 at each job's
            source; hops: 0 with ``start`` at it, the pad slot INF),
            the all-true job mask, and the start level's frontier as
            the pair list the push reads — the sources ARE the list, so
            nothing is compacted. Returns ``(dist, active, pj, pv,
            count)``; the list's capacity is ``cap`` (the caller hands
            it forward only where ``K <= cap``). With ``parents`` (bfs
            mode) ``dist`` is the pair ``(dist, par)``: the parent plane
            -1 with each job's source its own parent."""
            K = src.shape[0]
            k = jnp.arange(K, dtype=jnp.int32)
            if expand:
                dist = jnp.zeros((K, n_ + 1), jnp.int32) \
                    .at[k, src].set(start).at[:, n_].set(INF)
            else:
                dist = jnp.full((K, n_ + 1), INF, jnp.int32) \
                    .at[k, src].set(0)
            room = max(cap, K)
            pj = jnp.zeros((room,), jnp.int32).at[:K].set(k)[:cap]
            pv = jnp.full((room,), n_, jnp.int32).at[:K].set(src)[:cap]
            if parents:
                dist = (dist, jnp.full((K, n_ + 1), -1, jnp.int32)
                        .at[k, src].set(src))
            return dist, jnp.ones((K,), bool), pj, pv, jnp.int32(K)
        return bseed
    return _get("batched_seed", build)


def _batched_list():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("caps", "n_"))
        def blist(dist, active, level, rung, degc, caps: tuple, n_: int):
            """The scan road to a push: list the (job, vertex) pairs
            with ``dist[k, v] == level`` of active jobs from the state
            itself — what a level does when the program before it left
            no list (after a plan, a pull, a resume; past the list's
            capacity). A pair with no chunk pushes nothing and is not
            listed (``degc[n] = 0`` drops the pad slot too), so pairs
            never outnumber the frontier's chunks.

            A compaction costs its INPUT's width (7 ms a million on a
            v5e: 110 ms at K = 16), so on a rung below n columns: the
            frontier's distinct vertices first (n wide, <= pairs <=
            the rung of them), then their K x rung memberships.
            ``rung`` (device scalar) picks the branch; one executable a
            K holds every rung's. Returns ``(pj, pv, count)`` at the
            ladder's top capacity."""
            K = dist.shape[0]
            row = n_ + 1
            cap = caps[-1]
            front = (dist == level) & active[:, None] & (degc > 0)[None]

            def whole(p_cap):
                def go(_):
                    count, ids = compact_ids(front.ravel(), p_cap,
                                             K * row)
                    return count, ids // row, ids % row
                return go

            def split(p_cap):
                def go(_):
                    _, verts = compact_ids(front.any(axis=0), p_cap, n_)
                    count, ids = compact_ids(
                        jnp.take(front, verts, axis=1).ravel(), p_cap,
                        K * p_cap)
                    return count, ids // p_cap, verts[ids % p_cap]
                return go

            def at_capacity(branch, p_cap):
                # every op above is the rung wide, not the capacity
                # (a gather costs its output's width too)
                def go(_):
                    count, pj, pv = branch(None)
                    room = (0, cap - p_cap)
                    return count, jnp.pad(pj, room), jnp.pad(pv, room)
                return go

            count, pj, pv = jax.lax.switch(
                rung, [at_capacity((whole if c >= row else split)(c), c)
                       for c in caps], None)
            return pj, pv, count
        return blist
    return _get("batched_list", build)


def _batched_td():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("p_cap", "n_", "expand",
                                            "lists"),
                           donate_argnums=(0,))
        def btd(state, pj, pv, count, active, level, want, dstT, colstart,
                degc, p_cap: int, n_: int, expand: bool = False,
                lists: bool = False):
            """One top-down level for all K jobs, from the level's
            frontier as a list of (job, vertex) pairs (``pj``, ``pv``,
            the first ``count`` live; pairs of jobs retired since the
            list was made are masked by ``active[job]`` here, so a mask
            change needs no re-plan): the pairs' chunks are enumerated
            into ``p_cap`` columns, each column gathered ONCE from dstT
            and scattered into its owner's job row: ``min`` of ``level
            + 1`` (bfs: visited entries are smaller and stay), ``max``
            with ``expand`` (hops: the re-stamp contract of bstep). Pad
            lanes (n + 1) and dead columns (the all-pad sink column)
            drop. Caller guarantee (_td_cap): ``count`` and the live
            pairs' chunk mass <= p_cap, and K * (n + 1) < 2^31.

            NO n-wide compaction: on a rung that ``lists`` (static:
            ``_td_lists``) and with ``want`` (device scalar: the level
            after this one will run) the NEXT frontier is deduped
            from the scatter targets at the scatter's own width — lanes
            that reached the same (job, vertex) race on a claim array
            (ops.compaction.claim_dedup; the array is made here, a K x
            (n + 1) fill at HBM bandwidth, so no claim state outlives
            the program), in bfs mode only where ``dist`` read INF
            before the scatter, in hops mode every target (the re-stamp
            contract) — and the winners are compacted 8 x p_cap wide
            into the list the next push takes, with the next level's
            plan statistics beside it (``nf``, ``mass`` from the
            winners; ``c_count`` an n-wide REDUCTION). A rung where
            deduping is dearer than looking makes no list, whatever
            ``want`` says.

            Returns ``(dist, nj, nv, ncount, stats)``; ``stats`` =
            ``[pairs, columns, ncount, c_count, nf[K], mass[K]]`` with
            ``ncount`` = -1 where no list was made (it may exceed the
            capacity: the list is then cut and the caller scans).

            ``state`` (``_with_parents``; bfs mode): with a parent plane
            the pushing vertex of a column is the parent of every lane
            of it that read INF before the scatter; of the pushers that
            reach one vertex in one level the largest id stays (a max:
            any is a valid parent, the max is the same on every run)."""
            dist, par = _split_state(state)
            K = dist.shape[0]
            row = n_ + 1
            cap = pj.shape[0]
            take = min(p_cap, cap)
            valid = jnp.arange(take) < count
            job = jnp.where(valid, pj[:take], 0)
            valid = valid & active[job]
            v = jnp.where(valid, pv[:take], n_)
            cols, p_total, owner = enumerate_chunk_pairs(
                valid, degc[v], colstart[v], p_cap, dstT.shape[1] - 1,
                with_owner=True)
            nbr = jnp.take(dstT, cols, axis=1)           # [8, p_cap]
            rows = jnp.broadcast_to(job[owner][None, :], nbr.shape)
            pushed = jnp.stack([valid.sum().astype(jnp.int32), p_total])
            if lists or par is not None:
                # the dist gather reads PRE-scatter state: duplicates
                # of one new vertex all see INF and race on the claim
                found = nbr < n_
                if not expand:
                    found = found & (
                        dist[rows, jnp.minimum(nbr, n_)] >= INF)
            if par is not None:
                par = par.at[rows, jnp.where(found, nbr, n_ + 1)].max(
                    jnp.broadcast_to(v[owner][None, :], nbr.shape),
                    mode="drop")
            if expand:
                dist = dist.at[rows, nbr].max(level + 1, mode="drop")
            else:
                dist = dist.at[rows, nbr].min(level + 1, mode="drop")
            if not lists:
                none = jnp.zeros((0,), jnp.int32)
                return (_with_parents(dist, par), none, none,
                        jnp.int32(-1), jnp.concatenate(
                            [pushed,
                             jnp.full((2 + 2 * K,), -1, jnp.int32)]))

            def hand_on(_):
                key = jnp.where(found, rows * row + nbr, K * row)
                lane = jnp.arange(8 * p_cap, dtype=jnp.int32) \
                    .reshape(8, p_cap)
                _, won = claim_dedup(
                    jnp.full((K * row,), CLAIM_SENTINEL, jnp.int32),
                    key, lane)
                won = won & found
                degn = degc[jnp.minimum(nbr, n_)]
                mine = won[None] & (
                    rows[None] == jnp.arange(K)[:, None, None])
                nf = mine.sum(axis=(1, 2), dtype=jnp.int32)
                mass = jnp.where(mine, degn[None], 0).sum(
                    axis=(1, 2), dtype=jnp.int32)
                ncount, (nj, nv) = scatter_compact(
                    won.ravel(), (rows.ravel(), nbr.ravel()), cap,
                    (0, n_))
                if expand:
                    unvis = degc[:n_] > 0
                else:
                    unvis = ((dist[:, :n_] >= INF) & active[:, None]) \
                        .any(axis=0) & (degc[:n_] > 0)
                c_count = unvis.sum().astype(jnp.int32)
                return nj, nv, ncount, jnp.concatenate(
                    [ncount[None], c_count[None], nf, mass])

            def leave(_):
                return (jnp.zeros((cap,), jnp.int32),
                        jnp.full((cap,), n_, jnp.int32), jnp.int32(-1),
                        jnp.full((2 + 2 * K,), -1, jnp.int32))

            nj, nv, ncount, nxt = jax.lax.cond(want != 0, hand_on,
                                               leave, None)
            return (_with_parents(dist, par), nj, nv, ncount,
                    jnp.concatenate([pushed, nxt]))
        return btd
    return _get("batched_td", build)


def hop_extract():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def bext(dist, want, n_: int):
            """The lane's hop sets from the state as the level loop
            leaves it (``[K, n+1]``, sliced here): row k's set is
            ``dist[k] == want[k]``. Returns ``(masks [K, n] bool,
            sizes [K])``."""
            masks = dist[:, :n_] == want[:, None]
            return masks, masks.sum(axis=1, dtype=jnp.int32)
        return bext
    return _get("batched_extract", build)


def _td_caps(g) -> tuple:
    """The ladder of one layout, lowest rung first: TD_RUNG_SHIFTS of
    the largest power of two at or below half its chunk columns."""
    top = 1 << max((int(g["q_total"]) // 2).bit_length() - 1, 1)
    return tuple(sorted({max(top >> s, 2) for s in TD_RUNG_SHIFTS}))


def _bu_caps(g) -> tuple:
    """The pull's ladders of one layout, each lowest rung first:
    ``(c_caps, ex_pairs)``. ``c_caps``: ``bstep``'s candidate caps,
    BU_RUNG_SHIFTS of the power of two at or above n. ``ex_pairs``:
    every ``(c_cap, p_cap)`` a ``bex`` can take: its ``c_cap`` the
    lowest or the top of ``c_caps``, its ``p_cap`` EX_RUNG_SHIFTS of
    the power of two at or above the chunk columns; a survivor has a
    chunk left, so a pair whose ``p_cap`` lies under the rung below its
    ``c_cap`` is never taken and not in the set."""
    top = _next_pow2(max(g["n"], 2))
    c_caps = tuple(sorted({max(top >> s, 2) for s in BU_RUNG_SHIFTS}))
    ptop = _next_pow2(max(int(g["q_total"]), 2))
    p_caps = sorted({max(ptop >> s, 2) for s in EX_RUNG_SHIFTS})
    ex_c = sorted({c_caps[0], c_caps[-1]})
    return c_caps, tuple(
        (c, p) for i, c in enumerate(ex_c) for p in p_caps
        if i == 0 or ex_c[i - 1] < p)


def _rung(caps, count: int) -> int:
    """The lowest rung of an ascending ladder that holds ``count``."""
    return next(cap for cap in caps if count <= cap)


def _bu_fuse(expand: bool, rounds: int) -> int:
    """How many of a pulled level's BU_CHUNK_ROUNDS chunk rounds the next
    ``bstep`` runs in one dispatch, ``rounds`` of them behind it. A BFS
    level: ONE, and the host looks at what it left: a round costs its
    rung's lanes whoever is still alive in them, and on an undirected
    graph the first round decides nearly every candidate (graph500-22,
    six drawn sources: 0 to 8 survivors of 0.7-2.2 M candidates; CPU
    count, PR 49), so seven rounds fused behind it swept dead lanes for
    seven eighths of a job's time. Each later round runs on the rung its
    survivors take. A hop's level (``expand``): all that are left, as
    ever: a candidate there retires only once EVERY live job has
    stamped it, so the rounds rarely thin out and a readback between
    them buys nothing."""
    return BU_CHUNK_ROUNDS - rounds if expand else 1


def _ex_rung(pairs, c_count: int, rem8: int) -> tuple:
    """The ``(c_cap, p_cap)`` a ``bex`` over ``c_count`` survivors with
    ``rem8`` chunk columns left takes."""
    c_cap = _rung(sorted({c for c, _ in pairs}), c_count)
    return c_cap, _rung([p for c, p in pairs if c == c_cap], rem8)


def _td_lists(p_cap: int, n: int) -> bool:
    """Whether a push on rung ``p_cap`` hands the next level its list
    (and statistics): while deduping its 8 x p_cap lanes is cheaper
    than listing the next frontier from dist, n wide."""
    return 8 * p_cap * TD_DEDUP_COST < n


def _td_cap(g, K: int, mass: int, c_count: int, masked: bool,
            rounds: int = BU_CHUNK_ROUNDS):
    """The direction rule of a batched level, from what the plan read
    back and what the layout and masks say: the rung (``p_cap``) a
    top-down step takes, or None for bottom-up. Top-down when (a) the
    frontier's chunk mass fits the top rung (and flat (job, vertex)
    ids fit int32), (b) the layout is its own transpose — not one
    orientation of a directed graph, whose columns hold parents, not
    children, (c) no slot bitmap (tombstones, a hop's label mask) is in
    force this level, (d) the cohort is not mesh-placed, and (e) the
    push is the cheaper side: against a pull whose first dispatch runs
    ``rounds`` chunk rounds over every candidate (``_bu_fuse``)."""
    if masked or g.get("directed") or "_mesh" in g:
        return None
    if K * (g["n"] + 1) >= (1 << 31):
        return None
    if mass * TD_BU_COST > rounds * c_count:
        return None
    return next((cap for cap in _td_caps(g) if mass <= cap), None)


def _seed_stats(g: dict, src, expand: bool):
    """What ``bplan`` would read back at the start level of a
    single-start batch, from the layout's host copy of ``degc`` and no
    readback: ``[c_count, nf[K], mass[K]]``. Every job's frontier is its
    source; every vertex with an edge is a candidate (hops), or one
    unvisited in SOME job (bfs: all but the source that every job
    shares)."""
    degc = g["_host"]["degc"]
    nz = g.get("_nz")
    if nz is None:
        nz = g["_nz"] = int(np.count_nonzero(degc))
    mass = degc[src]
    shared = not expand and bool((src == src[0]).all() and mass[0] > 0)
    return np.concatenate([[nz - shared], np.ones(len(src)), mass]) \
        .astype(np.int32)


def warm_batched_td(g, K: int, expand: bool) -> None:
    """Run the plan, the scan road's listing and every rung of the
    top-down step once at batch size ``K`` on an empty frontier, so that
    no level of a later batch of that size builds (or loads) an
    executable: a level's rung follows its mass and its road follows
    what the level before left, which a warm-up by batch sizes cannot
    cover."""
    from titan_tpu.utils.jitcache import dev_scalar

    n, degc, caps = g["n"], g["degc"], _td_caps(g)
    dist, active, *_ = _batched_seed()(
        np.zeros(K, np.int32), dev_scalar(1), n_=n, cap=caps[-1],
        expand=expand)
    level = dev_scalar(2)               # nothing is stamped 2
    _batched_plan()(dist, active, level, degc,
                    c_cap=_next_pow2(max(n, 2)), n_=n, expand=expand)
    pj, pv, count = _batched_list()(dist, active, level, dev_scalar(0),
                                    degc, caps=caps, n_=n)
    for cap in caps:
        dist, *_ = _batched_td()(dist, pj, pv, count, active, level,
                                 dev_scalar(1), g["dstT"], g["colstart"],
                                 degc, p_cap=cap, n_=n, expand=expand,
                                 lists=_td_lists(cap, n))
    dist.block_until_ready()


#: the (n, chunk columns, K, expand, parents) ``warm_batched`` has run
#: at: the executables are keyed by a layout's shape, not by the layout,
#: and live as long as the process
_WARMED: set = set()
#: threads ``warm_batched`` builds on
WARM_THREADS = 4


def batched_is_warm(g, K: int, expand: bool = False,
                    parents: bool = False) -> bool:
    return (g["n"], int(g["q_total"]), K, expand, parents) in _WARMED


def warm_batched(g, K: int, expand: bool = False,
                 parents: bool = False) -> None:
    """Build (or load) every executable a batch of size ``K`` can meet
    on this layout, unmasked and off a mesh, before its first level: the
    seed, the plan, the scan road's listing, every rung of the push
    (what ``warm_batched_td`` runs for the lane) and the pull's finite
    set (``_bu_caps``): ``bstep`` on every rung of its ladder and
    ``bex`` on every pair of its, each once over dead lanes. So the
    programs a source meets follow from the layout alone, whatever its
    levels weigh. With ``parents`` the state every program takes is the
    pair (``_with_parents``), which is a set of executables of its own
    (the plan and the listing read ``dist`` alone and are shared).

    The programs are built SIDE BY SIDE on a few threads: each call
    below is independent of every other (its own state from the seed,
    empty lists made here), and a build is the compiler's time, outside
    the interpreter's lock: graph500-22's twenty-three programs are some
    260 s of builds one after another (PERF.md 6, PR 49), which
    matters to the job that waits for them. Once a shape of layout, K,
    mode and state in a process (``batched_is_warm``)."""
    import contextlib
    import os
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    from titan_tpu.obs import tracing
    from titan_tpu.utils.jitcache import dev_scalar

    if batched_is_warm(g, K, expand, parents):
        return
    n, degc, dstT, colstart = g["n"], g["degc"], g["dstT"], g["colstart"]
    caps = _td_caps(g)
    c_caps, ex_pairs = _bu_caps(g)
    # fetched here: a program's first ``jit_once`` must not race
    bseed, bplan, blist = _batched_seed(), _batched_plan(), _batched_list()
    btd, bstep, bex = _batched_td(), _batched_bu(), _batched_exhaust()
    level, zero = dev_scalar(2), dev_scalar(0)  # nothing is stamped 2
    src = np.zeros(K, np.int32)

    def seed():
        return bseed(src, dev_scalar(1), n_=n, cap=caps[-1], expand=expand,
                     **({"parents": True} if parents else {}))

    jax.block_until_ready(seed()[0])    # the one every other call needs
    # what a pull reads where no plan ran: no frontier bit, no candidate
    fbits = jnp.zeros((K, _fbits_bytes(n)), jnp.uint8)
    cand = jnp.full((c_caps[-1],), n + 1, jnp.int32)
    off = jnp.zeros((c_caps[-1],), jnp.int32)
    prog = jnp.asarray([0, 0], jnp.int32)
    tbits = jnp.zeros((1,), jnp.uint8)

    def plan():
        state, active, *_ = seed()
        return bplan(_split_state(state)[0], active, level, degc,
                     c_cap=c_caps[-1], n_=n, expand=expand)

    def listing():
        state, active, *_ = seed()
        return blist(_split_state(state)[0], active, level, zero, degc,
                     caps=caps, n_=n)

    def push(p_cap):
        def go():
            dist, active, pj, pv, count = seed()
            return btd(dist, pj, pv, count, active, level, dev_scalar(1),
                       dstT, colstart, degc, p_cap=p_cap, n_=n,
                       expand=expand, lists=_td_lists(p_cap, n))
        return go

    def pull(c_cap):
        def go():
            return bstep(seed()[0], fbits, cand, off, prog, level, dstT,
                         colstart, degc, tbits, c_cap=c_cap, n_=n,
                         fuse=_bu_fuse(expand, 0), masked=False,
                         expand=expand)
        return go

    def exhaust(c_cap, p_cap):
        def go():
            return bex(seed()[0], fbits, cand, off, prog, level, dstT,
                       colstart, degc, tbits, c_cap=c_cap, p_cap=p_cap,
                       n_=n, masked=False, expand=expand)
        return go

    # the longest builds first (the listing holds every rung's branch)
    calls = [listing, plan] + [push(c) for c in reversed(caps)] \
        + [pull(c) for c in reversed(c_caps)] \
        + [exhaust(c, p) for c, p in reversed(ex_pairs)]
    where = tracing.current_span()

    def build(call):
        # a build's ``compile`` span and the call's ``kernel`` span
        # journal where the caller stands, not in a trace of their own;
        # the call is awaited here and its outputs dropped, so no more
        # than the pool's width of them are ever pending or alive
        with tracing.scope(*where) if where is not None \
                else contextlib.nullcontext():
            jax.block_until_ready(call())

    with ThreadPoolExecutor(min(WARM_THREADS, os.cpu_count() or 1),
                            thread_name_prefix="bfs-build") as pool:
        list(pool.map(build, calls))
    _WARMED.add((g["n"], int(g["q_total"]), K, expand, parents))


def _batched_exhaust():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("c_cap", "p_cap", "n_",
                                            "masked", "expand"),
                           donate_argnums=(0,))
        def bex(state, fbits, cand, off, prog, level, dstT, colstart,
                degc, tbits, c_cap: int, p_cap: int, n_: int,
                masked: bool = False, expand: bool = False):
            """One masked sweep over ALL remaining chunks of the
            surviving candidates (hub stragglers; the first ``c_cap``
            of ``cand`` / ``off``), per-job any-hit via a shared owner
            scatter. ``masked``/``tbits``: tombstoned slots never hit
            (see _batched_bu). With a parent plane (``state``:
            ``_with_parents``) the owner scatter carries the largest
            frontier id of a survivor's remaining lanes, which says both
            that one hit and who."""
            dist, par = _split_state(state)
            c_count = prog[0]
            cand, off = cand[:c_cap], off[:c_cap]
            valid = jnp.arange(c_cap) < c_count
            v = jnp.minimum(cand, n_)
            rem = jnp.maximum(degc[v] - off, 0)
            cols, p_total, owner = enumerate_chunk_pairs(
                valid, rem, colstart[v] + off, p_cap,
                dstT.shape[1] - 1, with_owner=True)
            parents = jnp.take(dstT, cols, axis=1)       # [8, p_cap]
            hitl = _bit_of_batched(fbits, parents)       # [K, 8, p_cap]
            if masked:
                lane = jnp.arange(8, dtype=jnp.int32)[:, None]
                slot = cols[None, :] * 8 + lane
                hitl = hitl & ~_bit_of(tbits, slot)[None]
            hit = hitl.any(axis=1)                       # [K, p_cap]
            j = jnp.arange(p_cap, dtype=jnp.int32)
            own = jnp.where(j < p_total, owner, c_cap - 1)
            found_per = jnp.zeros((dist.shape[0], c_cap), jnp.int32) \
                .at[:, own].max(hit.astype(jnp.int32), mode="drop")
            if expand:
                found = (found_per > 0) & valid[None, :]
                return dist.at[:, jnp.where(valid, v, n_ + 1)].max(
                    jnp.where(found, level + 1, 0), mode="drop")
            undec = dist[:, v] >= INF
            found = undec & (found_per > 0) & valid[None, :]
            to = jnp.where(valid, v, n_ + 1)
            dist = dist.at[:, to].min(
                jnp.where(found, level + 1, INF), mode="drop")
            if par is not None:
                via = jnp.full((dist.shape[0], c_cap), -1, jnp.int32) \
                    .at[:, own].max(
                        jnp.where(hitl, parents[None], -1).max(axis=1),
                        mode="drop")
                par = par.at[:, to].max(jnp.where(found, via, -1),
                                        mode="drop")
            return _with_parents(dist, par)
        return bex
    return _get("batched_ex", build)


def _overlay_scatter_batched():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("cap", "n_", "expand"),
                           donate_argnums=(0,))
        def oscat(state, fbits, ov_src, ov_dst, level, cap: int,
                  n_: int, expand: bool = False):
            """Delta-COO expansion pass: for every live overlay edge
            (u, v), jobs whose frontier bitmap holds u scatter
            level+1 into v — the add-edge half of the overlay seam
            (tombstones mask the base pull; this pushes the adds).
            Pad entries (n+1) miss every bitmap and drop from the
            scatter; min keeps earlier levels, so the pass composes
            with the base sweep in any order. ``expand`` (hops mode):
            max-scatter of the hop stamp instead — same monotone
            re-stamp contract as the base sweep. With a parent plane
            (``state``: ``_with_parents``; bfs mode) an overlay edge
            that reaches a vertex whose depth read INF before this pass
            is its parent (of several, the largest source)."""
            dist, par = _split_state(state)
            hit = _bit_of_batched(fbits, ov_src)          # [K, cap]
            if expand:
                return dist.at[:, ov_dst].max(
                    jnp.where(hit, level + 1, 0), mode="drop")
            if par is not None:
                new = hit & (dist[:, jnp.minimum(ov_dst, n_)] >= INF)
                par = par.at[:, ov_dst].max(
                    jnp.where(new, ov_src[None, :], -1), mode="drop")
            msg = jnp.where(hit, level + 1, INF)
            return _with_parents(
                dist.at[:, ov_dst].min(msg, mode="drop"), par)
        return oscat
    return _get("batched_overlay_scatter", build)


def frontier_bfs_batched(snap_or_graph, sources, max_levels: int = 1000,
                         on_level=None, return_device: bool = False,
                         init_dist=None, start_level: int = 0,
                         checkpoint=None, overlay=None,
                         mode: str = "bfs", level_masks=None,
                         parents: bool = False, init_parent=None):
    """Batched multi-source BFS: run K BFS jobs over the SAME graph as
    one device run with [K, n] state. Each job's ``dist`` row is
    bit-equal to ``frontier_bfs_hybrid`` from that source (BFS distances
    are canonical); the per-level plan is shared across jobs, and each
    level runs bottom-up (every edge-chunk gather shared) or top-down
    (a push from the frontier's (job, vertex) pairs), as ``_td_cap``
    chooses from the plan's counts: same ``dist`` either way.

    ``on_level(level, frontier_counts)``: optional host callback after
    each level's plan, receiving the per-job frontier sizes (np int32
    [K]); it may return a boolean KEEP mask [K] — jobs masked out
    (cancellation, deadline, timeout) stop executing before the level's
    sweep and report ``completed=False``. Returning None keeps all.

    Checkpoint plane (olap/recovery): the level-synchronous state is
    exactly ``(dist, level)`` — the frontier is ``dist == level`` —
    so ``checkpoint(level, dist, active)`` (dist [K, n+1] device,
    active np bool [K]) at a level boundary captures everything, and
    ``init_dist`` ([K, n] int32) + ``start_level`` restart the loop
    from a captured boundary with bit-equal continuation (``sources``
    then only sizes/validates the batch).

    Live overlay (olap/live): ``overlay`` — an ``OverlayView`` (default:
    the snapshot's attached ``_live_overlay``) — makes the run
    overlay-aware: tombstoned base slots stop counting as parents in
    the bottom-up hit tests, and a per-level delta-COO scatter pass
    expands the overlay's added edges; the result is bit-equal to a
    freshly rebuilt snapshot (BFS levels are canonical) while the base
    device CSR stays resident and untouched.

    Hops mode (``mode="hops"`` — the interactive traversal lane,
    olap/serving/interactive): the SAME shared plan/sweep machinery
    computes exact per-hop frontier SETS instead of BFS levels — no
    visited mask, so a vertex reached at hop h is reached AGAIN at hop
    h' > h when a path exists (Gremlin ``out()*h`` set semantics,
    which BFS levels cannot express). Encoding: dist[k, v] = the LAST
    loop level at which v was in job k's frontier (max-scatter of
    ``level + 1``; 0 = never reached), so the hop-d frontier of a job
    deactivated after its own depth via the ``on_level`` keep mask is
    exactly ``dist == d + start_level``. Requires ``start_level >= 1``
    (0 is the never-reached background) and seeds stamped
    ``start_level`` in ``init_dist`` (or via ``sources`` when
    ``init_dist`` is None — multi-source rows seed through init_dist).

    Per-level label masks (``level_masks`` — the interactive lane's
    mixed-label-chain seam, ISSUE 13): a list of per-level edge-slot
    bitmaps (device uint8, same packing as the overlay tombstone
    bitmap: byte = chunk column, bit = lane; 1 = the slot does NOT
    count as a parent this level), indexed ``level - start_level``
    (None entries and levels past the list run unmasked). This is what
    lets a ``V(x).out("a").out("b")`` chain compile onto the hops
    kernels instead of falling back to the interpreter: the lease is
    the union-label snapshot and each hop masks down to its own label
    set. Unsupported together with a live overlay (the overlay's
    add-COO edges carry labels the slot mask cannot filter) — raises
    ValueError rather than answering wrong.

    Mesh placement (``parallel/partition.place_batched_csr``): a graph
    dict carrying ``_state_sharding`` pins the ``[K, n+1]`` dist to
    that ``NamedSharding`` (vertex axis sharded over ``"v"``, K
    replicated); the kernels are unchanged — GSPMD partitions them
    from the committed input placements.

    The BFS tree (``parents``, bfs mode: GAP's and Graph500's answer):
    a second ``[K, n+1]`` int32 plane beside ``dist``, carried through
    the loop by the programs that already hold the id (the push's
    scatter, the pull's hit, the overlay's edge): ``parent[k, v]`` is
    the vertex job k's search reached v from, a neighbour one level
    nearer the source; the source is its own parent; -1 where the
    source reaches nobody. ANY valid tree is an answer (which of a
    vertex's possible parents it names follows the direction a level
    took; on one road the largest id, so a run repeats itself). The
    state is then the pair: ``checkpoint`` is handed ``(dist, par)`` and
    a resume needs ``init_parent`` ([K, n]) beside ``init_dist``. A run
    without ``parents`` builds and runs what it did before the plane.

    Returns ``(dist, levels, completed)``: dist [K, n] (device array
    when ``return_device``, else numpy; INF = unreachable — partial for
    non-completed jobs; with ``parents`` the pair ``(dist, parent)``,
    each [K, n]), levels np int32 [K] (the level at which each job's
    frontier emptied), completed np bool [K] (False = deactivated early
    via on_level)."""
    state, levels, completed = batched_bfs_state(
        snap_or_graph, sources, max_levels=max_levels, on_level=on_level,
        init_dist=init_dist, start_level=start_level,
        checkpoint=checkpoint, overlay=overlay, mode=mode,
        level_masks=level_masks, parents=parents, init_parent=init_parent)
    out = [a[:, :a.shape[1] - 1] for a in _split_state(state)
           if a is not None]
    if not return_device:
        from titan_tpu.obs import devprof
        for i, a in enumerate(out):
            devprof.count_d2h("bfs.dist", a.nbytes)
            out[i] = np.asarray(a)
    return (tuple(out) if parents else out[0]), levels, completed


def batched_bfs_state(snap_or_graph, sources, max_levels: int = 1000,
                      on_level=None, init_dist=None, start_level: int = 0,
                      checkpoint=None, overlay=None, mode: str = "bfs",
                      level_masks=None, parents: bool = False,
                      init_parent=None):
    """``frontier_bfs_batched``'s level loop, returning the state as
    the loop leaves it: ``dist`` [K, n+1] on the device, pad slot
    included (with ``parents`` the pair ``(dist, par)``:
    ``_with_parents``), for callers that read it with a program of
    their own (the interactive lane's ``hop_extract``) and want no slice
    dispatched in between.

    The loop's state is ``(dist, level)`` and two caches of it that one
    program hands the next: the level's frontier as a list of (job,
    vertex) pairs, and the level's plan statistics. The seed of a
    single-start batch leaves both (the sources are the list) and a
    pushed level leaves both for the level after it, so a run of pushed
    levels is one dispatch and one readback a level with no n-wide
    compaction. A level with nothing in hand (after a pull, a resume, a
    multi-start seed, under a live overlay, past the list's capacity)
    plans and lists from ``dist`` as ever; ``dist`` stays the truth."""
    import jax.numpy as jnp

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    ov = overlay
    if ov is None and not isinstance(snap_or_graph, dict):
        ov = getattr(snap_or_graph, "_live_overlay", None)
    if ov is not None and ov.empty:
        ov = None
    if level_masks is not None and ov is not None:
        raise ValueError(
            "level_masks under a live overlay is unsupported (overlay "
            "add-edges carry labels the slot mask cannot filter) — "
            "compact the overlay first or fall back to the interpreter")
    masked = ov is not None and ov.tomb_count > 0
    n = g["n"]
    dstT, colstart, degc = g["dstT"], g["colstart"], g["degc"]
    tbits = ov.tomb_dev if masked else jnp.zeros((1,), jnp.uint8)
    oscat = _overlay_scatter_batched() if ov is not None \
        and ov.count > 0 else None
    if mode not in ("bfs", "hops"):
        raise ValueError(f"mode must be 'bfs' or 'hops', got {mode!r}")
    expand = mode == "hops"
    if expand and start_level < 1:
        raise ValueError("hops mode needs start_level >= 1 (0 is the "
                         "never-reached background value)")
    if parents and expand:
        raise ValueError("parents needs mode='bfs': a hop set re-stamps "
                         "a vertex it reaches again, and has no tree")
    if parents and (init_dist is None) != (init_parent is None):
        raise ValueError("a run with parents resumes from init_dist AND "
                         "init_parent: depths alone do not say by which "
                         "edge a vertex was reached")
    K = len(sources)
    if K == 0:
        raise ValueError("frontier_bfs_batched needs >= 1 source")
    src_arr = np.asarray(sources, np.int64)
    if len(src_arr) and (src_arr.min() < 0 or src_arr.max() >= n):
        raise IndexError(f"source out of range [0, {n})")
    bplan = _batched_plan()
    btd = _batched_td()
    bstep = _batched_bu()
    bex = _batched_exhaust()
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase
    from titan_tpu.utils.jitcache import dev_scalar

    # the plan lists the candidates at the pull ladder's top rung, and
    # the lists keep that width from program to program
    c_caps, ex_pairs = _bu_caps(g)
    cap_n = c_caps[-1]
    caps = _td_caps(g)
    # what one program may hand the next (the list, the statistics):
    # only where a level can push at all, and not under a live overlay,
    # whose add-edges put vertices in a frontier that no push listed
    # and whose scatter reads the plan's bitmaps at every level
    hand_on = ov is None and _td_cap(g, K, 0, 1, False) is not None
    lst, held, st = None, 0, None
    par = None
    with phase("bfs.seed", K=K, n=n, mode=mode, parents=parents):
        if init_dist is None:
            # (a run without parents passes no flag: the call it made
            # before the plane, so the executable it built before)
            state, active, pj, pv, count = _batched_seed()(
                src_arr.astype(np.int32), dev_scalar(int(start_level)),
                n_=n, cap=caps[-1], expand=expand,
                **({"parents": True} if parents else {}))
            dist, par = _split_state(state)
            # the start level's frontier is the sources (bfs: stamped
            # 0, so only a run from level 0 has one)
            if hand_on and "_host" in g and K <= caps[-1] \
                    and (expand or start_level == 0):
                lst, held = (pj, pv, count), K
                st = _seed_stats(g, src_arr, expand)
        else:
            d = np.asarray(init_dist, np.int32)
            if d.shape != (K, n):
                raise ValueError(f"init_dist must be [K={K}, n={n}], "
                                 f"got {d.shape}")
            # col n is the scatter pad slot; it starts (and stays) INF
            # in a fresh run, so a resumed row re-appends it — on the
            # host: uploads, and no program to build for a rare road
            dist = jnp.asarray(np.concatenate(
                [d, np.full((K, 1), INF, np.int32)], axis=1))
            if parents:
                p = np.asarray(init_parent, np.int32)
                if p.shape != (K, n):
                    raise ValueError(f"init_parent must be [K={K}, "
                                     f"n={n}], got {p.shape}")
                par = jnp.asarray(np.concatenate(
                    [p, np.full((K, 1), -1, np.int32)], axis=1))
            active = jnp.asarray(np.ones(K, bool))
        if "_state_sharding" in g:
            # mesh-placed cohort (parallel/partition.place_batched_csr):
            # pin the [K, n+1] state to its P(None, "v") placement up
            # front so the first level doesn't pay a layout decision +
            # reshard
            import jax
            dist = jax.device_put(dist, g["_state_sharding"])
            if par is not None:
                par = jax.device_put(par, g["_state_sharding"])
        act_h = np.ones(K, bool)
    levels = np.zeros(K, np.int32)
    completed = np.zeros(K, bool)
    level = int(start_level)

    def plan(replan: bool):
        """``bplan`` dispatched to its statistics read back: ONE sync
        per level for ALL jobs."""
        with phase("bfs.plan", level=level) as ph:
            fbits, cand, stats = bplan(dist, active, dev_scalar(level),
                                       degc, c_cap=cap_n, n_=n,
                                       expand=expand)
            with ph.sync():
                st = np.asarray(stats)
            devprof.count_d2h("bfs.stats", st.nbytes)
            ph.set(c_count=int(st[0]), frontier=int(st[1:1 + K].sum()),
                   replan=replan, carried=False)
        return fbits, cand, st

    while level < max_levels:
        # the level's statistics: handed on by the program before (the
        # span then holds the host's decision alone), else planned
        planned = st is None
        if planned:
            fbits, cand, st = plan(False)
        else:
            with phase("bfs.plan", level=level, c_count=int(st[0]),
                       frontier=int(st[1:1 + K].sum()), replan=False,
                       carried=True, sync_ms=0.0):
                pass
        nf = st[1:1 + K]
        mask_changed = False
        # frontier emptied => that job's BFS is complete
        newly_done = act_h & (nf == 0)
        if newly_done.any():
            completed[newly_done] = True
            levels[newly_done] = level
            act_h = act_h & ~newly_done
            mask_changed = True
        if on_level is not None and act_h.any():
            keep = on_level(level, nf.copy())
            if keep is not None:
                dropped = act_h & ~np.asarray(keep, bool)
                if dropped.any():
                    levels[dropped] = level
                    act_h = act_h & ~dropped
                    mask_changed = True
        if not act_h.any():
            break
        if checkpoint is not None:
            # consistent boundary: every level < ``level`` is final in
            # dist, this level's frontier (dist == level) is unswept
            checkpoint(level, _with_parents(dist, par), act_h.copy())
        if mask_changed:
            active = jnp.asarray(act_h)
            if planned or not expand:
                # deactivated jobs (completed OR dropped) must stop
                # influencing the sweep: re-plan with the new mask — it
                # zeroes their bitmap rows AND drops their unvisited
                # sets from the shared candidate list (a completed
                # small-component job would otherwise re-contribute ~n
                # dead candidates to every remaining level)
                fbits, cand, st = plan(True)
                planned = True
            else:
                # statistics handed on, hops mode: the counts are per
                # job and every vertex with an edge stays a candidate,
                # so the new mask is applied here; the push masks the
                # list's pairs by ``active[job]`` itself
                st = np.where(np.concatenate([[True], act_h, act_h]),
                              st, 0)
        c_count = int(st[0])
        mass = int(st[1 + K:].sum(dtype=np.int64))
        # per-level label mask (mixed-label hops chains): this level's
        # slot bitmap rides the SAME tbits seam as overlay tombstones —
        # one static `masked` variant serves both, so no new kernel
        # bodies compile (overlay and level_masks are mutually
        # exclusive, guarded above)
        tb_l, masked_l = tbits, masked
        if level_masks is not None:
            i_lm = level - start_level
            lm = level_masks[i_lm] \
                if 0 <= i_lm < len(level_masks) else None
            if lm is not None:
                tb_l, masked_l = lm, True
        p_cap = _td_cap(g, K, mass, c_count, masked_l,
                        _bu_fuse(expand, 0))
        if p_cap is None and not planned:
            # a pull reads the plan's bitmaps and candidate list: made
            # only now that one will (the overlay's scatter reads them
            # too, and under an overlay every level plans)
            fbits, cand, st = plan(False)
            c_count = int(st[0])
        if p_cap is None:
            devprof.count_level("bu", "none")
            lst = st = None
        else:
            # top-down: a push from the frontier's (job, vertex) pairs,
            # BEFORE the overlay pass below — in hops mode the
            # overlay's max-scatter may re-stamp a frontier vertex to
            # level + 1. The pairs are the list the program before left
            # where it holds them all within this rung, else listed
            # from dist (the scan road)
            road = "carried" if lst is not None and held <= p_cap \
                else "scan"
            devprof.count_level("td", road)
            want = hand_on and level + 1 < max_levels
            with phase("bfs.sweep", level=level, dir="td", p_cap=p_cap,
                       mass=mass, list=road) as ph:
                if road == "scan":
                    lst = _batched_list()(
                        dist, active, dev_scalar(level),
                        dev_scalar(caps.index(p_cap)), degc, caps=caps,
                        n_=n)
                state, nj, nv, ncount, pushed = btd(
                    _with_parents(dist, par), *lst, active,
                    dev_scalar(level),
                    dev_scalar(int(want)), dstT, colstart, degc,
                    p_cap=p_cap, n_=n, expand=expand,
                    lists=_td_lists(p_cap, n))
                dist, par = _split_state(state)
                with ph.sync():
                    got = np.asarray(pushed)
                devprof.count_d2h("bfs.stats", got.nbytes)
                ph.set(pairs=int(got[0]), handed=int(got[2]))
            # what the push left for the level after it: statistics
            # where it deduped its targets, the list too where that
            # fits the list's capacity
            held = int(got[2])
            st = got[3:] if held >= 0 else None
            lst = (nj, nv, ncount) if 0 <= held <= caps[-1] else None
            c_count = 0
        if oscat is not None:
            # overlay add-edges expand top-down off the level's final
            # bitmaps — independent of the base sweep in either
            # direction (all scatter level+1 with the same min / max,
            # so order is immaterial), and it must run even when the
            # base candidate list is empty (vertices reachable only
            # through overlay edges)
            dist, par = _split_state(oscat(
                _with_parents(dist, par), fbits, ov.src_dev, ov.dst_dev,
                dev_scalar(level), cap=ov.cap, n_=n, expand=expand))
        # bottom-up: chunk rounds over the shared candidate list
        # (bu_more shape)
        off = None
        rounds = 0
        prog = None
        while c_count > 0 and rounds < BU_CHUNK_ROUNDS:
            # the rung that holds the candidates: the lanes above them
            # are dead (``arange < c_count``), so the rung only pads
            c_cap2 = _rung(c_caps, c_count)
            devprof.count_pull_rung(c_cap2)
            fuse = _bu_fuse(expand, rounds)
            with phase("bfs.sweep", level=level, dir="bu", c_cap=c_cap2,
                       fuse=fuse, candidates=c_count) as ph:
                if off is None:
                    off = jnp.zeros((cap_n,), jnp.int32)
                    prog = jnp.asarray([c_count, 0], jnp.int32)
                state, cand, off, prog = bstep(
                    _with_parents(dist, par), fbits, cand, off, prog,
                    dev_scalar(level), dstT, colstart, degc, tb_l,
                    c_cap=c_cap2, n_=n, fuse=fuse, masked=masked_l,
                    expand=expand)
                dist, par = _split_state(state)
                with ph.sync():
                    left = np.asarray(prog)
                devprof.count_d2h("bfs.stats", left.nbytes)
                c_count, rem8 = (int(x) for x in left)
                ph.set(c_count=c_count, rem8=rem8)
            rounds += fuse
        if c_count > 0:
            c_cap2, rem_cap = _ex_rung(ex_pairs, c_count, rem8)
            # no sync here: bex's device time falls into the next phase
            # that reads back (the next level's plan, or the caller's)
            with phase("bfs.exhaust", level=level, c_cap=c_cap2,
                       p_cap=rem_cap, survivors=c_count, rem8=rem8,
                       **{"async": True}):
                dist, par = _split_state(bex(
                    _with_parents(dist, par), fbits, cand, off, prog,
                    dev_scalar(level), dstT, colstart, degc, tb_l,
                    c_cap=c_cap2, p_cap=rem_cap, n_=n, masked=masked_l,
                    expand=expand))
        level += 1
    # jobs still active at max_levels count as completed-at-cap
    if act_h.any():
        completed[act_h] = True
        levels[act_h] = level
    return _with_parents(dist, par), levels, completed


def frontier_bfs_hybrid(snap, source_dense: int, max_levels: int = 1000,
                        return_device: bool = False):
    """Direction-optimizing BFS. Returns (dist, levels); ``dist`` is a
    device array over [n] (INF = unreachable) when ``return_device`` else
    numpy (note: a numpy readback of a scale-26 dist is a 268 MB D2H
    transfer — benches should keep it on device)."""
    import jax.numpy as jnp

    ov = getattr(snap, "_live_overlay", None) \
        if not isinstance(snap, dict) else None
    if ov is not None and not ov.empty:
        # the direction-optimizing single-source path has no overlay
        # seam (its head/endgame loops fuse whole level ranges) — the
        # serving layer routes every BFS through the overlay-aware
        # batched kernel instead
        raise RuntimeError(
            "frontier_bfs_hybrid on a live overlay: use "
            "frontier_bfs_batched (overlay-aware) or compact the "
            "overlay first (LiveGraphPlane.compact_if_dirty)")
    g = snap if isinstance(snap, dict) else build_chunked_csr(snap)
    n = g["n"]
    dstT, colstart, degc = g["dstT"], g["colstart"], g["degc"]
    head = _head_loop()
    td = _td_step()
    bu0 = _bu_start()
    bu0a = _bu_startL()
    bu0b = _bu_finish_chunk0()
    bu = _bu_more()
    ex = _bu_exhaust()
    endgame = _endgame()
    frontier_of = _frontier_of()

    total_chunks = int((g["q_total"] - 1))
    cap_n = _next_pow2(max(n, 2))

    def pad(a):
        # capacity buckets are powers of two, which can exceed a list's
        # natural length (n); pad once so every [:cap] slice is exact
        if a.shape[0] < cap_n:
            a = jnp.concatenate(
                [a, jnp.full((cap_n - a.shape[0],), n, a.dtype)])
        return a

    from titan_tpu.obs import devprof
    from titan_tpu.ops import vmem_gather
    from titan_tpu.obs.tracing import phase
    from titan_tpu.utils.jitcache import dev_scalar

    def stats_of(ph, dev):
        # the host step's one blocking readback, timed into its phase
        with ph.sync():
            got = np.atleast_1d(np.asarray(dev))
        devprof.count_d2h("bfs.stats", got.nbytes)
        return [int(x) for x in got]

    # One `bfs.level` phase a host step (obs/tracing: a leaf span under
    # the caller's scope and a profiler annotation): `dir` is what the
    # step ran (`head`: the fused early top-down levels; `td`; `bu`: one
    # bottom-up level, opener to exhaust; `end`: every trailing level in
    # one dispatch), beside the caps it ran under and `sync_ms`; a `bu`
    # step also says what its later programs were sized from (`missed`
    # the split opener's first lanes, `left` the opener, `exhaust` and
    # `rem8` the chunk rounds: candidates and their unread chunks) and
    # which `opener` it took (`dense`: the split opener, n-wide over the
    # leading-lane image; `plain`: `bu0`, every lane at once).
    # `device.bfs.levels{dir, list="single"}` counts the LEVELS a step
    # covered, so a run's counts sum to the `levels` it returns;
    # `device.bfs.opener{impl}` the pulled levels by their opener;
    # `device.bfs.frontier_test{prog, impl}` the calls of the programs
    # that test parents against the frontier, by what served the test.
    graph_impl = vmem_gather.gather_impl(n)

    def served_by(prog: str, columns: int) -> str:
        # the program's static `impl`: the road of its frontier test
        impl = _frontier_road(graph_impl, columns)
        devprof.count_frontier_test(prog, impl)
        return impl

    # ---- fused head: source + early top-down levels, one readback
    f_cap_h = min(HEAD_F_CAP, cap_n)
    p_cap_h = min(HEAD_P_CAP, _next_pow2(max(total_chunks + n, 2)))
    with phase("bfs.level", level=0, dir="head", f_cap=f_cap_h,
               p_cap=p_cap_h) as ph:
        dist, frontier, st_dev = head(
            dev_scalar(source_dense), dev_scalar(max_levels), dstT,
            colstart, degc, f_cap=f_cap_h, p_cap=p_cap_h, n_=n)
        f_count, m8_f, m8_unvis, n_unvis, level = stats_of(ph, st_dev)
        ph.set(levels=level)
    devprof.count_level("head", "single", level)
    # head refusal (source mass > p_cap_h) returns its initial state:
    # f_count=1, frontier=[source], level=0 — the main loop just takes over
    frontier = pad(frontier) if f_count <= f_cap_h else None

    while f_count > 0 and level < max_levels:
        # ---- fused endgame: every remaining level in one dispatch
        if n_unvis <= END_C_CAP and m8_unvis <= END_P_CAP:
            c_cap = _next_pow2(max(n_unvis, 2))
            p_cap = _next_pow2(max(m8_unvis, 2))
            with phase("bfs.level", level=level, dir="end", c_cap=c_cap,
                       p_cap=p_cap) as ph:
                dist, iters = endgame(
                    dist, dev_scalar(level), dev_scalar(max_levels), dstT,
                    colstart, degc, c_cap=c_cap, p_cap=p_cap, n_=n,
                    impl=served_by("end", p_cap))
                # +1: the empty probe level, matching the host loop's
                # count
                ran = min(stats_of(ph, iters)[0] + 1, max_levels - level)
                ph.set(levels=ran)
            devprof.count_level("end", "single", ran)
            level += ran
            break

        use_bu = m8_f * ALPHA > m8_unvis and f_count > 1
        if not use_bu:
            if m8_f == 0:
                break
            f_cap = min(_next_pow2(max(f_count, 2)), cap_n)
            p_cap = min(_next_pow2(max(m8_f, 2)),
                        _next_pow2(max(total_chunks + n, 2)))
            with phase("bfs.level", level=level, dir="td", f_cap=f_cap,
                       p_cap=p_cap) as ph:
                if frontier is None:  # after bottom-up / head overflow
                    frontier = pad(frontier_of(dist, dev_scalar(level),
                                               n_=n))
                dist, st_dev = td(
                    dist, frontier[:f_cap], st_dev,
                    dev_scalar(level), dstT, colstart, degc,
                    f_cap=f_cap, p_cap=p_cap, n_=n)
                # the td kernel no longer builds the next frontier list
                # — the lazy frontier_of path at the top of this branch
                # materializes it only if the next level stays top-down
                frontier = None
                f_count, m8_f, m8_unvis, n_unvis = stats_of(ph, st_dev)
            devprof.count_level("td", "single")
        else:
            c_cap = min(_next_pow2(max(n_unvis, 2)), cap_n)
            split = c_cap >= SPLIT_LANE_MIN
            opener = "dense" if split else "plain"
            with phase("bfs.level", level=level, dir="bu", c_cap=c_cap,
                       split=split, opener=opener) as ph:
                if split:
                    # split-lane opener: SPLIT_LANES-wide test over
                    # everyone (n-wide over the leading-lane image),
                    # then the remaining lanes only for the minority
                    # that missed (host-sized)
                    dist, fbits, cand, prog, st_dev = bu0a(
                        dist, dev_scalar(level),
                        leading_lanes(g, SPLIT_LANES), g["deg"], degc,
                        c_cap=c_cap, n_=n, lanes=SPLIT_LANES,
                        impl=served_by(
                            "bu0a", vmem_gather.padded_columns(n + 1)))
                    nu = stats_of(ph, prog)[0]
                    ph.set(missed=nu)
                    if nu > 0:
                        u_cap = min(_next_pow2(max(nu, 2)), cap_n)
                        cand = pad(cand)
                        dist, cand, prog, st_dev = bu0b(
                            dist, fbits, cand[:u_cap], dev_scalar(level),
                            dstT, colstart, degc, c_cap=u_cap, n_=n,
                            impl=served_by("bu0b", u_cap))
                        nc, rem8 = stats_of(ph, prog)
                    else:
                        nc, rem8 = 0, 0
                else:
                    dist, fbits, cand, prog, st_dev = bu0(
                        dist, dev_scalar(level), dstT, colstart, degc,
                        c_cap=c_cap, n_=n, impl=served_by("bu0", c_cap))
                    nc, rem8 = stats_of(ph, prog)
                ph.set(left=nc)
                rounds = 1
                off = None
                while nc > 0 and rounds < BU_CHUNK_ROUNDS:
                    c_cap2 = min(_next_pow2(max(nc, 2)), cap_n)
                    if off is None:
                        cand = pad(cand)
                        off = jnp.ones((cap_n,), jnp.int32)
                    fuse = BU_CHUNK_ROUNDS - rounds
                    dist, cand, off, prog, st_dev = bu(
                        dist, fbits, cand[:c_cap2], off[:c_cap2],
                        prog, dev_scalar(level), dstT, colstart,
                        degc, c_cap=c_cap2, n_=n, fuse=fuse,
                        impl=served_by("bu", c_cap2))
                    cand, off = pad(cand), pad(off)
                    nc, rem8 = stats_of(ph, prog)
                    rounds += fuse
                ph.set(rounds=rounds, exhaust=nc, rem8=rem8 if nc else 0)
                if nc > 0:
                    # exhaustive sweep for the stragglers (stats
                    # included)
                    c_cap2 = min(_next_pow2(max(nc, 2)), cap_n)
                    rem_cap = _next_pow2(max(rem8, 2))
                    if off is None:
                        cand = pad(cand)
                        off = jnp.ones((cap_n,), jnp.int32)
                    dist, st_dev = ex(dist, fbits, cand[:c_cap2],
                                      off[:c_cap2], prog,
                                      dev_scalar(level), dstT, colstart,
                                      degc, c_cap=c_cap2, p_cap=rem_cap,
                                      n_=n, impl=served_by("ex", rem_cap))
                f_count, m8_f, m8_unvis, n_unvis = stats_of(ph, st_dev)
            devprof.count_level("bu", "single")
            devprof.count_opener(opener)
            frontier = None
        level += 1
    out = dist[:n]
    if not return_device:
        out = np.asarray(out)
    return out, level
