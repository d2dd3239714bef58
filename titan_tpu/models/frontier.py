"""Frontier-sparse (active-set) traversal kernels on the chunked CSR.

Generalizes the top-down machinery of ``bfs_hybrid`` to value-carrying
relaxations — the frontier-sparse analogs of the reference's OLAP
fixtures (reference: titan-test olap/ShortestDistanceVertexProgram for
SSSP, min-label propagation for connected components): instead of full
edge sweeps every superstep (O(E x rounds), the FulgoraGraphComputer
model), each round expands ONLY the vertices whose value improved since
their last EXPANSION — ``val_expanded`` records the value each vertex
last pushed, so the frontier needs no per-round state copies and a round
interrupted mid-way (slice-cap overflow) resumes exactly where it left
off.

* ``frontier_sssp`` — DELTA-STEPPING (Meyer & Sanders) over hashed edge
  weights: vertices are expanded in distance buckets of width ``delta``
  (one-sided: every improved vertex below the current bucket top is
  eligible, so stragglers never accumulate), which re-examines each
  vertex's edge list a small constant number of times instead of the
  O(rounds) full re-relaxation a plain Bellman-Ford improvement
  frontier pays on continuous weights. Weights are derived ON DEVICE by
  hashing the edge slot id (uniform in [min_w, min_w+w_range)), so a
  scale-26 run needs no second 9GB weight array; ``slot_weights_np``
  reproduces them on the host for verification.
* ``frontier_wcc`` — hybrid connected components: one
  direction-optimized BFS (models/bfs_hybrid — the most optimized
  kernel in the repo) peels off the seed vertex's ENTIRE component in
  one shot (on power-law graphs that is ~all edge mass), then min-label
  propagation runs only over the leftover components' tiny edge mass.
  A component is a closed set — no edge crosses the peeled boundary —
  so the two phases compose exactly, and the propagation's rounds plan
  on the LIST of what the peel left, never on all n (``_list_plan``).

All state stays on device with one small plan readback per round
(large D2H readbacks are the cost to avoid); the graph dict is
``bfs_hybrid``'s chunked CSR (GraphSnapshot or ``graph500.to_device``
output).
"""

from __future__ import annotations

import contextlib
import functools

import numpy as np

from titan_tpu.models.bfs_hybrid import (build_chunked_csr,
                                         enumerate_chunk_pairs,
                                         frontier_bfs_hybrid)
from titan_tpu.models.bfs import INF, _next_pow2
from titan_tpu.utils.jitcache import dev_scalar, jit_once

FINF = np.float32(3.0e38)
IINF = np.int32(1 << 30)


def _hash_weight_expr(slot, min_w: float, w_range: float):
    """uniform [min_w, min_w + w_range) from an int32 edge slot id
    (murmur-style integer mix, reproduced by slot_weights_np)."""
    import jax.numpy as jnp

    x = slot.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    u = (x & jnp.uint32(0xFFFFFF)).astype(jnp.float32) * (1.0 / (1 << 24))
    return min_w + w_range * u


def slot_weights_np(slots: np.ndarray, min_w: float = 0.0,
                    w_range: float = 1.0) -> np.ndarray:
    x = slots.astype(np.uint32)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    u = (x & np.uint32(0xFFFFFF)).astype(np.float32) / np.float32(1 << 24)
    return (min_w + w_range * u).astype(np.float32)


# per-slice chunk budget: caps the [8, p_cap] working blocks (neighbors +
# message + weight-hash temporaries, ~4 of them) at ~1GB — at scale 26
# the graph itself holds 9GB of the 16GB HBM, and unbounded pair caps
# OOMed. Rounds whose frontier mass exceeds the budget are processed as
# multiple slices planned ON DEVICE (one boundary readback per round).
# A round with more mass than SLICE_K_MAX slices simply leaves the
# overflow vertices improved-but-unexpanded; the next plan picks them
# up — the expansion-tracked frontier makes partial rounds sound.
SLICE_BUDGET_CHUNKS = 1 << 23
SLICE_K_MAX = 64
# legacy dense-window machinery (kept for pagerank_dense, where every
# vertex IS active every iteration and slot padding is the only waste)
DENSE_WINDOW = 1 << 22


def _colowner(g):
    """column -> owning vertex map (lazy, cached in the graph dict):
    lets dense sweeps read contiguous column windows with no pair
    enumeration. Pad/sink columns own the sink vertex n."""
    import jax.numpy as jnp

    co = g.get("colowner")
    if co is None:
        n = g["n"]
        q_total = g["q_total"]
        # computed on device (jnp.repeat with a static total length) —
        # reading colstart back to build it on the host would D2H 268MB
        # at scale 26
        degc = g["degc"]
        ids = jnp.arange(n + 1, dtype=jnp.int32)
        owner = jnp.repeat(ids, degc, total_repeat_length=q_total - 1)
        co = jnp.concatenate([owner, jnp.full((1,), n, jnp.int32)])
        g["colowner"] = co
    return co


def _plan_stats(nf, m8, overflow, pmin):
    """A plan's one readback: ``[nf, m8, overflow, pmin]`` as int32
    (a float32 ``pmin`` travels as its bits)."""
    import jax
    import jax.numpy as jnp

    return jnp.concatenate(
        [jnp.stack([nf, m8, overflow]),
         jax.lax.bitcast_convert_type(pmin, jnp.int32)[None]
         if pmin.dtype == jnp.float32 else pmin[None]])


def _band_plan(kind: str):
    """Round plan for EVERY scheduler mode — ONE dispatch, one
    readback, built on ``ops.compaction.banded_frontier``: membership
    mask -> compacted in-band list + per-member masses (shared-index
    double scatter: NO n-wide nonzero, NO f_cap-wide ``degc[flist]``
    re-gather — the r5 quantile plan paid both, ~1.1s/round at scale
    26) + mass-balanced segment bounds. With ``quantile_mass`` > 0
    (float32 kinds only) the band threshold is computed ON DEVICE by a
    two-level histogram so the band carries ~that much chunk mass;
    otherwise the threshold is the caller's ``bucket_end`` (the
    delta-stepping bucket top, or the +inf sentinel for the plain
    expand-everything frontier). ``f_cap`` is ONE compile bucket per
    scheduler mode (QUANT_LIST_CAP for quantile bands, full w_max for
    plain/delta so a dense round keeps one-round coverage — see
    _frontier_run); an in-band set larger than f_cap is truncated by
    the compaction, which is SOUND: unlisted vertices stay improved
    (val < val_exp) and the next round re-plans them. The
    listed-mass cumsum runs in int64 when x64 is enabled and is
    overflow-flagged otherwise (ADVICE r5 #3): stats[2] nonzero means
    the segment bounds are corrupt and the host must refuse the round.
    The list/bounds/threshold are returned ON DEVICE: push segments
    read them via pooled index scalars, so the host never ships
    per-segment values (each scalar put is a host↔device round
    trip)."""
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.compaction import banded_frontier

        @functools.partial(jax.jit,
                           static_argnames=("n_", "f_cap", "k_max",
                                            "budget", "quantile_mass",
                                            "bins"))
        def bplan(val, val_exp, degc, bucket_end, n_: int, f_cap: int,
                  k_max: int, budget: int, quantile_mass: int,
                  bins: int = 512):
            hasdeg = degc[:n_] > 0
            changed = (val[:n_] < val_exp[:n_]) & hasdeg
            big_ = jnp.asarray(FINF if val.dtype == jnp.float32
                               else IINF, val.dtype)
            if quantile_mass:
                # two-level histogram threshold (the straddling bin is
                # re-histogrammed = bins^2 resolution — one 512-bin pass
                # over power-law value concentrations overshot the
                # target mass up to 10x, PERF_NOTES r5)
                vals = jnp.where(changed, val[:n_], big_)
                lo = vals.min()
                hi0 = jnp.where(changed, val[:n_], -big_).max()
                span = jnp.maximum(hi0 - lo, 1e-30)
                mass = jnp.where(changed, degc[:n_], 0)
                b = jnp.clip(((val[:n_] - lo) / span
                              * bins).astype(jnp.int32), 0, bins - 1)
                b = jnp.where(changed, b, bins - 1)
                hist = jnp.zeros((bins,), jnp.int32).at[b].add(
                    mass, mode="drop")
                cum = jnp.cumsum(hist)
                pick = jnp.minimum(jnp.searchsorted(
                    cum, jnp.int32(quantile_mass), side="left"),
                    bins - 1)
                lo2 = lo + span * pick.astype(val.dtype) / bins
                span2 = span / bins
                before = jnp.where(pick > 0,
                                   cum[jnp.maximum(pick - 1, 0)], 0)
                in2 = changed & (b == pick)
                b2 = jnp.clip(((val[:n_] - lo2) / span2
                               * bins).astype(jnp.int32), 0, bins - 1)
                hist2 = jnp.zeros((bins,), jnp.int32).at[
                    jnp.where(in2, b2, bins - 1)].add(
                    jnp.where(in2, degc[:n_], 0), mode="drop")
                cum2 = jnp.cumsum(hist2)
                pick2 = jnp.minimum(jnp.searchsorted(
                    cum2, jnp.int32(quantile_mass) - before,
                    side="left"), bins - 1)
                thr = lo2 + span2 * (pick2 + 1).astype(val.dtype) / bins
                thr = jnp.maximum(thr, jnp.nextafter(lo, big_))
            else:
                thr = jnp.asarray(bucket_end, val.dtype)

            inb = changed & (val[:n_] < thr)
            # degc is passed RAW as the mass payload — the compaction
            # only lands masked entries, so no where() pre-mask needed
            nf, m8, overflow, flist, lb = banded_frontier(
                inb, degc[:n_], f_cap, k_max, budget, n_)
            # pending = improved vertices parked above the threshold;
            # their minimum tells the host where the next bucket starts
            pending = changed & ~inb
            pmin = jnp.min(jnp.where(pending, val[:n_], big_))
            return _plan_stats(nf, m8, overflow, pmin), flist, lb, \
                jnp.asarray(thr, val.dtype)
        return bplan
    return jit_once(f"frontier_bandplan_{kind}", build)


# fixed in-band list width for the merged band plan (one compile
# bucket; truncation is sound — see _band_plan)
QUANT_LIST_CAP = 1 << 23


def _list_plan(kind: str):
    """``_band_plan``'s round plan over a LIST of candidates instead of
    all n — the min-label rounds behind a peel, whose every improved
    vertex lies in the peel's remainder (``_wcc_seed_labels``). ``rlist``
    holds the candidates' ids ascending with fill ``n_``; its first
    ``w`` entries (a static width covering them all) are planned:
    ``val``, ``val_exp`` and ``degc`` are gathered at list width and
    ``banded_frontier`` compacts at list width, so a round costs the
    remainder's size and not the graph's. Threshold modes: the caller's
    ``bucket_end`` only (no quantile band: int-valued kinds never take
    one). Returns what ``bplan`` returns — the same members in the same
    ascending order, so the same masses, segment bounds and ``pmin`` —
    with ``flist`` [w] in place of [f_cap]: ``_push_list`` reads either."""
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.compaction import banded_frontier

        @functools.partial(jax.jit,
                           static_argnames=("n_", "w", "k_max", "budget"))
        def lplan(val, val_exp, degc, rlist, bucket_end, n_: int, w: int,
                  k_max: int, budget: int):
            cand = rlist[:w]
            v = jnp.minimum(cand, n_)
            valv, mass = val[v], degc[v]
            changed = (cand < n_) & (valv < val_exp[v]) & (mass > 0)
            big_ = jnp.asarray(FINF if val.dtype == jnp.float32
                               else IINF, val.dtype)
            thr = jnp.asarray(bucket_end, val.dtype)
            inb = changed & (valv < thr)
            nf, m8, overflow, flist, lb = banded_frontier(
                inb, mass, w, k_max, budget, n_, ids=cand)
            pmin = jnp.min(jnp.where(changed & ~inb, valv, big_))
            return _plan_stats(nf, m8, overflow, pmin), flist, lb, thr
        return lplan
    return jit_once(f"frontier_listplan_{kind}", build)


def _push_list(kind: str):
    """Push one mass-balanced SEGMENT of the round's compacted in-band
    list (every mode — quantile band, delta bucket, or the plain
    improved-set frontier; the threshold device scalar encodes the
    difference). Membership is rechecked live (an earlier segment may
    have improved a member further — it pushes its current value); a
    vertex appears in exactly one segment and segment mass is fixed by
    the plan, so p_cap = pow2(segment mass) never defers."""
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.models.bfs_hybrid import _bit_of

        @functools.partial(jax.jit,
                           static_argnames=("f_cap", "p_cap", "n_",
                                            "masked"),
                           donate_argnums=(0, 1))
        def pushl(val, val_exp, flist, lbounds, i, thr, dstT, colstart,
                  degc, wparams, tbits, f_cap: int, p_cap: int,
                  n_: int, masked: bool = False):
            p0 = lbounds[i]
            p1 = lbounds[i + 1]
            L = flist.shape[0]
            s0 = jnp.clip(p0, 0, max(L - f_cap, 0))
            pos = s0 + jnp.arange(f_cap, dtype=jnp.int32)
            seg = jax.lax.dynamic_slice(flist, (s0,), (f_cap,))
            v = jnp.minimum(seg, n_)
            member = (pos >= p0) & (pos < p1) & (seg < n_) \
                & (val[v] < val_exp[v]) & (val[v] < thr)
            valv = val[v]
            counts = jnp.where(member, degc[v], 0).astype(jnp.int32)
            # a segment's true mass can exceed the plan target by one
            # straddling vertex; only members whose WHOLE chunk range
            # fits p_cap are marked expanded — the rest stay improved
            # and the next round re-plans them (same contract as the
            # vertex-range push)
            ends = jnp.cumsum(counts)
            fits = member & (ends <= p_cap)
            val_exp = val_exp.at[jnp.where(fits, v, n_ + 1)].set(
                valv, mode="drop")
            cols, _, owner = enumerate_chunk_pairs(
                fits, counts, colstart[v], p_cap, dstT.shape[1] - 1,
                with_owner=True)
            src_val = valv[owner]
            nbr = jnp.take(dstT, cols, axis=1)
            lane = jnp.arange(8, dtype=jnp.int32)[:, None]
            slot = cols[None, :] * 8 + lane
            if masked:
                # live-overlay tombstones (olap/live): a dead base slot
                # relaxes nothing — its lane scatters to the drop pad
                nbr = jnp.where(_bit_of(tbits, slot), n_ + 1, nbr)
            if kind == "sssp":
                w = _hash_weight_expr(slot, wparams[0], wparams[1])
                msg = src_val[None, :] + w
            else:
                msg = jnp.broadcast_to(src_val[None, :], nbr.shape)
            return val.at[nbr].min(msg, mode="drop"), val_exp
        return pushl
    return jit_once(f"frontier_pushlist_{kind}", build)


def _overlay_relax(kind: str):
    """Relax every LIVE overlay add-edge with the sources' current
    values — the delta-COO push pass of the live plane's expansion seam
    (olap/live). SSSP/WCC are monotone min-fixpoint computations, so
    extra relaxations are always sound; ``_frontier_run`` calls this
    after each round's base pushes (one overlay hop per round) and on
    empty plans, where the returned improvement count decides whether
    overlay-only progress keeps the loop alive. Overlay edges hash
    their weights from slots past the base layout (``slot_base + i``,
    stable under append; a compaction re-slots them with the rebuilt
    CSR — docs/live.md)."""
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("cap", "n_"),
                           donate_argnums=(0,))
        def relax(val, ov_src, ov_dst, wparams, slot_base, cap: int,
                  n_: int):
            s = jnp.minimum(ov_src, n_)    # pad (n+1) reads val[n]=inf
            src_val = val[s]
            if kind == "sssp":
                slot = slot_base + jnp.arange(cap, dtype=jnp.int32)
                w = _hash_weight_expr(slot, wparams[0], wparams[1])
                msg = src_val + w
            else:
                msg = src_val
            # improvement detected PRE-scatter (lane-wise msg vs current
            # target value): no read of the donated buffer after the
            # update, and >0 iff the scatter changes anything
            nimp = (msg < val[jnp.minimum(ov_dst, n_)]) \
                .sum(dtype=jnp.int32)
            new = val.at[ov_dst].min(msg, mode="drop")
            return new, nimp
        return relax
    return jit_once(f"frontier_overlay_relax_{kind}", build)


def _quantize_cap(mass: int, p_full: int) -> int:
    """Round a slice's kernel width up to the next power of FOUR
    (capped at p_full). Mass-exact pow2 caps created a distinct compile
    per bucket, so a cold
    22-round SSSP paid more compile than compute. Power-of-four rounding
    halves the bucket count for at most 2x dead lanes on the SMALL
    slices (full budget-sized slices hit p_full either way)."""
    c = _next_pow2(max(mass, 2))
    if (c.bit_length() - 1) % 2:
        c <<= 1
    return min(c, p_full)


def _max_degc(g) -> int:
    got = g.get("_max_degc")
    if got is None:
        got = int(np.asarray(g["degc"].max()))
        g["_max_degc"] = got
    return got


# default per-round band mass (chunks) for quantile-batched SSSP — the
# measured r5 winner and the DEFAULT mode: scale-26 warm, same chip-day:
# plain 247s / 1118M chunks vs quantile-2^24 121-130s / 394M chunks
# (after the r5 fixes: two-level threshold so one histogram bin cannot
# swallow 10x the target mass, pow-4 f_cap buckets so band sizes stop
# compiling fresh kernels, and the merged single-dispatch _quant_plan).
# Band-size sweep: 2^23 = 45 rounds (per-round floors dominate), 2^24 =
# 31 rounds/394M, 2^25 = 30/518M, 2^26 = 28/716M — rounds are
# WAVE-limited below 2^24, re-expansion grows above it.
QUANTILE_MASS_DEFAULT = 1 << 24


def _round_planner(kind: str, g, budget: int, remainder=None):
    """The round's plan as both round loops dispatch it: ``plan(val,
    val_exp, be_dev, quantile_mass) -> (qf_cap, stats, flist, lbounds,
    thr_dev)``, ``qf_cap`` the width of ``flist``.

    Without ``remainder``: ``_band_plan`` over all n. List width:
    quantile mode caps at QUANT_LIST_CAP (the band carries
    ~quantile_mass chunks, so members are bounded and truncation only
    defers); plain/delta modes must cover EVERY improved vertex in one
    round when possible (a dense WCC round lists up to n members —
    capping it at 2^23 would multiply round count by n/2^23, each
    paying the plan sync), so they list at full w_max width — per-round
    coverage is then bounded by nseg exactly like the r5 vertex-range
    path (64 x budget chunks). Computed per round: a quantile->plain
    escalation flips it (one extra plan compile, rare fp corner).

    With ``remainder = (rlist, w)`` (a peel's: ``_wcc_domain``):
    ``_list_plan`` over the list's first ``w`` entries, every round —
    the same members in the same order at the list's cost.

    WCC's plans are counted by the road they took
    (``device.wcc.plans{domain}``)."""
    from titan_tpu.obs import devprof

    n, degc = g["n"], g["degc"]
    # the in-band list never usefully exceeds the vertex count: cap its
    # width at the largest power of two that fits the state arrays
    w_max = 1 << ((n + 1).bit_length() - 1)
    if remainder is None:
        bplan = _band_plan(kind)

        def plan(val, val_exp, be_dev, quantile_mass):
            qf_cap = min(QUANT_LIST_CAP, w_max) if quantile_mass \
                else w_max
            if kind == "wcc":
                devprof.count_wcc_plan("n")
            return (qf_cap,) + bplan(
                val, val_exp, degc, be_dev, n_=n, f_cap=qf_cap,
                k_max=SLICE_K_MAX, budget=budget,
                quantile_mass=quantile_mass)
    else:
        rlist, w = remainder
        lplan = _list_plan(kind)

        def plan(val, val_exp, be_dev, quantile_mass):
            assert not quantile_mass, "a list plan has no quantile band"
            if kind == "wcc":
                devprof.count_wcc_plan("list")
            return (w,) + lplan(
                val, val_exp, degc, rlist, be_dev, n_=n, w=w,
                k_max=SLICE_K_MAX, budget=budget)
    return plan


class RoundInterrupted(Exception):
    """Raised out of ``_frontier_run`` when the caller's ``on_round``
    callback vetoes continuing — the serving layer's cancellation /
    timeout path for single-execution SSSP/WCC jobs (olap/serving
    drops the job at a round boundary instead of abandoning the whole
    process; the device state simply stops being advanced)."""

    def __init__(self, rounds: int):
        super().__init__(f"interrupted after {rounds} rounds")
        self.rounds = rounds


def _frontier_run(snap_or_graph, val, val_exp, kind: str, wparams,
                  max_rounds: int, delta: float | None = None,
                  quantile_mass: int = 0, on_round=None,
                  checkpoint=None, start_rounds: int = 0,
                  bucket_end0: float | None = None, overlay=None,
                  sync=contextlib.nullcontext, remainder=None):
    """Expansion-tracked round loop: one plan readback per round
    (_band_plan — compacted in-band list + mass-balanced segment
    bounds, no n-wide nonzero; over ``remainder``'s list where a peel
    hands one: ``_round_planner``), then one _push_list dispatch per
    ~budget chunks of listed mass. With ``delta``, rounds expand only
    the current distance bucket (one-sided) and the bucket advances to
    the minimum pending value when it drains — delta-stepping. With
    ``quantile_mass``, each round's threshold is computed ON DEVICE so
    the expanded band carries ~that much chunk mass — priority-batched
    expansion in near-sorted value order. Without either, every
    improved vertex is in-band every round (threshold = the +inf
    sentinel).

    Checkpoint plane (olap/recovery): ``checkpoint(rounds, state)`` is
    called at every round boundary (after the on_round veto) with the
    COMPLETE loop state — ``{"val", "val_exp", "bucket_end",
    "quantile_mass"}`` — and owns its own cadence; a run restarted
    with that state via ``start_rounds`` / ``bucket_end0`` /
    ``quantile_mass`` continues the exact trajectory (the pushes are
    min-scatters, order-independent and exact, so the final arrays are
    bit-equal to an uninterrupted run even if kernel-width choices
    differ after resume). ``sync()`` is entered round each round's one
    blocking readback (a caller's phase times it: ``Phase.sync``)."""
    import time as _time

    import jax.numpy as jnp

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    n = g["n"]
    dstT, colstart, degc = g["dstT"], g["colstart"], g["degc"]
    pushl = _push_list(kind)
    # live-overlay expansion seam (olap/live): tombstoned base slots
    # are masked out of every push; overlay add-edges relax after each
    # round's pushes (and on empty plans, where overlay-only progress
    # keeps the loop alive — see the nf == 0 branch)
    ov = overlay
    if ov is None and not isinstance(snap_or_graph, dict):
        ov = getattr(snap_or_graph, "_live_overlay", None)
    if ov is not None and ov.empty:
        ov = None
    masked = ov is not None and ov.tomb_count > 0
    has_adds = ov is not None and ov.count > 0
    relax = _overlay_relax(kind) if has_adds else None
    max_dc = _max_degc(g)
    is_f32 = val.dtype == jnp.float32
    big = float(FINF) if is_f32 else int(IINF)
    # a segment carries up to budget + max_dc chunks (one vertex of
    # overshoot), so budget == 2^k would push p_cap to 2^(k+1) and HALF
    # of every big segment's lanes would be padding — shave max_dc off
    # the budget so full segments fit a 2^k kernel exactly (measured
    # 2026-07-31: scale-26 SSSP round cost is dominated by these lanes)
    target = _next_pow2(max(SLICE_BUDGET_CHUNKS, 2))
    if max_dc <= target // 2:
        budget = target - max_dc
        p_full = target
    else:                       # degenerate hub: conservative old scheme
        budget = SLICE_BUDGET_CHUNKS
        p_full = _next_pow2(max(budget + max_dc, 2))
    plan = _round_planner(kind, g, budget, remainder)

    wp = jnp.asarray(np.asarray(wparams, np.float32))
    tbits = ov.tomb_dev if masked else jnp.zeros((1,), jnp.uint8)

    def _relax(v):
        return relax(v, ov.src_dev, ov.dst_dev, wp,
                     dev_scalar(ov.slot_base), cap=ov.cap, n_=n)

    if has_adds and start_rounds == 0 and bucket_end0 is None:
        # fresh start: seed the overlay's one-hop reach of the initial
        # values (a source with ONLY overlay edges would otherwise
        # terminate on its first empty plan)
        val, _ = _relax(val)
    # the quantile threshold math in _band_plan is float32-only (span
    # floor 1e-30, jnp.nextafter on lo); int-valued kinds (e.g. WCC
    # labels) would trace-error or mis-threshold — fall back to the
    # plain improved-set frontier for them
    if quantile_mass and not is_f32:
        quantile_mass = 0
    bucket_end = big if not delta or delta <= 0 else delta
    if bucket_end0 is not None:         # resume: restored bucket state
        bucket_end = bucket_end0
    trace = g.get("_trace_rounds")      # optional perf instrumentation:
    rounds = int(start_rounds)          # set g["_trace_rounds"] = [] to
    dtname = "float32" if is_f32 else "int32"
    prev_sig = None                     # collect per-round 5-tuples
    # plan-cost isolation drain: opt-in SEPARATELY from the trace — it
    # buys exact per-round plan numbers at one extra host round trip
    # per round, which the plain
    # mass-accounting trace consumers must not pay
    drain = trace is not None and g.get("_trace_plan_drain")
    while rounds < max_rounds:
        # serving-layer veto (cancellation/timeout) at the round
        # boundary — same per-job early-exit discipline as the batched
        # BFS level mask, for the single-execution kinds
        if on_round is not None and not on_round(rounds):
            raise RoundInterrupted(rounds)
        # checkpoint capture at the same boundary: the callback owns
        # cadence and readback; (val, val_exp) here is a CONSISTENT
        # state — every push of earlier rounds has landed, none of this
        # round's has started
        if checkpoint is not None:
            checkpoint(rounds, {"val": val, "val_exp": val_exp,
                                "bucket_end": bucket_end,
                                "quantile_mass": quantile_mass})
        if drain:
            # drain the queued pushes first so the plan sync below
            # measures the plan alone, not their completion
            val.block_until_ready()
        t_plan = _time.time()
        qf_cap, stats, flist, lbounds, thr_dev = plan(
            val, val_exp, dev_scalar(bucket_end, dtname), quantile_mass)
        with sync():
            st_h = np.asarray(stats)       # ONE sync per round
        plan_s = _time.time() - t_plan
        nf, m8 = int(st_h[0]), int(st_h[1])
        if int(st_h[2]):
            raise RuntimeError(
                "banded_frontier: listed chunk mass overflowed int32 — "
                "segment bounds are corrupt (enable JAX x64 or shard "
                "the graph below 2^31 chunks)")
        pmin = st_h[3:4].view(np.float32)[0] if is_f32 else st_h[3]
        if trace is not None:
            trace.append((0.0 if quantile_mass else float(bucket_end),
                          nf, m8, _time.time(), plan_s))
        if nf == 0 or m8 == 0:
            if has_adds:
                # the base plan is dry: only overlay edges can make
                # progress (e.g. chains through vertices with no base
                # edges). One relax per round; terminate only when it
                # improves nothing — then base+overlay are at the
                # fixpoint together.
                val, nimp = _relax(val)
                if int(np.asarray(nimp)) > 0:
                    rounds += 1
                    continue
            if float(pmin) >= big * (1 - 1e-6):
                return val[:n], rounds     # no pending work anywhere
            if quantile_mass:
                # the device threshold always includes the minimum
                # value, so an empty round with pending work cannot
                # recur — guard fp corner-cases by escalating to the
                # direct-threshold (expand-everything) mode
                quantile_mass = 0
                continue
            if delta and delta > 0:
                # bucket drained: advance to the minimum pending
                # value's bucket (strictly increases — pmin >= current
                # bucket_end)
                bucket_end = float((np.floor(float(pmin) / delta) + 1)
                                   * delta)
                continue
            # plain mode admits every improved vertex: pending work
            # with an empty band means corrupt state — fail loudly
            # rather than spin
            raise RuntimeError(
                f"frontier_{kind}: empty round with pending work "
                f"(pmin={pmin!r}) in plain mode")
        # a round that changed NOTHING means every listed member was
        # deferred (pathological packing) — escalate to full-size
        # kernels for one round
        sig = (nf, m8, float(pmin), float(bucket_end), quantile_mass)
        escalate = sig == prev_sig
        prev_sig = sig
        nseg = min(-(-m8 // budget), SLICE_K_MAX)
        # f bucket quantized to powers of FOUR: per-nf pow2 buckets
        # compiled a fresh kernel per distinct band size (measured
        # scale 26: seven one-call pushlist compiles — more compile than
        # push). A segment holds at most ~budget vertices.
        f_bucket = _quantize_cap(min(nf, budget + max_dc), qf_cap)
        for k in range(nseg):
            # +max_dc headroom: a vertex straddling the mass target
            # lands wholly in one segment (full segments then size
            # to exactly p_full — the budget is pre-shaved by
            # max_dc, see above)
            mass_k = min(budget, m8 - k * budget) + max_dc
            p_cap = p_full if escalate else _quantize_cap(mass_k, p_full)
            fk = min(qf_cap, p_full) if escalate \
                else min(f_bucket, p_cap)
            val, val_exp = pushl(
                val, val_exp, flist, lbounds, dev_scalar(k),
                thr_dev, dstT, colstart, degc, wp, tbits,
                f_cap=fk, p_cap=p_cap, n_=n, masked=masked)
        if has_adds:
            # one overlay hop per round, tracking the base expansion
            val, _ = _relax(val)
        rounds += 1
    return val[:n], rounds


class _CohortMember:
    """Host-side loop state for ONE member of a fused frontier cohort
    (``frontier_sssp_batched`` / ``frontier_wcc_batched``): its own
    device value arrays plus the scheduler-mode knobs the sequential
    ``_frontier_run`` keeps in locals — so every per-round decision the
    cohort driver makes for this member is computed from exactly the
    state the member's solo run would have had."""

    __slots__ = ("k", "val", "val_exp", "bucket_end", "quantile_mass",
                 "prev_sig", "rounds", "out", "stopped")

    def __init__(self, k: int, val, val_exp, bucket_end, quantile_mass):
        self.k = k
        self.val = val
        self.val_exp = val_exp
        self.bucket_end = bucket_end
        self.quantile_mass = int(quantile_mass)
        self.prev_sig = None
        self.rounds = 0
        self.out = None        # device [n] result once terminated
        self.stopped = None    # on_round veto: the vetoed round number


def _frontier_cohort(g, members, kind: str, wparams, max_rounds: int,
                     delta: float = 0.0, on_round=None, checkpoint=None,
                     overlay=None, sync=contextlib.nullcontext,
                     remainder=None) -> None:
    """Shared round loop over K per-member ``(val, val_exp)`` states —
    the cohort generalization of ``_frontier_run``. Each round
    dispatches every active member's band plan (the member's OWN static
    args, so the SAME jit entries as a solo run) and reads all K stats
    vectors back in ONE stacked host sync — the per-round plan-readback
    floor (one D2H round trip) is paid once
    per cohort round instead of once per member.

    Bit-equality contract: every per-member decision — threshold mode,
    segment count, kernel-width buckets, quantile->plain escalation,
    delta bucket advance, repeated-signature escalation, termination —
    is computed from that member's own stats with the sequential code's
    exact expressions, and the pushes are order-independent min-
    scatters, so each member's final arrays AND round count are
    bit-equal to its solo ``_frontier_run``. Mode transitions that
    re-plan without advancing the round (the sequential ``continue``
    branches) are serviced solo for that member — an extra sync on the
    rare transition round, never on the steady state.

    ``on_round(k, rounds)`` / ``checkpoint(k, rounds, state)`` are the
    per-member forms of the sequential hooks (same boundary ordering:
    veto, then checkpoint, then the plan); a vetoed member records
    ``stopped`` and simply leaves the cohort — the analog of
    ``RoundInterrupted`` that cannot abandon its K-1 batchmates.
    Fresh-start cohorts only: resumed jobs run solo through
    ``frontier_sssp``/``frontier_wcc`` (their round counter differs
    from any fresh batchmate — the same split the batched BFS makes).
    ``sync()`` is entered round each blocking plan readback, as in
    ``_frontier_run``. ``remainder``: the peel's list, as there — ONE
    list for the cohort, read by every member's plan and never donated."""
    import jax.numpy as jnp

    n = g["n"]
    dstT, colstart, degc = g["dstT"], g["colstart"], g["degc"]
    pushl = _push_list(kind)
    ov = overlay
    if ov is not None and ov.empty:
        ov = None
    masked = ov is not None and ov.tomb_count > 0
    has_adds = ov is not None and ov.count > 0
    relax = _overlay_relax(kind) if has_adds else None
    max_dc = _max_degc(g)
    is_f32 = members[0].val.dtype == jnp.float32
    big = float(FINF) if is_f32 else int(IINF)
    dtname = "float32" if is_f32 else "int32"
    target = _next_pow2(max(SLICE_BUDGET_CHUNKS, 2))
    if max_dc <= target // 2:
        budget = target - max_dc
        p_full = target
    else:
        budget = SLICE_BUDGET_CHUNKS
        p_full = _next_pow2(max(budget + max_dc, 2))
    plan = _round_planner(kind, g, budget, remainder)
    wp = jnp.asarray(np.asarray(wparams, np.float32))
    tbits = ov.tomb_dev if masked else jnp.zeros((1,), jnp.uint8)

    def _relax(v):
        return relax(v, ov.src_dev, ov.dst_dev, wp,
                     dev_scalar(ov.slot_base), cap=ov.cap, n_=n)

    if has_adds:
        # fresh start: seed the overlay's one-hop reach per member
        # (cohorts are fresh-only — see the docstring)
        for m in members:
            m.val, _ = _relax(m.val)

    def _boundary(m) -> bool:
        """Round-boundary hooks in the sequential order (veto first,
        then checkpoint); False = the member was vetoed out."""
        if on_round is not None and not on_round(m.k, m.rounds):
            m.stopped = m.rounds
            return False
        if checkpoint is not None:
            checkpoint(m.k, m.rounds,
                       {"val": m.val, "val_exp": m.val_exp,
                        "bucket_end": m.bucket_end,
                        "quantile_mass": m.quantile_mass})
        return True

    def _dispatch(m):
        return plan(m.val, m.val_exp, dev_scalar(m.bucket_end, dtname),
                    m.quantile_mass)

    def _host_step(m, st_h, qf_cap, flist, lbounds, thr_dev) -> str:
        """One member's host-side round logic over its synced stats —
        'done' | 'advanced' | 'replan' (the sequential ``continue``)."""
        nf, m8 = int(st_h[0]), int(st_h[1])
        if int(st_h[2]):
            raise RuntimeError(
                "banded_frontier: listed chunk mass overflowed int32 — "
                "segment bounds are corrupt (enable JAX x64 or shard "
                "the graph below 2^31 chunks)")
        pmin = st_h[3:4].view(np.float32)[0] if is_f32 else st_h[3]
        if nf == 0 or m8 == 0:
            if has_adds:
                m.val, nimp = _relax(m.val)
                if int(np.asarray(nimp)) > 0:
                    m.rounds += 1
                    return "advanced"
            if float(pmin) >= big * (1 - 1e-6):
                m.out = m.val[:n]
                return "done"
            if m.quantile_mass:
                m.quantile_mass = 0
                return "replan"
            if delta and delta > 0:
                m.bucket_end = float(
                    (np.floor(float(pmin) / delta) + 1) * delta)
                return "replan"
            raise RuntimeError(
                f"frontier_{kind}: empty round with pending work "
                f"(pmin={pmin!r}) in plain mode")
        sig = (nf, m8, float(pmin), float(m.bucket_end), m.quantile_mass)
        escalate = sig == m.prev_sig
        m.prev_sig = sig
        nseg = min(-(-m8 // budget), SLICE_K_MAX)
        f_bucket = _quantize_cap(min(nf, budget + max_dc), qf_cap)
        for k in range(nseg):
            mass_k = min(budget, m8 - k * budget) + max_dc
            p_cap = p_full if escalate else _quantize_cap(mass_k, p_full)
            fk = min(qf_cap, p_full) if escalate \
                else min(f_bucket, p_cap)
            m.val, m.val_exp = pushl(
                m.val, m.val_exp, flist, lbounds, dev_scalar(k),
                thr_dev, dstT, colstart, degc, wp, tbits,
                f_cap=fk, p_cap=p_cap, n_=n, masked=masked)
        if has_adds:
            m.val, _ = _relax(m.val)
        m.rounds += 1
        return "advanced"

    def _solo(m) -> None:
        """Drain a member's re-plan rounds alone (its mode knobs just
        changed; the cohort's shared sync has already happened)."""
        while m.out is None and m.stopped is None \
                and m.rounds < max_rounds:
            if not _boundary(m):
                return
            qf_cap, stats, flist, lbounds, thr_dev = _dispatch(m)
            with sync():
                st_h = np.asarray(stats)
            if _host_step(m, st_h, qf_cap, flist, lbounds,
                          thr_dev) != "replan":
                return
        if m.out is None and m.stopped is None:
            m.out = m.val[:n]            # max_rounds exhausted

    active = list(members)
    while True:
        for m in active:
            if m.rounds >= max_rounds and m.out is None \
                    and m.stopped is None:
                m.out = m.val[:n]
        active = [m for m in active
                  if m.out is None and m.stopped is None]
        if not active:
            return
        ready = []
        for m in active:
            if _boundary(m):
                ready.append((m, _dispatch(m)))
        if not ready:
            continue
        # THE amortization: K members' round plans in one stacked sync
        with sync():
            st_all = np.asarray(jnp.stack([d[1] for _m, d in ready]))
        replans = []
        for (m, (qf_cap, _stats, flist, lbounds, thr_dev)), st_h \
                in zip(ready, st_all):
            if _host_step(m, st_h, qf_cap, flist, lbounds,
                          thr_dev) == "replan":
                replans.append(m)
        for m in replans:
            _solo(m)


def frontier_sssp_batched(snap_or_graph, sources, min_w: float = 0.0,
                          w_range: float = 1.0, max_rounds: int = 10_000,
                          delta: float | None = None,
                          quantile_mass: int | None = None,
                          on_round=None, checkpoint=None,
                          return_device: bool = False, overlay=None):
    """K-source SSSP cohort over one shared round loop
    (``_frontier_cohort``): per-member device state, ONE stacked plan
    readback per round. Each member's distances and round count are
    bit-equal to ``frontier_sssp(source=sources[k])`` with the same
    knobs — the mode knobs (``delta``/``quantile_mass``/``max_rounds``)
    are cohort-wide, which is why the serving batch key pins them.

    ``on_round(k, rounds)``: per-member veto — a False drops member
    ``k`` from the cohort (``stopped[k]`` records the round) without
    touching its batchmates. ``checkpoint(k, rounds, state)``: the
    sequential state dict per member. Returns ``(dists, rounds,
    stopped)`` lists of length K; a vetoed member's dist is None."""
    import jax.numpy as jnp

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    n = g["n"]
    if delta is None:
        delta = 0.0
    if quantile_mass is None:
        quantile_mass = 0 if delta and delta > 0 \
            else QUANTILE_MASS_DEFAULT
    if overlay is None and not isinstance(snap_or_graph, dict):
        overlay = getattr(snap_or_graph, "_live_overlay", None)
    bucket0 = float(FINF) if not delta or delta <= 0 else float(delta)
    members = []
    for k, s in enumerate(sources):
        val = jnp.full((n + 1,), FINF, jnp.float32) \
            .at[int(s)].set(0.0)
        val_exp = jnp.full((n + 1,), FINF, jnp.float32)
        members.append(_CohortMember(k, val, val_exp, bucket0,
                                     int(quantile_mass)))
    _frontier_cohort(g, members, "sssp", (min_w, w_range), max_rounds,
                     delta=float(delta), on_round=on_round,
                     checkpoint=checkpoint, overlay=overlay)
    outs = [m.out if return_device or m.out is None
            else np.asarray(m.out) for m in members]
    return outs, [m.rounds for m in members], \
        [m.stopped for m in members]


def frontier_wcc_batched(snap_or_graph, count: int,
                         max_rounds: int = 10_000, on_round=None,
                         checkpoint=None, return_device: bool = False,
                         overlay=None):
    """K-member WCC cohort. WCC has no per-job source, so the BFS peel
    and seed labels are computed ONCE and copied per member; members
    then differ only in their serving-layer hooks (per-job veto,
    checkpoint cadence, fault injection) while sharing the round loop's
    single stacked plan sync and, behind a peel, its ONE remainder list
    (``_wcc_domain``). Each member's labels and round count are
    bit-equal to a solo ``frontier_wcc``. ``checkpoint(k, rounds,
    state)`` states carry ``levels`` like the sequential form. Returns
    ``(labels, rounds, stopped)`` with rounds including the shared BFS
    peel's level count. Phases as ``frontier_wcc``'s, under the caller's
    scope: the shared peel and ONE ``wcc.propagate`` over the cohort's
    round loop (``k`` members; ``rounds`` the longest member's), then a
    ``wcc.result`` a member."""
    import jax.numpy as jnp

    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    if overlay is None and not isinstance(snap_or_graph, dict):
        overlay = getattr(snap_or_graph, "_live_overlay", None)
    if overlay is not None and overlay.empty:
        overlay = None
    n = g["n"]
    if n == 0:
        z = jnp.zeros((0,), jnp.int32)
        out = z if return_device else np.asarray(z)
        return [out] * count, [0] * count, [None] * count
    if overlay is not None:
        # no BFS peel over a live overlay (same fallback as the
        # sequential path): pure min-label propagation from own ids
        ids = jnp.arange(n, dtype=jnp.int32)
        val0 = jnp.concatenate([ids, jnp.full((1,), IINF, jnp.int32)])
        exp0 = jnp.concatenate(
            [ids + 1, jnp.full((1,), IINF, jnp.int32)])
        levels, rem = 0, None
    else:
        val0, exp0, levels, rem = _wcc_peel(g)
    ck = None
    if checkpoint is not None:
        def ck(k, rounds, state, _levels=levels):
            state = dict(state)
            state["levels"] = _levels
            checkpoint(k, rounds, state)
    # per-member COPIES: _push_list donates its value buffers, so two
    # members must never alias one device array
    members = [_CohortMember(k, jnp.array(val0, copy=True),
                             jnp.array(exp0, copy=True),
                             int(IINF), 0)
               for k in range(count)]
    with phase("wcc.propagate", k=count) as ph:
        _frontier_cohort(g, members, "wcc", (0.0, 0.0), max_rounds,
                         on_round=on_round, checkpoint=ck,
                         overlay=overlay, sync=ph.sync,
                         remainder=_wcc_domain(n, rem, ph))
        ph.set(rounds=max(m.rounds for m in members))
    for m in members:
        devprof.count_wcc_rounds(m.rounds)
    outs = [m.out if return_device or m.out is None
            else _wcc_readback(m.out) for m in members]
    return outs, [m.rounds + levels for m in members], \
        [m.stopped for m in members]


def frontier_sssp(snap_or_graph, source_dense: int, min_w: float = 0.0,
                  w_range: float = 1.0, max_rounds: int = 10_000,
                  delta: float | None = None,
                  quantile_mass: int | None = None,
                  return_device: bool = False, on_round=None,
                  checkpoint=None, resume: dict | None = None,
                  overlay=None):
    """SSSP over hashed edge weights with an expansion-tracked frontier;
    ``delta`` > 0 adds delta-stepping buckets. Returns (dist float32 [n]
    with FINF unreachable, rounds).

    ``checkpoint(rounds, state)``: round-boundary state capture (see
    ``_frontier_run``). ``resume``: a dict with ``val``/``val_exp``
    ([n+1] float32), ``rounds``, ``bucket_end`` and ``quantile_mass``
    from a prior checkpoint — the run continues that trajectory and
    its final distances are bit-equal to an uninterrupted run.

    Default is NO buckets: on hub-dominated power-law graphs the
    shortest-path distances concentrate in a band narrower than any
    useful bucket width (measured scale-26 R-MAT: ~all mass lands in
    one bucket at delta=1/4 through 1/32, total relaxation mass floors
    at ~3.2x E/8 regardless), so buckets only add rounds — scale-26 on
    v5e: delta=0 270s/26 rounds vs delta=0.125 300s/64 rounds. On
    graphs with spread distance distributions (road networks, uniform
    meshes) pass delta ~ mean edge weight."""
    import jax.numpy as jnp

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    n = g["n"]
    if delta is None:
        delta = 0.0
    if quantile_mass is None:
        # default: priority-batched expansion at the measured-optimal
        # band mass (see QUANTILE_MASS_DEFAULT — 2x faster than the
        # plain improved-set frontier at scale 26). Pass 0 for the
        # plain expand-everything frontier, or delta>0 for
        # delta-stepping buckets (spread distance distributions).
        quantile_mass = 0 if delta and delta > 0 \
            else QUANTILE_MASS_DEFAULT
    start_rounds, bucket_end0 = 0, None
    if resume is not None:
        # restored checkpoint state overrides the fresh-start init AND
        # the mode knobs that may have mutated mid-run (quantile
        # escalation, delta bucket advance)
        val = jnp.asarray(resume["val"], jnp.float32)
        val_exp = jnp.asarray(resume["val_exp"], jnp.float32)
        start_rounds = int(resume["rounds"])
        bucket_end0 = float(resume["bucket_end"])
        quantile_mass = int(resume["quantile_mass"])
    else:
        val = jnp.full((n + 1,), FINF, jnp.float32) \
            .at[source_dense].set(0.0)
        # nothing has pushed yet: only the source reads as improved
        # (val < val_exp); unreached sit at val == val_exp == FINF
        val_exp = jnp.full((n + 1,), FINF, jnp.float32)
    if overlay is None and not isinstance(snap_or_graph, dict):
        overlay = getattr(snap_or_graph, "_live_overlay", None)
    out, rounds = _frontier_run(g, val, val_exp, "sssp",
                                (min_w, w_range), max_rounds,
                                delta=delta, quantile_mass=quantile_mass,
                                on_round=on_round, checkpoint=checkpoint,
                                start_rounds=start_rounds,
                                bucket_end0=bucket_end0, overlay=overlay)
    if not return_device:
        out = np.asarray(out)
    return out, rounds


# A peel's remainder is listed, and the rounds behind it planned on the
# list, up to n / LIST_PLAN_RATIO vertices; past that the rounds plan
# over all n, as they do without a peel. A list plan gathers three
# values an entry where the n-wide plan reads them in order, so it
# costs 70 ns an entry against 16.5 ns a vertex (v5e, n = 8,871,268,
# experiments/wcc_listplan_probe.py, PR 37: the n-wide plan 146.2 ms; a
# FULL list of 2^13 1.7 ms, 2^18 16.2, 2^20 = n / 8.5 70.8, 2^21 =
# n / 4.2 150.5, 2^23 581.9): the two cross at n / 4.2, and the list
# has its own n-wide compaction to pay back first (54 ms in the
# seeding). At n / 8 the dearest list plan is half the n-wide one.
LIST_PLAN_RATIO = 8


def _remainder_cap(n: int) -> int:
    """The static width ``_wcc_seed_labels`` lists the remainder at:
    the largest power of two within n / LIST_PLAN_RATIO — from the
    graph's size alone, so one seeding executable a graph whatever the
    peel left."""
    return max(2, 1 << (max(n // LIST_PLAN_RATIO, 1).bit_length() - 1))


def _wcc_peel(g):
    """The giant component off the top: one direction-optimising BFS
    from the largest-degree vertex — on power-law graphs it anchors the
    giant component, so the BFS peels about all the edge mass — then the
    seed labels and the REMAINDER: the vertices the BFS did not reach
    that have an edge, listed once, ids ascending, at ``_remainder_cap``.
    The BFS ran to exhaustion, so no edge leaves the reached set: every
    vertex a propagation round can improve is on that list, from the
    first plan to the one that finds nothing. Returns ``(val, val_exp,
    levels, (rlist, count))``, the last two on the device (``count`` may
    exceed the cap: ``_wcc_domain`` decides). Phases: the BFS's own
    ``bfs.level``s, then ``wcc.seed`` (``levels``, ``source_deg``,
    ``r_cap``; it dispatches, nothing blocks)."""
    import jax.numpy as jnp

    from titan_tpu.obs.tracing import phase

    n = g["n"]
    at = jnp.argmax(g["deg"][:n])
    seed_v, seed_deg = (int(x) for x in
                        np.asarray(jnp.stack([at, g["deg"][at]])))
    # max_levels=n: a truncated BFS would freeze the partially-peeled
    # region as expanded, silently splitting its component's labels
    dist, levels = frontier_bfs_hybrid(g, seed_v, max_levels=n,
                                       return_device=True)
    r_cap = _remainder_cap(n)
    with phase("wcc.seed", levels=int(levels), source_deg=seed_deg,
               r_cap=r_cap, **{"async": True}):
        # frontier_bfs_hybrid returns dist[:n]; the seeding jit
        # re-appends nothing — it only reads [:n_]
        val, val_exp, rlist, count = _wcc_seed_labels()(
            dist, g["degc"], n_=n, r_cap=r_cap)
    return val, val_exp, levels, (rlist, count)


def _wcc_domain(n: int, rem, ph):
    """What the propagation plans on, decided from what it can observe:
    the peel's remainder ``rem = (rlist, count)`` where its count — read
    back here, once, inside ``wcc.propagate`` (``ph``), so the phase
    holds the seeding's n-wide compaction — fits the list's cap: then
    ``(rlist, w)``, ``w`` the count's power of two, for ``_frontier_run``
    / ``_frontier_cohort``. None (all n, today's ``bplan``) where no peel
    ran (``rem`` None: a live overlay, a resumed run) or the remainder
    outgrew the cap (no giant component). ``ph`` gets ``domain`` (the
    width planned on) and, after a peel, ``remainder`` (the count)."""
    if rem is None:
        ph.set(domain=n)
        return None
    rlist, count = rem
    with ph.sync():
        count = int(np.asarray(count))
    if count > rlist.shape[0]:
        ph.set(domain=n, remainder=count)
        return None
    w = _next_pow2(max(count, 2))
    ph.set(domain=w, remainder=count)
    return rlist, w


def _wcc_readback(out):
    """The labels on the host: ``wcc.result`` (``bytes``, ``sync_ms``;
    everything the propagation dispatched after its last plan drains
    inside it), counted under ``device.xfer.d2h_bytes{site=
    "wcc.result"}``."""
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    with phase("wcc.result", bytes=int(out.nbytes)) as ph:
        devprof.count_d2h("wcc.result", out.nbytes)
        with ph.sync():
            return np.asarray(out)


def _wcc_seed_labels():
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.compaction import compact_ids

        @functools.partial(jax.jit, static_argnames=("n_", "r_cap"))
        def seed(dist, degc, n_: int, r_cap: int):
            """Label arrays from a finished BFS: the reached component
            collapses to its minimum vertex id (already expanded — a
            closed component never pushes again); the rest start at
            their own id, improved-state so round 1 expands them. And
            the remainder, in the one pass that reads ``dist`` over n:
            the unreached vertices that have an edge (one without
            pushes nothing and is reached by nothing), compacted to
            ``r_cap`` ids ascending, fill ``n_``, with their count."""
            ids = jnp.arange(n_, dtype=jnp.int32)
            reached = dist[:n_] < INF
            rmin = jnp.min(jnp.where(reached, ids, IINF))
            lab = jnp.where(reached, rmin, ids)
            val = jnp.concatenate([lab, jnp.full((1,), IINF, jnp.int32)])
            exp = jnp.concatenate(
                [jnp.where(reached, lab, lab + 1),
                 jnp.full((1,), IINF, jnp.int32)])
            count, rlist = compact_ids(~reached & (degc[:n_] > 0),
                                       r_cap, n_)
            return val, exp, rlist, count
        return seed
    return jit_once("wcc_seed_labels", build)


def pagerank_dense(snap_or_graph, iterations: int = 20,
                   damping: float = 0.85, tol: float | None = None,
                   return_device: bool = False, on_round=None,
                   checkpoint=None, resume: dict | None = None,
                   overlay=None, reset=None):
    """PageRank over the chunked CSR:
    rank' = (1-d)/n + d * sum over in-edges of rank[src]/outdeg[src]
    (semantics match the pull-mode engine program in models/pagerank.py,
    incl. leaking dangling mass). Returns (rank float32 [n], iterations
    run). The uniform iteration on a snapshot is a pull over its
    in-edges, one program an iteration (``_pagerank_pull``,
    models/pagerank_pull.py); a caller that hands the out-layout dict
    has no in-edges to read, and the personalised iteration
    (``reset``) is pinned to the batched kernel's bits: both push
    through dense window sweeps, as below.
    ``tol``: early exit when the L1 delta falls below it.
    ``on_round``: per-iteration veto (RoundInterrupted) — the serving
    layer's cancellation/timeout hook, same contract as
    ``_frontier_run``.

    ``checkpoint(it, {"rank": rank})``: called after each completed
    iteration ``it`` (rank [n+1] device). ``resume``: ``{"rank", "it"}``
    — continue from iteration ``it``; ``contrib`` is a pure elementwise
    function of rank (same IEEE expressions as the in-loop recompute),
    so the continuation is bit-equal to an uninterrupted run.

    ``reset`` ([n] float, sums to 1): PERSONALIZED PageRank — the
    teleport distribution becomes ``(1-d) * reset`` (a one-hot row =
    one user's random walk with restart) and the initial rank IS the
    reset vector. ``None`` keeps the uniform formulation above,
    bit-identical to the pre-personalization kernel (it runs the same
    jit cache entries). This is the sequential oracle
    ``models/pagerank.pagerank_personalized_batched`` is pinned
    bit-equal to, per source row."""
    import jax.numpy as jnp

    from titan_tpu.obs.tracing import phase

    # an explicitly passed view (the serving lease's, frozen at the
    # job's epoch) overrides the snapshot's latest attached view — the
    # scheduler compacts before leasing for this kind, so its view is
    # empty even when later deltas already re-dirtied the plane
    ov = overlay
    if ov is None and not isinstance(snap_or_graph, dict):
        ov = getattr(snap_or_graph, "_live_overlay", None)
    if ov is not None and not ov.empty:
        # dense sweeps read contiguous base-CSR column windows — there
        # is no per-edge seam to mask tombstones or inject adds. The
        # documented fallback: fold the overlay first (the serving
        # scheduler does this for 'pagerank'/'dense' kinds).
        raise RuntimeError(
            "pagerank_dense on a live overlay: compact the overlay "
            "first (LiveGraphPlane.compact_if_dirty) — dense window "
            "sweeps have no overlay seam")
    if reset is None and not isinstance(snap_or_graph, dict):
        return _pagerank_pull(snap_or_graph, iterations, damping, tol,
                              return_device, on_round, checkpoint, resume)
    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    n = g["n"]
    dstT = g["dstT"]
    deg = g["deg"].astype(jnp.float32)
    colowner = _colowner(g)
    total = g["q_total"]
    W = min(DENSE_WINDOW, total)
    windows = -(-total // W)
    win = _pr_window()
    reset_dev = None
    if reset is not None:
        r = jnp.asarray(reset, jnp.float32)
        if r.shape != (n,):
            raise ValueError(f"reset must be [n={n}], got {r.shape}")
        reset_dev = jnp.concatenate(
            [r, jnp.zeros((1,), jnp.float32)])
    fin = _pr_finish() if reset_dev is None else _pr_finish_reset()
    it0 = 0
    if resume is not None:
        rank = jnp.asarray(resume["rank"], jnp.float32)
        it0 = int(resume["it"])
    elif reset_dev is not None:
        rank = reset_dev
    else:
        rank = jnp.full((n + 1,), 1.0 / n, jnp.float32) \
            .at[n].set(0.0)
    contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1.0), 0.0)

    def step(it, rank):
        nonlocal contrib
        with phase("pr.sweep", it=it, windows=windows):
            acc = jnp.zeros((n + 1,), jnp.float32)
            for w0 in range(0, total, W):
                # pooled window starts: a fresh scalar put per window
                # costs a host↔device round trip (64 windows/iteration
                # at scale 26)
                acc = win(acc, contrib, dev_scalar(w0), dstT, colowner,
                          W=W)
        with phase("pr.finish", it=it):
            if reset_dev is None:
                rank, contrib, delta = fin(acc, rank, deg,
                                           jnp.float32(damping), n_=n)
            else:
                rank, contrib, delta = fin(acc, rank, reset_dev, deg,
                                           jnp.float32(damping), n_=n)
        return rank, delta

    rank, it = _pr_loop(rank, it0, iterations, tol, on_round, checkpoint,
                        step)
    return _pr_readback(rank[:n], return_device), it


def _pr_loop(rank, it0, iterations, tol, on_round, checkpoint, step):
    """The iterations of ``pagerank_dense``, whichever sweep: ``step(it,
    rank) -> (rank, delta)`` opens the leaf phases (obs/tracing: spans
    under the caller's scope — the job's `run` — and profiler
    annotations). Nothing here waits for the device: with tol=None the
    loop only dispatches, and what it dispatched drains inside
    `pr.result`'s one blocking readback."""
    from titan_tpu.obs import devprof

    it = it0
    for it in range(it0 + 1, iterations + 1):
        if on_round is not None and not on_round(it - 1):
            raise RoundInterrupted(it - 1)
        rank, delta = step(it, rank)
        devprof.count_pr_iteration()
        if checkpoint is not None:
            checkpoint(it, {"rank": rank})
        if tol is not None and float(delta) < tol:
            break
    return rank, it


def _pr_readback(out, return_device: bool):
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    if not return_device:
        with phase("pr.result", bytes=int(out.nbytes)) as ph:
            devprof.count_d2h("pagerank.result", out.nbytes)
            with ph.sync():
                out = np.asarray(out)
    return out


def _pagerank_pull(snap, iterations, damping, tol, return_device,
                   on_round, checkpoint, resume):
    """The uniform iteration on a snapshot: one ``pagerank_pull`` and
    one ``pagerank_finish`` an iteration (models/pagerank_pull.py), no
    windows and no eager program — the start vector is made on the
    host, ``contrib`` is computed from ``rank`` inside the pull (so a
    resumed run is bit-equal to a straight one), the answer is cut to
    [n] by ``pagerank_result``."""
    import jax.numpy as jnp

    from titan_tpu.models import pagerank_pull as pp
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase
    from titan_tpu.ops import vmem_gather

    im = pp.pull_image(snap)
    n = im["n"]
    impl = vmem_gather.gather_impl(n)
    blocks = im["q_in"] // vmem_gather.BLOCK
    pull, fin, cut = pp.pull_step(), _pr_finish(), _pr_result()
    d = dev_scalar(float(damping), "float32")
    it0 = 0
    if resume is not None:
        rank = jnp.asarray(np.asarray(resume["rank"], np.float32))
        it0 = int(resume["it"])
    else:
        start = np.full(n + 1, 1.0 / n, np.float32)
        start[n] = 0.0
        rank = jnp.asarray(start)

    def step(it, rank):
        with phase("pr.sweep", it=it, windows=blocks, impl=impl):
            acc = pull(rank, im["deg"], im["idx"], im["first"],
                       im["last"], im["has"], impl=impl,
                       seg_max=im["seg_max"])
        devprof.count_pr_gather(impl, 8 * im["q_in"])
        with phase("pr.finish", it=it):
            rank, _contrib, delta = fin(acc, rank, im["deg"], d, n_=n)
        return rank, delta

    rank, it = _pr_loop(rank, it0, iterations, tol, on_round, checkpoint,
                        step)
    return _pr_readback(cut(rank, n_=n), return_device), it


def _pr_result():
    def build():
        import jax

        @functools.partial(jax.jit, static_argnames=("n_",))
        def cut(rank, n_: int):
            return rank[:n_]
        return cut
    return jit_once("pagerank_result", build)


def _pr_window():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("W",),
                           donate_argnums=(0,))
        def step(acc, contrib, w0, dstT, colowner, W: int):
            # the final window's slice start gets clamped so it fits, which
            # OVERLAPS the previous window; scatter-ADD is not idempotent,
            # so already-processed columns must contribute exactly 0
            w0c = jnp.minimum(w0, colowner.shape[0] - W)
            owner = jax.lax.dynamic_slice(colowner, (w0c,), (W,))
            nbr = jax.lax.dynamic_slice(dstT, (0, w0c), (8, W))
            fresh = (w0c + jnp.arange(W, dtype=jnp.int32)) >= w0
            c = jnp.where(fresh, contrib[owner], 0.0)
            return acc.at[nbr].add(jnp.broadcast_to(c[None, :], nbr.shape),
                                   mode="drop")
        return step
    return jit_once("pagerank_window", build)


def _pr_finish():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def fin(acc, rank, deg, damping, n_: int):
            new_rank = (1.0 - damping) / n_ + damping * acc[:n_]
            new_rank = jnp.concatenate(
                [new_rank, jnp.zeros((1,), jnp.float32)])
            delta = jnp.abs(new_rank[:n_] - rank[:n_]).sum()
            contrib = jnp.where(deg > 0, new_rank / jnp.maximum(deg, 1), 0.0)
            return new_rank, contrib, delta
        return fin
    return jit_once("pagerank_finish", build)


def _pr_finish_reset():
    """Personalized finish: teleport mass lands on the ``reset``
    distribution instead of uniformly — its own jit entry so the
    uniform path keeps its exact pre-personalization cache key and
    HLO. The per-row expressions here must stay IDENTICAL to the
    vmapped batched kernel in models/pagerank.py (bit-equality per
    source is the contract)."""
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def fin(acc, rank, reset, deg, damping, n_: int):
            new_rank = (1.0 - damping) * reset[:n_] + damping * acc[:n_]
            new_rank = jnp.concatenate(
                [new_rank, jnp.zeros((1,), jnp.float32)])
            delta = jnp.abs(new_rank[:n_] - rank[:n_]).sum()
            contrib = jnp.where(deg > 0, new_rank / jnp.maximum(deg, 1), 0.0)
            return new_rank, contrib, delta
        return fin
    return jit_once("pagerank_finish_reset", build)


def frontier_wcc(snap_or_graph, max_rounds: int = 10_000,
                 return_device: bool = False, on_round=None,
                 checkpoint=None, resume: dict | None = None,
                 overlay=None):
    """Hybrid connected components (symmetrized graphs): peel the seed
    vertex's whole component with one direction-optimized BFS, then run
    min-label propagation over the remaining components only. Returns
    (label int32 [n] = component minimum vertex id, rounds) where
    rounds counts BFS levels + propagation rounds.

    ``checkpoint(rounds, state)``: propagation-phase round-boundary
    capture (the state dict additionally carries ``levels``, the BFS
    peel's level count, so a resumed run reports the same total).
    ``resume``: ``{"val", "val_exp", "rounds", "levels"}`` — skips the
    BFS peel entirely and continues label propagation; final labels are
    bit-equal to an uninterrupted run.

    Phases (obs/tracing: leaf spans under the caller's scope — a served
    job's ``run`` — and profiler annotations): the peel's ``bfs.level``s
    and ``wcc.seed`` (``_wcc_peel``), ``wcc.propagate`` (``rounds``,
    ``sync_ms``: the plan readback a round; ``domain``, ``remainder``:
    what the rounds planned on, ``_wcc_domain`` — the peel's remainder
    where it is small beside n, all n without a peel), ``wcc.result``
    (``_wcc_readback``); ``device.wcc.rounds`` counts the rounds,
    ``device.wcc.plans{domain}`` their plans by road."""
    import jax.numpy as jnp

    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    if overlay is None and not isinstance(snap_or_graph, dict):
        overlay = getattr(snap_or_graph, "_live_overlay", None)
    if overlay is not None and overlay.empty:
        overlay = None
    n = g["n"]
    if n == 0:
        out = jnp.zeros((0,), jnp.int32)
        return (out if return_device else np.asarray(out)), 0
    start_rounds, rem = 0, None
    if resume is not None:
        val = jnp.asarray(resume["val"], jnp.int32)
        val_exp = jnp.asarray(resume["val_exp"], jnp.int32)
        start_rounds = int(resume["rounds"])
        levels = int(resume.get("levels", 0))
    elif overlay is not None:
        # live overlay: the BFS peel has no overlay seam, so skip it
        # and run pure min-label propagation — every vertex starts at
        # its own id in improved state. Slower (no giant-component
        # shortcut) but exact: labels converge to the component minimum
        # either way, so the result stays bit-equal to a rebuilt
        # snapshot's frontier_wcc.
        ids = jnp.arange(n, dtype=jnp.int32)
        val = jnp.concatenate([ids, jnp.full((1,), IINF, jnp.int32)])
        val_exp = jnp.concatenate(
            [ids + 1, jnp.full((1,), IINF, jnp.int32)])
        levels = 0
    else:
        val, val_exp, levels, rem = _wcc_peel(g)
    if checkpoint is not None:
        _ck = checkpoint

        def checkpoint(rounds, state, _ck=_ck, _levels=levels):
            state = dict(state)
            state["levels"] = _levels
            _ck(rounds, state)
    # one leaf phase over the whole propagation: its rounds keep their
    # host-stamped `round` events (run_single bridges `_trace_rounds`)
    with phase("wcc.propagate") as ph:
        out, rounds = _frontier_run(g, val, val_exp, "wcc", (0.0, 0.0),
                                    max_rounds, on_round=on_round,
                                    checkpoint=checkpoint,
                                    start_rounds=start_rounds,
                                    overlay=overlay, sync=ph.sync,
                                    remainder=_wcc_domain(n, rem, ph))
        ran = int(rounds) - start_rounds
        ph.set(rounds=ran)
    devprof.count_wcc_rounds(ran)
    if not return_device:
        out = _wcc_readback(out)
    return out, rounds + levels
