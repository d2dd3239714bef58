"""Whole-BFS-in-one-dispatch: the direction-optimizing level loop runs
entirely on device, with the alpha/beta mode switch AND the capacity
bucketing done by ``lax.switch``/``lax.cond`` over a ladder of
power-of-two-width branches.

Why: the host-driven hybrid (models/bfs_hybrid.py) sizes every kernel
from per-level stats READBACKS — 4-6 of them per scale-26 BFS. Each
readback costs a host↔device round trip, so the measured TEPS moves
with the host link's latency. The insight that
makes on-device sizing possible is that a ``lax.cond``/``lax.switch``
branch executes ONLY its taken side on TPU, so a ladder of prebuilt
bucket widths gives the same dead-lane economics as host-sized
dispatch without the readback: each level computes its masses on
device and switches into the matching width.

Structure per level (one ``lax.while_loop`` iteration):

* done      — f_count == 0 or max levels: identity.
* endgame   — remaining unvisited fits (END_C_CAP, END_P_CAP): run the
              trailing levels to completion in an inner while_loop
              (same body as bfs_hybrid._endgame) and mark done.
* td@k      — top-down expansion at (f_cap, p_cap) bucket k; the
              frontier list is rebuilt from ``dist == level`` inside
              the branch (no frontier state carried across levels).
* bu@j      — bottom-up at candidate bucket j: split-lane chunk-0 test
              (lanes 0-3), then an inner cond-ladder refetches lanes
              4-7 for the few misses at a narrower width, then the
              fused chunk rounds + exhaust sweep, again cond-laddered
              by survivor count.

The single dispatch returns (dist, stats); ONE host readback ends the
run. Numerics and level semantics are identical to the host-driven
hybrid — tests/test_frontier_models.py pins bit-equality with plain
BFS over the same graphs (buckets monkeypatched small so every branch
executes on CPU-sized inputs).

Trade-off: the fused program compiles every branch of every ladder
(~10-20 kernel bodies) — a one-time multi-minute compile, amortized by
the persistent XLA compile cache. The host-driven path remains the
default for interactive use; the bench selects the fused path via
``TITAN_TPU_FUSED_BFS=1`` once its numbers win on real hardware.

SYMMETRIC GRAPHS ONLY (same contract as bfs_hybrid).
"""

# graftlint: allow-file[opscan] reason=single-dispatch fused experiment, not a round-loop hot path — its in-branch nonzero compactions are the measured alternative ops.compaction is judged against (exempt since ISSUE r6)

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.models.bfs import INF, _next_pow2
from titan_tpu.models.bfs_hybrid import (_bit_of, _level_stats, _pack_bits,
                                         build_chunked_csr,
                                         enumerate_chunk_pairs)
from titan_tpu.utils.jitcache import jit_once as _get

# stats vector layout
SF, SM8F, SM8U, SNU, SLEVEL, SDONE, SOVERFLOW = range(7)

BU_CHUNK_ROUNDS = 8
END_C_CAP = 1 << 21
END_P_CAP = 1 << 22
# branch-memory diet: the ladders top out here instead of cap_n/cap_q —
# every switch branch's temporaries coexist with the ~9.3GB scale-26
# graph, and the first cut's cap_n-wide branches OOM'd at compile. A bu
# level whose candidate count exceeds the top bucket sets the overflow
# stat instead of truncating, and the driver transparently re-runs via
# the host-driven hybrid (never happens on Graph500-class inputs: the
# heavy level's candidates are ~0.4n < 2^25 at scale 26).
FUSED_BU_MAX = 1 << 25
FUSED_TD_MAX = (1 << 23, 1 << 25)


def _ladders(n: int, total_chunks: int):
    """Bucket ladders sized to the graph (all static at trace time)."""
    cap_n = _next_pow2(max(n, 2))
    cap_q = _next_pow2(max(total_chunks + 1, 2))
    # td (f_cap, p_cap) pairs, ascending; the last p covers any single
    # vertex's mass (max degree < n) and any frontier the alpha test
    # leaves in td mode at bench scales
    # (f, p) pairs tuned to the level shapes a direction-optimized
    # Graph500 run actually visits (head levels; the mid td level whose
    # frontier is ~1/16 of its chunk mass; the pre-switch heavy td).
    # A mismatched pair is pure dead-lane cost — the first fused cut
    # paired (2^18,2^22)->(2^24,2^26) and measured +44% vs the host
    # path at scale 24 because a 1M-vertex/5M-chunk frontier fell into
    # the 2^26-wide kernel. Frontiers past the top pair force bu mode;
    # candidates past FUSED_BU_MAX set the overflow stat (module doc).
    td = []
    for fb, pb in ((1 << 12, 1 << 18), (1 << 20, 1 << 22),
                   FUSED_TD_MAX):
        td.append((min(fb, cap_n), min(pb, cap_q)))
    td = sorted(set(td))
    # bu candidate caps
    bu = sorted({min(1 << 23, cap_n), min(FUSED_BU_MAX, cap_n)})
    return td, bu, cap_n, cap_q


def _bu_level_body(dist, level, dstT, colstart, degc, deg, c_cap: int,
                   n_: int):
    """One full bottom-up level at candidate width ``c_cap`` —
    split-lane opener + laddered survivor rounds + exhaust, all traced
    inline (runs inside a switch branch)."""
    import jax
    import jax.numpy as jnp

    q_pad = dstT.shape[1] - 1
    fbits = _pack_bits(dist, level, n_)
    unvis = (dist[:n_] >= INF) & (degc[:n_] > 0)
    cand = jnp.nonzero(unvis, size=c_cap,
                       fill_value=n_)[0].astype(jnp.int32)
    c_count = unvis.sum().astype(jnp.int32)
    # a candidate set wider than the bucket would be TRUNCATED by the
    # nonzero — flag it so the driver discards and re-runs host-driven
    overflow = (c_count > c_cap).astype(jnp.int32)
    alive = jnp.arange(c_cap) < c_count
    v = jnp.minimum(cand, n_)
    cols = jnp.where(alive, colstart[v], q_pad)
    parents4 = jnp.take(dstT[:4], jnp.clip(cols, 0, q_pad), axis=1)
    found = alive & _bit_of(fbits, parents4).any(axis=0)
    dist = dist.at[jnp.where(found, v, n_ + 1)].set(
        level + 1, mode="drop")
    untested = alive & ~found & (deg[v] > 4)
    nu = untested.sum().astype(jnp.int32)

    def finish47(dist, cand_u, u_cap: int):
        """Lanes 4-7 for the compacted untested list at width u_cap;
        then the chunk rounds + exhaust for full-chunk0 misses."""
        cc = (cand_u < n_).sum().astype(jnp.int32)
        al = jnp.arange(u_cap) < cc
        vv = jnp.minimum(cand_u, n_)
        cl = jnp.where(al, colstart[vv], q_pad)
        p47 = jnp.take(dstT[4:], jnp.clip(cl, 0, q_pad), axis=1)
        fnd = al & _bit_of(fbits, p47).any(axis=0)
        dist = dist.at[jnp.where(fnd, vv, n_ + 1)].set(
            level + 1, mode="drop")
        surv = al & ~fnd & (degc[vv] > 1)
        nc = surv.sum().astype(jnp.int32)
        idx = jnp.nonzero(surv, size=u_cap, fill_value=u_cap - 1)[0]
        keep = jnp.arange(u_cap) < nc
        cand2 = jnp.where(keep, cand_u[idx], n_).astype(jnp.int32)
        off2 = jnp.where(keep, 1, 0).astype(jnp.int32)

        def rounds_and_exhaust(dist, cand_r, off_r, nc_r, w: int):
            def round_(state, _):
                dist, cand, off, ncr = state
                alv = jnp.arange(w) < ncr
                lv = jnp.minimum(cand, n_)
                cls = jnp.where(alv, colstart[lv] + off, q_pad)
                par = jnp.take(dstT, jnp.clip(cls, 0, q_pad), axis=1)
                ft = alv & _bit_of(fbits, par).any(axis=0)
                dist = dist.at[jnp.where(ft, lv, n_ + 1)].set(
                    level + 1, mode="drop")
                sv = alv & ~ft & (off + 1 < degc[lv])
                ix = jnp.nonzero(sv, size=w, fill_value=w - 1)[0]
                nc2 = sv.sum().astype(jnp.int32)
                kp = jnp.arange(w) < nc2
                cand = jnp.where(kp, cand[ix], n_)
                off = jnp.where(kp, off[ix] + 1, 0)
                return (dist, cand, off, nc2), None

            (dist, cand_r, off_r, nc_r), _ = jax.lax.scan(
                round_, (dist, cand_r, off_r, nc_r), None,
                length=BU_CHUNK_ROUNDS - 1)
            # stragglers: K-chunk-stride while_loop — every iteration
            # checks the next K chunks of EVERY survivor, so completion
            # is guaranteed for any degree (a bounded single exhaust
            # sweep would silently drop a hub's chunks past its cap —
            # the enumerate primitive drops out-of-range starts)
            K = max((1 << 16) // max(w, 1), 1)

            def ex_cond(s):
                _, _, _, ncr = s
                return ncr > 0

            def ex_body(s):
                dist, cand, off, ncr = s
                alv = jnp.arange(w) < ncr
                lv = jnp.minimum(cand, n_)
                rem = jnp.where(alv,
                                jnp.maximum(degc[lv] - off, 0), 0)
                j = jnp.arange(K, dtype=jnp.int32)[None, :]
                cls = (colstart[lv] + off)[:, None] + j      # [w, K]
                live = alv[:, None] & (j < rem[:, None])
                cls = jnp.where(live, jnp.clip(cls, 0, q_pad), q_pad)
                par = jnp.take(dstT, cls.reshape(-1), axis=1)
                hit = _bit_of(fbits, par).any(axis=0).reshape(w, K)
                ft = alv & (hit & live).any(axis=1)
                dist = dist.at[jnp.where(ft, lv, n_ + 1)].set(
                    level + 1, mode="drop")
                sv = alv & ~ft & (rem > K)
                ix = jnp.nonzero(sv, size=w, fill_value=w - 1)[0]
                nc2 = sv.sum().astype(jnp.int32)
                kp = jnp.arange(w) < nc2
                cand = jnp.where(kp, cand[ix], n_)
                off = jnp.where(kp, off[ix] + K, 0)
                return (dist, cand, off, nc2)

            dist, _, _, _ = jax.lax.while_loop(
                ex_cond, ex_body, (dist, cand_r, off_r, nc_r))
            return dist

        # survivor-width ladder for the chunk rounds
        wl = sorted({min(1 << 12, u_cap), u_cap})
        if len(wl) == 1:
            return jax.lax.cond(
                nc > 0,
                lambda d: rounds_and_exhaust(d, cand2, off2, nc, u_cap),
                lambda d: d, dist)
        return jax.lax.cond(
            nc == 0, lambda d: d,
            lambda d: jax.lax.cond(
                nc <= wl[0],
                lambda d2: rounds_and_exhaust(
                    d2, cand2[:wl[0]], off2[:wl[0]], nc, wl[0]),
                lambda d2: rounds_and_exhaust(d2, cand2, off2, nc,
                                              u_cap), d), dist)

    # untested-width ladder (measured ~10% of candidates at heavy
    # levels miss lanes 0-3 — the narrow branches are the common case)
    def with_u(u_cap: int):
        def go(dist):
            idx = jnp.nonzero(untested, size=u_cap,
                              fill_value=c_cap - 1)[0]
            keep = jnp.arange(u_cap) < nu
            cand_u = jnp.where(keep, cand[idx], n_).astype(jnp.int32)
            return finish47(dist, cand_u, u_cap)
        return go

    ul = sorted({max(c_cap // 16, 8), max(c_cap // 4, 8), c_cap})

    def pick(dist, ladder):
        # nested cond ladder: smallest fitting width runs
        if len(ladder) == 1:
            return with_u(ladder[0])(dist)
        return jax.lax.cond(nu <= ladder[0], with_u(ladder[0]),
                            lambda d: pick(d, ladder[1:]), dist)

    dist = jax.lax.cond(nu == 0, lambda d: d,
                        lambda d: pick(d, ul), dist)
    return dist, overflow


def _td_level_body(dist, level, dstT, colstart, degc, f_cap: int,
                   p_cap: int, n_: int):
    import jax.numpy as jnp

    q_pad = dstT.shape[1] - 1
    fr_mask = dist[:n_] == level
    frontier = jnp.nonzero(fr_mask, size=f_cap,
                           fill_value=n_)[0].astype(jnp.int32)
    f_count = fr_mask.sum().astype(jnp.int32)
    valid = jnp.arange(f_cap) < f_count
    v = jnp.minimum(frontier, n_)
    cols, _, _ = enumerate_chunk_pairs(
        valid, degc[v], colstart[v], p_cap, q_pad)
    nbr = jnp.take(dstT, cols, axis=1)
    return dist.at[nbr].min(level + 1, mode="drop")


def _endgame_body(dist, level0, max_lv, dstT, colstart, degc,
                  c_cap: int, p_cap: int, n_: int):
    """Inner while_loop finishing every trailing level (same body as
    bfs_hybrid._endgame, traced inline). Returns (dist, final_level)."""
    import jax
    import jax.numpy as jnp

    q_pad = dstT.shape[1] - 1

    def cond(s):
        _, _, _, level, found = s
        return (found > 0) & (level < max_lv)

    def body(s):
        dist, cand, c_count, level, _ = s
        fbits = _pack_bits(dist, level, n_)
        valid = jnp.arange(c_cap) < c_count
        v = jnp.minimum(cand, n_)
        cols, p_total, owner = enumerate_chunk_pairs(
            valid, degc[v], colstart[v], p_cap, q_pad, with_owner=True)
        parents = jnp.take(dstT, cols, axis=1)
        hit = _bit_of(fbits, parents).any(axis=0)
        j = jnp.arange(p_cap, dtype=jnp.int32)
        found_per = jnp.zeros((c_cap,), jnp.int32) \
            .at[jnp.where(j < p_total, owner, c_cap - 1)] \
            .max(hit.astype(jnp.int32), mode="drop")
        found = valid & (found_per > 0)
        dist = dist.at[jnp.where(found, v, n_ + 1)].set(
            level + 1, mode="drop")
        nfound = found.sum().astype(jnp.int32)
        surv = valid & ~found
        idx = jnp.nonzero(surv, size=c_cap, fill_value=c_cap - 1)[0]
        nc = surv.sum().astype(jnp.int32)
        keep = jnp.arange(c_cap) < nc
        cand = jnp.where(keep, v[idx], n_).astype(jnp.int32)
        return (dist, cand, nc, level + 1, nfound)

    unvis = (dist[:n_] >= INF) & (degc[:n_] > 0)
    cand0 = jnp.nonzero(unvis, size=c_cap,
                        fill_value=n_)[0].astype(jnp.int32)
    c0 = unvis.sum().astype(jnp.int32)
    state = (dist, cand0, c0, level0, jnp.int32(1))
    dist, _, _, level, _ = jax.lax.while_loop(cond, body, state)
    return dist, level


def _fused_bfs():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(
            jax.jit,
            static_argnames=("n_", "total_chunks", "end_c", "end_p"),
            donate_argnums=(0,))
        def run(dist, st, max_lv, dstT, colstart, degc, deg, n_: int,
                total_chunks: int, end_c: int, end_p: int):
            td_buckets, bu_buckets, cap_n, cap_q = _ladders(
                n_, total_chunks)

            def level_body(state):
                dist, st = state
                f_count = st[SF]
                m8_f = st[SM8F]
                m8_unvis = st[SM8U]
                n_unvis = st[SNU]
                level = st[SLEVEL]

                endgame_ok = (n_unvis <= end_c) & (m8_unvis <= end_p)
                # a frontier that exceeds the td ladder (by count OR
                # mass) is forced bottom-up — bu is mode-correct for
                # any level. The bu ladder tops out at FUSED_BU_MAX
                # (memory diet), so a wider candidate set WOULD be
                # truncated: _bu_level_body flags SOVERFLOW and the
                # driver re-runs host-driven instead of trusting the
                # result. Do not remove that guard.
                use_bu = ((m8_f > m8_unvis // 8) & (f_count > 1)) \
                    | (m8_f > td_buckets[-1][1]) \
                    | (f_count > td_buckets[-1][0])

                # branch index: 0 = endgame, 1..T = td buckets,
                # T+1..T+B = bu buckets
                T = len(td_buckets)
                tdi = jnp.int32(T - 1)
                for k in range(T - 2, -1, -1):
                    fits = (f_count <= td_buckets[k][0]) \
                        & (m8_f <= td_buckets[k][1])
                    tdi = jnp.where(fits, jnp.int32(k), tdi)
                bui = jnp.int32(len(bu_buckets) - 1)
                for k in range(len(bu_buckets) - 2, -1, -1):
                    bui = jnp.where(n_unvis <= bu_buckets[k],
                                    jnp.int32(k), bui)
                idx = jnp.where(
                    endgame_ok, jnp.int32(0),
                    jnp.where(use_bu, jnp.int32(1 + T) + bui,
                              jnp.int32(1) + tdi))

                def endgame_branch(dist, st):
                    d2, lvl = _endgame_body(
                        dist, st[SLEVEL], max_lv, dstT, colstart, degc,
                        end_c, end_p, n_)
                    # +1 = the empty probe level (host-loop parity)
                    st2 = jnp.stack([
                        jnp.int32(0), jnp.int32(0), jnp.int32(0),
                        jnp.int32(0),
                        jnp.minimum(lvl + 1, max_lv), jnp.int32(1),
                        st[SOVERFLOW]])
                    return d2, st2

                def td_branch(k):
                    def go(dist, st):
                        d2 = _td_level_body(
                            dist, st[SLEVEL], dstT, colstart, degc,
                            td_buckets[k][0], td_buckets[k][1], n_)
                        s4 = _level_stats(d2, degc, st[SLEVEL], n_)
                        st2 = jnp.stack([
                            s4[0], s4[1], s4[2], s4[3],
                            st[SLEVEL] + 1,
                            (s4[0] == 0).astype(jnp.int32),
                            st[SOVERFLOW]])
                        return d2, st2
                    return go

                def bu_branch(k):
                    def go(dist, st):
                        d2, ovf = _bu_level_body(
                            dist, st[SLEVEL], dstT, colstart, degc,
                            deg, bu_buckets[k], n_)
                        s4 = _level_stats(d2, degc, st[SLEVEL], n_)
                        ovf = jnp.maximum(st[SOVERFLOW], ovf)
                        st2 = jnp.stack([
                            s4[0], s4[1], s4[2], s4[3],
                            st[SLEVEL] + 1,
                            # overflow also ends the loop — the result
                            # will be discarded by the driver anyway
                            jnp.maximum((s4[0] == 0).astype(jnp.int32),
                                        ovf),
                            ovf])
                        return d2, st2
                    return go

                branches = [endgame_branch] \
                    + [td_branch(k) for k in range(T)] \
                    + [bu_branch(k) for k in range(len(bu_buckets))]
                dist, st = jax.lax.switch(idx, branches, dist, st)
                return (dist, st)

            def cond(state):
                _, st = state
                return (st[SDONE] == 0) & (st[SLEVEL] < max_lv)

            dist, st = jax.lax.while_loop(cond, level_body, (dist, st))
            return dist, st
        return run
    return _get("hybrid_fused", build)


def frontier_bfs_hybrid_fused(snap, source_dense: int,
                              max_levels: int = 1000,
                              return_device: bool = False):
    """Single-dispatch direction-optimizing BFS (see module doc).
    Returns (dist, levels) like frontier_bfs_hybrid."""
    import jax.numpy as jnp

    from titan_tpu.utils.jitcache import dev_scalar

    g = snap if isinstance(snap, dict) else build_chunked_csr(snap)
    n = g["n"]
    dstT, colstart, degc, deg = (g["dstT"], g["colstart"], g["degc"],
                                 g["deg"])
    total_chunks = int(g["q_total"] - 1)
    run = _fused_bfs()
    end_c = min(END_C_CAP, _next_pow2(max(n, 2)))
    end_p = min(END_P_CAP, _next_pow2(max(total_chunks + 1, 2)))
    dist = jnp.full((n + 1,), INF, jnp.int32).at[source_dense].set(0)
    m8_f0 = degc[source_dense]
    st0 = jnp.stack([
        jnp.int32(1), m8_f0.astype(jnp.int32),
        jnp.where(dist[:n] >= INF, degc[:n], 0).sum(dtype=jnp.int32),
        ((dist[:n] >= INF) & (degc[:n] > 0)).sum().astype(jnp.int32),
        jnp.int32(0), jnp.int32(0), jnp.int32(0)])
    dist, st = run(dist, st0, dev_scalar(max_levels), dstT, colstart,
                   degc, deg, n_=n, total_chunks=total_chunks,
                   end_c=end_c, end_p=end_p)
    st_h = np.asarray(st)
    if int(st_h[SOVERFLOW]):
        # a bu level's candidate set exceeded the trimmed ladder (never
        # on Graph500-class inputs — see FUSED_BU_MAX): the fused result
        # is invalid; re-run through the host-driven hybrid
        from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
        return frontier_bfs_hybrid(g, source_dense,
                                   max_levels=max_levels,
                                   return_device=return_device)
    levels = int(st_h[SLEVEL])
    out = dist[:n]
    if not return_device:
        out = np.asarray(out)
    return out, levels
