"""Multi-chip direction-optimizing BFS over a vertex-block mesh.

Round 1's sharded BFS replicated the distance array and pmin-reduced all
n elements per level (a 256MB all-reduce x levels at scale 26 — VERDICT
weak point 5). The r4 redesign kept the EDGE data sharded (each chip
holds only its vertex block's 8-aligned chunked out-CSR) and exchanged
only SPARSE newly-found vertex lists over ICI, but drove every level
through a CHAIN of host-sized dispatches — td: frontier_of + expand +
exchange; bu: bu0 + bu_more + bu_exhaust (+ jitted cap trims) +
exchange — measuring ~2.0× over the plain hybrid on a ONE-device mesh
(PERF_NOTES r4-late: 4.69s sharded vs 2.32s plain at scale 23), i.e.
the overhead was dispatch/merge machinery, not communication.

The ISSUE-13 rebuild fuses each level into ONE dispatch per mode per
cap bucket:

* **td level** (``shx_td``): frontier list build (replicated
  compaction of ``dist == level`` — the per-level n-scale pass every
  design pays once), per-shard expansion of OWNED frontier vertices
  through the block's local CSR, then the sparse exchange
  (``parallel/partition.exchange_found``: compact per-shard newly-found
  ids, all-gather ONLY those lists — O(frontier) comm), the replicated
  merge and the full stats vector. One dispatch, one host readback.
* **bu level** (``shx_bu``): per-shard candidate build from the block
  window + chunk-0 bitmap test, then the fused chunk rounds and the
  K-chunk-stride exhaust while_loop run INSIDE the same dispatch under
  a ``lax.cond`` survivor-width ladder (the pmax'd survivor count is
  replicated, so every shard takes the same branch and collectives
  stay outside the conds — dead-lane width still tracks the actual
  per-chip survivor maxima, r4's cap-bucket economics without the
  host round trips), then the same fused exchange tail. One dispatch.

The per-level all-gather is issued inside the dispatch right after the
sweep's scatters and BEFORE the n-scale merge/stat reductions, so XLA's
latency-hiding scheduler can overlap the collective with compute — the
host-driven chain serialized it behind a dispatch boundary and a stats
sync. ``found_cap`` is DEVICE-CHECKED exactly as before: the stats
carry the true per-chip found max and the host retries the LEVEL with
the exact cap on overflow (the merged result is discarded; the guess
tracks 4× the previous level's max, so retries are rare) — worst case
2 dispatches for that level, which is the documented budget:
``device.exec.calls`` per level ≤ 2 (tests/test_sharded_exchange.py
pins it through the DeviceCostProfiler).

Explicit shardings end to end (ISSUE 13): the per-shard edge arrays
upload ONCE through ``parallel/partition.place_shards`` (committed
``NamedSharding(mesh, P("v", ...))`` — no per-dispatch resharding), the
replicated vertex arrays through ``place_replicated``, and the kernels
compile through ``parallel/mesh.mesh_jit`` with OUTPUT shardings pinned
(dist and stats replicated), cached per (kernel, mesh) and shimmed by
the device-cost profiler like every single-chip kernel.

The dist array itself stays replicated (n int32 = 268MB at scale 26:
cheap memory, zero steady-state traffic) — a deliberate trade
documented here: per-vertex *model state* in the dense engine is
sharded; BFS replicates dist precisely so the exchange can be sparse.
Bottom-up levels are FULLY LOCAL until the level-end exchange
(symmetric graph: candidates check their own block's out-CSR; parents'
dist==level values were settled by the previous level's exchange).

Single- AND multi-process (DCN) meshes run the SAME driver (the
reference contract: the distributed executor runs the same machinery as
in-process — titan-hadoop HadoopScanMapper.java:33-110): the kernels
return REPLICATED outputs only, so the host never indexes per-shard
rows of a non-addressable global array; the multihost loader
(parallel/multihost) supplies host-sharded ``_dev`` arrays through the
same 6-tuple contract.

Per-shard edge arrays use LOCAL column indices, so each shard stays
int32-safe as long as its own chunk count is < 2^31 — 8 shards of a
scale-26 graph are ~35M columns each.

Symmetric graphs only (see bfs_hybrid). Validated bit-equal against the
single-chip hybrid on 1/2/8-device CPU meshes in
tests/test_sharded_bfs.py and tests/test_sharded_exchange.py.
"""

from __future__ import annotations

import numpy as np

from titan_tpu.models.bfs import INF, _next_pow2
from titan_tpu.models.bfs_hybrid import (_fbit_of, _pack_bits,
                                         enumerate_chunk_pairs)
from titan_tpu.ops.compaction import compact_ids, scatter_compact

ALPHA = 8.0
BU_CHUNK_ROUNDS = 8

# stats vector layout (the exchange's replicated output; the first four
# entries predate the per-chip cap stats)
ST_NF, ST_M8F, ST_M8UNVIS, ST_FOUNDMAX, ST_M8F_CHIP, ST_NUNV_CHIP = range(6)

# instrumentation: found_cap used by each level's exchange in the most
# recent run (tests assert the exchange stays sparse)
LAST_EXCHANGE_CAPS: list = []
# full per-level communication profile of the most recent run: mode,
# frontier size, per-chip found max, exchange cap/volume, retries, and
# the per-level dispatch count (the fused-kernel budget evidence)
# (MULTICHIP evidence — the dryrun prints it)
LAST_PROFILE: list = []


def plan_shard_cuts(colstart: np.ndarray, n: int, num_shards: int):
    """Edge-balanced vertex-range cuts on the chunk prefix, with the
    int32 safety guard: per-shard arrays use LOCAL column indices, so
    every shard's chunk span must stay < 2^31 even when the GLOBAL chunk
    count exceeds int32 (``colstart`` is int64 host-side). Returns
    (bounds [d_eff+1] int64, b_max, q_max). Raises NotImplementedError
    when any shard's local span would overflow int32 — shard wider."""
    total = int(colstart[n])
    cuts = [0]
    for k in range(1, num_shards):
        cuts.append(int(np.searchsorted(colstart[:n + 1],
                                        k * total / num_shards)))
    cuts.append(n)
    bounds = np.asarray(sorted(set(cuts)), np.int64)
    d_eff = len(bounds) - 1
    b_max = max(1, int((bounds[1:] - bounds[:-1]).max()))
    spans = [int(colstart[bounds[d + 1]] - colstart[bounds[d]])
             for d in range(d_eff)]
    q_max = max(1, max(spans)) + 1       # +1 local sink col
    if q_max >= (1 << 31):
        raise NotImplementedError(
            f"a shard's local chunk span ({max(spans)}) exceeds int32; "
            f"use more shards than {num_shards} (local column indices "
            "are int32)")
    return bounds, b_max, q_max


def shard_unvisited_cap(degc_all: np.ndarray, bounds) -> int:
    """Max over shards of the count of expandable (degc>0) block
    vertices — the size bound for the FIRST bottom-up level's per-chip
    candidate list, before any exchange stats exist. The ONLY definition
    (single-host shard_chunked_csr and the multihost host-sharded loader
    both call it, so the bu0 c_cap guarantee cannot drift)."""
    counts = [int((degc_all[int(bounds[d]):int(bounds[d + 1])] > 0).sum())
              for d in range(len(bounds) - 1)]
    return max(counts, default=1) or 1


def pack_shard_block(d: int, colstart: np.ndarray, dstT: np.ndarray,
                     degc_all: np.ndarray, bounds: np.ndarray,
                     b_max: int, q_max: int, n: int):
    """Pack vertex block ``d`` into the padded per-shard layout:
    (dstT [8, q_max] pad n+1, LOCAL colstart [b_max+1] with the tail
    held at the last live value, degc [b_max]). The ONLY definition of
    the shard block layout — shard_chunked_csr (single-host) and the
    multihost host-sharded loader both call it, so the two paths cannot
    drift."""
    dstT_b = np.full((8, q_max), n + 1, np.int32)
    cs_b = np.zeros(b_max + 1, np.int32)
    degc_b = np.zeros(b_max, np.int32)
    if d < len(bounds) - 1 and bounds[d] < bounds[d + 1]:
        lo, hi = int(bounds[d]), int(bounds[d + 1])
        c0, c1 = int(colstart[lo]), int(colstart[hi])
        dstT_b[:, :c1 - c0] = dstT[:, c0:c1]
        local = (colstart[lo:hi + 1] - c0).astype(np.int32)
        cs_b[:hi - lo + 1] = local
        cs_b[hi - lo + 1:] = local[-1]
        degc_b[:hi - lo] = degc_all[lo:hi]
    return dstT_b, cs_b, degc_b


def shard_chunked_csr(snap_or_graph, num_shards: int):
    """Edge-balanced vertex-range shards of the chunked CSR, padded to
    uniform shapes: dict with ``dstT_sh`` [D, 8, Qmax] (pad n+1),
    ``colstart_sh`` [D, Bmax+1] LOCAL column starts, ``degc_sh``
    [D, Bmax], ``bounds`` [D+1], ``degc`` (global, replicated),
    ``layout`` (parallel/partition.BlockLayout descriptor) — numpy;
    device placement happens in the runner (explicit NamedShardings,
    parallel/partition.place_shards). Cached on the source object."""
    from titan_tpu.models.bfs_hybrid import build_chunked_csr
    from titan_tpu.parallel.partition import block_layout

    if isinstance(snap_or_graph, dict):
        g = snap_or_graph
    else:
        g = build_chunked_csr(snap_or_graph)
    cache = g.get("_shards")
    if cache is not None and cache[0] == num_shards:
        return cache[1]
    n = g["n"]
    q_total = g["q_total"]
    # shard from HOST arrays only — np.asarray on the device arrays would
    # read gigabytes back from the device
    host = g.get("_host", g)
    colstart = host["colstart"]
    dstT = host["dstT"]
    if "degc" in host:
        degc_all = np.asarray(host["degc"])[:n]
    else:                      # graph500.load_or_build host dict
        deg = np.asarray(host["deg"])
        degc_all = (-(-deg // 8)).astype(np.int32)
    for a in (colstart, dstT):
        if not isinstance(a, np.ndarray):   # np.memmap passes
            raise TypeError(
                "shard_chunked_csr needs host (numpy) graph arrays; pass "
                "the graph500.load_or_build dict or a GraphSnapshot, not "
                "a to_device() result")
    colstart = np.asarray(colstart)
    dstT = np.asarray(dstT)
    layout = block_layout(colstart, degc_all, n, num_shards)
    bounds_full = np.asarray(layout.bounds, np.int64)
    b_max, q_max = layout.b_max, layout.q_max
    d_eff = layout.live_shards
    total = int(colstart[n])
    dstT_sh = np.full((num_shards, 8, q_max), n + 1, np.int32)
    colstart_sh = np.zeros((num_shards, b_max + 1), np.int32)
    degc_sh = np.zeros((num_shards, b_max), np.int32)
    for d in range(d_eff):
        dstT_sh[d], colstart_sh[d], degc_sh[d] = pack_shard_block(
            d, colstart, dstT, degc_all, bounds_full, b_max, q_max, n)
    out = {
        "dstT_sh": dstT_sh, "colstart_sh": colstart_sh,
        "degc_sh": degc_sh, "bounds": bounds_full, "n": n,
        "b_max": b_max, "q_max": q_max, "q_total": q_total,
        "degc": np.concatenate([degc_all, [0]]).astype(np.int32),
        "total_chunks": total,
        "layout": layout,
        # per-shard chunk spans — the edge-balance evidence the comm
        # profile reports (cuts are planned on the chunk prefix, so
        # these should be near-uniform)
        "shard_chunks": list(layout.shard_chunks),
        "nunv_chip_max": layout.nunv_cap,
    }
    if isinstance(g, dict):
        g["_shards"] = (num_shards, out)
    return out


# ---------------------------------------------------------------------------
# fused per-level kernels (one dispatch per level per cap bucket)
# ---------------------------------------------------------------------------

def _exchange_tail(dist, level, degc, degc_l, lo, hi, found_cap: int,
                   n_: int, b_max: int):
    """The fused exchange, traced inline at the end of BOTH level
    kernels: sparse found-list gather (parallel/partition.
    exchange_found — O(frontier) comm, issued before the n-scale
    merge/stat reductions so the collective can overlap them), the
    replicated merge, and the stats vector whose per-chip maxima size
    the NEXT level's kernel caps (frontier chunk mass owned by one
    chip; unvisited expandable vertices in one block) so dead-lane
    width never exceeds one chip's actual share. ``found_cap`` is
    device-checked via ST_FOUNDMAX (host retries the level on
    overflow)."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.parallel.mesh import VERTEX_AXIS
    from titan_tpu.parallel.partition import exchange_found

    newly = dist[:n_] == level + 1
    all_ids, found_max = exchange_found(newly, found_cap, n_)
    merged = dist.at[all_ids.ravel()].min(level + 1, mode="drop")
    changed = merged[:n_] == level + 1
    nf = changed.sum().astype(jnp.int32)
    m8_f = jnp.where(changed, degc[:n_], 0).sum(dtype=jnp.int32)
    unvis = merged[:n_] >= INF
    m8_unvis = jnp.where(unvis, degc[:n_], 0).sum(dtype=jnp.int32)
    # per-chip cap stats over this chip's block window
    blk = jnp.minimum(lo + jnp.arange(b_max, dtype=jnp.int32), n_)
    bmask = jnp.arange(b_max, dtype=jnp.int32) < (hi - lo)
    vis_blk = merged[blk]
    m8f_chip = jnp.where(bmask & (vis_blk == level + 1), degc_l, 0) \
        .sum(dtype=jnp.int32)
    nunv_chip = (bmask & (vis_blk >= INF) & (degc_l > 0)) \
        .sum().astype(jnp.int32)
    m8f_chip = jax.lax.pmax(m8f_chip, VERTEX_AXIS)
    nunv_chip = jax.lax.pmax(nunv_chip, VERTEX_AXIS)
    return merged, jnp.stack(
        [nf, m8_f, m8_unvis, found_max, m8f_chip, nunv_chip])


def _td_level(mesh):
    """One whole top-down level, fused: frontier build + owned-share
    expansion + sparse exchange + stats. Compiled once per (mesh,
    f_cap, p_cap, found_cap) via mesh_jit with replicated out
    shardings pinned."""
    from jax.sharding import PartitionSpec as P

    from titan_tpu.parallel.mesh import VERTEX_AXIS, mesh_jit

    def builder(mesh):
        import jax.numpy as jnp

        from titan_tpu.parallel.mesh import shard_map_compat

        def td(dist, stats, level, dstT_sh, colstart_sh, degc_sh, degc,
               lo_sh, hi_sh, f_cap: int, p_cap: int, found_cap: int,
               n_: int, b_max: int):
            def per_shard(dist, degc, dstT_l, cs_l, degc_l, lo, hi):
                dstT_l, cs_l, degc_l = dstT_l[0], cs_l[0], degc_l[0]
                lo, hi = lo[0], hi[0]
                q_pad = dstT_l.shape[1] - 1
                f_count = stats[ST_NF]
                # frontier list from the merged dist (replicated
                # compaction — deduped by construction, so chunk-pair
                # enumeration never double-counts a vertex's mass)
                _, frontier = compact_ids(dist[:n_] == level, f_cap,
                                          n_ + 1)
                valid = (jnp.arange(f_cap) < f_count) \
                    & (frontier >= lo) & (frontier < hi)
                v = jnp.clip(frontier - lo, 0, b_max - 1)
                cols, _, _ = enumerate_chunk_pairs(
                    valid, degc_l[v], cs_l[v], p_cap, q_pad)
                nbr = jnp.take(dstT_l, cols, axis=1)
                dist = dist.at[nbr].min(level + 1, mode="drop")
                return _exchange_tail(dist, level, degc, degc_l, lo,
                                      hi, found_cap, n_, b_max)

            return shard_map_compat(
                per_shard, mesh=mesh,
                in_specs=(P(), P(), P(VERTEX_AXIS, None, None),
                          P(VERTEX_AXIS, None), P(VERTEX_AXIS, None),
                          P(VERTEX_AXIS), P(VERTEX_AXIS)),
                out_specs=(P(), P()),
            )(dist, degc, dstT_sh, colstart_sh, degc_sh, lo_sh, hi_sh)
        return td

    return mesh_jit(
        "shx_td", mesh, builder, out_specs=(P(), P()),
        static_argnames=("f_cap", "p_cap", "found_cap", "n_", "b_max"))


def _bu_level(mesh):
    """One whole bottom-up level, fused: candidate build + chunk-0
    bitmap test + fused chunk rounds + K-stride exhaust (inside a
    replicated survivor-width cond ladder) + sparse exchange + stats.
    One dispatch per level per (c_cap, found_cap) bucket (<= 2 per
    level with the found_cap retry)."""
    from jax.sharding import PartitionSpec as P

    from titan_tpu.parallel.mesh import VERTEX_AXIS, mesh_jit

    def builder(mesh):
        import jax
        import jax.numpy as jnp

        from titan_tpu.parallel.mesh import shard_map_compat

        def bu(dist, level, dstT_sh, colstart_sh, degc_sh, degc, lo_sh,
               hi_sh, c_cap: int, found_cap: int, n_: int, b_max: int):
            def per_shard(dist, degc, dstT_l, cs_l, degc_l, lo, hi):
                dstT_l, cs_l, degc_l = dstT_l[0], cs_l[0], degc_l[0]
                lo, hi = lo[0], hi[0]
                q_pad = dstT_l.shape[1] - 1
                fbits = _pack_bits(dist, level, n_)
                block = jnp.arange(b_max, dtype=jnp.int32)
                cand_mask = (block < hi - lo) \
                    & (dist[jnp.minimum(block + lo, n_)] >= INF) \
                    & (degc_l > 0)
                c_count, cand = compact_ids(cand_mask, c_cap, b_max)
                alive = jnp.arange(c_cap) < c_count
                lv = jnp.clip(cand, 0, b_max - 1)
                cols = jnp.where(alive, cs_l[lv], q_pad)
                parents = jnp.take(dstT_l, jnp.clip(cols, 0, q_pad),
                                   axis=1)
                found = alive & _fbit_of(fbits, parents).any(axis=0)
                dist = dist.at[jnp.where(found, lv + lo, n_ + 1)].set(
                    level + 1, mode="drop")
                surv = alive & ~found & (degc_l[lv] > 1)
                nc = surv.sum().astype(jnp.int32)
                # REPLICATED survivor max: every shard takes the same
                # ladder branch, so no collective ever sits inside a
                # cond (a divergent branch with a collective deadlocks
                # the mesh); dead-lane width still tracks the actual
                # per-chip survivor maximum — the r4 cap-bucket
                # economics, now without the host round trip
                nc_max = jax.lax.pmax(nc, VERTEX_AXIS)

                def rounds_at(w: int):
                    def go(dist):
                        _, (cand_w, off_w) = scatter_compact(
                            surv,
                            (cand, jnp.ones((c_cap,), jnp.int32)),
                            w, (b_max, 0))
                        ncr = jnp.minimum(nc, w)

                        def round_(state, _):
                            dist, cand, off, ncr = state
                            alv = jnp.arange(w) < ncr
                            lvv = jnp.clip(cand, 0, b_max - 1)
                            cls = jnp.where(alv, cs_l[lvv] + off, q_pad)
                            par = jnp.take(dstT_l,
                                           jnp.clip(cls, 0, q_pad),
                                           axis=1)
                            ft = alv & _fbit_of(fbits, par).any(axis=0)
                            dist = dist.at[
                                jnp.where(ft, lvv + lo, n_ + 1)].set(
                                level + 1, mode="drop")
                            sv = alv & ~ft & (off + 1 < degc_l[lvv])
                            nc2, (cand, off) = scatter_compact(
                                sv, (cand, off + 1), w, (b_max, 0))
                            return (dist, cand, off, nc2), None

                        (dist, cand_w, off_w, ncr), _ = jax.lax.scan(
                            round_, (dist, cand_w, off_w, ncr), None,
                            length=BU_CHUNK_ROUNDS - 1)
                        # stragglers: K-chunk-stride while_loop — every
                        # iteration checks the next K chunks of EVERY
                        # survivor, so completion is guaranteed for any
                        # degree (no p_cap to size, no dropped hub
                        # chunks, no host sync; per-shard trip counts
                        # are fine — the loop is collective-free)
                        K = max((1 << 16) // max(w, 1), 1)

                        def ex_cond(s):
                            return s[3] > 0

                        def ex_body(s):
                            dist, cand, off, ncr = s
                            alv = jnp.arange(w) < ncr
                            lvv = jnp.clip(cand, 0, b_max - 1)
                            rem = jnp.where(
                                alv,
                                jnp.maximum(degc_l[lvv] - off, 0), 0)
                            j = jnp.arange(K, dtype=jnp.int32)[None, :]
                            cls = (cs_l[lvv] + off)[:, None] + j
                            live = alv[:, None] & (j < rem[:, None])
                            cls = jnp.where(live,
                                            jnp.clip(cls, 0, q_pad),
                                            q_pad)
                            par = jnp.take(dstT_l, cls.reshape(-1),
                                           axis=1)
                            hit = _fbit_of(fbits, par).any(axis=0) \
                                .reshape(w, K)
                            ft = alv & (hit & live).any(axis=1)
                            dist = dist.at[
                                jnp.where(ft, lvv + lo, n_ + 1)].set(
                                level + 1, mode="drop")
                            sv = alv & ~ft & (rem > K)
                            nc2, (cand, off) = scatter_compact(
                                sv, (cand, off + K), w, (b_max, 0))
                            return (dist, cand, off, nc2)

                        dist, _, _, _ = jax.lax.while_loop(
                            ex_cond, ex_body, (dist, cand_w, off_w, ncr))
                        return dist
                    return go

                def pick(dist, ladder):
                    if len(ladder) == 1:
                        return rounds_at(ladder[0])(dist)
                    return jax.lax.cond(nc_max <= ladder[0],
                                        rounds_at(ladder[0]),
                                        lambda d: pick(d, ladder[1:]),
                                        dist)

                wl = sorted({max(c_cap // 8, min(8, c_cap)), c_cap})
                dist = jax.lax.cond(nc_max == 0, lambda d: d,
                                    lambda d: pick(d, wl), dist)
                return _exchange_tail(dist, level, degc, degc_l, lo,
                                      hi, found_cap, n_, b_max)

            return shard_map_compat(
                per_shard, mesh=mesh,
                in_specs=(P(), P(), P(VERTEX_AXIS, None, None),
                          P(VERTEX_AXIS, None), P(VERTEX_AXIS, None),
                          P(VERTEX_AXIS), P(VERTEX_AXIS)),
                out_specs=(P(), P()),
            )(dist, degc, dstT_sh, colstart_sh, degc_sh, lo_sh, hi_sh)
        return bu

    return mesh_jit(
        "shx_bu", mesh, builder, out_specs=(P(), P()),
        static_argnames=("c_cap", "found_cap", "n_", "b_max"))


def frontier_bfs_hybrid_sharded(snap_or_graph, source_dense: int, mesh,
                                max_levels: int = 1000,
                                return_device: bool = False):
    """Direction-optimizing BFS over an ICI vertex mesh (see module doc).
    Returns (dist [n] int32 with INF unreachable, levels)."""
    import jax
    import jax.numpy as jnp

    num = int(mesh.devices.size)
    sh = shard_chunked_csr(snap_or_graph, num)
    n = sh["n"]
    b_max = sh["b_max"]
    cap_n = _next_pow2(max(n, 2))
    multiproc = jax.process_count() > 1
    if multiproc and cap_n != n:
        raise NotImplementedError(
            "multihost sharded BFS requires a power-of-two vertex count "
            "(the frontier pad would mix global and process-local "
            "arrays); pad the snapshot to the next power of two")
    dev = sh.get("_dev")
    if dev is None:
        # upload once to the EXPLICIT final placement and cache —
        # re-uploading ~9GB of edge shards per call would dominate every
        # timed run, and uncommitted arrays would pay a reshard on
        # every dispatch
        from titan_tpu.parallel.partition import (place_replicated,
                                                  place_shards)
        bounds = sh["bounds"]
        dstT_sh, colstart_sh, degc_sh = place_shards(
            mesh, sh["dstT_sh"], sh["colstart_sh"], sh["degc_sh"])
        lo_sh, hi_sh = place_shards(
            mesh, bounds[:-1].astype(np.int32),
            bounds[1:].astype(np.int32))
        degc, = place_replicated(mesh, sh["degc"])
        dev = (dstT_sh, colstart_sh, degc_sh, degc, lo_sh, hi_sh)
        sh["_dev"] = dev
    dstT_sh, colstart_sh, degc_sh, degc, lo_sh, hi_sh = dev
    total_chunks = sh["total_chunks"]
    cap_b = _next_pow2(max(b_max, 2))
    cap_q = _next_pow2(max(sh["q_max"], 2))
    td = _td_level(mesh)
    bu = _bu_level(mesh)

    from titan_tpu.utils.jitcache import dev_scalar

    f_count = 1
    # host numpy read — an eager device gather here would be a host↔device
    # round trip on TPU and is outright unsupported on process-spanning
    # CPU meshes (the multihost dryrun's first failure point)
    m8_f = int(sh["degc"][source_dense])
    m8_unvis = total_chunks - m8_f
    nunv_chip = sh["nunv_chip_max"]
    m8f_chip = m8_f
    st0 = np.asarray([1, m8_f, m8_unvis, 0, m8f_chip, nunv_chip],
                     np.int32)
    if multiproc:
        # multihost: initial state must be GLOBAL (replicated) arrays —
        # a process-local jnp array cannot feed a process-spanning jit
        from titan_tpu.parallel.multihost import host_replicated
        d0 = np.full((n + 1,), INF, np.int32)
        d0[source_dense] = 0
        dist = host_replicated(mesh, d0)
        st_dev = host_replicated(mesh, st0)
    else:
        from titan_tpu.parallel.partition import place_replicated
        dist, st_dev = place_replicated(
            mesh,
            jnp.full((n + 1,), INF, jnp.int32).at[source_dense].set(0),
            st0)
    level = 0
    # level-0 discoveries are bounded by the source's degree — seed the
    # exchange cap from it instead of always paying an overflow retry
    found_guess = min(_next_pow2(max(8 * m8_f, 4)), cap_n)
    LAST_EXCHANGE_CAPS.clear()
    LAST_PROFILE.clear()
    num_dev = int(mesh.devices.size)
    while f_count > 0 and level < max_levels:
        use_bu = m8_f * ALPHA > m8_unvis and f_count > 1
        if not use_bu and m8_f == 0:
            break
        # one fused dispatch per level (mode- and cap-bucketed); the
        # SOLE host sync per level is the stats readback below. An
        # exchange-cap overflow re-runs the level with the exact cap
        # (the merged result is discarded — dist was not donated), so
        # the per-level dispatch budget is 1 + retries ≤ 2 in steady
        # state (the guess tracks 4x the previous level's max).
        found_cap, retries = found_guess, 0
        bu_caps = {}
        while True:
            if use_bu:
                c_cap = min(_next_pow2(max(nunv_chip, 2)), cap_b)
                bu_caps = {"c_cap": c_cap}
                dist_m, st = bu(dist, dev_scalar(level), dstT_sh,
                                colstart_sh, degc_sh, degc, lo_sh,
                                hi_sh, c_cap=c_cap, found_cap=found_cap,
                                n_=n, b_max=b_max)
            else:
                f_cap = min(_next_pow2(max(f_count, 2)), cap_n)
                # p_cap covers the heaviest single chip's OWNED share
                # of the frontier mass (each vertex expands on its
                # owner only)
                p_cap = min(_next_pow2(max(m8f_chip, 2)), cap_q)
                dist_m, st = td(dist, st_dev, dev_scalar(level),
                                dstT_sh, colstart_sh, degc_sh, degc,
                                lo_sh, hi_sh, f_cap=f_cap, p_cap=p_cap,
                                found_cap=found_cap, n_=n, b_max=b_max)
            st_h = [int(x) for x in np.asarray(st)]
            found_max = st_h[ST_FOUNDMAX]
            if found_max <= found_cap:
                # commit the attempt's stats ONLY on acceptance — an
                # overflowed attempt's readback must not leak into the
                # retry's cap sizing (the retry re-runs THIS level and
                # needs the level-entry f_count/m8f_chip/nunv_chip; a
                # truncated candidate list from a clobbered cap loses
                # discoveries silently)
                (f_count, m8_f, m8_unvis, found_max, m8f_chip,
                 nunv_chip) = st_h
                break
            found_cap = _next_pow2(max(found_max, 2))
            retries += 1
        dist = dist_m
        st_dev = st
        LAST_EXCHANGE_CAPS.append(found_cap)
        LAST_PROFILE.append({
            "level": level, "mode": "bu" if use_bu else "td",
            "nf": f_count, "m8_f": m8_f,
            "found_max_per_chip": found_max, "found_cap": found_cap,
            "exchanged_ids": num_dev * found_cap, "retries": retries,
            "dispatches": 1 + retries,
            "bu_dispatches": (1 + retries) if use_bu else 0,
            "bu_trail": ([{"step": "bu_fused", **bu_caps,
                           "retries": retries}] if use_bu else [])})
        found_guess = min(_next_pow2(max(4 * found_max, 4)), cap_n)
        level += 1
    out = dist[0, :n] if dist.ndim == 2 else dist[:n]
    if not return_device:
        out = np.asarray(out)
    return out, level
