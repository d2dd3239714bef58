"""Breadth-first search (unweighted shortest hop count) as a DenseProgram.

The BASELINE north-star kernel (Graph500 BFS TEPS): full-edge-sweep
pull-mode supersteps — dist' = min(dist, min over in-edges of dist[src]+1) —
terminating when no distance changed (psum-agreed across chips).
"""

# graftlint: allow-file[opscan] reason=plain reference model, not a round-loop hot path (exempt from the ops.compaction contract since ISSUE r6)

from __future__ import annotations

import jax.numpy as jnp

from titan_tpu.olap.api import DenseProgram

INF = jnp.int32(1 << 30)


class BFS(DenseProgram):
    combine = "min"

    def __init__(self, max_iterations: int = 1000):
        self.max_iterations = max_iterations

    def init(self, n, params):
        import numpy as np
        dist = np.full((n,), int(INF), dtype=np.int32)
        dist[int(params["source_dense"])] = 0
        return {"dist": jnp.asarray(dist)}

    def message(self, src_state, edge_data, params):
        d = src_state["dist"]
        return jnp.where(d >= INF, INF, d + 1).astype(jnp.int32)

    def apply(self, state, agg, iteration, params):
        return {"dist": jnp.minimum(state["dist"], agg)}

    def done(self, state, new_state, agg, iteration, params):
        return jnp.all(new_state["dist"] == state["dist"])

    def outputs(self, state, params):
        return {"dist": state["dist"]}


def run(computer, source, snapshot=None, max_iterations: int = 1000):
    """``source``: original vertex id (graph mode) or dense index
    (snapshot mode)."""
    snap = snapshot or computer.snapshot()
    dense = snap.dense_of(source) if in_snapshot_ids(snap, source) \
        else int(source)
    prog = BFS(max_iterations)
    return computer.run(prog, params={"source_dense": dense}, snapshot=snap)


def in_snapshot_ids(snap, source) -> bool:
    import numpy as np
    i = np.searchsorted(snap.vertex_ids, source)
    return i < snap.n and snap.vertex_ids[i] == source


# ---------------------------------------------------------------------------
# frontier-sparse BFS (single chip)
# ---------------------------------------------------------------------------

def _next_pow2(x: int) -> int:
    return 1 << max(1, (int(x) - 1).bit_length())


def _expand_neighbors(mask, degs, indptr_vals, dst_arr, m_cap: int, n_: int):
    """The frontier-expansion core shared by the single-chip and sharded
    level steps: delta-scatter + cumsum — exactly TWO per-edge index ops
    (the neighbor gather here and the relax scatter at the caller). A
    searchsorted formulation costs log(F) extra gathers per edge and
    measured 10× slower than the dense sweep; see PERF_NOTES.md.

    ``mask``: which frontier slots this caller expands; ``degs``: their
    out-degrees (0 where masked); ``indptr_vals``: each slot's first edge
    offset into ``dst_arr``. Returns neighbor ids with n_ on dead lanes."""
    degs = jnp.where(mask, degs, 0).astype(jnp.int32)
    offsets = jnp.cumsum(degs)                       # inclusive
    starts = offsets - degs                          # exclusive
    m_total = offsets[-1]
    # base2[i] = indptr_vals[i] - starts[i]; at edge position j of frontier
    # slot i: edge_idx = base2[i] + j. Propagate base2 to every position
    # with a scatter of CONSECUTIVE DELTAS at the segment starts followed
    # by a cumsum (colliding starts of empty slots sum their deltas — the
    # net delta is still right).
    base2 = jnp.where(mask, indptr_vals, 0) - starts
    delta = jnp.diff(base2, prepend=0)
    # drop (not clamp!) starts that fall at/after m_cap: a clamped delta
    # would land on the last LIVE lane and corrupt its edge index
    acc = jnp.zeros((m_cap,), jnp.int32).at[starts].add(delta, mode="drop")
    j = jnp.arange(m_cap, dtype=jnp.int32)
    edge_idx = jnp.cumsum(acc) + j
    return jnp.where(
        j < m_total,
        dst_arr[jnp.clip(edge_idx, 0, dst_arr.shape[0] - 1)],
        n_).astype(jnp.int32)


def _frontier_level_step():
    """Module-level jitted level step, built once: defining it inside
    frontier_bfs would make every call a fresh function object and
    recompile every (f_cap, m_cap) bucket on every run (~8s each)."""
    global _LEVEL_STEP
    if _LEVEL_STEP is not None:
        return _LEVEL_STEP
    import functools

    import jax

    @functools.partial(jax.jit, static_argnames=("f_cap", "m_cap", "n_"))
    def level_step(dist, frontier, f_count, level, dst_by_src, indptr_out,
                   out_degree, f_cap: int, m_cap: int, n_: int):
        # frontier: [f_cap] int32, padded with n_ (sink)
        valid_f = jnp.arange(f_cap) < f_count
        fvert = jnp.minimum(frontier, n_ - 1)
        nbr = _expand_neighbors(valid_f, out_degree[fvert],
                                indptr_out[fvert], dst_by_src, m_cap, n_)
        # relax into the padded sink row n_ for dead lanes
        dist = dist.at[nbr].min(level + 1)
        changed = (dist == level + 1) & (jnp.arange(n_ + 1) < n_)
        nf_count = changed.sum().astype(jnp.int32)
        # next level's edge total, computed here so the host needs only ONE
        # readback per level (int32 is safe: callers guard e_total < 2^31)
        m_next = jnp.where(changed[:n_], out_degree, 0).sum(dtype=jnp.int32)
        next_frontier = jnp.nonzero(changed, size=n_, fill_value=n_)[0] \
            .astype(jnp.int32)
        return dist, next_frontier, nf_count, m_next

    _LEVEL_STEP = level_step
    return level_step


_LEVEL_STEP = None


def _shard_out_csr(snap, num_shards: int):
    """Per-shard slices of the out-CSR: shard d owns the contiguous vertex
    block [d*block, (d+1)*block) and exactly its vertices' out-edges (the
    src-sorted layout makes each shard's edge range contiguous). Padded to
    identical static shapes. Cached per (snapshot, D)."""
    import numpy as np

    cache = getattr(snap, "_frontier_shards", None)
    if cache is None:
        cache = {}
        snap._frontier_shards = cache
    got = cache.get(num_shards)
    if got is not None:
        return got
    n = snap.n
    dst_by_src, indptr_out = snap.out_csr()
    block = -(-max(n, 1) // num_shards)
    starts = [int(indptr_out[min(d * block, n)]) for d in range(num_shards)]
    ends = [int(indptr_out[min((d + 1) * block, n)])
            for d in range(num_shards)]
    e_max = max(1, max(e - s for s, e in zip(starts, ends)))
    dst_sh = np.full((num_shards, e_max), n, np.int32)
    ip_sh = np.zeros((num_shards, block + 1), np.int32)
    deg_sh = np.zeros((num_shards, block), np.int32)
    for d in range(num_shards):
        # clamp BOTH bounds: with small n the last shards' blocks may start
        # past the end of the vertex range entirely
        lo_v = min(d * block, n)
        hi_v = min((d + 1) * block, n)
        s, e = starts[d], ends[d]
        dst_sh[d, :e - s] = dst_by_src[s:e]
        ip = indptr_out[lo_v:hi_v + 1] - s        # local edge offsets
        ip_sh[d, :hi_v - lo_v + 1] = ip
        ip_sh[d, hi_v - lo_v + 1:] = ip[-1] if len(ip) else 0
        deg_sh[d, :hi_v - lo_v] = snap.out_degree[lo_v:hi_v]
    got = (block, e_max, dst_sh, ip_sh, deg_sh)
    cache[num_shards] = got
    return got


def _sharded_level_step():
    global _SHARDED_LEVEL_STEP
    if _SHARDED_LEVEL_STEP is not None:
        return _SHARDED_LEVEL_STEP
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from titan_tpu.parallel.mesh import VERTEX_AXIS

    @functools.partial(
        jax.jit, static_argnames=("mesh", "f_cap", "m_cap", "n_", "block"))
    def level_step(dist, frontier, f_count, level, dst_sh, ip_sh, deg_sh,
                   out_degree, mesh, f_cap: int, m_cap: int, n_: int,
                   block: int):
        def per_shard(dist, frontier, dst_l, ip_l, deg_l):
            # my block of vertices: [base, base+block)
            d = jax.lax.axis_index(VERTEX_AXIS)
            base = d * block
            dst_l, ip_l, deg_l = dst_l[0], ip_l[0], deg_l[0]
            valid = (jnp.arange(f_cap) < f_count)
            local = jnp.clip(frontier - base, 0, block - 1)
            mine = valid & (frontier >= base) & (frontier < base + block)
            nbr = _expand_neighbors(mine, deg_l[local], ip_l[local], dst_l,
                                    m_cap, n_)
            new_dist = dist.at[nbr].min(level + 1)
            # ICI all-reduce: every chip gets the global minimum distances
            return jax.lax.pmin(new_dist, VERTEX_AXIS)

        from titan_tpu.parallel.mesh import shard_map_compat
        dist = shard_map_compat(
            per_shard, mesh=mesh,
            in_specs=(P(), P(), P(VERTEX_AXIS, None), P(VERTEX_AXIS, None),
                      P(VERTEX_AXIS, None)),
            out_specs=P(),
        )(dist, frontier, dst_sh, ip_sh, deg_sh)

        # device-side compaction: the host reads back ONE small stats array
        # per level (not the n-element frontier) — matching the single-chip
        # contract; the next level's per-shard edge maximum sizes the bucket
        changed = (dist[:n_] == level + 1)
        nf_count = changed.sum().astype(jnp.int32)
        next_frontier = jnp.nonzero(changed, size=n_, fill_value=n_)[0] \
            .astype(jnp.int32)
        fdeg = jnp.where(changed, out_degree, 0)
        fdeg_pad = jnp.zeros((_round_up(n_, block),), jnp.int32) \
            .at[:n_].set(fdeg)
        per_shard_m = fdeg_pad.reshape(-1, block).sum(axis=1)
        stats = jnp.concatenate(
            [nf_count[None], per_shard_m.max()[None]]).astype(jnp.int32)
        return dist, next_frontier, stats

    _SHARDED_LEVEL_STEP = level_step
    return level_step


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


_SHARDED_LEVEL_STEP = None


def frontier_bfs_sharded(snap, source_dense: int, mesh,
                         max_levels: int = 1000):
    """Multi-chip frontier BFS: the distance array is REPLICATED (n int32
    fits every chip at Graph500 scales), the out-CSR is sharded by source
    block, each chip expands its share of the frontier with the same
    delta-scatter expansion as the single-chip path, and one pmin
    all-reduce per level merges relaxations over ICI. The host drives
    levels exactly like frontier_bfs (one scalar readback per level).

    Returns (dist ndarray [n] int32 with INF for unreachable, levels)."""
    import numpy as np

    n = snap.n
    num_shards = mesh.devices.size
    if snap.num_edges >= (1 << 31):
        raise NotImplementedError("int32 edge indices; shard below 2^31")
    block, e_max, dst_sh, ip_sh, deg_sh = _shard_out_csr(snap, num_shards)
    dev = getattr(snap, "_dev_frontier_sh", None)
    if dev is None or dev[0] != num_shards:
        dev = (num_shards, jnp.asarray(dst_sh), jnp.asarray(ip_sh),
               jnp.asarray(deg_sh),
               jnp.asarray(snap.out_degree.astype(np.int32)))
        snap._dev_frontier_sh = dev
    _, dst_d, ip_d, deg_d, outdeg_d = dev
    level_step = _sharded_level_step()

    dist = jnp.full((n + 1,), INF, jnp.int32).at[source_dense].set(0)
    frontier = jnp.full((n,), n, jnp.int32).at[0].set(source_dense)
    f_count, level = 1, 0
    m_shard_max = int(snap.out_degree[source_dense])
    while f_count > 0 and m_shard_max > 0 and level < max_levels:
        f_cap = min(_next_pow2(f_count), n)
        # edge bucket: max PER-SHARD frontier degree sum, computed on
        # device by the previous level step
        m_cap = min(_next_pow2(m_shard_max), _next_pow2(e_max))
        dist, frontier, stats = level_step(
            dist, frontier[:f_cap], jnp.int32(f_count), jnp.int32(level),
            dst_d, ip_d, deg_d, outdeg_d, mesh=mesh, f_cap=f_cap,
            m_cap=m_cap, n_=n, block=block)
        # ONE small readback per level
        f_count, m_shard_max = (int(x) for x in np.asarray(stats))
        level += 1
    return np.asarray(dist[:n]), level


def frontier_bfs(snap, source_dense: int, max_levels: int = 1000):
    """Host-driven frontier BFS: each level expands ONLY the frontier's
    out-edges, so total index-op work is O(E) for the whole run instead of
    O(E × diameter) for full-edge supersteps (PERF_NOTES escape route #2 —
    on a diameter-7 Graph500 graph this cuts per-edge gathers ~7×).

    XLA needs static shapes, so the frontier vertex count and expanded edge
    count are padded to power-of-2 capacity buckets; each (F_cap, M_cap)
    pair compiles once and is reused across levels and runs. The level loop
    runs on the host (one scalar readback per level) — supersteps at
    Graph500 scale dwarf the sync cost.

    Returns (dist ndarray [n] int32 with INF for unreachable, levels)."""
    import numpy as np

    n = snap.n
    e_total = int(snap.num_edges)
    if e_total >= (1 << 31):
        raise NotImplementedError(
            "frontier_bfs uses int32 edge indices (x64 is off); shard the "
            "snapshot below 2^31 edges per chip")
    dst_by_src, indptr_out = snap.out_csr()
    dev = getattr(snap, "_dev_frontier", None)
    if dev is None:
        dev = {
            "dst_by_src": jnp.asarray(dst_by_src),
            "indptr_out": jnp.asarray(indptr_out.astype(np.int32)),
            "out_degree": jnp.asarray(snap.out_degree.astype(np.int32)),
        }
        snap._dev_frontier = dev

    level_step = _frontier_level_step()

    dist = jnp.full((n + 1,), INF, jnp.int32).at[source_dense].set(0)
    frontier_full = jnp.full((n,), n, jnp.int32).at[0].set(source_dense)
    f_count = 1
    m_total = int(snap.out_degree[source_dense])
    level = 0
    while f_count > 0 and m_total > 0 and level < max_levels:
        f_cap = min(_next_pow2(f_count), n)
        m_cap = min(_next_pow2(m_total), max(_next_pow2(e_total), 2))
        dist, frontier_full, nf, m_next = level_step(
            dist, frontier_full[:f_cap], jnp.int32(f_count),
            jnp.int32(level), dev["dst_by_src"], dev["indptr_out"],
            dev["out_degree"], f_cap=f_cap, m_cap=m_cap, n_=n)
        # ONE host sync per level (both scalars come back together)
        f_count, m_total = int(nf), int(m_next)
        level += 1
    return np.asarray(dist[:n]), level
