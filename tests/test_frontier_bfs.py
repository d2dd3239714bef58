"""Frontier-sparse BFS (bucketed static shapes) vs reference BFS."""

import numpy as np
import pytest

from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.models.bfs import _next_pow2, frontier_bfs


def np_bfs(n, src, dst, s0):
    from collections import deque
    adj = [[] for _ in range(n)]
    for a, b in zip(src, dst):
        adj[a].append(b)
    d = np.full(n, 1 << 30, np.int64)
    d[s0] = 0
    q = deque([s0])
    while q:
        u = q.popleft()
        for v in adj[u]:
            if d[v] > d[u] + 1:
                d[v] = d[u] + 1
                q.append(v)
    return d


def test_next_pow2():
    assert [_next_pow2(x) for x in (1, 2, 3, 4, 5, 1023, 1024)] == \
        [2, 2, 4, 4, 8, 1024, 1024]


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_frontier_matches_reference(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(30, 600))
    e = int(rng.integers(0, n * 5))
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    snap = snap_mod.from_arrays(n, src, dst)
    s0 = int(rng.integers(0, n))
    dist, levels = frontier_bfs(snap, s0)
    ref = np_bfs(n, src, dst, s0)
    assert np.array_equal(np.where(dist >= (1 << 30), 1 << 30, dist), ref)
    finite = ref[ref < (1 << 30)]
    assert levels >= int(finite.max()) if len(finite) else levels == 0


def test_isolated_source():
    snap = snap_mod.from_arrays(5, np.array([1, 2], np.int32),
                                np.array([2, 3], np.int32))
    dist, levels = frontier_bfs(snap, 0)    # degree-0 source
    assert dist[0] == 0 and (dist[1:] >= (1 << 30)).all()
    assert levels == 0


def test_chain_graph_many_levels():
    n = 300
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    snap = snap_mod.from_arrays(n, src, dst)
    dist, levels = frontier_bfs(snap, 0)
    assert np.array_equal(dist, np.arange(n))
    assert levels == n - 1


@pytest.mark.parametrize("seed", [0, 5])
@pytest.mark.slow
def test_sharded_matches_single_chip(seed):
    from titan_tpu.models.bfs import frontier_bfs_sharded
    from titan_tpu.parallel.mesh import vertex_mesh
    rng = np.random.default_rng(seed)
    n = int(rng.integers(50, 800))
    e = int(rng.integers(10, n * 6))
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    snap = snap_mod.from_arrays(n, src, dst)
    s0 = int(rng.integers(0, n))
    d_single, _ = frontier_bfs(snap, s0)
    d_sharded, _ = frontier_bfs_sharded(snap, s0, vertex_mesh(8))
    assert np.array_equal(d_single, d_sharded)
    assert np.array_equal(np.where(d_sharded >= (1 << 30), 1 << 30,
                                   d_sharded), np_bfs(n, src, dst, s0))


def test_sharded_chain():
    from titan_tpu.models.bfs import frontier_bfs_sharded
    from titan_tpu.parallel.mesh import vertex_mesh
    n = 100
    src = np.arange(n - 1, dtype=np.int32)
    dst = np.arange(1, n, dtype=np.int32)
    snap = snap_mod.from_arrays(n, src, dst)
    dist, levels = frontier_bfs_sharded(snap, 0, vertex_mesh(8))
    assert np.array_equal(dist, np.arange(n))
    assert levels == n - 1


def test_matches_dense_program():
    from titan_tpu.olap.tpu.engine import TPUGraphComputer
    from titan_tpu.models.bfs import BFS
    rng = np.random.default_rng(9)
    n, e = 256, 1500
    src = rng.integers(0, n, e).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    snap = snap_mod.from_arrays(n, src, dst)
    dist, _ = frontier_bfs(snap, 0)
    comp = TPUGraphComputer(snapshot=snap, num_devices=1)
    res = comp.run(BFS(max_iterations=300), params={"source_dense": 0},
                   snapshot=snap)
    assert np.array_equal(np.asarray(res["dist"]), dist)
