"""The BC reference against a brute-force count of shortest paths
(every pair's distance and path count by a BFS a vertex, the dependency
of s on v summed over the targets t by its definition) on 200 vertices,
against shapes worked by hand, and what ``check`` counts."""

import numpy as np
import pytest

from reference import bc, csr


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return csr.structure(n, *csr.symmetrise(a, b))


def all_pairs(indptr, indices):
    """(dist int [n, n] (-1: no path), paths float64 [n, n]) by a BFS
    from every vertex, a vertex at a time."""
    n = len(indptr) - 1
    dist = np.full((n, n), -1, np.int64)
    paths = np.zeros((n, n))
    for s in range(n):
        dist[s, s], paths[s, s] = 0, 1.0
        frontier = [s]
        while frontier:
            nxt = []
            for u in frontier:
                for v in indices[indptr[u]:indptr[u + 1]].tolist():
                    if dist[s, v] < 0:
                        dist[s, v] = dist[s, u] + 1
                        nxt.append(v)
                    if dist[s, v] == dist[s, u] + 1:
                        paths[s, v] += paths[s, u]
            frontier = nxt
    return dist, paths


def by_definition(dist, paths, s):
    """delta_s[v] = sum over t not in {s, v} of the share of shortest
    s-t paths that pass v."""
    n = len(dist)
    delta = np.zeros(n)
    for v in range(n):
        if v == s or dist[s, v] < 0:
            continue
        for t in range(n):
            if t in (s, v) or dist[s, t] < 0 or dist[v, t] < 0:
                continue
            if dist[s, v] + dist[v, t] == dist[s, t]:
                delta[v] += paths[s, v] * paths[v, t] / paths[s, t]
    return delta


def random_graph(seed: int, n: int = 200, m: int = 420):
    """Sparse enough for six to eight levels and for some vertices no
    root reaches; a few hubs, so that path counts grow."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, n, m)
    b = np.where(rng.random(m) < 0.2, rng.integers(0, 5, m),
                 rng.integers(0, n, m))
    keep = a != b
    return n, list(zip(a[keep].tolist(), b[keep].tolist()))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_against_the_definition_on_200_vertices(seed):
    n, pairs = random_graph(seed)
    indptr, indices = both_ways(n, pairs)
    dist, paths = all_pairs(indptr, indices)
    roots = [int(r) for r in np.random.default_rng(seed).choice(n, 6)]
    ref = bc.prepare(n, indptr, indices, {"a": roots[:3], "b": roots[3:]},
                     {})
    assert sorted(ref.delta) == sorted(set(roots))
    assert paths.max() > 8 and (dist < 0).any()
    for s in roots:
        want = by_definition(dist, paths, s)
        assert ref.delta[s].dtype == np.float64
        assert np.allclose(ref.delta[s], want, rtol=1e-12, atol=1e-12)
        assert ref.delta[s][s] == 0 and want.max() > 0
        _delta, levels = bc.dependencies(ref.out, s)
        assert levels == dist[s].max() + 1
    body = {"kind": "bc", "sources": roots[:4]}
    total = sum(by_definition(dist, paths, s) for s in roots[:4])
    want = total / total.max()
    assert np.allclose(ref.answer(body)["result"], want, rtol=1e-12,
                       atol=0)
    # a root no pool holds is worked when it is asked for
    other = next(v for v in range(n) if v not in ref.delta
                 and indptr[v + 1] > indptr[v])
    got = ref.answer({"sources_dense": [other]})["result"]
    alone = by_definition(dist, paths, other)
    assert np.allclose(got, alone / alone.max(), rtol=1e-12, atol=0)


def test_by_hand():
    # a path 0-1-2-3-4 from its end: delta_0 = [0, 3, 2, 1, 0]
    ref = bc.prepare(5, *both_ways(5, [(0, 1), (1, 2), (2, 3), (3, 4)]),
                     {"r": [0, 2]}, {})
    assert ref.n == 5 and ref.edges == 8
    assert ref.delta[0].tolist() == [0, 3, 2, 1, 0]
    assert ref.delta[2].tolist() == [0, 1, 0, 1, 0]
    assert ref.answer({"sources": [0]})["result"].tolist() \
        == [0, 1, 2 / 3, 1 / 3, 0]
    # a root named twice counts twice
    assert ref.answer({"sources": [0, 2, 2]})["result"].tolist() \
        == [0, 1, 2 / 5, 3 / 5, 0]
    # a 4-cycle from a corner: two shortest paths to the far corner,
    # each side carries half of it
    ref = bc.prepare(4, *both_ways(4, [(0, 1), (1, 2), (2, 3), (3, 0)]),
                     {"r": [0]}, {})
    assert ref.delta[0].tolist() == [0, 0.5, 0, 0.5]
    # a root without an edge, and scores that are all 0
    ref = bc.prepare(3, *both_ways(3, [(0, 1)]), {"r": [2, 0]}, {})
    assert ref.answer({"sources": [2, 0]})["result"].tolist() == [0, 0, 0]


def test_what_check_counts():
    n, pairs = random_graph(5)
    indptr, indices = both_ways(n, pairs)
    roots = [7, 19, 40, 99]
    ref = bc.prepare(n, indptr, indices, {"r": roots}, {})
    body = {"kind": "bc", "sources": roots}
    want = ref.answer(body)["result"]
    good = want.astype(np.float32)
    assert ref.check(body, good) == {"scores": 0}
    nonzero = np.flatnonzero(want > 0)
    one = good.copy()
    one[nonzero[0]] *= np.float32(1.0002)
    assert ref.check(body, one) == {"scores": 1}
    one = good.copy()
    one[np.flatnonzero(want == 0)[0]] = 1e-12    # a 0 wants an exact 0
    assert ref.check(body, one) == {"scores": 1}
    assert ref.check(body, good[:-1]) == {"scores": n}
    bad = good.copy()
    bad[3] = np.nan
    assert ref.check(body, bad) == {"scores": n}
    # another request's roots are another answer
    assert ref.check({"sources": roots[:3] + [5]}, good)["scores"] > 10
    # the precision below the configuration's: bfloat16's three digits
    # fail the rule on nearly every score that is not 0 (and exactly 1)
    import ml_dtypes
    low = want.astype(ml_dtypes.bfloat16).astype(np.float32)
    out = ref.check(body, low)["scores"]
    assert out > 0.8 * (len(nonzero) - 1), (out, len(nonzero))
    assert bc.COMPARED == ("scores",) and bc.EPSILON == 1e-4
