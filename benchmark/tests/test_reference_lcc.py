"""The LCC reference against graphs worked by hand (a triangle, K5, a
star, a path, two cliques joined by an edge, vertices of degree 0 and 1,
a self-loop), against ``set`` intersections a vertex at a time on
Kronecker graphs, over tiles of every size, and what ``check`` counts."""

import numpy as np
import pytest

from reference import csr, lcc


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return csr.structure(n, *csr.symmetrise(a, b))


def clique(ids):
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


def by_sets(indptr, indices):
    """The specification read literally: ordered pairs of neighbours
    that are an edge, over |N| (|N| - 1)."""
    n = len(indptr) - 1
    nbrs = [set(indices[indptr[v]:indptr[v + 1]].tolist()) - {v}
            for v in range(n)]
    counts, coeff = np.zeros(n, np.int64), np.zeros(n)
    for v in range(n):
        pairs = sum(len(nbrs[v] & nbrs[u]) for u in nbrs[v])
        counts[v] = pairs // 2
        if len(nbrs[v]) >= 2:
            coeff[v] = pairs / (len(nbrs[v]) * (len(nbrs[v]) - 1))
    return counts, coeff


@pytest.mark.parametrize("n, pairs, counts, coeff", [
    (3, clique([0, 1, 2]), [1, 1, 1], [1.0, 1.0, 1.0]),
    (5, clique(range(5)), [6] * 5, [1.0] * 5),
    # no two leaves of a star are joined; vertex 6 has no edge
    (7, [(3, v) for v in (0, 1, 2, 4, 5)], [0] * 7, [0.0] * 7),
    (5, [(0, 1), (1, 2), (2, 3), (3, 4)], [0] * 5, [0.0] * 5),
    # the bridge 3 - 4 closes nothing: its ends have 4 neighbours, 3 of
    # them joined pairwise: 6 ordered pairs of 12
    (8, clique([0, 1, 2, 3]) + clique([4, 5, 6, 7]) + [(3, 4)],
     [3] * 8, [1.0, 1.0, 1.0, 0.5, 0.5, 1.0, 1.0, 1.0]),
    # 4 hangs off the triangle: 2 has 3 neighbours, one pair joined
    (6, clique([0, 1, 2]) + [(2, 4)], [1, 1, 1, 0, 0, 0],
     [1.0, 1.0, 1 / 3, 0.0, 0.0, 0.0]),
    # a self-loop is no neighbour
    (3, clique([0, 1, 2]) + [(1, 1)], [1, 1, 1], [1.0, 1.0, 1.0]),
])
def test_by_hand(n, pairs, counts, coeff):
    ref = lcc.prepare(n, *both_ways(n, pairs), {}, {})
    assert ref.triangles.dtype == np.int64
    assert ref.triangles.tolist() == counts
    assert ref.lcc.dtype == np.float64
    assert ref.lcc.tolist() == pytest.approx(coeff, rel=1e-15)
    assert ref.n == n and ref.edges == len(both_ways(n, pairs)[1])


def kronecker(scale, seed, edge_factor=16):
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edge_factor << scale
    s, d = np.zeros(m, np.int64), np.zeros(m, np.int64)
    for bit in range(scale):
        quad = np.searchsorted([0.57, 0.76, 0.95], rng.random(m),
                               side="right")
        s |= (quad >> 1) << bit
        d |= (quad & 1) << bit
    return n, csr.structure(n, *csr.symmetrise(s, d))


@pytest.mark.parametrize("tile", [1, 7, 1000, 4 << 20])
@pytest.mark.parametrize("scale, seed", [(8, 1), (10, 2)])
def test_against_sets_over_tiles_of_every_size(monkeypatch, scale, seed,
                                               tile):
    monkeypatch.setattr(lcc, "TILE_WEDGES", tile)
    n, (indptr, indices) = kronecker(scale, seed)
    counts, deg = lcc.triangles(indptr, indices)
    want_counts, want_coeff = by_sets(indptr, indices)
    assert (counts == want_counts).all() and counts.sum() % 3 == 0
    assert counts.sum() > 0
    assert np.allclose(lcc.coefficients(counts, deg), want_coeff,
                       rtol=1e-13, atol=0)


def test_what_check_counts():
    n, (indptr, indices) = kronecker(9, 3)
    ref = lcc.prepare(n, indptr, indices, {}, {"request": {}})
    body = {"kind": "lcc"}
    assert lcc.COMPARED == ("lcc",)
    assert ref.check(body, ref.answer(body)["result"]) == {"lcc": 0}
    assert ref.check(body, ref.lcc.astype(np.float32)) == {"lcc": 0}
    some = np.flatnonzero(ref.lcc > 0)
    none = np.flatnonzero(ref.lcc == 0)
    assert len(some) and len(none)
    # inside the rule, at its edge, outside it
    got = ref.lcc.copy()
    got[some[0]] *= 1 + 0.9e-4
    assert ref.check(body, got) == {"lcc": 0}
    got[some[1]] *= 1 + 1.1e-4
    got[some[2]] *= 1 - 1.1e-4
    assert ref.check(body, got) == {"lcc": 2}
    # a reference 0 wants an exact 0; a NaN is outside
    got = ref.lcc.copy()
    got[none[0]] = 1e-12
    got[some[0]] = np.nan
    assert ref.check(body, got) == {"lcc": 2}
    assert ref.check(body, ref.lcc[:-1]) == {"lcc": n}
    # the precision below float32 fails the rule
    import ml_dtypes
    rounded = ref.lcc.astype(ml_dtypes.bfloat16).astype(np.float64)
    assert ref.check(body, rounded)["lcc"] > len(some) // 2


def test_nothing_of_the_program_and_no_table_in_it():
    text = open(lcc.__file__).read()
    code = text.split('"""', 2)[2]
    assert "titan_tpu" not in code and "import jax" not in code
    for word in ("popcount", "bitmap", "hub"):
        assert word not in code
