"""The WCC reference against a 12-vertex graph worked by hand, against
scipy's ``connected_components`` on random graphs, and what ``check``
counts."""

import numpy as np
import pytest

from reference import csr, wcc

# A path that the passes have to walk the long way (9 - 7 - 5 - 3 - 1),
# a triangle (2, 4, 6), a pair (8, 11), and 0 and 10 alone (no edge):
#   labels: 1 3 5 7 9 -> 1; 2 4 6 -> 2; 8 11 -> 8; 0 -> 0; 10 -> 10
PAIRS = [(9, 7), (7, 5), (5, 3), (3, 1), (2, 4), (4, 6), (6, 2), (8, 11)]
BY_HAND = [0, 1, 2, 1, 2, 1, 2, 1, 8, 1, 10, 8]


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return csr.structure(n, *csr.symmetrise(a, b))


def test_by_hand():
    got = wcc.components(*both_ways(12, PAIRS))
    assert got.dtype == np.int32
    assert got.tolist() == BY_HAND


def test_a_graph_without_an_edge():
    indptr, indices = both_ways(4, [])
    assert wcc.components(indptr, indices).tolist() == [0, 1, 2, 3]


@pytest.mark.parametrize("seed", [0, 1, 2, 3000000601])
def test_against_scipy(seed):
    """Sparse random graphs (many components of every size, long paths
    among them): scipy's components, each named by its smallest
    member."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    rng = np.random.default_rng(seed)
    n, m = 5000, 3500
    pairs = list(zip(rng.integers(0, n, m), rng.integers(0, n, m)))
    path = rng.permutation(n)[:400]              # one long path
    pairs += list(zip(path[:-1], path[1:]))
    indptr, indices = both_ways(n, pairs)
    count, comp = connected_components(
        csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                   shape=(n, n)), directed=False)
    least = np.full(count, n)
    np.minimum.at(least, comp, np.arange(n))
    got = wcc.components(indptr, indices)
    assert count > 100
    assert np.array_equal(got, least[comp])


def test_check_counts_the_vertices_whose_label_differs():
    indptr, indices = both_ways(12, PAIRS)
    ref = wcc.prepare(12, indptr, indices, {}, {})
    assert (ref.n, ref.edges) == (12, 16)
    body = {"kind": "wcc"}
    want = ref.answer(body)["result"]
    assert want.tolist() == BY_HAND
    assert wcc.COMPARED == tuple(ref.check(body, want))
    assert ref.check(body, want.copy()) == {"labels": 0}
    one = want.copy()
    one[5] = 5                          # one altered label reads 1
    assert ref.check(body, one) == {"labels": 1}
    # the same partition under other names is not the canonical answer:
    # the exact comparison is stricter than Graphalytics' equivalence
    renamed = np.where(want == 2, 6, want)
    assert ref.check(body, renamed) == {"labels": 3}
    assert ref.check(body, want[:11]) == {"labels": 12}
    assert ref.check(body, want.astype(np.int64)) == {"labels": 0}
