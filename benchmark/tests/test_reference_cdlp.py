"""The CDLP reference against graphs worked by hand (a tie, a pair that
flips every round, a star with a vertex off it, two cliques joined by an
edge), against a ``collections.Counter`` count a vertex at a time on
random graphs, and what ``check`` counts."""

import collections

import numpy as np
import pytest

from reference import cdlp, csr


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return csr.structure(n, *csr.symmetrise(a, b))


def clique(ids):
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


STAR = [(3, v) for v in (0, 1, 2, 4, 5)]          # vertex 6 has no edge
CLIQUES = clique([0, 1, 2, 3]) + clique([4, 5, 6, 7]) + [(3, 4)]


@pytest.mark.parametrize("n, pairs, rounds, want", [
    # 0 hears 1, 2 and 3 once each: the smallest label wins the tie
    (4, [(0, 1), (0, 2), (0, 3)], 1, [1, 0, 0, 0]),
    (4, [(0, 1), (0, 2), (0, 3)], 2, [0, 1, 1, 1]),
    # synchronous: a pair swaps its labels every round
    (2, [(0, 1)], 0, [0, 1]),
    (2, [(0, 1)], 1, [1, 0]),
    (2, [(0, 1)], 2, [0, 1]),
    (2, [(0, 1)], 9, [1, 0]),
    (7, STAR, 1, [3, 3, 3, 0, 3, 3, 6]),
    (7, STAR, 2, [0, 0, 0, 3, 0, 0, 6]),
    (8, CLIQUES, 1, [1, 0, 0, 0, 3, 4, 4, 4]),
    (8, CLIQUES, 2, [0, 0, 0, 0, 4, 4, 4, 4]),
    (8, CLIQUES, 10, [0, 0, 0, 0, 4, 4, 4, 4]),
    (3, [], 5, [0, 1, 2]),
])
def test_by_hand(n, pairs, rounds, want):
    got = cdlp.propagate(*both_ways(n, pairs), rounds)
    assert got.dtype == np.int32
    assert got.tolist() == want


def by_counter(indptr, indices, rounds):
    n = len(indptr) - 1
    labels = list(range(n))
    for _ in range(rounds):
        new = list(labels)
        for v in range(n):
            heard = [labels[u] for u in indices[indptr[v]:indptr[v + 1]]]
            if heard:
                count = collections.Counter(heard)
                most = max(count.values())
                new[v] = min(l for l, c in count.items() if c == most)
        labels = new
    return labels


@pytest.mark.parametrize("seed", [0, 1, 2, 3000000601])
@pytest.mark.parametrize("rounds", [1, 3, 10])
def test_against_a_count_a_vertex_at_a_time(seed, rounds):
    rng = np.random.default_rng(seed)
    n, m = 700, 1200
    pairs = list(zip((rng.random(m) ** 2 * n).astype(int),
                     rng.integers(0, n, m)))
    indptr, indices = both_ways(n, pairs)
    assert (np.diff(indptr) == 0).any()         # some hear nobody
    assert cdlp.propagate(indptr, indices, rounds).tolist() == \
        by_counter(indptr, indices, rounds)


def test_check_counts_the_vertices_whose_label_differs():
    indptr, indices = both_ways(8, CLIQUES)
    mix = {"request": {"body": {"kind": "cdlp", "iterations": 2}}}
    ref = cdlp.prepare(8, indptr, indices, {}, mix)
    assert (ref.n, ref.edges, ref.iterations) == (8, 26, 2)
    body = mix["request"]["body"]
    want = ref.answer(body)["result"]
    assert want.tolist() == [0, 0, 0, 0, 4, 4, 4, 4]
    assert cdlp.COMPARED == tuple(ref.check(body, want))
    assert ref.check(body, want.copy()) == {"labels": 0}
    one = want.copy()
    one[5] = 5                          # one altered label reads 1
    assert ref.check(body, one) == {"labels": 1}
    # the same communities under other names are not the answer:
    # Graphalytics validates CDLP by exact match
    renamed = np.where(want == 4, 7, want)
    assert ref.check(body, renamed) == {"labels": 4}
    assert ref.check(body, want[:7]) == {"labels": 8}
    assert ref.check(body, want.astype(np.int64)) == {"labels": 0}
    # a round short is another answer
    short = cdlp.propagate(indptr, indices, 1)
    assert ref.check(body, short) == {"labels": 2}
