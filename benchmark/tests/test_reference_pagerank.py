"""The PageRank reference against a 5-vertex graph worked by hand, and
what ``check`` counts."""

import numpy as np
import pytest

from reference import csr, pagerank

# 0 -> 1, 0 -> 2, 1 -> 2, 2 -> 0, 3 -> 2, 3 -> 4; vertex 4 dangles
SRC = np.array([0, 0, 1, 2, 3, 3])
DST = np.array([1, 2, 2, 0, 2, 4])
# d = 0.85, PR_0 = 0.2. Iteration 1: vertex 4 hands on 0.2 / 5 = 0.04;
#   v0 = 0.03 + 0.85 (0.2 + 0.04), v1 = 0.03 + 0.85 (0.1 + 0.04),
#   v2 = 0.03 + 0.85 (0.1 + 0.2 + 0.1 + 0.04), v3 = 0.03 + 0.85 (0.04),
#   v4 = 0.03 + 0.85 (0.1 + 0.04)
ONE = [0.234, 0.149, 0.404, 0.064, 0.149]
# Iteration 2: vertex 4 hands on 0.149 / 5 = 0.0298;
#   v0 = 0.03 + 0.85 (0.404 + 0.0298), v1 = 0.03 + 0.85 (0.117 + 0.0298),
#   v2 = 0.03 + 0.85 (0.117 + 0.149 + 0.032 + 0.0298),
#   v3 = 0.03 + 0.85 (0.0298), v4 = 0.03 + 0.85 (0.032 + 0.0298)
TWO = [0.39873, 0.15478, 0.30863, 0.05533, 0.08253]


@pytest.mark.parametrize("iterations, want", [(0, [0.2] * 5), (1, ONE),
                                              (2, TWO)])
def test_by_hand(iterations, want):
    indptr, indices = csr.structure(5, SRC, DST)
    got = pagerank.pagerank(indptr, indices, iterations, 0.85)
    assert got.dtype == np.float64
    assert got == pytest.approx(want, rel=1e-12)
    assert got.sum() == pytest.approx(1.0, rel=1e-12)


def test_check_counts_the_ranks_outside_epsilon():
    indptr, indices = csr.structure(5, SRC, DST)
    mix = {"request": {"body": {"kind": "pagerank", "iterations": 2,
                                "damping": 0.85}}}
    ref = pagerank.prepare(5, indptr, indices, {}, mix)
    assert (ref.n, ref.edges) == (5, 6)
    body = mix["request"]["body"]
    want = ref.answer(body)["result"]
    assert want == pytest.approx(TWO, rel=1e-12)
    assert pagerank.COMPARED == tuple(ref.check(body, want))
    assert ref.check(body, want.astype(np.float32)) == {"rank": 0}
    off = want.copy()
    off[1] *= 1.0 + 2e-4                    # outside: relative 1e-4
    off[2] *= 1.0 + 0.5e-4                  # inside
    assert ref.check(body, off) == {"rank": 1}
    assert ref.check(body, want[:4]) == {"rank": 5}
    assert ref.check(body, np.where(np.arange(5) == 0, np.inf, want)) \
        == {"rank": 5}
    assert isinstance(ref.check(body, off)["rank"], int)     # JSON-safe
    # another body is another answer, computed when first asked for
    one = ref.answer(dict(body, iterations=1))["result"]
    assert one == pytest.approx(ONE, rel=1e-12)


def test_it_imports_nothing_of_the_program():
    code = open(pagerank.__file__).read().split('"""', 2)[2]
    assert "titan_tpu" not in code and "jax" not in code


def test_the_precision_below_is_not_correct():
    """The configuration states float32 ranks. The same formula with every
    vector stored in the precision below, bfloat16, has to fail the
    epsilon rule (at graph500-22: 2,318,378 of 2,396,390 ranks out, CPU
    count, PR 33), and float32 storage has to pass it."""
    import ml_dtypes
    import scipy.sparse as sp

    from conftest import small_config
    import loadgen

    cfg = dict(small_config("graph500_simple", 10), scale=12)
    n, src, dst, _perm = loadgen.make_graph(cfg, 3000000019)
    indptr, indices = csr.structure(n, src, dst)
    want = pagerank.pagerank(indptr, indices, 10, 0.85)
    into = sp.csr_matrix((np.ones(len(indices)), indices, indptr),
                         shape=(n, n)).T
    outdeg = np.diff(indptr).astype(np.float64)

    def stored_as(dtype):
        def r(x):
            return x.astype(dtype).astype(np.float64)
        rank = r(np.full(n, 1.0 / n))
        for _ in range(10):
            rank = r(0.15 / n + 0.85 * r(into @ r(rank / outdeg)))
        return rank

    assert pagerank.out_of_epsilon(stored_as(np.float32), want) == 0
    assert pagerank.out_of_epsilon(stored_as(ml_dtypes.bfloat16),
                                   want) > 0.9 * n
