"""Plain reference for PageRank jobs: LDBC Graphalytics' PR (benchmark
specification v1.0, section 2.3.2) in float64, as written there, the
dangling term included:

    PR_0(v) = 1 / n
    PR_i(v) = (1 - d) / n
              + d * (sum over in-neighbours u of PR_{i-1}(u) / outdeg(u)
                     + sum over dangling w of PR_{i-1}(w) / n)

for a fixed number of iterations, as a loop of scipy SpMVs over the
structure ``reference/csr.py`` makes of the generated edges. ``check``
holds every rank of an answer to the specification's epsilon rule:
``|got - want| <= 1e-4 * |want|``. An answer of the wrong length, or with
a value that is not finite, counts as all ``n`` ranks out.
"""

from __future__ import annotations

import numpy as np

COMPARED = ("rank",)
EPSILON = 1e-4


def pagerank(indptr, indices, iterations: int, damping: float):
    import scipy.sparse as sp

    n = len(indptr) - 1
    # row v of the transpose: the in-neighbours of v (a view, no copy)
    into = sp.csr_matrix((np.ones(len(indices), np.float64), indices,
                          indptr), shape=(n, n)).T
    outdeg = np.diff(indptr).astype(np.float64)
    dangling = outdeg == 0
    rank = np.full(n, 1.0 / n, np.float64)
    for _ in range(iterations):
        share = np.where(dangling, 0.0, rank / np.maximum(outdeg, 1.0))
        rank = (1.0 - damping) / n + damping * (
            into @ share + rank[dangling].sum() / n)
    return rank


def out_of_epsilon(got, want) -> int:
    """How many ranks lie outside the epsilon rule (all of them where the
    answer has another length or holds a value that is not finite)."""
    got = np.asarray(got, np.float64).ravel()
    if got.shape != want.shape or not np.isfinite(got).all():
        return int(want.size)
    return int((np.abs(got - want) > EPSILON * np.abs(want)).sum())


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        self.n = int(n)
        self.edges = int(len(indices))      # directed edge slots
        self.indptr, self.indices = indptr, indices
        self._ranks: dict = {}
        self.answer(mix["request"]["body"])

    def answer(self, body: dict) -> dict:
        key = (int(body["iterations"]), float(body["damping"]))
        if key not in self._ranks:
            self._ranks[key] = pagerank(self.indptr, self.indices, *key)
        return {"result": self._ranks[key]}

    def check(self, body: dict, result) -> dict:
        return {"rank": out_of_epsilon(result,
                                       self.answer(body)["result"])}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
