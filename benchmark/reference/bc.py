"""Plain reference for BC jobs: the GAP Benchmark Suite's betweenness
centrality kernel (Beamer, Asanovic, Patterson, arXiv:1508.03619,
section 3.1: Brandes' algorithm from a few roots a trial, the scores
divided by the largest) over the structure ``reference/csr.py`` makes of
the generated edges, in numpy / scipy float64, nothing of ``titan_tpu``
in it. For a root s:

    depth[v]   = BFS distance from s
    sigma[s]   = 1
    sigma[v]   = sum of sigma[u] over edges u -> v, depth[u] = depth[v] - 1
    delta_s[u] = sigma[u] * sum over edges u -> v, depth[v] = depth[u] + 1,
                 of (1 + delta_s[v]) / sigma[v];      delta_s[s] = 0

    scores = sum over the request's roots of delta_s, divided by its
             largest entry where that is positive

A level is one sparse product: the forward phase multiplies the
TRANSPOSE by sigma masked to the level before (a vertex without a depth
whose product is positive joins), the backward phase the matrix by
``(1 + delta) / sigma`` masked to the level behind. ``prepare`` works
every root of every pool ahead of the window, the roots on a thread pool
(the product releases the lock), and keeps each ``delta_s``; ``check``
sums the request's roots. A root named twice counts twice, as GAP's
picker may repeat one.

``check`` holds every score to the epsilon rule ``|got - want| <= 1e-4 *
|want|`` (so a reference 0 wants an exact 0); the number compared is the
count of scores outside it, limit 0; an answer of another length, or with
a value that is not finite, counts as all ``n`` out. Why this rule holds
a float32 program and no coarser one: every term of both recurrences is
non-negative, so nothing cancels and float32 differs from float64 by
rounding alone (about 1e-6 relative after the few hundred thousand
additions of a hub's sum in a tree order); an exact zero (a vertex with
no neighbour a level further, or not reached) stays an exact zero in
both; and bfloat16's 3 significant digits (4e-3 a rounding) fail it on
nearly every score that is not zero. GAP's own verifier compares with
its serial Brandes; the relative rule is the stricter of the two on
small scores (the configuration's ``assumed``).
"""

from __future__ import annotations

import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np

COMPARED = ("scores",)

EPSILON = 1e-4
WORKERS = 8


def dependencies(out, root: int):
    """``(delta_s float64 [n], levels that hold a vertex)`` of ``root``
    over the scipy CSR matrix ``out`` (``out[u, v]`` = edges u -> v)."""
    n = out.shape[0]
    into = out.T                    # a view: row v, the edges into v
    depth = np.full(n, -1, np.int64)
    sigma = np.zeros(n, np.float64)
    depth[root], sigma[root] = 0, 1.0
    d = 0
    while True:
        d += 1
        paths = into @ np.where(depth == d - 1, sigma, 0.0)
        new = (depth < 0) & (paths > 0)
        if not new.any():
            break
        depth[new], sigma[new] = d, paths[new]
    delta = np.zeros(n, np.float64)
    for k in range(d - 1, 1, -1):
        behind = depth == k
        share = np.zeros(n, np.float64)
        share[behind] = (1.0 + delta[behind]) / sigma[behind]
        at = depth == k - 1
        delta[at] = sigma[at] * (out @ share)[at]
    return delta, d


def normalised(total) -> np.ndarray:
    top = float(total.max()) if total.size else 0.0
    return total / top if top > 0 else total


def outside(got, want) -> int:
    """How many scores lie outside the epsilon rule (all of them where
    the answer has another length or a value that is not finite)."""
    got = np.asarray(got).ravel()
    if got.shape != want.shape or not np.isfinite(got).all():
        return int(want.size)
    err = np.abs(got.astype(np.float64) - want)
    return int((~(err <= EPSILON * np.abs(want))).sum())


def worst(got, want) -> float:
    """The largest relative error over the scores the reference has
    positive: the reading the rule's 1e-4 is held against (nan where the
    answer has another length)."""
    got = np.asarray(got).ravel()
    at = want > 0
    if got.shape != want.shape or not at.any():
        return float("nan")
    return float((np.abs(got[at].astype(np.float64) - want[at])
                  / want[at]).max())


def roots_of(body: dict) -> list:
    return [int(r) for r in body.get("sources_dense",
                                     body.get("sources", ()))]


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        import scipy.sparse as sp

        self.n = int(n)
        self.edges = int(len(indices))      # directed edge slots
        self.out = sp.csr_matrix(
            (np.ones(len(indices), np.float64), indices, indptr),
            shape=(self.n, self.n))
        self.delta: dict = {}
        roots = sorted({int(r) for pool in pools.values() for r in pool})
        with ThreadPoolExecutor(WORKERS) as workers:
            for root, (delta, _levels) in zip(roots, workers.map(
                    lambda r: dependencies(self.out, r), roots)):
                self.delta[root] = delta

    def answer(self, body: dict) -> dict:
        total = np.zeros(self.n, np.float64)
        for root in roots_of(body):
            if root not in self.delta:      # a root no pool holds
                self.delta[root] = dependencies(self.out, root)[0]
            total += self.delta[root]
        return {"result": normalised(total)}

    def check(self, body: dict, result) -> dict:
        want = self.answer(body)["result"]
        out = outside(result, want)
        print(f"[reference bc] roots {roots_of(body)}: {out} of {self.n} "
              f"scores outside {EPSILON:g}, largest relative error "
              f"{worst(result, want):.3g}, {int((want > 0).sum())} "
              "positive", file=sys.stderr, flush=True)
        return {"scores": out}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
