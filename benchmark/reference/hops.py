"""Plain reference for point traversals: the set reached by exactly
``hops`` steps (walk semantics: e_v^T A^hops has a non-zero there), as a
count. ``prepare`` computes it for every start of the pool.
"""

from __future__ import annotations

import numpy as np

from reference import csr

COMPARED = ("hop_count",)


def hop_count(indptr, indices, start: int, hops: int, mark=None) -> int:
    n = len(indptr) - 1
    if mark is None:
        mark = np.zeros(n, bool)
    cur = np.array([start], np.int64)
    for _ in range(hops):
        mark[:] = False
        mark[csr.neighbours(indptr, indices, cur)] = True
        cur = np.flatnonzero(mark)
    return int(cur.size)


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        self.hops = int(mix["request"]["body"]["hops"])
        mark = np.zeros(n, bool)
        self.count = {int(v): hop_count(indptr, indices, int(v), self.hops,
                                        mark)
                      for v in pools["start"]}

    def answer(self, body: dict) -> dict:
        (v,) = body["start"]
        return {"result": self.count[int(v)]}

    def check(self, body: dict, result) -> dict:
        return {"hop_count": int(result != self.answer(body)["result"])}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
