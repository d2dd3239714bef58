"""Plain reference for LCC jobs: LDBC Graphalytics' local clustering
coefficient (benchmark specification v1.0, the LCC algorithm) over the
structure ``reference/csr.py`` makes of the generated edges, in numpy
alone, float64:

    N(v)   = the neighbours of v, v itself never
    LCC(v) = |{ (u, w) : u, w in N(v), (u, w) in E }| / (|N(v)| (|N(v)| - 1))
             if |N(v)| >= 2, else 0

On an undirected graph an edge between two neighbours counts in both
directions, so LCC(v) = 2 T(v) / (d(v) (d(v) - 1)) with T(v) the
triangles through v. The triangles are listed, each once: the vertices
are renamed by their rank in (degree, id) order, every edge points to its
endpoint of higher rank, and a triangle a < b < c is the wedge (b, c) at
its lowest vertex a that an edge b -> c closes. Centres of equal
out-degree d form a block ``[count, d]`` of their higher neighbours (a
row rising, as the keys are sorted); ``np.triu_indices`` lists a row's
pairs; a pair is an edge where its key ``b * n + c`` stands among the
sorted edge keys (``np.searchsorted``); ``np.bincount`` credits a closed
wedge to its three vertices. Centres in tiles of about 4 M wedges on a
thread pool, as ``graphs/kron.py`` uses one (numpy releases the lock in
the search). No table of hubs, no bitmap, nothing of ``titan_tpu``.

Departures from the specification, each the configuration's (``assumed``):
the graph is the served one (an undirected configuration is symmetrised
before it is served, duplicates merged, so N(v) is a set; a self-loop is
dropped here, as the specification's N(v) leaves v out); a directed
configuration's numerator (a pair counted once a direction) is not made.

Graphalytics validates LCC by its epsilon rule, and so does ``check``:
the number compared is the count of vertices outside ``|got - want| <=
1e-4 * |want|`` (so a reference 0 wants an exact 0), limit 0. An answer
of another length counts as all ``n`` out.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np

COMPARED = ("lcc",)

EPSILON = 1e-4
WORKERS = 8
TILE_WEDGES = 4 << 20


def triangles(indptr, indices):
    """(int64 [n]: the triangles through each vertex, int64 [n]: its
    neighbours, itself not counted)."""
    n = len(indptr) - 1
    deg = np.diff(indptr)
    row = np.repeat(np.arange(n, dtype=np.int64), deg)
    loop = indices == row
    deg = deg - np.bincount(row[loop], minlength=n)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n, dtype=np.int64)
    a, b = rank[row], rank[indices]
    keys = (a * n + b)[a < b]          # each edge once, towards the higher
    del a, b, row, loop
    keys.sort()
    centre, higher = keys // n, keys % n
    out = np.bincount(centre, minlength=n)
    start = np.cumsum(out) - out
    total = np.zeros(n, np.int64)
    lock = threading.Lock()

    def tile(job):
        d, centres = job
        block = higher[start[centres][:, None] + np.arange(d)]
        i, j = np.triu_indices(d, 1)
        b, c = block[:, i], block[:, j]
        want = b * n + c
        at = np.minimum(np.searchsorted(keys, want.ravel()),
                        len(keys) - 1).reshape(want.shape)
        closed = keys[at] == want
        credit = np.bincount(b[closed], minlength=n)
        credit += np.bincount(c[closed], minlength=n)
        credit[centres] += closed.sum(axis=1)
        with lock:
            np.add(total, credit, out=total)

    jobs = []
    order = np.argsort(out, kind="stable")
    sizes, firsts = np.unique(out[order], return_index=True)
    for d, lo, hi in zip(sizes.tolist(), firsts.tolist(),
                         firsts.tolist()[1:] + [n]):
        if d < 2:
            continue
        per = max(TILE_WEDGES // (d * (d - 1) // 2), 1)
        jobs += [(d, order[k:min(k + per, hi)])
                 for k in range(lo, hi, per)]
    with ThreadPoolExecutor(WORKERS) as pool:
        list(pool.map(tile, jobs))
    return total[rank], deg


def coefficients(counts, deg) -> np.ndarray:
    """float64 [n]: 2 T / (d (d - 1)), 0 where d < 2."""
    d = deg.astype(np.float64)
    return np.where(deg >= 2, 2.0 * counts / np.maximum(d * (d - 1), 1.0),
                    0.0)


def outside(got, want) -> int:
    """How many vertices' coefficients lie outside the epsilon rule (all
    of them where the answer has another length)."""
    got = np.asarray(got).ravel()
    if got.shape != want.shape:
        return int(want.size)
    err = np.abs(got.astype(np.float64) - want)
    return int((~(err <= EPSILON * np.abs(want))).sum())


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        self.n = int(n)
        self.edges = int(len(indices))      # directed edge slots
        self.triangles, self.degree = triangles(indptr, indices)
        self.lcc = coefficients(self.triangles, self.degree)

    def answer(self, body: dict) -> dict:
        return {"result": self.lcc}

    def check(self, body: dict, result) -> dict:
        return {"lcc": outside(result, self.lcc)}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
