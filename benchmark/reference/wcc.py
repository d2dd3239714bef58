"""Plain reference for WCC jobs: LDBC Graphalytics' weakly connected
components (benchmark specification v1.0, section 2.3.3) over the
structure ``reference/csr.py`` makes of the generated edges, in numpy
alone: every vertex starts with its own id as its label; a pass gives
every vertex the smallest label among itself and its neighbours (one
``minimum.reduceat`` over the rows), then jumps pointers (``label =
label[label]``, a label being a vertex of the same component) until
nothing moves; passes repeat until one changes nothing. A label is then
its component's smallest vertex id, the one canonical naming. The served
graph holds each edge in both directions (an undirected configuration is
symmetrised before it is served), so the rows are the neighbours both
ways and "weakly" asks nothing more.

Graphalytics validates WCC by equivalence: two vertices carry the same
label in the output exactly when they do in the reference output.
``check`` asks more, the canonical label itself, vertex by vertex; equal
canonical labels imply that equivalence. The number compared is the count
of vertices whose label differs, limit 0. An answer of another length
counts as all ``n`` vertices out.
"""

from __future__ import annotations

import numpy as np

COMPARED = ("labels",)


def components(indptr, indices):
    """int32 [n]: the smallest vertex id of each vertex's component."""
    n = len(indptr) - 1
    labels = np.arange(n, dtype=np.int32)
    rows = np.flatnonzero(np.diff(indptr) > 0)   # reduceat: no empty row
    starts = indptr[rows]
    while len(rows):
        least = np.minimum.reduceat(labels[indices], starts)
        new = labels.copy()
        new[rows] = np.minimum(new[rows], least)
        while True:
            jumped = new[new]
            if np.array_equal(jumped, new):
                break
            new = jumped
        if np.array_equal(new, labels):
            break
        labels = new
    return labels


def mislabelled(got, want) -> int:
    """How many vertices carry another label than the reference's (all of
    them where the answer has another length)."""
    got = np.asarray(got).ravel()
    if got.shape != want.shape:
        return int(want.size)
    return int((got != want).sum())


class Reference:
    def __init__(self, n, indptr, indices, pools: dict, mix: dict):
        self.n = int(n)
        self.edges = int(len(indices))      # directed edge slots
        self.labels = components(indptr, indices)

    def answer(self, body: dict) -> dict:
        return {"result": self.labels}

    def check(self, body: dict, result) -> dict:
        return {"labels": mislabelled(result, self.labels)}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
