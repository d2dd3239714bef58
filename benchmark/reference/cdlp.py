"""Plain reference for CDLP jobs: LDBC Graphalytics' community detection
by label propagation (benchmark specification v1.0, section 2.3.4: the
deterministic, synchronous variant of Raghavan et al.) over the structure
``reference/csr.py`` makes of the generated edges, in numpy alone:

    L_0(v) = v
    L_i(v) = min { l : count_i(v, l) = max over l' of count_i(v, l') },
             count_i(v, l) = |{ u in N(v) : L_{i-1}(u) = l }|

for i = 1 .. ``iterations``, every L_i from L_{i-1} alone; a vertex without
a neighbour keeps its label. A round is one sort: every (row, neighbour's
label) pair as the 64-bit key ``row << b | label`` (b: the bits of n - 1),
so that equal labels of one row stand in runs; a run's length is its count;
each row keeps the run whose ``count << b | (2^b - 1 - label)`` is largest:
the longest, and of equally long runs the one of the smallest label (one
``maximum.reduceat`` over the rows' runs). No loop over vertices.

Departures from the specification, each the configuration's (``assumed``):
the initial labels are the served graph's dense ids, not the data set's
sparse ones; the rows are the neighbours as the served snapshot holds them
(an undirected configuration is symmetrised before it is served, duplicates
merged: each neighbour once; for a directed graph the specification counts
a neighbour reached by both directions twice, which a directed
configuration's reference would have to add).

Graphalytics validates CDLP by exact match of every label, and so does
``check``: the number compared is the count of vertices whose label
differs, limit 0. An answer of another length counts as all ``n`` out.
"""

from __future__ import annotations

import numpy as np

COMPARED = ("labels",)


def propagate(indptr, indices, iterations: int):
    """int32 [n]: L_iterations."""
    n = len(indptr) - 1
    bits = max(int(n - 1).bit_length(), 1)      # a label's bits in a key
    mask = (1 << bits) - 1
    labels = np.arange(n, dtype=np.int64)
    row_base = np.repeat(np.arange(n, dtype=np.int64) << bits,
                         np.diff(indptr))
    for _ in range(int(iterations)):
        key = row_base + labels[indices]
        key.sort()
        starts = np.empty(len(key), bool)
        starts[:1] = True
        np.not_equal(key[1:], key[:-1], out=starts[1:])
        run_start = np.flatnonzero(starts)
        run_key = key[run_start]
        count = np.diff(run_start, append=len(key))
        score = (count << bits) | (mask - (run_key & mask))
        run_row = run_key >> bits
        rows = np.empty(len(run_row), bool)
        rows[:1] = True
        np.not_equal(run_row[1:], run_row[:-1], out=rows[1:])
        row_start = np.flatnonzero(rows)
        if len(row_start):
            best = np.maximum.reduceat(score, row_start)
            labels = labels.copy()
            labels[run_row[row_start]] = mask - (best & mask)
    return labels.astype(np.int32)


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
        body = mix["request"]["body"]
        self.iterations = int(body["iterations"])
        self.labels = propagate(indptr, indices, self.iterations)

    def answer(self, body: dict) -> dict:
        return {"result": self.labels}

    def check(self, body: dict, result) -> dict:
        return {"labels": mislabelled(result, self.labels)}


def prepare(n, indptr, indices, pools, mix) -> Reference:
    return Reference(n, indptr, indices, pools, mix)
