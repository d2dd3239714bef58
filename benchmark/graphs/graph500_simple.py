"""LDBC Graphalytics *graph500-<scale>*: Graph500 Kronecker edges made a
simple undirected graph, as Graphalytics publishes its data sets.

``kron.py``'s R-MAT edges (the configuration's ``scale``, ``edge_factor``,
A, B, C, from ``graph_seed``; the run's seed relabels the 2^scale ids),
then: self-loops dropped, each undirected pair kept once, vertices without
an edge dropped and the ids that are left made dense in the order of the
relabelled ones. So the graph is one fixed data set, what sits in memory
differs from seed to seed, and the degree sequence — with it every shape
the program derives from the graph — does not. Returns ``(n, src, dst,
perm)``: each pair once, ``src < dst`` (the caller symmetrises an
undirected configuration), and ``perm[v]`` the served id of the data set's
vertex v (the dense ids under the identity relabelling). No vertex of the
result is without an edge, so none dangles; a graph that breaks this is
refused here, in set-up, because the program's PageRank leaks dangling
mass where Graphalytics redistributes it.
"""

from __future__ import annotations

import numpy as np

import files


def simplify(n_raw: int, src, dst, perm_raw):
    """Self-loops and duplicate pairs out, isolated vertices out, dense
    ids; ``perm_raw`` is the relabelling ``src`` / ``dst`` already
    carry."""
    bits = max(int(n_raw - 1).bit_length(), 1)
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    keep = lo != hi
    key = np.unique((lo[keep] << bits) | hi[keep])
    del lo, hi, keep
    lo = (key >> bits).astype(np.int32)
    hi = (key & ((1 << bits) - 1)).astype(np.int32)
    del key
    used = np.zeros(n_raw, bool)
    used[lo] = True
    used[hi] = True
    dense = (np.cumsum(used) - 1).astype(np.int32)
    n = int(used.sum())
    # data set's vertex v (dense under the identity relabelling, so in
    # the order of the raw ids) -> the id it is served under
    perm_raw = np.asarray(perm_raw)
    perm = dense[perm_raw[used[perm_raw]]]
    return n, dense[lo], dense[hi], perm


def generate(config: dict, seed: int):
    kron = files.load_module("graphs", "kron")
    n_raw, src, dst, perm_raw = kron.generate(config, seed)
    n, src, dst, perm = simplify(n_raw, src, dst, perm_raw)
    degree = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    if n == 0 or int(degree.min()) == 0:
        raise SystemExit("graph500_simple: a vertex without an edge "
                         "dangles; this deployment refuses such a graph")
    return n, src, dst, perm
