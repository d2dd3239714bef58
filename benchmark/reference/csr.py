"""Adjacency structure of the generated edge list, scipy/numpy only."""

from __future__ import annotations

import numpy as np


def symmetrise(src, dst):
    """Every generated edge in both directions (duplicates and
    self-loops kept, as the served snapshot keeps them)."""
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def structure(n: int, src, dst):
    """(indptr, indices) of src->dst with duplicates merged — structure
    is all hop sets need."""
    import scipy.sparse as sp

    m = sp.csr_matrix((np.ones(len(src), np.int8), (src, dst)),
                      shape=(n, n))
    m.sum_duplicates()
    return m.indptr.astype(np.int64), m.indices.astype(np.int64)


def neighbours(indptr, indices, frontier):
    """All out-neighbours of ``frontier`` (with repeats)."""
    starts = indptr[frontier]
    counts = indptr[frontier + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64)
    base = np.repeat(starts - (np.cumsum(counts) - counts), counts)
    return indices[base + np.arange(total, dtype=np.int64)]

