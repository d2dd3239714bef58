"""GAP *Kron*: Graph500 R-MAT edges, plain numpy.

One quadrant draw per bit and edge (A, B, C, D of the configuration, each
quantised to 1/65536 so four draws come out of one 64-bit word), from the
configuration's ``graph_seed``: like GAP's own Kron, the graph is one fixed
data set. The run's seed relabels its vertices with a permutation (the
Graph500 generator scrambles ids likewise), so what sits in memory differs
from seed to seed while the degree sequence — and with it every array shape
the program derives from the graph — does not. Edges are made in blocks,
each from its own stream, so the result does not depend on how many threads
made them. Returns ``(n, src, dst, perm)``: the edge list as generated —
directed, duplicates and self-loops kept; the caller symmetrises — and the
relabelling (``perm[v]`` is the served id of the data set's vertex v).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 18
THREADS = 4
_TAG = 0x6b726f6e


def _block(seed: int, index: int, count: int, scale: int, thresholds):
    ta, tab, tabc = thresholds
    rng = np.random.default_rng([seed, _TAG, index])
    words = -(-scale // 4)
    r16 = rng.integers(0, 1 << 64, size=(words, count), dtype=np.uint64) \
        .view(np.uint16).reshape(words, count, 4)
    src = np.zeros(count, np.int32)
    dst = np.zeros(count, np.int32)
    sb = np.empty(count, bool)
    db = np.empty(count, bool)
    hi = np.empty(count, bool)
    for bit in range(scale):
        r = r16[bit // 4, :, bit % 4]
        np.greater_equal(r, tab, out=sb)          # quadrants C, D: src bit
        np.greater_equal(r, ta, out=db)
        np.logical_xor(db, sb, out=db)            # quadrant B
        np.greater_equal(r, tabc, out=hi)         # quadrant D
        np.logical_or(db, hi, out=db)
        np.left_shift(src, 1, out=src)
        np.bitwise_or(src, sb, out=src)
        np.left_shift(dst, 1, out=dst)
        np.bitwise_or(dst, db, out=dst)
    return src, dst


def generate(config: dict, seed: int):
    scale = int(config["scale"])
    n = 1 << scale
    m = n * int(config["edge_factor"])
    a, b, c = (float(config[k]) for k in ("a", "b", "c"))
    thresholds = tuple(np.uint16(round(x * 65536))
                       for x in (a, a + b, a + b + c))
    graph_seed = int(config["graph_seed"])
    src = np.empty(m, np.int32)
    dst = np.empty(m, np.int32)
    starts = range(0, m, BLOCK)

    def fill(i):
        lo = starts[i]
        s, d = _block(graph_seed, i, min(BLOCK, m - lo), scale, thresholds)
        src[lo:lo + len(s)] = s
        dst[lo:lo + len(d)] = d

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(len(starts))))
    perm = np.random.default_rng([int(seed), _TAG]).permutation(n) \
        .astype(np.int32)
    return n, perm[src], perm[dst], perm
