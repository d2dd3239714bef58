"""GAP *Urand*: both endpoints of each of n * edge_factor edges uniform.

The edges come from the configuration's ``graph_seed`` (one fixed data set,
as GAP's own Urand is); the run's seed relabels the vertices with a
permutation, so what sits in memory differs from seed to seed while the
degree sequence — and every array shape the program derives from it — does
not. Returns ``(n, src, dst, perm)``: the edge list as generated —
directed, duplicates and self-loops kept; the caller symmetrises — and the
relabelling (``perm[v]`` is the served id of the data set's vertex v).
"""

from __future__ import annotations

import numpy as np

_TAG = 0x7572616e


def generate(config: dict, seed: int):
    n = 1 << int(config["scale"])
    m = n * int(config["edge_factor"])
    rng = np.random.default_rng([int(config["graph_seed"]), _TAG])
    ends = rng.integers(0, n, (2, m), dtype=np.int32)
    perm = np.random.default_rng([int(seed), _TAG]).permutation(n) \
        .astype(np.int32)
    return n, perm[ends[0]], perm[ends[1]], perm
