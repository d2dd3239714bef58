"""The Graphalytics-style generator: a simple undirected graph with no
vertex left without an edge, the same data set under every seed's
relabelling, and the reference's hand-worked case."""

import numpy as np
import pytest

from conftest import small_config
import files
import loadgen
from reference import csr

BIG = 3000000019          # more than 32 signed bits hold


def graph(seed: int, scale: int = 10):
    cfg = dict(small_config("graph500_simple", 10), scale=scale)
    gen = files.load_module("graphs", cfg["generator"])
    return cfg, gen.generate(cfg, seed)


@pytest.mark.parametrize("seed", [5, BIG])
def test_simple_and_dense(seed):
    _cfg, (n, src, dst, perm) = graph(seed)
    assert len(src) == len(dst) > 0
    assert np.all(src < dst)                         # no self-loop ...
    pairs = src.astype(np.int64) * n + dst
    assert len(np.unique(pairs)) == len(pairs)       # ... no duplicate
    degree = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    assert degree.min() >= 1 and len(degree) == n    # ... none isolated
    assert 0 <= src.min() and dst.max() == n - 1     # dense ids
    assert sorted(perm.tolist()) == list(range(n))   # a relabelling


def test_the_same_data_set_under_every_seed():
    _c, (n, src, dst, perm) = graph(5)
    _c, (n2, src2, dst2, perm2) = graph(BIG)
    assert n == n2 and len(src) == len(src2)
    deg = np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
    deg2 = np.bincount(src2, minlength=n) + np.bincount(dst2, minlength=n)
    assert np.array_equal(np.sort(deg), np.sort(deg2))
    # vertex v of the data set keeps its degree and its neighbours
    assert np.array_equal(deg[perm], deg2[perm2])
    back, back2 = np.argsort(perm), np.argsort(perm2)

    def edges(s, d, b):
        lo, hi = np.minimum(b[s], b[d]), np.maximum(b[s], b[d])
        return set(zip(lo.tolist(), hi.tolist()))

    assert edges(src, dst, back) == edges(src2, dst2, back2)
    assert not np.array_equal(src, src2)             # stored otherwise
    # ... and a pure function of the seed
    _c, (_n, src3, dst3, perm3) = graph(BIG)
    assert np.array_equal(src2, src3) and np.array_equal(perm2, perm3)


def test_it_is_krons_edges_made_simple():
    cfg, (n, src, dst, perm) = graph(7)
    kron = files.load_module("graphs", "kron")
    n_raw, ksrc, kdst, _p = kron.generate(cfg, 7)
    keep = ksrc != kdst
    lo = np.minimum(ksrc, kdst)[keep].astype(np.int64)
    hi = np.maximum(ksrc, kdst)[keep].astype(np.int64)
    want = np.unique(lo * n_raw + hi)
    assert len(want) == len(src)
    used = np.unique(np.concatenate([lo, hi]))
    assert len(used) == n < n_raw
    # ids are dense in the order of the relabelled ones
    assert np.array_equal(used[src] * n_raw + used[dst], want)


def test_symmetrised_as_served_no_vertex_dangles():
    cfg, _g = graph(11)
    n, src, dst, _perm = loadgen.make_graph(cfg, 11)
    indptr, indices = csr.structure(n, src, dst)
    assert len(indices) == len(src)                  # nothing merged
    assert np.diff(indptr).min() >= 1


def test_a_vertex_without_an_edge_is_refused(monkeypatch):
    gen = files.load_module("graphs", "graph500_simple")
    real = gen.simplify

    def one_more(*a):
        n, src, dst, perm = real(*a)
        return n + 1, src, dst, np.append(perm, n).astype(perm.dtype)

    monkeypatch.setattr(gen, "simplify", one_more)
    with pytest.raises(SystemExit, match="dangles"):
        gen.generate(dict(small_config("graph500_simple", 10), scale=8), 3)


def test_counts_at_scale_16_are_the_files():
    """The counts the configuration's file gives for scale 22 come from
    this generator; at scale 16 they are 46,778 and 909,824 (CPU count,
    PR 33), whatever the seed."""
    _cfg, (n, src, _dst, _perm) = graph(123, scale=16)
    assert (n, len(src)) == (46778, 909824)
