"""What the seed decides is a pure function of the seed, and every seed
offers the same load in another order."""

import numpy as np

import files
import loadgen

BIG = 3000000019          # more than 32 signed bits hold


def test_arrivals_are_a_pure_function_of_the_seed():
    a = loadgen.arrival_times(8.0, 45.0, BIG)
    b = loadgen.arrival_times(8.0, 45.0, BIG)
    c = loadgen.arrival_times(8.0, 45.0, BIG + 1)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == 360 and a[0] == 0.0 and a[-1] < 45.0
    assert np.all(np.diff(a) > 0)
    # the same gaps, so the same offered load, whatever the seed
    gaps, other = (np.diff(np.append(t, 45.0)) for t in (a, c))
    assert np.allclose(np.sort(gaps), np.sort(other))
    assert 0.8 < gaps.std() / gaps.mean() < 1.2      # exponential-like


def test_request_order_same_keys_each_seed():
    a = loadgen.request_order(64, 200, BIG)
    b = loadgen.request_order(64, 200, 7)
    assert a == loadgen.request_order(64, 200, BIG) and a != b
    assert len(a) == 200
    for r in range(3):            # each round is the whole pool once
        assert sorted(a[64 * r:64 * (r + 1)]) == sorted(
            b[64 * r:64 * (r + 1)]) == list(range(64))


def test_pools_and_bodies():
    mix = {"pools": {"source": {"size": 64, "among": "nonzero_degree"},
                     "other": {"size": 10, "among": "nonzero_degree"}},
           "request": {"body": {"kind": "bfs", "source": {"draw": "source"},
                                "pair": [{"draw": "other"}, 7]}}}
    degree = np.array([0, 3, 1, 0, 2] * 40)
    config = {"graph_seed": 9}
    same = np.arange(200)
    pools = loadgen.draw_pools(degree, mix, config, same)
    assert pools == loadgen.draw_pools(degree, mix, config, same)
    assert len(set(pools["source"])) == 64
    assert all(degree[v] > 0 for v in pools["source"])
    # relabelled by a seed, the pools are the same vertices under new ids
    perm = np.random.default_rng(BIG).permutation(200)
    moved = loadgen.draw_pools(degree[np.argsort(perm)], mix, config,
                               perm)
    assert moved == {k: [int(perm[v]) for v in vs]
                     for k, vs in pools.items()}
    bodies = loadgen.Bodies(mix, pools, BIG)
    first = [bodies.get(i) for i in range(64)]
    assert sorted(b["source"] for b in first) == sorted(pools["source"])
    assert all(b["pair"][0] in pools["other"] and b["pair"][1] == 7
               and b["kind"] == "bfs" for b in first)
    assert bodies.get(5) == loadgen.Bodies(mix, pools, BIG).get(5)


def test_graphs_are_a_pure_function_of_the_seed():
    for name in ("kron", "urand"):
        gen = files.load_module("graphs", name)
        cfg = {"scale": 12, "edge_factor": 16, "a": .57, "b": .19, "c": .19,
               "graph_seed": 4}
        n, s1, d1, _p = gen.generate(cfg, BIG)
        _n, s2, d2, _p = gen.generate(cfg, BIG)
        _n, s3, d3, _p = gen.generate(cfg, BIG + 1)
        assert n == 4096 and len(s1) == n * 16
        assert np.array_equal(s1, s2) and np.array_equal(d1, d2)
        assert not np.array_equal(s1, s3)
        assert 0 <= s1.min() and s1.max() < n
        # another seed relabels the same graph: the degree sequence, and
        # so every shape the program derives from it, stays
        deg1 = np.bincount(np.concatenate([s1, d1]), minlength=n)
        deg3 = np.bincount(np.concatenate([s3, d3]), minlength=n)
        assert np.array_equal(np.sort(deg1), np.sort(deg3))
        _n, s4, _d4, _p = gen.generate(dict(cfg, graph_seed=5), BIG)
        assert not np.array_equal(s1, s4)


def test_kron_is_skewed_and_urand_is_not():
    cfg = {"scale": 14, "edge_factor": 16, "a": .57, "b": .19, "c": .19,
           "graph_seed": 4}
    deg = {}
    for name in ("kron", "urand"):
        n, s, d, _p = files.load_module("graphs", name).generate(cfg, 3)
        deg[name] = np.bincount(np.concatenate([s, d]), minlength=n)
    assert (deg["kron"] == 0).mean() > 0.2 and deg["kron"].max() > 2000
    assert (deg["urand"] == 0).sum() == 0 and deg["urand"].max() < 100
