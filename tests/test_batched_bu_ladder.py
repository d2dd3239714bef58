"""The batched BFS's bottom-up (pull) step on its cap ladder (ISSUE 49).

``bstep``'s ``c_cap`` and ``bex``'s ``(c_cap, p_cap)`` were the powers
of two of counts read back from the device, so a new source met new
shapes and built them inside a served window (ROADMAP S1). Now they come
from ladders one function states from the layout alone
(``bfs_hybrid._bu_caps``), and one function builds the whole set ahead
(``warm_batched``). What is pinned here, all on the CPU:

* the ladder: what it is made of, that every count has a rung, and which
  ``bex`` pairs are in the set;
* a padded rung only pads: with every pulled level forced onto one rung
  of the ladder (and every ``bex`` onto one pair) ``dist``, ``levels``
  and ``completed`` are bit-equal to the run on the exact powers of two
  the counts had before, undirected and directed, in both modes;
* the count that holds S1 (1): after ``warm_batched`` at K = 1, sixteen
  sources whose pulled levels fall on different rungs build no
  executable, and the set's size is the number the ladder states.
"""

import numpy as np
import pytest

from titan_tpu.models import bfs_hybrid as bh
from titan_tpu.models.bfs import _next_pow2
from titan_tpu.obs import devprof
from titan_tpu.obs.tracing import Tracer, scope
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager

SCALE = 10


def hub_edges(scale: int = SCALE, seed: int = 3):
    """One endpoint skewed to the low ids, so a few vertices hold most
    edges: hubs of hundreds of chunk columns (survivors for ``bex``),
    leaves, and a few vertices with no edge at all."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * 8
    dst = rng.integers(0, n, m)
    src = (n * rng.random(m) ** 4).astype(np.int64)
    keep = src != dst
    return n, src[keep].astype(np.int32), dst[keep].astype(np.int32)


@pytest.fixture(scope="module")
def layouts():
    """{"undirected": the symmetrised graph's layout, "directed": one
    orientation's, which pulls at every level}."""
    n, src, dst = hub_edges()
    both = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    g = bh.build_chunked_csr(both)
    return {"undirected": g, "directed": dict(g, directed=True)}


def powers(top: int) -> tuple:
    return tuple(1 << e for e in range(1, top.bit_length()))


def exact_caps(g) -> tuple:
    """The caps of before: the power of two of every count."""
    c_caps = powers(_next_pow2(max(g["n"], 2)))
    p_caps = powers(_next_pow2(max(int(g["q_total"]), 2)))
    return c_caps, tuple((c, p) for c in c_caps for p in p_caps)


def phases_of(run):
    """``(result, [(name, attrs)] of the run's pull phases)``."""
    tracer = Tracer()
    root = tracer.start("t", "run")
    with scope(tracer, "t", root):
        out = run()
    tracer.end(root)
    return out, [(s.name, s.attrs) for s in tracer.spans("t")
                 if s.name == "bfs.exhaust"
                 or s.name == "bfs.sweep" and s.attrs["dir"] == "bu"]


def run_kw(mode: str) -> dict:
    return {"mode": mode, "start_level": 1, "max_levels": 4} \
        if mode == "hops" else {"mode": mode}


# -- the ladder ---------------------------------------------------------------

@pytest.mark.parametrize("n, q_total", [(2_396_390, 17_447_196),
                                        (1 << 20, 4_650_000),
                                        (1024, 3000), (5, 9), (1, 2)])
def test_the_ladder(n, q_total):
    c_caps, ex_pairs = bh._bu_caps({"n": n, "q_total": q_total})
    top, ptop = _next_pow2(max(n, 2)), _next_pow2(max(q_total, 2))
    assert list(c_caps) == sorted(set(c_caps)) and c_caps[-1] == top
    assert all(c & (c - 1) == 0 for c in c_caps)
    assert len(c_caps) <= len(bh.BU_RUNG_SHIFTS)
    # a pulled level costs its rung: from the middle up a factor of two
    # apart, below it no more than four
    upper = [c for c in c_caps if c >= top >> 4]
    assert all(b == 2 * a for a, b in zip(upper, upper[1:]))
    assert all(b <= 4 * a for a, b in zip(c_caps, c_caps[1:]))
    # every count has a rung, the lowest that holds it
    for count in {1, 2, c_caps[0], min(c_caps[0] + 1, top), top - 1, top}:
        rung = bh._rung(c_caps, count)
        assert rung >= count and all(c < count for c in c_caps if c < rung)
    # bex: the lowest and the top candidate rung; every reachable pair
    ex_c = sorted({c for c, _p in ex_pairs})
    assert ex_c == sorted({c_caps[0], top})
    assert max(p for _c, p in ex_pairs) == ptop
    assert len(ex_pairs) <= 2 * len(bh.EX_RUNG_SHIFTS)
    for c_count, rem8 in ((1, 1), (1, q_total), (c_caps[0], c_caps[0]),
                          (min(c_caps[0] + 1, top), q_total),
                          (top, top), (top, max(q_total, top))):
        if rem8 < c_count or rem8 > ptop:
            continue
        c, p = bh._ex_rung(ex_pairs, c_count, rem8)
        assert (c, p) in ex_pairs and c >= c_count and p >= rem8


def test_the_cell_states_its_set():
    """graph500-22 as the benchmark's cells serve it: eight ``bstep``
    rungs, six ``bex`` pairs."""
    c_caps, ex_pairs = bh._bu_caps({"n": 2_396_390,
                                    "q_total": 17_447_196})
    assert c_caps == tuple(1 << e for e in (12, 14, 16, 18, 19, 20, 21, 22))
    assert ex_pairs == ((1 << 12, 1 << 16), (1 << 12, 1 << 21),
                        (1 << 12, 1 << 25), (1 << 22, 1 << 16),
                        (1 << 22, 1 << 21), (1 << 22, 1 << 25))


# -- a rung only pads ----------------------------------------------------------

#: the ladder of the test graph, by position: eight candidate rungs,
#: six ``bex`` pairs (fewer where two shifts meet: the case then repeats
#: one)
RUNGS = [("c", i) for i in range(len(bh.BU_RUNG_SHIFTS))] \
    + [("ex", i) for i in range(2 * len(bh.EX_RUNG_SHIFTS))]


@pytest.fixture(scope="module")
def exact(layouts):
    """{(layout, mode): the run on the exact powers of two}."""
    made: dict = {}

    def of(layout: str, mode: str, srcs):
        key = (layout, mode)
        if key not in made:
            g = layouts[layout]
            patched = pytest.MonkeyPatch()
            patched.setattr(bh, "_bu_caps", exact_caps)
            try:
                made[key] = phases_of(lambda: bh.frontier_bfs_batched(
                    g, srcs, **run_kw(mode)))
            finally:
                patched.undo()
        return made[key]
    return of


@pytest.mark.parametrize("mode", ["bfs", "hops"])
@pytest.mark.parametrize("layout", ["undirected", "directed"])
@pytest.mark.parametrize("which, at", RUNGS)
def test_every_rung_gives_the_same_dist(layouts, exact, monkeypatch,
                                        which, at, layout, mode):
    g = layouts[layout]
    c_caps, ex_pairs = bh._bu_caps(g)
    top = c_caps[-1]
    ptop = max(p for _c, p in ex_pairs)
    if which == "c":
        rung = c_caps[min(at, len(c_caps) - 1)]
        forced = (tuple(sorted({rung, top})), ex_pairs)
    else:
        c, p = ex_pairs[min(at, len(ex_pairs) - 1)]
        forced = (c_caps, tuple(sorted({(c, p), (c, ptop), (top, p),
                                        (top, ptop)})))
    degs = np.asarray(g["degc"])[:g["n"]]
    srcs = [int(v) for v in np.random.default_rng(7).choice(
        np.flatnonzero(degs > 0), 3, replace=False)]
    (want, want_levels, want_done), before = exact(layout, mode, srcs)
    assert before                                   # some level pulled
    monkeypatch.setattr(bh, "_bu_caps", lambda _g: forced)
    (dist, levels, done), after = phases_of(
        lambda: bh.frontier_bfs_batched(g, srcs, **run_kw(mode)))
    np.testing.assert_array_equal(dist, want)
    np.testing.assert_array_equal(levels, want_levels)
    np.testing.assert_array_equal(done, want_done)
    # the same levels pulled, each on a rung of the forced ladder that
    # holds what the exact run counted
    assert [(n, a["level"]) for n, a in after] \
        == [(n, a["level"]) for n, a in before]
    for (name, a), (_n, b) in zip(after, before):
        if name == "bfs.sweep":
            assert a["c_cap"] in forced[0] and a["c_cap"] >= b["c_cap"]
            assert a["candidates"] == b["candidates"] <= a["c_cap"]
        else:
            assert (a["c_cap"], a["p_cap"]) in forced[1]
            assert a["c_cap"] >= b["c_cap"] and a["p_cap"] >= b["p_cap"]


def test_the_stragglers_sweep_is_exercised(layouts, exact):
    """The directed layout pulls from level 0, where the hubs outlive
    the eight chunk rounds: the cases above do run a ``bex``."""
    srcs = [int(v) for v in np.random.default_rng(7).choice(
        np.flatnonzero(np.asarray(layouts["directed"]["degc"])
                       [:layouts["directed"]["n"]] > 0), 3, replace=False)]
    for mode in ("bfs", "hops"):
        _out, phases = exact("directed", mode, srcs)
        assert any(name == "bfs.exhaust" for name, _a in phases)


# -- S1 (1): after the warm function no source builds --------------------------

def test_after_the_warm_function_no_source_builds(layouts):
    """``warm_batched`` at K = 1 builds the set the ladder states; then
    sixteen sources whose pulled levels fall on different rungs, on a
    layout that pushes and pulls and on one that pulls at every level
    (the stragglers' sweep among its programs), build nothing."""
    g = layouts["undirected"]
    c_caps, ex_pairs = bh._bu_caps(g)
    degs = np.asarray(g["degc"])[:g["n"]]
    # leaves, hubs and the middle: other first levels, other rungs
    order = np.argsort(degs, kind="stable")
    order = order[degs[order] > 0]
    srcs = [int(v) for v in order[np.linspace(0, len(order) - 1, 16)
                                  .astype(int)]]
    metrics = MetricManager()
    prof = devprof.DeviceCostProfiler(metrics=metrics)
    bh._WARMED.clear()
    with prof:
        bh.warm_batched(g, 1)
        stats = prof.kernel_stats()
        # the set's size is the number the ladder states: a call a rung
        # (the directed layout shares every executable: keyed by shape)
        assert stats["batched_bu"]["calls"] == len(c_caps)
        assert stats["batched_ex"]["calls"] == len(ex_pairs)
        assert stats["batched_td"]["calls"] == len(bh._td_caps(g))
        assert prof.compiles("batched_bu") == len(c_caps)
        assert prof.compiles("batched_ex") == len(ex_pairs)
        built = prof.compiles()
        rungs, pairs = set(), set()
        for layout in ("undirected", "directed"):
            for s in srcs:
                _out, phases = phases_of(
                    lambda: bh.frontier_bfs_batched(layouts[layout], [s]))
                rungs |= {a["c_cap"] for name, a in phases
                          if name == "bfs.sweep"}
                pairs |= {(a["c_cap"], a["p_cap"]) for name, a in phases
                          if name == "bfs.exhaust"}
        assert prof.compiles() == built
    assert len(rungs) >= 3 and rungs <= set(c_caps)
    assert pairs and pairs <= set(ex_pairs)
    # one count a pulled level, by the rung it took
    taken = {c: metrics.counter("device.bfs.pull_rung",
                                labels={"c_cap": str(c)}).count
             for c in c_caps}
    assert {c for c, k in taken.items() if k} == rungs
    # a second call is the set's lookup: nothing runs
    calls = prof.kernel_stats()["batched_bu"]["calls"]
    bh.warm_batched(g, 1)
    assert prof.kernel_stats()["batched_bu"]["calls"] == calls


# -- the parent plane on the pull's roads (ISSUE 50) ---------------------------

def tree_faults(g, source: int, dist, parent) -> str:
    """GAP's rule for one job's row against the layout's own edges (the
    test graph is symmetric, so a chunk column of v holds v's
    neighbours): '' where ``parent`` is a BFS tree of ``dist``."""
    n = g["n"]
    if parent[source] != source:
        return f"parent[source] = {parent[source]}"
    there = dist < bh.INF
    if ((parent >= 0) != there).any():
        return "a parent off the tree, or a reached vertex without one"
    dstT, colstart = np.asarray(g["dstT"]), np.asarray(g["colstart"])
    degc = np.asarray(g["degc"])
    for v in np.flatnonzero(there):
        if v == source:
            continue
        p = int(parent[v])
        if not (0 <= p < n and dist[p] == dist[v] - 1):
            return f"parent[{v}] = {p} is not one level nearer"
        cols = dstT[:, colstart[v]:colstart[v] + degc[v]]
        if p not in cols:
            return f"parent[{v}] = {p} is no neighbour"
    return ""


@pytest.mark.parametrize("layout", ["undirected", "directed"])
@pytest.mark.parametrize("k", [1, 3])
def test_the_parent_plane_on_every_road_of_the_pull(layouts, layout, k):
    """The chunk rounds and, on the layout that pulls from level 0, the
    stragglers' sweep over the hubs' remaining columns: every job's
    parents keep GAP's rule, ``dist`` is the depth-only run's bit for
    bit, and the same levels ran the same programs on the same rungs."""
    g = layouts[layout]
    degs = np.asarray(g["degc"])[:g["n"]]
    srcs = [int(v) for v in np.random.default_rng(7).choice(
        np.flatnonzero(degs > 0), 3, replace=False)][:k]
    (want, want_levels, _d), before = phases_of(
        lambda: bh.frontier_bfs_batched(g, srcs))
    ((dist, par), levels, done), after = phases_of(
        lambda: bh.frontier_bfs_batched(g, srcs, parents=True))
    assert done.all()
    np.testing.assert_array_equal(dist, want)
    np.testing.assert_array_equal(levels, want_levels)
    strip = [(name, {k_: v for k_, v in a.items() if k_ != "sync_ms"})
             for name, a in after]
    assert strip == [(name, {k_: v for k_, v in a.items()
                             if k_ != "sync_ms"}) for name, a in before]
    if layout == "directed":
        assert any(name == "bfs.exhaust" for name, _a in after)
    for i, s in enumerate(srcs):
        assert tree_faults(g, s, dist[i], par[i]) == ""


def test_the_tree_has_a_set_of_its_own_and_no_source_adds_to_it(layouts):
    """``warm_batched`` with parents builds the programs that carry the
    plane (a seed, a push a rung, a pull a rung, a sweep a pair) and
    shares the plan and the listing, which read the depths alone; then
    eight pairs of sources with parents, and the same without, build
    nothing."""
    g = layouts["undirected"]
    c_caps, ex_pairs = bh._bu_caps(g)
    degs = np.asarray(g["degc"])[:g["n"]]
    order = np.argsort(degs, kind="stable")
    order = order[degs[order] > 0]
    srcs = [int(v) for v in order[np.linspace(0, len(order) - 1, 16)
                                  .astype(int)]]
    keys = ("batched_seed", "batched_plan", "batched_list", "batched_td",
            "batched_bu", "batched_ex")
    prof = devprof.DeviceCostProfiler(metrics=MetricManager())
    bh._WARMED.clear()
    with prof:
        # at K = 2, a batch size no other test of this file runs (the
        # executables live as long as the process)
        bh.warm_batched(g, 2)
        before = {k: prof.compiles(k) for k in keys}
        bh.warm_batched(g, 2, parents=True)
        assert bh.batched_is_warm(g, 2, parents=True)
        added = {k: prof.compiles(k) - before[k] for k in keys}
        assert added == {"batched_seed": 1, "batched_plan": 0,
                         "batched_list": 0,
                         "batched_td": len(bh._td_caps(g)),
                         "batched_bu": len(c_caps),
                         "batched_ex": len(ex_pairs)}
        built = prof.compiles()
        for layout in ("undirected", "directed"):
            for pair in zip(srcs[::2], srcs[1::2]):
                for parents in (True, False):
                    bh.frontier_bfs_batched(layouts[layout], list(pair),
                                            parents=parents)
        assert prof.compiles() == built
