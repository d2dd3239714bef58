"""The batched BFS's top-down (push) step, its direction rule (ISSUE
26) and what one level's program hands the next (ISSUE 29).

What is pinned here, all on the CPU:

* with the push engaged ``frontier_bfs_batched`` is bit-equal to the
  run with it held off through the rule's own inputs (a directed
  layout, a slot mask that masks nothing), in both modes, at K = 1, 3
  and 16, on a hub graph and a uniform one, and equal to scipy's
  hop sets for hops 1-3;
* which levels go which way: a mass above the top rung, a masked level,
  an ``out()`` chain's layout and a mesh-placed cohort pull;
* after the lane's first batch on a snapshot, a batch of any size up to
  ``max_fuse`` and a level on any rung build nothing;
* the ladder (ISSUE 31): bottom, middle and top rung where they were,
  from the middle up a factor of two apart; a level gives the same
  ``dist`` on every rung that holds it; a query whose second level
  lands on a rung between the middle and the top answers as the plain
  reference does and builds nothing;
* the frontier handed forward as a pair list with its statistics (the
  carried road) gives the same ``dist``, ``levels`` and ``completed`` as
  listing it from ``dist`` at every level (the scan road) and as the
  pull: mixed depths, retired jobs, multi-start rows, resumes,
  checkpoints; what a push hands on IS the next level's frontier and
  plan; a list past its capacity or its rung is scanned for instead; a
  single-start 2-hop query is four programs and three readbacks.
"""

import threading

import numpy as np
import pytest
import scipy.sparse as sp

from titan_tpu.models import bfs_hybrid as bh
from titan_tpu.obs import devprof
from titan_tpu.obs.tracing import Tracer, scope
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager


def edges(kind: str, scale: int, seed: int = 3):
    """Symmetrised edge list: ``hub`` skews one endpoint to the low ids
    (a few vertices hold most edges), ``uniform`` draws both uniformly."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * 8
    dst = rng.integers(0, n, m)
    src = (n * rng.random(m) ** 4).astype(np.int64) if kind == "hub" \
        else rng.integers(0, n, m)
    keep = src != dst
    src, dst = src[keep], dst[keep]
    return n, np.concatenate([src, dst]).astype(np.int32), \
        np.concatenate([dst, src]).astype(np.int32)


@pytest.fixture(scope="module", params=["hub", "uniform"])
def graph(request):
    scale = 11 if request.param == "hub" else 10
    n, src, dst = edges(request.param, scale)
    snap = snap_mod.from_arrays(n, src, dst)
    adj = sp.csr_matrix((np.ones(len(src), np.int8), (src, dst)),
                        shape=(n, n))
    adj.data[:] = 1
    return snap, bh.build_chunked_csr(snap), adj


def spans_of(run, *names):
    """``(result, [(name, attrs)] of the run's spans called ``names``)``."""
    tracer = Tracer()
    root = tracer.start("t", "interactive")
    with scope(tracer, "t", root):
        out = run()
    tracer.end(root)
    return out, [(s.name, s.attrs) for s in tracer.spans("t")
                 if s.name in names]


def sweep_attrs(run):
    """``(result, the attributes of the run's bfs.sweep spans)``."""
    out, spans = spans_of(run, "bfs.sweep")
    return out, [attrs for _name, attrs in spans]


def sweeps(run):
    """``(result, [(level, dir)] of the run's bfs.sweep spans)``."""
    out, attrs = sweep_attrs(run)
    return out, [(a["level"], a["dir"]) for a in attrs]


def run_kw(mode: str) -> dict:
    return {"mode": "bfs"} if mode == "bfs" \
        else {"mode": "hops", "start_level": 1, "max_levels": 4}


def light(g, adj, k: int) -> list:
    """The ``k`` vertices with an edge whose 2-hop frontier weighs the
    fewest chunks: their fused batches fit the layout's ladder at both
    levels of a 2-hop query, hubs or not."""
    degc = np.asarray(g["degc"])[:g["n"]].astype(np.int64)
    mass2 = (adj > 0).astype(np.int64) @ degc
    mass2[degc == 0] = np.iinfo(np.int64).max
    picked = np.argsort(mass2, kind="stable")[:k]
    assert int(mass2[picked].sum()) <= bh._td_caps(g)[-1]
    return [int(v) for v in picked]


def no_mask(g):
    """A per-level slot bitmap that masks no slot (byte = chunk column)."""
    import jax.numpy as jnp

    return jnp.zeros((g["q_total"],), jnp.uint8)


# -- same answers whichever way a level went ---------------------------------

@pytest.mark.parametrize("K", [1, 3, 16])
@pytest.mark.parametrize("mode", ["bfs", "hops"])
def test_push_bit_equal_to_pull(graph, mode, K):
    _snap, g, adj = graph
    n = g["n"]
    rng = np.random.default_rng(K)
    srcs = [int(v) for v in rng.choice(
        np.flatnonzero(np.diff(adj.indptr) > 0), K, replace=False)]
    kw = run_kw(mode)
    (dist, levels, done), dirs = sweeps(
        lambda: bh.frontier_bfs_batched(g, srcs, **kw))
    assert "td" in {d for _lv, d in dirs}           # the push engaged
    held = [dict(g, directed=True)]
    if mode == "hops":
        held.append(g)
    for layout in held:
        masks = None if layout is not g else [no_mask(g)] * 3
        (dist2, levels2, done2), dirs2 = sweeps(
            lambda: bh.frontier_bfs_batched(layout, srcs,
                                            level_masks=masks, **kw))
        assert {d for _lv, d in dirs2} == {"bu"}    # ... and was held off
        assert np.array_equal(dist, dist2)
        assert np.array_equal(levels, levels2)
        assert np.array_equal(done, done2)
    if mode == "hops":
        dist3 = dist
        # a run of depth h leaves the exact hop-h set (walk semantics)
        # at dist == h + 1: the non-zeros of e_v^T A^h
        reach = sp.identity(n, dtype=np.int8, format="csr")[srcs]
        for h in (1, 2, 3):
            reach = (reach @ adj).astype(bool).astype(np.int8)
            dist = dist3 if h == 3 else bh.frontier_bfs_batched(
                g, srcs, **dict(kw, max_levels=h + 1))[0]
            assert np.array_equal(dist == h + 1, reach.toarray() > 0)
    else:
        ref = sp.csgraph.shortest_path(adj, method="D", unweighted=True,
                                       indices=srcs)
        want = np.where(np.isinf(ref), bh.INF, ref).astype(np.int64)
        assert np.array_equal(dist.astype(np.int64), want)


def test_multi_start_rows_take_the_same_step(graph):
    """The lane's ``init_dist`` seeding: the frontier is read from dist,
    not from ``sources``."""
    _snap, g, adj = graph
    n = g["n"]
    init = np.zeros((2, n), np.int32)
    starts = [np.flatnonzero(np.diff(adj.indptr) > 0)[:3],
              np.flatnonzero(np.diff(adj.indptr) > 0)[5:6]]
    for k, vs in enumerate(starts):
        init[k, vs] = 1
    kw = dict(run_kw("hops"), init_dist=init, max_levels=2)
    (dist, _l, _c), dirs = sweeps(
        lambda: bh.frontier_bfs_batched(g, [0, 0], **kw))
    assert "td" in {d for _lv, d in dirs}
    dist2, _l, _c = bh.frontier_bfs_batched(dict(g, directed=True),
                                            [0, 0], **kw)
    assert np.array_equal(dist, dist2)
    seed = sp.csr_matrix(init.astype(np.int8))
    assert np.array_equal(dist == 2, (seed @ adj).toarray() > 0)


@pytest.mark.parametrize("mode", ["bfs", "hops"])
def test_rungs_below_n_columns_list_the_same_pairs(graph, mode,
                                                   monkeypatch):
    """Below n columns the step lists the frontier's distinct vertices
    first and their job memberships second; at n and above it compacts
    the [K, n] mask at once. Same pairs, same dist."""
    _snap, g, adj = graph
    n = g["n"]
    srcs = [int(v) for v in np.flatnonzero(np.diff(adj.indptr) > 0)[3:8]]
    srcs += srcs[:2]                    # two jobs share a start
    kw = run_kw(mode)
    want = bh.frontier_bfs_batched(dict(g, directed=True), srcs, **kw)
    top = bh._next_pow2(len(srcs) * g["q_total"])
    monkeypatch.setattr(bh, "_td_caps", lambda _g: (n // 8, n // 2, top))
    # every level pushes, the heavy ones too (a BFS level's rule weighs
    # its pull at one chunk round since ISSUE 50 and would pull them):
    # the listing's two forms are what is under test
    monkeypatch.setattr(bh, "TD_BU_COST", 0)
    got, attrs = sweep_attrs(
        lambda: bh.frontier_bfs_batched(g, srcs, **kw))
    caps = {a["p_cap"] for a in attrs if a["dir"] == "td"}
    assert min(caps) <= n // 2 and max(caps) > n    # both ways ran
    for a, b in zip(want, got):
        assert np.array_equal(a, b)


# -- which levels go which way ----------------------------------------------

def test_the_rule():
    """``_td_cap`` on its inputs alone: the rung follows the mass; a mass
    past the top rung, a mask, a directed or mesh-placed layout, flat
    ids past int32 and a dearer push all pull."""
    g = {"n": 1 << 20, "q_total": 4_563_401}      # the Kron cell's layout
    caps = bh._td_caps(g)
    assert caps == (1 << 12, 1 << 17, 1 << 18, 1 << 19, 1 << 20, 1 << 21)
    assert caps == bh._td_caps({"n": 1 << 20, "q_total": 4_650_000})
    big = 1 << 20                                   # candidates: n-wide
    assert bh._td_cap(g, 16, 0, big, False) == caps[0]
    assert bh._td_cap(g, 16, caps[0], big, False) == caps[0]
    assert bh._td_cap(g, 16, caps[0] + 1, big, False) == caps[1]
    # the Kron cell's ten starts of 150,732-193,702 columns and its one
    # of 892,911 (ISSUE 31): a rung a factor of two above, not the top
    assert bh._td_cap(g, 1, 150_732, big, False) == 1 << 18
    assert bh._td_cap(g, 1, 193_702, big, False) == 1 << 18
    assert bh._td_cap(g, 1, 892_911, big, False) == 1 << 20
    assert bh._td_cap(g, 16, caps[-1], big, False) == caps[-1]
    assert bh._td_cap(g, 16, caps[-1] + 1, big, False) is None
    assert bh._td_cap(g, 16, 100, big, True) is None
    assert bh._td_cap(dict(g, directed=True), 16, 100, big, False) is None
    assert bh._td_cap(dict(g, _mesh=object()), 16, 100, big,
                      False) is None
    assert bh._td_cap(g, 2048, 100, big, False) is None
    # (e): few candidates left, a heavy frontier — a BFS job's middle
    few = 1000
    edge = bh.BU_CHUNK_ROUNDS * few // bh.TD_BU_COST
    assert bh._td_cap(g, 16, edge, few, False) is not None
    assert bh._td_cap(g, 16, edge + 1, few, False) is None
    # a BFS level's pull runs its chunk rounds a dispatch each, and the
    # first decides nearly every candidate: one round to weigh, not
    # eight (ISSUE 50); a hop's level fuses all eight as ever
    assert bh._bu_fuse(False, 0) == 1
    assert bh._bu_fuse(True, 0) == bh.BU_CHUNK_ROUNDS
    edge1 = few // bh.TD_BU_COST
    assert bh._td_cap(g, 16, edge1, few, False, 1) is not None
    assert bh._td_cap(g, 16, edge1 + 1, few, False, 1) is None
    # the ladder follows the layout: the top rung is the largest power
    # of two at or below half its chunk columns
    assert bh._td_caps({"n": 64, "q_total": 300}) == (2, 8, 16, 32, 64, 128)
    assert bh._td_caps({"n": 1, "q_total": 1}) == (2,)
    assert bh._td_caps({"n": 1 << 26, "q_total": 290_000_000})[-1] == 1 << 27


@pytest.mark.parametrize("q_total", [
    1, 5, 40, 300, 2_400, 4_563_401, 4_650_000, 18_600_000, 290_000_000])
def test_the_ladder(q_total):
    """The ladder is a function of the layout's chunk columns alone. Its
    bottom, middle and top rungs stand where PR 26 put them (the top the
    largest power of two at or below half the columns, the others 2^9
    and 2^4 below it), so a level that ran on one of them runs the same
    executable; from the middle up the rungs are a factor of two apart,
    so no level past the middle pays for more than twice its mass; a toy
    layout's rungs collapse onto the floor of 2 without duplicates."""
    caps = bh._td_caps({"q_total": q_total})
    top = caps[-1]
    assert top & (top - 1) == 0
    assert top <= max(q_total // 2, 2) < 2 * top
    assert caps[0] == max(top >> 9, 2)
    assert max(top >> 4, 2) in caps
    assert list(caps) == sorted(set(caps))          # no rung twice
    upper = [c for c in caps if c >= top >> 4]
    assert upper == [max(top >> s, 2) for s in (4, 3, 2, 1, 0)][
        -len(upper):]
    assert all(b == 2 * a for a, b in zip(upper, upper[1:]))
    assert [c for c in caps if c < top >> 4] in ([], [caps[0]])


@pytest.mark.parametrize("K", [1, 4])
@pytest.mark.parametrize("mode", ["bfs", "hops"])
def test_a_level_gives_the_same_on_every_rung_that_holds_it(graph, mode,
                                                            K):
    """A rung is a capacity, not an algorithm: one level pushed on every
    rung that holds its pairs and their chunk mass leaves bit-equal
    ``dist`` and reads back the same ``pushed`` (pairs, columns)."""
    from titan_tpu.utils.jitcache import dev_scalar

    _snap, g, adj = graph
    n, caps = g["n"], bh._td_caps(g)
    assert len(caps) == 6
    expand = mode == "hops"
    srcs = np.asarray(light(g, adj, K), np.int32)
    start = 1 if expand else 0
    degc = np.asarray(g["_host"]["degc"])
    for level, lowest in ((start, 0), (start + 1, 1)):
        # the start level fits the bottom rung; the level after it is
        # made to begin at the middle by the lightest starts' mass
        dist, active, pj, pv, count = bh._batched_seed()(
            srcs, dev_scalar(start), n_=n, cap=caps[-1], expand=expand)
        if level > start:
            dist, *_ = bh._batched_td()(
                dist, pj, pv, count, active, dev_scalar(start),
                dev_scalar(0), g["dstT"], g["colstart"], g["degc"],
                p_cap=caps[-1], n_=n, expand=expand, lists=False)
            pj, pv, count = bh._batched_list()(
                dist, active, dev_scalar(level),
                dev_scalar(len(caps) - 1), g["degc"], caps=caps, n_=n)
        pairs = int(np.asarray(count))
        mass = int(degc[np.asarray(pv)[:pairs]].sum())
        holds = [c for c in caps if max(mass, pairs) <= c]
        assert len(holds) >= len(caps) - lowest - 1 >= 4
        before = np.asarray(dist)
        got = []
        for cap in holds:
            out = bh._batched_td()(
                dist + 0, pj, pv, count, active, dev_scalar(level),
                dev_scalar(0), g["dstT"], g["colstart"], g["degc"],
                p_cap=cap, n_=n, expand=expand,
                lists=bh._td_lists(cap, n))
            got.append((np.asarray(out[0]), np.asarray(out[4])[:2]))
        assert got[0][1].tolist() == [pairs, mass]
        assert not np.array_equal(got[0][0], before)    # it did push
        for d, pushed in got[1:]:
            assert np.array_equal(d, got[0][0])
            assert np.array_equal(pushed, got[0][1])


def test_a_mass_above_the_top_rung_pulls(graph, monkeypatch):
    _snap, g, adj = graph
    srcs = [int(v) for v in np.flatnonzero(np.diff(adj.indptr) > 0)[:3]]
    kw = dict(run_kw("hops"), max_levels=3)
    # one rung that holds whatever three frontiers weigh
    monkeypatch.setattr(bh, "_td_caps", lambda _g: (
        bh._next_pow2(3 * g["q_total"]),))
    want, dirs = sweeps(lambda: bh.frontier_bfs_batched(g, srcs, **kw))
    assert {d for _lv, d in dirs} == {"td"}
    # the ladder cut to one rung that holds L1 and not L2
    deg = np.diff(adj.indptr)
    l1 = int(sum(-(-deg[s] // 8) for s in srcs))
    monkeypatch.setattr(bh, "_td_caps", lambda _g: (bh._next_pow2(l1),))
    got, dirs = sweeps(lambda: bh.frontier_bfs_batched(g, srcs, **kw))
    assert dirs[0] == (1, "td")
    assert {d for lv, d in dirs if lv >= 2} == {"bu"}
    assert np.array_equal(want[0], got[0])


def test_a_masked_level_pulls_and_the_others_push(graph):
    _snap, g, adj = graph
    srcs = light(g, adj, 2)
    _out, dirs = sweeps(lambda: bh.frontier_bfs_batched(
        g, srcs, level_masks=[no_mask(g), None],
        **dict(run_kw("hops"), max_levels=3)))
    assert sorted(set(dirs)) == [(1, "bu"), (2, "td")]


def test_an_out_chain_and_a_mesh_placed_cohort_pull(graph):
    from titan_tpu.olap.serving.interactive.compile import \
        reversed_chunked_csr
    from titan_tpu.parallel.mesh import vertex_mesh
    from titan_tpu.parallel.partition import place_batched_csr

    snap, g, adj = graph
    srcs = [int(v) for v in np.flatnonzero(np.diff(adj.indptr) > 0)[:2]]
    rev = reversed_chunked_csr(snap)
    assert rev["directed"] is True
    placed = place_batched_csr(g, vertex_mesh(2))
    assert "_mesh" in placed
    want = bh.frontier_bfs_batched(g, srcs, **run_kw("hops"))[0]
    for layout in (rev, placed):
        (dist, _l, _c), dirs = sweeps(lambda: bh.frontier_bfs_batched(
            layout, srcs, **run_kw("hops")))
        assert {d for _lv, d in dirs} == {"bu"}
        assert np.array_equal(dist, want)   # the graph is symmetric


def test_levels_are_counted_by_direction(graph):
    _snap, g, adj = graph
    srcs = light(g, adj, 1)
    kw = dict(run_kw("hops"), max_levels=3)
    metrics = MetricManager()
    with devprof.DeviceCostProfiler(metrics=metrics):
        bh.frontier_bfs_batched(g, srcs, **kw)
        bh.frontier_bfs_batched(dict(g, directed=True), srcs, **kw)
    count = {(d, road): metrics.counter(
        "device.bfs.levels", labels={"dir": d, "list": road}).count
        for d, road in (("td", "carried"), ("td", "scan"), ("bu", "none"))}
    assert count == {("td", "carried"): 2, ("td", "scan"): 0,
                     ("bu", "none"): 2}


# -- a finite set of shapes, built before the first answer -------------------

def test_after_the_first_batch_no_size_and_no_rung_builds(graph):
    """One query makes the lane build every padded batch size and every
    rung for its snapshot; then bursts of every size up to ``max_fuse``
    and a step on each rung find their executables."""
    from titan_tpu.olap.serving.interactive import plan_from_wire
    from titan_tpu.olap.serving.scheduler import JobScheduler

    snap, g, adj = graph
    # a pushed level builds nothing; a pulled one's caps follow its
    # counts (ROADMAP S1), so: starts whose fused frontiers fit
    starts = light(g, adj, 16)
    prof = devprof.DeviceCostProfiler(metrics=MetricManager())
    sched = JobScheduler(snapshot=snap, autostart=False, profiler=prof,
                         interactive_window_s=0.05)
    prof.install()
    try:
        lane = sched.interactive()

        def ask(v, out):
            out.append(lane.submit(plan_from_wire(
                {"start": [int(snap.vertex_ids[v])], "dir": "both",
                 "hops": 2, "terminal": "count"})))

        first: list = []
        ask(starts[0], first)
        built = prof.compiles()
        # every rung at every padded size, and the query's own levels
        assert prof.kernel_stats()["batched_td"]["calls"] \
            >= 5 * len(bh._td_caps(g)) + 2
        # journaled as one ``build`` span that holds the dummy batches'
        # phases, apart from the query's own
        spans = sched.tracer.spans(first[0]["batch"])
        (build,) = [s for s in spans if s.name == "build"]
        assert build.attrs == {"n": g["n"], "q_total": g["q_total"],
                               "max_k": 16}
        inner = [s for s in spans if s.parent_id == build.span_id]
        assert [s.name for s in inner].count("extract") == 5
        assert [s.name for s in spans if s.parent_id == spans[0].span_id
                ].count("extract") == 1
        # keyed by the layout's shape: the next epoch's layout of the
        # same shape executes nothing
        calls = prof.kernel_stats()["batched_td"]["calls"]
        lane._build_shapes(dict(g))
        assert prof.kernel_stats()["batched_td"]["calls"] == calls
        fused = set()
        for size in (16, 5, 3, 2, 1):
            out: list = []
            threads = [threading.Thread(target=ask, args=(v, out))
                       for v in starts[:size]]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert len(out) == size
            fused |= {r["fused_k"] for r in out}
            two_hops = (adj[starts[:size]] @ adj).astype(bool).sum(axis=1)
            assert sorted(r["result"] for r in out) == sorted(
                int(c) for c in np.asarray(two_hops).ravel())
        assert max(fused) > 1                       # some did fuse
        for K in (1, 2, 4, 8, 16):
            bh.warm_batched_td(g, K, expand=True)   # every rung again
        assert prof.compiles() == built
    finally:
        prof.uninstall()
        sched.close()


@pytest.fixture(scope="module")
def served(graph):
    """A lane that has answered its first query on the module's graph,
    and so has built every padded batch size and every rung
    (``_build_shapes``)."""
    from titan_tpu.olap.serving.interactive import plan_from_wire
    from titan_tpu.olap.serving.scheduler import JobScheduler

    snap, g, adj = graph
    prof = devprof.DeviceCostProfiler(metrics=MetricManager())
    sched = JobScheduler(snapshot=snap, autostart=False, profiler=prof,
                         interactive_window_s=0.0)
    prof.install()
    try:
        lane = sched.interactive()

        def ask(vs):
            return lane.submit(plan_from_wire(
                {"start": [int(snap.vertex_ids[v]) for v in vs],
                 "dir": "both", "hops": 2, "terminal": "count"}))

        ask(light(g, adj, 1))
        yield sched, prof, ask
    finally:
        prof.uninstall()
        sched.close()


def starts_whose_second_level_weighs(g, adj, low: int, high: int) -> list:
    """Starts of one 2-hop query whose second level's chunk mass (the
    chunks of the starts' distinct neighbours) falls in ``(low, high]``:
    one start where the graph has such a vertex (the carried road, as
    the benchmark's queries), else the lightest vertices together until
    they weigh enough (a multi-start row: the scan road)."""
    degc = np.asarray(g["_host"]["degc"])[:g["n"]].astype(np.int64)
    reach = (adj > 0).astype(np.int64)
    mass2 = reach @ degc
    alone = np.flatnonzero((degc > 0) & (mass2 > low) & (mass2 <= high))
    if alone.size:
        return [int(alone[0])]
    order = [v for v in np.argsort(mass2, kind="stable") if degc[v] > 0]
    for k in range(2, len(order)):
        nbrs = np.asarray(reach[order[:k]].sum(axis=0)).ravel() > 0
        if low < int(degc[nbrs].sum()) <= high:
            return [int(v) for v in order[:k]]
    raise AssertionError(f"no starts weigh ({low}, {high}]")


@pytest.mark.parametrize("rung", [2, 3, 4])
def test_a_query_on_a_rung_between_middle_and_top(graph, served, rung):
    """The rungs ISSUE 31 brought, 2^1 to 2^3 above the middle one: a
    2-hop query whose second level lands there is pushed on that rung,
    not on the top one, answers what the plain reference counts, and
    finds its executables built."""
    _snap, g, adj = graph
    sched, prof, ask = served
    caps = bh._td_caps(g)
    assert len(caps) == 6
    vs = starts_whose_second_level_weighs(g, adj, caps[rung - 1],
                                          caps[rung])
    built = prof.compiles()
    resp = ask(vs)
    assert prof.compiles() == built
    level2 = [s.attrs for s in sched.tracer.spans(resp["batch"])
              if s.name == "bfs.sweep" and s.attrs["level"] == 2]
    assert [(a["dir"], a["p_cap"]) for a in level2] == [("td", caps[rung])]
    assert caps[rung - 1] < level2[0]["mass"] <= caps[rung]
    seed = sp.csr_matrix((np.ones(len(vs), np.int64),
                          (np.zeros(len(vs), np.int64), vs)),
                         shape=(1, g["n"]))
    hop = adj.astype(np.int64)
    want = ((seed @ hop).astype(bool).astype(np.int64) @ hop) \
        .astype(bool).sum()
    assert resp["result"] == int(want) > 0


# -- what one level's program hands the next (ISSUE 29) ---------------------

@pytest.fixture
def small_graph_lists(monkeypatch):
    """The hand-on rule is a measured cost ratio (``TD_DEDUP_COST``): on
    graphs of a thousand vertices only a rung of 2 columns would list.
    Here every rung does, so that the rungs these graphs use hand their
    lists on across several levels (and a list can outgrow its room)."""
    monkeypatch.setattr(bh, "_td_lists", lambda p_cap, n: True)


def test_the_hand_on_rule():
    """At the benchmark's scale the lowest rung hands on and the five
    above it do not (PERF.md 6, PR 29: rung 2^17 carried 54.5 ms against
    19.5 + a 6 ms listing)."""
    n = 1 << 20
    caps = bh._td_caps({"q_total": 4_563_401})
    assert [bh._td_lists(cap, n) for cap in caps] == [True] + [False] * 5
    assert bh._td_lists(1 << 14, n) and not bh._td_lists(1 << 15, n)


def scan_only(monkeypatch, g):
    """Hold the carried road off from outside: a layout with no host
    copy of its chunk counts (the seed then hands nothing on) and a push
    that is never asked for the next level's list. Every level plans and
    lists its frontier from ``dist``, the road from before the list."""
    from titan_tpu.utils.jitcache import dev_scalar

    real = bh._batched_td()

    def never_hands_on(dist, pj, pv, count, active, level, _want, *a, **kw):
        return real(dist, pj, pv, count, active, level, dev_scalar(0),
                    *a, **kw)

    monkeypatch.setattr(bh, "_batched_td", lambda: never_hands_on)
    return {k: v for k, v in g.items() if k != "_host"}


def same(a, b):
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def starts(adj, K, seed):
    rng = np.random.default_rng(seed)
    return [int(v) for v in rng.choice(
        np.flatnonzero(np.diff(adj.indptr) > 0), K, replace=False)]


@pytest.mark.parametrize("K", [1, 3, 16])
@pytest.mark.parametrize("mode", ["bfs", "hops"])
def test_carried_road_bit_equal_to_scan_and_pull(
        graph, mode, K, monkeypatch, small_graph_lists):
    _snap, g, adj = graph
    srcs = light(g, adj, K)
    kw = run_kw(mode)
    got, attrs = sweep_attrs(
        lambda: bh.frontier_bfs_batched(g, srcs, **kw))
    roads = [a["list"] for a in attrs if a["dir"] == "td"]
    # the seed is the L1 list, and L1's push hands L2 its own
    assert roads[:2] == ["carried", "carried"]
    pulled = bh.frontier_bfs_batched(dict(g, directed=True), srcs, **kw)
    same(got, pulled)
    bare = scan_only(monkeypatch, g)
    scanned, attrs = sweep_attrs(
        lambda: bh.frontier_bfs_batched(bare, srcs, **kw))
    roads = [a["list"] for a in attrs if a["dir"] == "td"]
    assert roads and set(roads) == {"scan"}
    same(got, scanned)


def test_mixed_depths_mask_without_a_replan(graph, monkeypatch,
                                            small_graph_lists):
    """The lane's batch: members of depths 1, 2 and 3 and two pad rows
    (depth 0) in one run. The keep mask retires rows at levels 1, 2 and
    3; their pairs stay in the list and are masked by the push; no
    level re-plans and none scans."""
    _snap, g, adj = graph
    depths = [3, 1, 2, 2, 1, 1] + [0] * 10
    srcs = light(g, adj, 6) + [0] * 10
    # one rung, so that the list L2 hands on (the pairs of three members)
    # fits the rung of L3 (the mass of one): a list past its rung is
    # test_a_list_past_its_room_is_scanned_for's
    top = bh._td_caps(g)[-1]
    monkeypatch.setattr(bh, "_td_caps", lambda _g: (top,))

    def on_level(level, _nf):
        keep = np.asarray([level <= d for d in depths])
        return None if keep.all() else keep

    kw = dict(run_kw("hops"), on_level=on_level)
    got, spans = spans_of(
        lambda: bh.frontier_bfs_batched(g, srcs, **kw),
        "bfs.plan", "bfs.sweep")
    plans = [a for name, a in spans if name == "bfs.plan"]
    assert [a["level"] for a in plans] == [1, 2, 3]
    assert all(a["carried"] and not a["replan"] for a in plans)
    assert [(a["dir"], a["list"]) for name, a in spans
            if name == "bfs.sweep"] == [("td", "carried")] * 3
    same(got, bh.frontier_bfs_batched(dict(g, directed=True), srcs, **kw))
    same(got, bh.frontier_bfs_batched(scan_only(monkeypatch, g), srcs,
                                      **kw))
    dist = got[0]
    reach = sp.identity(g["n"], dtype=np.int8, format="csr")[srcs]
    for h in (1, 2, 3):
        reach = (reach @ adj).astype(bool).astype(np.int8)
        for k, d in enumerate(depths):
            if d == h:
                assert np.array_equal(dist[k] == h + 1,
                                      reach[k].toarray().ravel() > 0)


def test_a_multi_start_row_scans_once_then_carries(graph,
                                                   small_graph_lists):
    _snap, g, adj = graph
    n = g["n"]
    init = np.zeros((2, n), np.int32)
    live = light(g, adj, 4)
    init[0, live[:3]] = 1
    init[1, live[3:]] = 1
    kw = dict(run_kw("hops"), init_dist=init)
    got, attrs = sweep_attrs(
        lambda: bh.frontier_bfs_batched(g, [0, 0], **kw))
    assert [(a["level"], a.get("list")) for a in attrs][:2] == [
        (1, "scan"), (2, "carried")]
    same(got, bh.frontier_bfs_batched(dict(g, directed=True), [0, 0],
                                      **kw))


def test_a_resumed_run_and_a_checkpointed_one(graph, small_graph_lists):
    """``checkpoint`` sees a complete state at every level whichever
    road the levels took (``dist`` is the truth, the list a cache of
    it), and a run resumed from any boundary ends bit-equal."""
    _snap, g, adj = graph
    srcs = starts(adj, 3, 11)
    seen, pulled_seen = [], []

    def keeper(into):
        return lambda level, dist, active: into.append(
            (level, np.asarray(dist), active))

    whole = bh.frontier_bfs_batched(g, srcs, checkpoint=keeper(seen))
    pulled = bh.frontier_bfs_batched(dict(g, directed=True), srcs,
                                     checkpoint=keeper(pulled_seen))
    same(whole, pulled)
    assert len(seen) == len(pulled_seen) >= 3
    for (lv, dist, act), (lv2, dist2, act2) in zip(seen, pulled_seen):
        assert lv == lv2 and np.array_equal(act, act2)
        assert np.array_equal(dist, dist2)
    for lv, dist, _act in seen[1:3]:
        resumed, attrs = sweep_attrs(lambda: bh.frontier_bfs_batched(
            g, srcs, init_dist=dist[:, :g["n"]], start_level=lv))
        same(whole, resumed)
        roads = [a["list"] for a in attrs if a["dir"] == "td"]
        assert not roads or roads[0] == "scan"


@pytest.mark.parametrize("K", [1, 3, 16])
@pytest.mark.parametrize("mode", ["bfs", "hops"])
def test_what_a_push_hands_on_is_the_next_frontier_and_plan(
        graph, mode, K, monkeypatch, small_graph_lists):
    """After every push that was asked for it: the list holds each
    (job, vertex) of the next frontier exactly once (lanes that reached
    the same vertex raced on the claim array and one won), and the
    statistics beside it are what ``bplan`` reads from the state."""
    from titan_tpu.utils.jitcache import dev_scalar

    _snap, g, adj = graph
    n = g["n"]
    real, checked = bh._batched_td(), []

    def spy(dist, pj, pv, count, active, level, want, *a, **kw):
        out = real(dist, pj, pv, count, active, level, want, *a, **kw)
        dist, nj, nv, ncount, stats = out
        stats = np.asarray(stats)
        if stats[2] < 0:        # not asked, or dearer than looking
            return out
        nxt = int(np.asarray(level)) + 1
        _fbits, _cand, plan = bh._batched_plan()(
            dist, active, dev_scalar(nxt), g["degc"],
            c_cap=bh._next_pow2(n), n_=n, expand=kw["expand"])
        assert np.array_equal(stats[3:], np.asarray(plan))
        assert stats[2] == int(np.asarray(ncount)) == stats[4:4 + K].sum()
        front = (np.asarray(dist)[:, :n] == nxt) \
            & np.asarray(active)[:, None]
        pairs = sorted(zip(np.asarray(nj)[:stats[2]].tolist(),
                           np.asarray(nv)[:stats[2]].tolist()))
        want_pairs = sorted(zip(*(x.tolist() for x in np.nonzero(front))))
        if stats[2] <= len(nj):
            assert pairs == want_pairs
        else:       # past its capacity the list is cut (the loop scans)
            assert len(set(pairs)) == len(nj) \
                and set(pairs) <= set(want_pairs)
        checked.append(nxt)
        return out

    monkeypatch.setattr(bh, "_batched_td", lambda: spy)
    bh.frontier_bfs_batched(g, light(g, adj, K), **run_kw(mode))
    assert len(checked) >= 2


@pytest.mark.parametrize("fits", ["neither", "the-ladder-not-the-rung"])
def test_a_list_past_its_room_is_scanned_for(graph, fits, monkeypatch,
                                             small_graph_lists):
    """Sixteen members, fifteen of depth 1: the list L1 hands on holds
    every member's neighbours, L2's frontier is one member's. Past the
    list's capacity (the ladder's top) the push says so and hands on
    the statistics alone; past L2's own rung the list is in hand and
    not used. Either way L2 lists from dist, with no plan."""
    _snap, g, adj = graph
    srcs = light(g, adj, 16)
    depths = [2] + [1] * 15
    deg = np.diff(adj.indptr)
    degc = -(-deg // 8)
    l1 = int(degc[srcs].sum())
    l2 = int(degc[adj[srcs[0]].indices].sum())
    handed = int(sum(deg[s] for s in srcs))      # no duplicate edges?
    rung2 = bh._next_pow2(l2)
    top = bh._next_pow2(max(l1, l2))
    if fits == "the-ladder-not-the-rung":
        top *= bh._next_pow2(handed)
    monkeypatch.setattr(bh, "_td_caps", lambda _g: tuple(sorted(
        {rung2, top})))

    def on_level(level, _nf):
        keep = np.asarray([level <= d for d in depths])
        return None if keep.all() else keep

    kw = dict(run_kw("hops"), max_levels=3, on_level=on_level)
    got, spans = spans_of(
        lambda: bh.frontier_bfs_batched(g, srcs, **kw),
        "bfs.plan", "bfs.sweep")
    sweeps_ = [a for name, a in spans if name == "bfs.sweep"]
    assert [(a["level"], a["dir"], a["list"]) for a in sweeps_] == [
        (1, "td", "carried"), (2, "td", "scan")]
    assert sweeps_[0]["handed"] > sweeps_[1]["p_cap"]
    assert (sweeps_[0]["handed"] > top) == (fits == "neither")
    assert [a["carried"] for name, a in spans if name == "bfs.plan"] \
        == [True, True]
    same(got, bh.frontier_bfs_batched(dict(g, directed=True), srcs, **kw))


def test_a_single_start_two_hop_is_four_programs_three_readbacks(graph):
    from titan_tpu.olap.serving.scheduler import JobScheduler

    class Xfers:
        def __init__(self):
            self.d2h = []

        def record(self, kind, **kw):
            if kind == "xfer" and kw["dir"] == "d2h":
                self.d2h.append(kw["site"])

    snap, g, adj = graph
    (src,) = light(g, adj, 1)
    sched = JobScheduler(snapshot=snap, autostart=False)
    try:
        lane = sched.interactive()
        lane._hops(g, [[src]], [2])                 # builds
        seen = Xfers()
        with devprof.DeviceCostProfiler(metrics=MetricManager(),
                                        recorder=seen) as prof:
            _masks, sizes = lane._hops(g, [[src]], [2])
        assert {k: v["calls"] for k, v in prof.kernel_stats().items()} \
            == {"batched_seed": 1, "batched_td": 2, "batched_extract": 1}
        assert seen.d2h == ["bfs.stats", "bfs.stats", "interactive.sizes"]
        assert int(sizes[0]) == (adj[[src]] @ adj).astype(bool).sum()
    finally:
        sched.close()
