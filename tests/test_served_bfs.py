"""BFS (ISSUE 49, ISSUE 50): GAP's BFS kernel (Graph500's kernel 2) as a
served job, on the CPU, with the answer GAP asks for: the parent array.
The served path (``POST /jobs`` with ``source`` and ``"parents": true``
-> ``Batcher.run_bfs_batch`` -> the batched level loop -> the result
plane) against the benchmark's plain reference
(``benchmark/reference/bfs.py``: a serial level-synchronous BFS in numpy
and GAP's verifier's rule, nothing of ``titan_tpu`` in it): on LDBC's
graph500 generator at scale 12 from sixteen sources of distinct
trajectories (a source in a component of two, a source of degree 1, the
hub, the spread of degrees between), alone (K = 1) and fused (K = 8), on
runs that only push, that pull wherever they can, and mixed; with a
member vetoed, a member cancelled, a job resumed from a checkpoint,
under a live overlay and over a mesh. Then what the plane must NOT
change: a depth-only job's executables, what fuses with what, what the
ledger is charged. And what ISSUE 49 added to the run: the level loop's
phases and its programs' ``kernel`` spans under the job's ``run`` span,
the build-ahead in the first job of a layout and nothing built by the
jobs behind it, the counters.
"""

import sys
import threading

import numpy as np
import pytest

from test_served_lcc import Served, both_ways
from test_served_wcc import BENCH, _by_file, graph500
from titan_tpu.models import bfs_hybrid as bh
from titan_tpu.obs import devprof
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving import batcher

CLEAN = {"source": 0, "reached": 0, "depth": 0, "edge": 0}


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator, structure and reference, by file. The
    generator finds its sibling through the harness's ``files`` module,
    and the reference its own (``reference.csr``), both importable only
    while this fixture holds the path."""
    sys.path.insert(0, BENCH)
    try:
        yield {"graph500_simple": _by_file("graphs", "graph500_simple"),
               "csr": _by_file("reference", "csr"),
               "bfs": _by_file("reference", "bfs")}
    finally:
        sys.path.remove(BENCH)
        for name in ("files", "reference", "reference.csr"):
            sys.modules.pop(name, None)


@pytest.fixture(scope="module")
def kron(bench):
    """(n, src, dst, the reference over sixteen sources of distinct
    trajectories, those sources): graph500 at scale 12."""
    n, src, dst = graph500(bench, 12, 3)
    indptr, indices = bench["csr"].structure(n, src, dst)
    ref = bench["bfs"]
    degree = np.diff(indptr)
    whole = ref.depths(indptr, indices, int(np.argmax(degree)))
    apart = np.flatnonzero(whole == ref.UNREACHED)
    assert apart.size                   # a component beside the giant
    leaves = np.flatnonzero((degree == 1) & (whole < ref.UNREACHED))
    order = np.argsort(degree, kind="stable")
    spread = order[np.linspace(0, n - 1, 24).astype(int)]
    sources = list(dict.fromkeys(
        [int(apart[0]), int(leaves[0]), int(np.argmax(degree))]
        + [int(v) for v in spread]))[:16]
    assert len(sources) == 16
    reference = ref.prepare(n, indptr, indices, {"source": sources}, {})
    return n, src, dst, reference, sources


@pytest.fixture(scope="module")
def served_kron(kron):
    n, src, dst, _ref, _sources = kron
    served = Served(n, src, dst)
    yield served
    served.close()


def cohort(served, bodies) -> list:
    """The bodies' final envelopes, the jobs queued together behind a
    gate job that holds the one worker: what can fuse does."""
    gate = threading.Event()
    hold = served.sched.submit(JobSpec(
        kind="callable", params={"fn": lambda: gate.wait(120)}))
    try:
        ids = [served.post(body) for body in bodies]
    finally:
        gate.set()
    assert hold.wait(120)
    for job_id in ids:
        assert served.sched.get(job_id).wait(180)
    return [served.sched.get(job_id).to_wire() for job_id in ids]


def sweeps_of(served, job_id) -> set:
    """The directions the levels of a job's run took."""
    devprof.drain()
    return {s.attrs["dir"] for s in served.sched.tracer.spans(job_id)
            if s.name == "bfs.sweep"}


@pytest.fixture(params=["mixed", "pull", "push"])
def road(request, monkeypatch):
    """The direction rule as it stands, or bent one way: every level
    that can pull does (a pushed column costs more than any pull), or
    every level pushes (a push costs nothing, on one rung that holds
    whatever a cohort's frontiers weigh)."""
    if request.param == "pull":
        monkeypatch.setattr(bh, "TD_BU_COST", 1 << 30)
    elif request.param == "push":
        monkeypatch.setattr(bh, "TD_BU_COST", 0)
        monkeypatch.setattr(bh, "_td_caps", lambda g: (
            bh._next_pow2(16 * int(g["q_total"])),))
    return request.param


# -- the reference itself -----------------------------------------------------

def test_the_reference_on_shapes_worked_by_hand(bench):
    ref, csr = bench["bfs"], bench["csr"]
    # a path of 6, a triangle apart from it, a vertex with no edge
    n, src, dst = both_ways(10, [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5),
                                 (6, 7), (7, 8), (8, 6)])
    indptr, indices = csr.structure(n, src, dst)
    U = ref.UNREACHED
    depth = ref.depths(indptr, indices, 2)
    assert depth.tolist() == [2, 1, 0, 1, 2, 3, U, U, U, U]
    assert ref.reached(depth) == 6 and ref.levels(depth) == 4
    # on a path the tree is the path
    assert ref.tree(indptr, indices, depth, 2).tolist() \
        == [1, 2, 2, 2, 3, 4, -1, -1, -1, -1]
    depth = ref.depths(indptr, indices, 7)
    assert depth.tolist() == [U] * 6 + [1, 0, 1, U]
    assert ref.reached(depth) == 3 and ref.levels(depth) == 2
    assert ref.tree(indptr, indices, depth, 7).tolist() \
        == [-1] * 6 + [7, 7, 7, -1]
    depth = ref.depths(indptr, indices, 9)          # no edge at all
    assert ref.reached(depth) == 1 and ref.levels(depth) == 1
    assert ref.tree(indptr, indices, depth, 9).tolist() == [-1] * 9 + [9]
    # a frontier read a tile at a time is the frontier read whole, and a
    # tree made a tile of rows at a time is a tree
    whole = ref.depths(indptr, indices, 0)
    tile, ref.TILE = ref.TILE, 2
    try:
        assert ref.depths(indptr, indices, 0).tolist() == whole.tolist()
        assert ref.broken(ref.transposed(indptr, indices), whole, 0,
                          ref.tree(indptr, indices, whole, 0)) == CLEAN
    finally:
        ref.TILE = tile
    # has_edge: every edge is one, no other pair is
    u = np.repeat(np.arange(n), n)
    v = np.tile(np.arange(n), n)
    dense = np.zeros((n, n), bool)
    dense[src, dst] = True
    assert ref.has_edge(ref.transposed(indptr, indices), u, v).tolist() \
        == dense.ravel().tolist()


def test_the_reference_against_scipy(bench, kron):
    import scipy.sparse as sp
    from scipy.sparse.csgraph import shortest_path

    n, src, dst, reference, sources = kron
    adj = sp.csr_matrix((np.ones(len(src)), (src, dst)), shape=(n, n))
    far = shortest_path(adj, unweighted=True, indices=sources)
    want = np.where(np.isinf(far), bench["bfs"].UNREACHED, far) \
        .astype(np.int32)
    for s, row in zip(sources, want):
        assert reference.depth[s].tolist() == row.tolist()
    # sixteen trajectories: no two sources share their level sizes
    sizes = {tuple(np.bincount(row[row < bench["bfs"].UNREACHED]))
             for row in want}
    assert len(sizes) >= 12
    # the job's work as the roofline counts it: the slots out of what
    # the median source reaches, at most the graph's
    degree = np.bincount(src, minlength=n)
    reach = sorted(int(degree[row < bench["bfs"].UNREACHED].sum())
                   for row in want)
    assert reference.edges == reach[len(reach) // 2] <= len(src)


#: one way to break GAP's rule each, on the hub's tree: (what is done to
#: a valid parent array, the counts that must read it)
FAULTS = {
    "the source under another": ("source", {"source": 1}),
    "a reached vertex let go": ("orphan", {"reached": 1, "depth": 1,
                                           "edge": 1}),
    "a parent for the unreached": ("ghost", {"reached": 1}),
    "a parent on the vertex's own level": ("level", {"depth": 1}),
    "a parent one level up that is no neighbour": ("stranger",
                                                   {"edge": 1}),
    "an id that is no vertex": ("range", {"depth": 1, "edge": 1}),
}


@pytest.mark.parametrize("name", sorted(FAULTS))
def test_what_check_counts(bench, kron, name):
    n, _src, _dst, reference, sources = kron
    ref = bench["bfs"]
    s = sources[2]                                  # the hub
    body = {"kind": "bfs", "source": s, "parents": True}
    answer = reference.answer(body)
    depth, parent = answer["depth"], answer["result"].copy()
    assert reference.check(body, parent) == CLEAN
    far = int(np.flatnonzero(depth == 2)[0])
    kind, want = FAULTS[name]
    if kind == "source":
        parent[s] = int(np.flatnonzero(depth == 1)[0])
    elif kind == "orphan":
        parent[far] = -1
    elif kind == "ghost":
        parent[int(np.flatnonzero(depth == ref.UNREACHED)[0])] = s
    elif kind == "level":
        # a vertex with a neighbour on its own level takes it as parent
        rows = np.repeat(np.arange(n), np.diff(reference.indptr))
        flat = (depth[rows] == depth[reference.indices]) \
            & (depth[rows] < ref.UNREACHED) & (depth[rows] > 0)
        at = int(np.flatnonzero(flat)[0])
        parent[rows[at]] = int(reference.indices[at])
    elif kind == "stranger":
        row = set(reference.indices[reference.indptr[far]:
                                    reference.indptr[far + 1]].tolist())
        parent[far] = next(int(v) for v in np.flatnonzero(depth == 1)
                           if int(v) not in row)
    else:
        parent[far] = n + 7
    assert reference.check(body, parent) == {**CLEAN, **want}


def test_what_check_answers_for(bench, kron):
    n, _src, _dst, reference, sources = kron
    body = {"kind": "bfs", "source": sources[3]}
    want = reference.answer(body)["result"]
    assert reference.check(body, want[:-1]) \
        == dict.fromkeys(bench["bfs"].COMPARED, n)
    # the tree of another source is not this one's
    other = reference.answer({"source": sources[4]})["result"]
    assert sum(reference.check(body, other).values()) > 0
    # `source_dense` names the same vertex; a source no pool holds is
    # worked when it is asked for
    assert reference.check({"source_dense": sources[3]}, want) == CLEAN
    fresh = next(v for v in range(n) if v not in reference.depth)
    assert reference.answer({"source": fresh})["result"][fresh] == fresh
    assert bench["bfs"].COMPARED == ("source", "reached", "depth", "edge")


# -- the served path ----------------------------------------------------------

@pytest.mark.parametrize("i", range(16))
def test_a_served_job_equals_the_reference(bench, kron, served_kron, i):
    n, _src, _dst, reference, sources = kron
    ref = bench["bfs"]
    body = {"kind": "bfs", "source": sources[i], "parents": True,
            "timeout_s": 60}
    env = served_kron.job(body)
    assert env["status"] == "done", env
    dist = served_kron.array(env["job"], "dist")
    parent = served_kron.array(env["job"], "parent")
    held = served_kron.sched.get(env["job"]).result
    assert dist.tobytes() == held["dist"].tobytes()
    assert parent.tobytes() == held["parent"].tobytes()
    assert env["arrays"] == {
        "dist": {"dtype": "int32", "shape": [n]},
        "parent": {"dtype": "int32", "shape": [n]}}
    assert reference.check(body, parent) == CLEAN
    # the depths are the serial BFS's, exactly; the envelope's integers
    # are the reference's
    want = reference.answer(body)["depth"]
    assert dist.tolist() == want.tolist()
    assert env["result"] == {"levels": ref.levels(want),
                             "reached": ref.reached(want), "n": n}
    assert int(parent[sources[i]]) == sources[i]
    assert (parent[want == ref.UNREACHED] == -1).all()
    if i == 0:                          # the component of two
        assert env["result"]["reached"] < 10
        assert sorted(np.flatnonzero(parent >= 0).tolist()) \
            == sorted(np.flatnonzero(want < ref.UNREACHED).tolist())


@pytest.mark.parametrize("k", [1, 8])
def test_every_road_gives_a_valid_tree(kron, road, k):
    """K = 1 and a fused K = 8 (the component of two among them), on
    runs that only push, that pull wherever one can, and as the rule
    stands: every member's parents keep GAP's rule and its depths are
    the serial BFS's."""
    n, src, dst, reference, sources = kron
    picked = sources[:k] if k > 1 else [sources[5]]
    bodies = [{"kind": "bfs", "source": s, "parents": True,
               "timeout_s": 120} for s in picked]
    served = Served(n, src, dst)
    try:
        envs = cohort(served, bodies)
        assert [e["status"] for e in envs] == ["done"] * k, envs
        assert {e["batch_k"] for e in envs} == {k}
        dirs = sweeps_of(served, envs[0]["job"])
        for body, env in zip(bodies, envs):
            got = served.sched.get(env["job"]).result
            assert reference.check(body, got["parent"]) == CLEAN
            assert got["dist"].tolist() \
                == reference.answer(body)["depth"].tolist()
    finally:
        served.close()
    if road == "push":
        assert dirs == {"td"}
    elif road == "pull":
        assert "bu" in dirs
    else:
        assert dirs == {"td", "bu"}


def test_one_altered_parent_reads_one_mismatch(kron, monkeypatch):
    """One parent moved to a vertex of its own level, where the job's
    answer is made: the reference's check, as the load generator applies
    it to the served array, reads 1."""
    n, src, dst, reference, sources = kron
    body = {"kind": "bfs", "source": sources[2], "parents": True}
    depth = reference.answer(body)["depth"]
    at = int(np.flatnonzero(depth == 2)[0])
    real = batcher._bfs_result

    def altered(snap, dist_row, levels, inf, params, parent_row=None):
        parent_row = parent_row.copy()
        parent_row[at] = at
        return real(snap, dist_row, levels, inf, params, parent_row)
    monkeypatch.setattr(batcher, "_bfs_result", altered)
    served = Served(n, src, dst)
    try:
        env = served.job(body)
        assert env["status"] == "done", env
        parent = served.array(env["job"], "parent")
    finally:
        served.close()
    assert reference.check(body, parent) == {**CLEAN, "depth": 1, "edge": 1}


def test_a_bad_source_or_a_bad_flag_fails_for_good(served_kron, kron):
    import urllib.error

    n, sources = kron[0], kron[4]
    for body in ({"kind": "bfs"}, {"kind": "bfs", "source": n + 5},
                 {"kind": "bfs", "source": "x", "parents": True}):
        env = served_kron.job(body)
        assert env["status"] == "failed" and env["attempt"] == 1, env
    # a flag that is no boolean is refused at submit, with a sentence
    for junk in ("yes", 1, None, [True]):
        with pytest.raises(urllib.error.HTTPError) as e:
            served_kron.post({"kind": "bfs", "source": sources[1],
                              "parents": junk})
        assert e.value.code == 400
        assert "'parents' must be true or false" in e.value.read().decode()
    with pytest.raises(ValueError, match="'parents' must be true or"):
        served_kron.sched.submit(JobSpec(
            kind="bfs", params={"source": sources[1], "parents": "no"}))


# -- vetoes, cancellation, resume ---------------------------------------------

def test_a_vetoed_and_a_cancelled_member_leave_the_rest_whole(kron):
    """A cohort of eight: one member times out at the first level
    boundary (the keep mask drops its row mid-run), one is cancelled
    while queued (it never joins). The six others' trees are whole."""
    n, src, dst, reference, sources = kron
    bodies = [{"kind": "bfs", "source": s, "parents": True,
               "timeout_s": 120} for s in sources[:8]]
    bodies[3]["timeout_s"] = 0.0
    served = Served(n, src, dst)
    try:
        gate = threading.Event()
        hold = served.sched.submit(JobSpec(
            kind="callable", params={"fn": lambda: gate.wait(120)}))
        ids = [served.post(body) for body in bodies]
        assert served.sched.cancel(ids[6])
        gate.set()
        assert hold.wait(120)
        jobs = [served.sched.get(i) for i in ids]
        for job in jobs:
            assert job.wait(180)
        states = [job.state.value for job in jobs]
        assert states[3] == "timeout" and states[6] == "cancelled", states
        assert jobs[3].result is None and jobs[6].result is None
        for i, (body, job) in enumerate(zip(bodies, jobs)):
            if i in (3, 6):
                continue
            assert job.state.value == "done" and job.batch_k == 7
            assert reference.check(body, job.result["parent"]) == CLEAN
    finally:
        served.close()


def test_a_resumed_job_keeps_its_tree(kron, tmp_path):
    """Crashed at level 2 and resumed from its checkpoint: the
    checkpoint holds both planes, and the resumed run's tree keeps the
    rule (the levels before the crash are the first attempt's)."""
    from titan_tpu.olap.recovery import FaultPlan
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.utils.metrics import MetricManager

    n, src, dst, reference, sources = kron
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics,
                         checkpoint_dir=str(tmp_path / "ckpt"))
    try:
        job = sched.submit(JobSpec(
            kind="bfs",
            params={"source_dense": sources[4], "parents": True,
                    "faults": FaultPlan(crash_at_round=2)},
            max_retries=1, checkpoint_every=1, retry_backoff_s=0.01))
        assert job.wait(180)
        assert job.state.value == "done", job.error
        assert job.attempt == 2 and job.checkpoint_round is not None
        ck = job.recovery.latest(kind="bfs", epoch=job.ran_epoch["epoch"])
        assert sorted(ck.arrays) == ["dist", "parent"]
    finally:
        sched.close()
    assert metrics.counter_value("serving.recovery.resumes") == 1
    body = {"source": sources[4]}
    assert reference.check(body, job.result["parent"]) == CLEAN
    assert job.result["dist"].tolist() \
        == reference.answer(body)["depth"].tolist()


def test_the_loop_resumes_from_both_planes_or_refuses(kron):
    from titan_tpu.olap.tpu import snapshot as snap_mod

    n, src, dst, reference, sources = kron
    snap = snap_mod.from_arrays(n, src, dst)
    s = sources[7]
    caps = {}

    def ck(level, state, act):
        dist, par = state
        caps[level] = (np.asarray(dist[:, :n]).copy(),
                       np.asarray(par[:, :n]).copy())

    (ref_d, _ref_p), levels, _ = bh.frontier_bfs_batched(
        snap, [s], checkpoint=ck, parents=True)
    ks = sorted(caps)
    assert len(ks) >= 3
    for k in (ks[1], ks[-1]):       # an early and the last boundary
        (d2, p2), lv2, c2 = bh.frontier_bfs_batched(
            snap, [s], init_dist=caps[k][0], init_parent=caps[k][1],
            start_level=k, parents=True)
        assert c2.all() and (d2 == ref_d).all() and (lv2 == levels).all()
        assert reference.check({"source": s}, p2[0]) == CLEAN
    # depths alone do not restore a run that wants its tree
    with pytest.raises(ValueError, match="init_dist AND init_parent"):
        bh.frontier_bfs_batched(snap, [s], init_dist=caps[ks[1]][0],
                                start_level=ks[1], parents=True)
    # and a hop set has no tree
    with pytest.raises(ValueError, match="parents needs mode='bfs'"):
        bh.frontier_bfs_batched(snap, [s], mode="hops", start_level=1,
                                parents=True)


# -- a live overlay, a mesh ---------------------------------------------------

@pytest.mark.parametrize("round_", [0, 1])
def test_the_tree_under_a_live_overlay(bench, round_):
    """Edges added and edges tombstoned over a resident base image: the
    tree is a tree of the graph as it now stands (an added edge may be a
    parent's, a tombstoned one never)."""
    import test_live_overlay as live

    ref, csr = bench["bfs"], bench["csr"]
    rng = np.random.default_rng(live.SEED + round_)
    base, view, rebuilt = live._apply_stream(rng, *live._base_edges(rng),
                                             n_add=60, n_rm=40)
    sources = [int(x) for x in rng.choice(live.N, 4, replace=False)]
    (dist, par), _lv, done = bh.frontier_bfs_batched(
        base, sources, overlay=view, parents=True)
    assert done.all()
    indptr, indices = csr.structure(live.N, rebuilt.src, rebuilt.dst)
    into = ref.transposed(indptr, indices)
    for k, s in enumerate(sources):
        depth = ref.depths(indptr, indices, s)
        assert dist[k].tolist() == depth.tolist()
        assert ref.broken(into, depth, s, par[k]) == CLEAN


def test_the_tree_over_an_overlay_only_chain(bench):
    """Vertices with no base edge, reached through overlay edges alone:
    each names the overlay edge's other end as its parent."""
    import test_live_overlay as live
    from titan_tpu.olap.live.overlay import DeltaOverlay

    N = live.N
    rng = np.random.default_rng(live.SEED)
    src = rng.integers(0, N - 3, live.M).astype(np.int32)
    dst = rng.integers(0, N - 3, live.M).astype(np.int32)
    base = live._sym_snapshot(src, dst)
    ov = DeltaOverlay(base, min_cap=live.CAP)
    a_s = np.asarray([0, N - 3, N - 2], np.int32)
    a_d = np.asarray([N - 3, N - 2, N - 1], np.int32)
    ov.append_edges(np.concatenate([a_s, a_d]),
                    np.concatenate([a_d, a_s]), np.zeros(6, np.int32))
    (dist, par), _lv, _done = bh.frontier_bfs_batched(
        base, [0], overlay=ov.view(), parents=True)
    assert par[0, N - 3:].tolist() == [0, N - 3, N - 2]
    assert dist[0, N - 3:].tolist() == [1, 2, 3]


def test_the_tree_over_a_mesh_and_its_share_on_the_ledger(bench):
    """A cohort of four placed over eight devices: the trees keep the
    rule, and the ledger is charged a device's share of the forward
    image and of the parent plane, released behind the run."""
    from titan_tpu.olap.serving.hbm import (bfs_plane_bytes,
                                            meshed_snapshot_csr_bytes)
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.parallel.mesh import vertex_mesh

    ref, csr = bench["bfs"], bench["csr"]
    n = 256
    rng = np.random.default_rng(11)
    a = rng.integers(0, n, 1200).astype(np.int32)
    b = rng.integers(0, n, 1200).astype(np.int32)
    src, dst = np.concatenate([a, b]), np.concatenate([b, a])
    snap = snap_mod.from_arrays(n, src, dst)
    indptr, indices = csr.structure(n, src, dst)
    sched = JobScheduler(snapshot=snap, mesh=vertex_mesh(8))
    try:
        sources = [0, 5, 9, 11]
        gate = threading.Event()
        hold = sched.submit(JobSpec(kind="callable",
                                    params={"fn": lambda: gate.wait(120)}))
        jobs = [sched.submit(JobSpec(
            kind="bfs", params={"source_dense": s, "parents": True}))
            for s in sources]
        gate.set()
        assert hold.wait(120)
        for j in jobs:
            assert j.wait(180), "mesh cohort did not finish"
        assert all(j.state.value == "done" for j in jobs)
        assert {j.batch_k for j in jobs} == {4}
        for j, s in zip(jobs, sources):
            depth = ref.depths(indptr, indices, s)
            assert j.result["dist"].tolist() == depth.tolist()
            assert ref.broken(ref.transposed(indptr, indices), depth, s,
                              j.result["parent"]) == CLEAN
        admit = next(s for s in sched.tracer.spans(jobs[0].id)
                     if s.name == "job.admit")
        plane = bfs_plane_bytes(n, 4, 8)
        assert plane == -(-4 * 4 * (n + 1) // 8)
        assert admit.attrs["bytes"] \
            == meshed_snapshot_csr_bytes(snap, 8) + plane
        # the working set left with the run; the image stays resident
        assert sched.ledger.resident_bytes() \
            == meshed_snapshot_csr_bytes(snap, 8)
    finally:
        sched.close()


# -- what the plane must not change -------------------------------------------

def test_who_fuses_with_whom_and_what_the_ledger_is_charged(kron):
    """Four jobs that want their trees and four that do not, queued
    together: two cohorts of four, never one of eight; the ledger holds
    the parent plane for the first alone."""
    from titan_tpu.olap.serving.hbm import (bfs_plane_bytes,
                                            snapshot_csr_bytes)
    from titan_tpu.olap.serving.kinds import KINDS, batch_key

    n, src, dst, reference, sources = kron
    bodies = [{"kind": "bfs", "source": s, "timeout_s": 120,
               **({"parents": True} if i % 2 else {})}
              for i, s in enumerate(sources[:8])]
    served = Served(n, src, dst)
    try:
        envs = cohort(served, bodies)
        assert [e["status"] for e in envs] == ["done"] * 8, envs
        assert {e["batch_k"] for e in envs} == {4}
        image = snapshot_csr_bytes(served.snap)
        for i, (body, env) in enumerate(zip(bodies, envs)):
            got = served.sched.get(env["job"]).result
            assert ("parent" in got) == bool(i % 2)
            assert got["dist"].tolist() \
                == reference.answer(body)["depth"].tolist()
            if i % 2:
                assert reference.check(body, got["parent"]) == CLEAN
        admits = {bool(i % 2): s.attrs["bytes"] for i, env in enumerate(envs)
                  for s in served.sched.tracer.spans(env["job"])
                  if s.name == "job.admit"}
        assert admits == {False: image,
                          True: image + bfs_plane_bytes(n, 4)}
        assert bfs_plane_bytes(n, 4) == 4 * 4 * (n + 1)
        assert served.sched.ledger.resident_bytes() == image
    finally:
        served.close()
    plain = JobSpec(kind="bfs", params={"source": 1})
    tree = JobSpec(kind="bfs", params={"source": 1, "parents": True})
    assert batch_key(plain) != batch_key(tree)
    assert batch_key(plain) == batch_key(
        JobSpec(kind="bfs", params={"source": 2, "parents": False}))
    assert KINDS["bfs"].work.price(served.snap, [plain], 1) == 0
    assert KINDS["bfs"].work.price(served.snap, [tree] * 3, 1) \
        == bfs_plane_bytes(n, 3)


def test_a_depth_only_job_builds_what_it_built_before_the_plane(bench):
    """The executables by program, counted: a depth-only job's first run
    on a layout builds the set ISSUE 49 states (a seed, a plan, a
    listing, a push a rung, a pull a rung, a stragglers' sweep a pair)
    and not one more; the first job that wants its tree builds the
    programs that carry the plane again (the plan and the listing read
    the depths alone and are shared); after either, no source builds."""
    # a shape no other test of this file has built
    n, src, dst = graph500(bench, 9, 5)
    degree = np.bincount(src, minlength=n)
    order = np.argsort(degree, kind="stable")
    order = order[degree[order] > 0]
    sources = [int(v) for v in order[np.linspace(0, len(order) - 1, 6)
                                     .astype(int)]]
    bh._WARMED.clear()
    served = Served(n, src, dst)
    try:
        g = bh.build_chunked_csr(served.snap)
        caps = bh._td_caps(g)
        c_caps, ex_pairs = bh._bu_caps(g)
        prof = served.sched.profiler
        keys = ("batched_seed", "batched_plan", "batched_list",
                "batched_td", "batched_bu", "batched_ex")

        def built():
            return {k: prof.compiles(k) for k in keys}

        def run(source, parents):
            env = served.job({"kind": "bfs", "source": source,
                              **({"parents": True} if parents else {})})
            assert env["status"] == "done", env
            return env

        before = built()
        run(sources[0], False)
        plain = {k: v - before[k] for k, v in built().items()}
        assert plain == {"batched_seed": 1, "batched_plan": 1,
                         "batched_list": 1, "batched_td": len(caps),
                         "batched_bu": len(c_caps),
                         "batched_ex": len(ex_pairs)}
        after_plain = built()
        for s in sources[1:3]:
            run(s, False)
        assert built() == after_plain       # a second source: nothing
        run(sources[3], True)
        tree = {k: v - after_plain[k] for k, v in built().items()}
        assert tree == {"batched_seed": 1, "batched_plan": 0,
                        "batched_list": 0, "batched_td": len(caps),
                        "batched_bu": len(c_caps),
                        "batched_ex": len(ex_pairs)}
        after_tree = built()
        total = prof.compiles()
        for s in sources[4:]:
            run(s, True)
        for s in sources[:2]:
            run(s, False)
        assert built() == after_tree and prof.compiles() == total
    finally:
        served.close()


# -- what the run journals and counts -----------------------------------------

def test_the_jobs_spans_counters_and_the_build_ahead(bench):
    # a shape no other test of this file has built
    n, src, dst = graph500(bench, 10, 3)
    degree = np.bincount(src, minlength=n)
    order = np.argsort(degree, kind="stable")
    sources = [int(order[-1]), int(order[0]), int(order[n // 2])]
    bh._WARMED.clear()
    served = Served(n, src, dst)
    try:
        g = bh.build_chunked_csr(served.snap)
        c_caps, ex_pairs = bh._bu_caps(g)
        prof = served.sched.profiler
        first = served.job({"kind": "bfs", "source": sources[0],
                            "parents": True})
        built = prof.compiles()
        envs = [served.job({"kind": "bfs", "source": s, "parents": True})
                for s in sources[1:]]
        assert all(e["status"] == "done" for e in [first] + envs)
        # the first job of the layout built the whole set; the jobs
        # behind it, of other sources, build nothing
        assert prof.compiles() == built
        devprof.drain()
        tracer = served.sched.tracer
        cold = {s.name: s for s in tracer.spans(first["job"])}
        first_pulled = sum(1 for s in tracer.spans(first["job"])
                           if s.name == "bfs.sweep"
                           and s.attrs["dir"] == "bu")
        jobs = [list(tracer.spans(e["job"])) for e in envs]
        m = served.metrics
    finally:
        served.close()
    assert cold["bfs.build"].attrs["K"] == 1
    assert cold["bfs.build"].attrs["parents"] is True
    assert cold["bfs.build"].parent_id == cold["run"].span_id
    pulled = 0
    for spans in jobs:
        by_name: dict = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        assert "bfs.build" not in by_name
        (run,) = by_name["run"]
        (result,) = by_name["bfs.result"]
        leaves = by_name["bfs.seed"] + by_name["bfs.plan"] \
            + by_name["bfs.sweep"] + by_name.get("bfs.exhaust", []) \
            + [result]
        assert all(s.parent_id == run.span_id for s in leaves)
        assert all(run.t_start <= s.t_start <= s.t_end <= run.t_end
                   for s in leaves)
        # the depths and the parents, one readback
        assert result.attrs["bytes"] == 2 * 4 * n
        assert result.attrs["parents"] is True
        assert result.attrs["sync_ms"] >= 0.0
        # every program of the loop has its kernel span under the job,
        # under the phase that dispatched it
        kernels = by_name["kernel"]
        keys = {s.attrs["key"] for s in kernels}
        assert {"batched_seed", "batched_td"} <= keys
        assert keys <= {"batched_seed", "batched_plan", "batched_list",
                        "batched_td", "batched_bu", "batched_ex"}
        ids = {s.span_id: s.name for s in spans}
        assert {ids[s.parent_id] for s in kernels} \
            <= {"bfs.seed", "bfs.plan", "bfs.sweep", "bfs.exhaust"}
        for s in by_name["bfs.sweep"]:
            a = s.attrs
            if a["dir"] == "bu":
                pulled += 1
                assert a["c_cap"] in c_caps
                assert a["candidates"] <= a["c_cap"] and a["fuse"] == 1
            else:
                assert a["p_cap"] in bh._td_caps(g)
        for s in by_name.get("bfs.exhaust", []):
            assert (s.attrs["c_cap"], s.attrs["p_cap"]) in ex_pairs
    assert pulled
    # the counters: a pulled level counts the rung its sweep took (the
    # first job's too; a level with no candidate left sweeps nothing);
    # the answer's bytes
    taken = sum(m.counter("device.bfs.pull_rung",
                          labels={"c_cap": str(c)}).count for c in c_caps)
    by_dir = m.counter("device.bfs.levels",
                       labels={"dir": "bu", "list": "none"}).count
    assert pulled + first_pulled == taken <= by_dir
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "bfs.result"}).count == 3 * 2 * 4 * n
