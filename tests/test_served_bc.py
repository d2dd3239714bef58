"""BC (ISSUE 46): GAP's betweenness centrality kernel (Brandes from a few
named roots) as a served job, on the CPU. The program (``models/bc.py``:
a forward and a backward phase over stored BFS levels, every level one
pull over PageRank's in-edge image) against the benchmark's plain
reference (``benchmark/reference/bc.py``: scipy float64, nothing of
``titan_tpu`` in it) by the epsilon rule: on LDBC's graph500 generator
at scale 10 and on two graphs worked by hand (a path of 64 vertices,
whose levels outnumber any Kronecker root's; two components with a root
in each). Then the served path: ``JobScheduler.submit`` and ``POST
/jobs`` with ``sources`` -> result plane, spans and counters, a cancel
between the phases, a timeout, what is refused and in what words, what
admission reserves; and the shared pull-sum: ``pagerank_pull`` bit-equal
to the program it was before ``pull_sum`` was cut out of it. Since ISSUE
47 a job's roots run in groups that share every pull: every root's
dependencies are what the root gives alone, whatever stands beside it.
"""

import functools
import sys
import urllib.error

import numpy as np
import pytest

from test_served_lcc import Served, both_ways
from test_served_wcc import BENCH, _by_file, graph500
from titan_tpu.models import bc as B
from titan_tpu.models import pagerank_pull as pp
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving.hbm import (snapshot_bc_work_bytes,
                                        snapshot_csr_bytes,
                                        snapshot_pull_bytes)
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import vmem_gather as vg
from titan_tpu.utils.metrics import MetricManager



@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator, structure and reference, by file. The
    generator finds its sibling through the harness's ``files`` module,
    which is importable only while this fixture holds the path."""
    sys.path.insert(0, BENCH)
    try:
        yield {"graph500_simple": _by_file("graphs", "graph500_simple"),
               "csr": _by_file("reference", "csr"),
               "bc": _by_file("reference", "bc")}
    finally:
        sys.path.remove(BENCH)
        sys.modules.pop("files", None)


def a_path(n: int = 64):
    return both_ways(n, [(v, v + 1) for v in range(n - 1)])


def two_components():
    """A 4-cycle with a tail (0-1-2-3-0, 3-4) and, apart from it, a
    star of five round 5 whose leaf 9 carries a leaf of its own; vertex
    11 has no edge."""
    return both_ways(12, [(0, 1), (1, 2), (2, 3), (3, 0), (3, 4),
                          (5, 6), (5, 7), (5, 8), (5, 9), (9, 10)])


#: name -> (the graph, the roots of a job on it)
CASES = {
    "graph500_s10": (lambda bench: graph500(bench, 10, 3), None),  # drawn
    "a_path": (lambda _bench: a_path(), [0, 63, 31, 10]),
    "two_components": (lambda _bench: two_components(), [1, 5, 10, 4]),
}


@pytest.fixture(scope="module")
def case(bench):
    made: dict = {}

    def of(name: str):
        if name not in made:
            make, roots = CASES[name]
            n, src, dst = make(bench)
            if roots is None:
                roots = [int(r) for r in np.random.default_rng(46)
                         .choice(n, 4, replace=False)]
            indptr, indices = bench["csr"].structure(n, src, dst)
            ref = bench["bc"].prepare(n, indptr, indices,
                                      {"roots": roots}, {})
            made[name] = (n, src, dst, roots, ref)
        return made[name]
    return of


def brandes(n, src, dst, root):
    """Brandes' algorithm as published (a queue, a stack, predecessor
    lists), a vertex at a time: ``delta_root`` float64 [n]."""
    nbrs = [[] for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        nbrs[u].append(v)
    sigma, dist = [0.0] * n, [-1] * n
    sigma[root], dist[root] = 1.0, 0
    preds = [[] for _ in range(n)]
    order, queue = [], [root]
    while queue:
        v = queue.pop(0)
        order.append(v)
        for w in nbrs[v]:
            if dist[w] < 0:
                dist[w] = dist[v] + 1
                queue.append(w)
            if dist[w] == dist[v] + 1:
                sigma[w] += sigma[v]
                preds[w].append(v)
    delta = [0.0] * n
    for w in reversed(order):
        for v in preds[w]:
            delta[v] += sigma[v] / sigma[w] * (1.0 + delta[w])
    delta[root] = 0.0
    return np.asarray(delta)


@pytest.mark.parametrize("name", sorted(CASES))
def test_program_reference_and_brandes_agree(bench, case, name):
    n, src, dst, roots, ref = case(name)
    scores, levels, reached = B.bc(snap_mod.from_arrays(n, src, dst),
                                   roots)
    assert scores.dtype == np.float32 and scores.shape == (n,)
    total = sum(brandes(n, src, dst, r) for r in roots)
    want = ref.answer({"sources": roots})["result"]
    assert np.allclose(want, total / total.max(), rtol=1e-12, atol=0)
    assert ref.check({"sources": roots}, scores) == {"scores": 0}
    assert float(scores.max()) == 1.0
    # an exact zero stays one
    assert (scores[want == 0] == 0).all() and (want == 0).any()
    by_ref = [bench["bc"].dependencies(ref.out, r) for r in roots]
    assert levels == [lv for _delta, lv in by_ref]
    if name == "a_path":
        assert levels == [64, 64, 33, 54] and reached == [64] * 4
        # from an end every inner vertex v lies on the paths to all
        # beyond it: delta_0[v] = 63 - v
        one, _, _ = B.bc(snap_mod.from_arrays(n, src, dst), [0])
        assert np.array_equal(
            one, np.float32(np.r_[0, 62:-1:-1]) / np.float32(62))
    if name == "two_components":
        assert reached == [5, 6, 6, 5] and levels == [4, 3, 4, 4]
        assert scores[11] == 0


def test_a_repeated_root_counts_twice(case):
    n, src, dst, _roots, ref = case("two_components")
    snap = snap_mod.from_arrays(n, src, dst)
    twice, levels, _ = B.bc(snap, [1, 5, 5])
    assert levels == [4, 3, 3]
    d1, d5 = brandes(n, src, dst, 1), brandes(n, src, dst, 5)
    want = (d1 + 2 * d5) / (d1 + 2 * d5).max()
    assert np.allclose(twice, want, rtol=1e-6, atol=0)
    assert ref.check({"sources": [1, 5, 5]}, twice) == {"scores": 0}
    once, _, _ = B.bc(snap, [1, 5])
    assert ref.check({"sources": [1, 5, 5]}, once)["scores"] > 0


def test_a_root_alone_and_a_root_with_one_level():
    """A vertex without an edge reaches itself in one pull; an edge's
    end has two levels and no backward pull; every score is 0 and stays
    0 where there is nothing to divide by."""
    n, src, dst = both_ways(4, [(0, 1)])
    scores, levels, reached = B.bc(snap_mod.from_arrays(n, src, dst),
                                   [3, 0])
    assert levels == [1, 2] and reached == [1, 2]
    assert scores.tolist() == [0.0] * 4


def test_what_the_model_refuses():
    class Overlay:
        empty = False
    n, src, dst = a_path(8)
    with pytest.raises(RuntimeError, match="compact the overlay"):
        B.bc(snap_mod.from_arrays(n, src, dst), [0], overlay=Overlay())
    with pytest.raises(ValueError, match="not an undirected"):
        B.bc(snap_mod.from_arrays(3, np.array([0, 1]), np.array([1, 2])),
             [0])
    snap = snap_mod.from_arrays(n, src, dst)
    for params, words in (
            ({}, "need 'sources'"), ({"sources": []}, "need 'sources'"),
            ({"sources": 3}, "need 'sources'"),
            ({"sources": list(range(8)) * 3}, "1 to 16"),
            ({"sources": [0, 99]}, "vertex 99 not in snapshot"),
            ({"sources": [0, "x"]}, "bad sources value"),
            ({"sources_dense": [0, 8]}, "dense index 8"),
            ({"sources_dense": [-1]}, "dense index -1")):
        with pytest.raises(ValueError, match=words):
            B.dense_roots(snap, params)
    assert B.dense_roots(snap, {"sources": [7, 7, 0]}) == [7, 7, 0]
    assert B.dense_roots(snap, {"sources": [1], "sources_dense": [2]}) \
        == [2]


@pytest.mark.parametrize("roots,limit", [
    # one group of two, 8 forward pulls and 6 backward: the veto falls
    # in its backward phase
    ([0, 7], 10),
    # 2 + 1: the pair's 14 pulls, then root 3 alone (5 forward pulls, 3
    # backward): the veto falls in the second group's forward phase
    ([0, 7, 3], 16)])
def test_a_veto_stops_at_a_level_boundary(roots, limit):
    from titan_tpu.models.frontier import RoundInterrupted

    n, src, dst = a_path(8)
    seen = []

    def veto(i):
        seen.append(i)
        return i < limit

    # a boundary a SHARED level: the pair's pulls are counted once
    with pytest.raises(RoundInterrupted):
        B.bc(snap_mod.from_arrays(n, src, dst), roots, on_round=veto)
    assert seen == list(range(1, limit + 1))
    asked = []
    _, levels, _ = B.bc(snap_mod.from_arrays(n, src, dst), roots,
                        on_round=lambda i: asked.append(i) or True)
    assert levels == [8, 8, 5][:len(roots)]
    assert asked == list(range(1, 14 + (8 if len(roots) == 3 else 0) + 1))


# -- the roots of a group share every level ------------------------------------

def deltas_of(snap, roots, monkeypatch):
    """``bc`` of ``roots`` with the groups' dependencies caught where
    ``bc_result`` takes them: ``(scores, levels, reached, deltas)``,
    ``deltas`` one ``[w, n]`` array a group."""
    real, caught = B._result, []

    def catching():
        result = real()

        def catch(deltas):
            caught.extend(np.asarray(d) for d in deltas)
            return result(deltas)
        return catch
    with monkeypatch.context() as m:
        m.setattr(B, "_result", catching)
        return B.bc(snap, roots) + (caught,)


#: name -> (graph, roots, the groups' widths)
GROUPS = {
    # four roots of 64, 64, 33 and 54 levels in one group
    "depths-differ": ("a_path", [0, 63, 31, 10], [4]),
    "a-root-alone": ("two_components", [11], [1]),
    # the isolated vertex (one level) beside a root of four
    "a-root-of-one-level": ("two_components", [11, 1], [2]),
    "a-repeated-root": ("two_components", [5, 5], [2]),
    "three-roots": ("two_components", [1, 5, 5], [2, 1]),
    "five-roots": ("two_components", [1, 5, 10, 4, 11], [4, 1]),
    "nine-roots": ("graph500_s10", None, [8, 1]),
}


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_every_root_of_a_group_is_the_root_alone(case, name, monkeypatch):
    """Each root's delta is BIT-equal to the same root run alone (the
    CPU: XLA's road, where a gathered ``[8, Q, w]`` is summed over its
    leading axis in the order ``[8, Q]`` is; the same scan; a root that
    is done pulls zeros), and the scores are inside the rule."""
    graph, roots, widths = GROUPS[name]
    n, src, dst, drawn, ref = case(graph)
    if roots is None:
        roots = drawn + [int(r) for r in np.random.default_rng(47)
                         .choice(n, 5, replace=False)]
    snap = snap_mod.from_arrays(n, src, dst)
    scores, levels, reached, deltas = deltas_of(snap, roots, monkeypatch)
    assert [d.shape for d in deltas] == [(w, n) for w in widths]
    assert [vg.shared_width(n, left) for left in (1, 2, 3, 5, 9, 16)] \
        == [1, 2, 2, 4, 8, 8]
    rows = np.concatenate(deltas)
    for i, root in enumerate(roots):
        _, (lv,), (seen,), (alone,) = deltas_of(snap, [root], monkeypatch)
        assert rows[i].tobytes() == alone[0].tobytes(), (name, root)
        assert (levels[i], reached[i]) == (lv, seen)
        exact = brandes(n, src, dst, root)
        assert np.allclose(rows[i], exact, rtol=1e-5, atol=0)
        assert np.array_equal(rows[i] == 0, exact == 0)
    # the scores inside the rule against the plain reference, which
    # works a root no pool holds when it meets it
    assert ref.check({"sources": roots}, scores) == {"scores": 0}


# -- the shared pull-sum ------------------------------------------------------

def _parents_pull_step():
    """``pagerank_pull`` as it stood before ``pull_sum`` was cut out of
    it (PR 45's ``models/pagerank_pull.pull_step``, word for word)."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.ops.segment import seg_scan

    @functools.partial(jax.jit, static_argnames=("impl", "seg_max"))
    def step(rank, deg, idx, first, last, has, impl: str, seg_max: int):
        contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
        colsum = (vg.colsum_vmem if impl == "vmem"
                  else pp._colsum_xla)(idx, vg.as_table(contrib))
        run = seg_scan(colsum, first, "sum", max_len=seg_max)
        return jnp.where(has, run[last], 0.0)
    return step


@pytest.mark.parametrize("impl", ["xla", "vmem"])
def test_pagerank_pull_is_bit_equal_to_the_parents(bench, impl,
                                                   monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    n, src, dst = graph500(bench, 10, 3)
    im = pp.pull_image(snap_mod.from_arrays(n, src, dst))
    rank = np.random.default_rng(5).random(n + 1).astype(np.float32)
    rank[n] = 0.0
    args = (jnp.asarray(rank), im["deg"], im["idx"], im["first"],
            im["last"], im["has"])
    now = pp.pull_step()(*args, impl=impl, seg_max=im["seg_max"])
    then = _parents_pull_step()(*args, impl=impl, seg_max=im["seg_max"])
    assert np.asarray(now).tobytes() == np.asarray(then).tobytes()
    # one table is the program it was: the same operations traced, not
    # only the same bits (ISSUE 47: the width is the table's shape)
    import inspect

    import jax
    traced = [str(jax.make_jaxpr(functools.partial(
        inspect.unwrap(step), impl=impl, seg_max=im["seg_max"]))(*args))
        for step in (pp.pull_step(), _parents_pull_step())]
    assert traced[0] == traced[1]
    assert float(now.sum()) > 0
    # and the levels' sums are the same function of another table
    table = jnp.asarray(rank)
    got = pp.pull_sum(table, im["idx"], im["first"], im["last"],
                      im["has"], impl, im["seg_max"])
    want = np.zeros(n)
    np.add.at(want, dst, rank[src].astype(np.float64))
    assert np.allclose(got, want, rtol=1e-5, atol=0)


def test_the_kernel_and_xla_agree_on_a_job(case, monkeypatch):
    """The Pallas gather (in its interpreter) serves the levels' tables
    as XLA's gather does, four roots side by side in one table: the
    same depths, the same scores."""
    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    n, src, dst, roots, ref = case("graph500_s10")
    snap = snap_mod.from_arrays(n, src, dst)
    by_xla = B.bc(snap, roots)
    monkeypatch.setattr(vg, "gather_impl", lambda _n, _width=1: "vmem")
    by_kernel = B.bc(snap, roots)                 # one group of four
    assert by_kernel[1:] == by_xla[1:]
    assert ref.check({"sources": roots}, by_kernel[0]) == {"scores": 0}
    assert np.allclose(by_kernel[0], by_xla[0], rtol=1e-5, atol=0)
    assert np.array_equal(by_kernel[0] == 0, by_xla[0] == 0)


# -- the served path ---------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CASES))
def test_a_served_job_equals_the_reference(case, name):
    n, src, dst, roots, ref = case(name)
    served = Served(n, src, dst)
    try:
        body = {"kind": "bc", "sources": roots, "timeout_s": 60}
        env = served.job(body)
        assert env["status"] == "done", env
        scores = served.array(env["job"], "scores")
        held = served.sched.get(env["job"]).result
        assert scores.tobytes() == held["scores"].tobytes()
        direct = served.sched.submit(JobSpec(
            kind="bc", params={"sources_dense": roots}))
        assert direct.wait(120) and direct.state.value == "done", \
            direct.error
    finally:
        served.close()
    assert env["arrays"] == {"scores": {"dtype": "float32", "shape": [n]}}
    assert set(env["result"]) == {"levels", "reached"}
    assert len(env["result"]["levels"]) == len(roots)
    assert env["result"]["reached"] == [
        int((d >= 0).sum()) for d in (_depths(n, src, dst, r)
                                      for r in roots)]
    assert ref.check(body, scores) == {"scores": 0}
    assert direct.result["scores"].tobytes() == scores.tobytes()
    # the rule sees one score, a reference 0 wants an exact 0, and an
    # answer of another length is all out
    want = ref.answer(body)["result"]
    one = scores.copy()
    one[int(np.flatnonzero(want > 0)[0])] *= 1.001
    assert ref.check(body, one) == {"scores": 1}
    one = scores.copy()
    one[int(np.flatnonzero(want == 0)[0])] = 1e-9
    assert ref.check(body, one) == {"scores": 1}
    assert ref.check(body, scores[:-1]) == {"scores": n}


def _depths(n, src, dst, root):
    depth = np.full(n, -1)
    depth[root], frontier, d = 0, np.array([root]), 0
    while frontier.size:
        d += 1
        nxt = np.unique(dst[np.isin(src, frontier)])
        frontier = nxt[depth[nxt] < 0]
        depth[frontier] = d
    return depth


def test_one_altered_score_reads_one_mismatch(case, monkeypatch):
    """One dependency too large at one vertex, where the job's answer is
    made: the reference's check, as the load generator applies it to the
    served array, reads 1."""
    n, src, dst, roots, ref = case("graph500_s10")
    want = ref.answer({"sources": roots})["result"]
    # neither the largest (all n would move) nor a zero
    at = int(np.flatnonzero((want > 0) & (want < 0.5))[0])
    real = B._result

    def altered():
        result = real()

        def one_more(deltas):
            return result((deltas[0].at[0, at].multiply(1.01),)
                          + deltas[1:])
        return one_more
    monkeypatch.setattr(B, "_result", altered)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "bc", "sources": roots})
        assert env["status"] == "done", env
        scores = served.array(env["job"], "scores")
    finally:
        served.close()
    assert ref.check({"kind": "bc", "sources": roots}, scores) \
        == {"scores": 1}
    assert scores[at] > want[at]


def test_what_the_served_path_refuses(case):
    n, src, dst, roots, _ref = case("two_components")
    served = Served(n, src, dst)
    try:
        with pytest.raises(ValueError) as e:
            served.sched.submit(JobSpec(kind="bc", directed=True,
                                        params={"sources": roots}))
        assert str(e.value) == (
            "bc on a directed snapshot: the backward phase walks the "
            "out-edges, whose image a directed snapshot would need "
            "beside the in-edges', is not implemented; submit with "
            "directed=false")
        assert served.metrics.counter(
            "serving.jobs.rejected",
            labels={"kind": "bc", "tenant": "default"}).count == 1
        with pytest.raises(urllib.error.HTTPError) as http:
            served.post({"kind": "bc", "sources": roots,
                         "directed": True})
        assert http.value.code == 400
        # an id the snapshot does not hold fails the job for good: no
        # retry is spent on it
        env = served.job({"kind": "bc", "sources": [1, 99],
                          "max_retries": 2})
        assert env["status"] == "failed" and env["attempt"] == 1
        assert env["error"] == \
            "ValueError: 'vertex 99 not in snapshot'"
        env = served.job({"kind": "bc"})
        assert env["status"] == "failed" and env["error"] == (
            "ValueError: job params need 'sources' (1 to 16 vertex "
            "ids) or 'sources_dense'")
        env = served.job({"kind": "bc", "sources": roots})
        assert env["status"] == "done", env
    finally:
        served.close()


def test_the_jobs_spans_and_counters(bench):
    # shapes no other test of this file has built
    n, src, dst = graph500(bench, 9, 3)
    roots = [int(r) for r in np.random.default_rng(9).choice(n, 4, False)]
    served = Served(n, src, dst)
    try:
        prof = served.sched.profiler
        before = prof.compiles()
        first = served.job({"kind": "bc", "sources": roots})
        built = prof.compiles() - before
        env = served.job({"kind": "bc", "sources": roots[::-1]})
        assert first["status"] == env["status"] == "done", env
        # the second job, of other roots in another order, builds nothing
        assert prof.compiles() - before == built
        from titan_tpu.obs import devprof
        devprof.drain()
        spans = list(served.sched.tracer.spans(env["job"]))
        cold = [s for trace in (first["job"], "compile")
                for s in served.sched.tracer.spans(trace) or ()
                if s.name == "compile"]
        m = served.metrics
        text = served.get("/metrics")[1].decode()
    finally:
        served.close()
    # every executable of a snapshot's first job comes from jit_once
    assert sorted(s.attrs["key"] for s in cold) == [
        "bc_backward_level", "bc_forward_level", "bc_result", "bc_seed"]
    assert built == 4
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (run,) = by_name["run"]
    (result,) = by_name["bc.result"]
    # four roots, one group: a span a phase, its pulls shared
    (forward,), (backward,) = by_name["bc.forward"], by_name["bc.backward"]
    levels, reached = env["result"]["levels"], env["result"]["reached"]
    deep = max(levels)
    for s in (forward, backward):
        assert s.attrs["roots"] == roots[::-1] and s.attrs["width"] == 4
        assert s.attrs["impl"] == "xla" and s.attrs["sync_ms"] >= 0
        assert "root" not in s.attrs
    # the scalars the benchmark's readers put in a set stay scalars: the
    # group's pulls of the phase, the vertices its roots reached, summed
    assert forward.attrs["levels"] == deep
    assert forward.attrs["reached"] == sum(reached)
    assert backward.attrs["levels"] == deep - 2
    assert forward.attrs["root_levels"] == levels
    assert forward.attrs["root_reached"] == reached
    assert backward.attrs["root_levels"] == [lv - 2 for lv in levels]
    leaves = [forward, backward, result]
    assert all(s.parent_id == run.span_id for s in leaves)
    assert forward.t_end <= backward.t_start \
        and backward.t_end <= result.t_start
    assert result.attrs["bytes"] == 4 * n and result.attrs["roots"] == 4
    assert len(by_name["job.lease"]) == len(by_name["job.admit"]) == 1
    # every program a kernel span under the phase that dispatched it,
    # the level programs' with the width they served
    under: dict = {s.span_id: [] for s in leaves}
    for s in by_name["kernel"]:
        under[s.parent_id].append(s.attrs["key"])
        if s.attrs["key"].endswith("_level"):
            assert s.attrs["width"] == 4 and s.attrs["impl"] == "xla"
    assert under[forward.span_id] == ["bc_seed"] + ["bc_forward_level"] * deep
    assert under[backward.span_id] == ["bc_backward_level"] * (deep - 2)
    assert under[result.span_id] == ["bc_result"]
    # a boundary a SHARED pull: the round the job stopped at, its timeline
    pulls = deep + deep - 2
    assert run.attrs["rounds"] == pulls == len(by_name["round"])
    both = 2                                    # jobs counted
    # a root's levels, as before; beside them the pulls that served them
    assert m.counter("device.bc.levels",
                     labels={"part": "forward"}).count == both * sum(levels)
    assert m.counter("device.bc.levels", labels={"part": "backward"}) \
        .count == both * sum(lv - 2 for lv in levels)
    assert m.counter("device.bc.pulls", labels={
        "part": "forward", "width": "4"}).count == both * deep
    assert m.counter("device.bc.pulls", labels={
        "part": "backward", "width": "4"}).count == both * (deep - 2)
    assert m.counter_value("device.bc.roots") == both * 4
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "bc.result"}).count == both * 4 * n
    for key in ("bc_forward_level", "bc_backward_level"):
        assert m.counter("device.exec.unstamped",
                         labels={"kernel": key}).count == 0
    text = text.replace(".", "_")
    assert "device_bc_levels" in text and "device_bc_pulls" in text


def test_cancel_between_the_phases_and_a_timeout(case, monkeypatch):
    n, src, dst, roots, _ref = case("graph500_s10")
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics)
    try:
        late = sched.submit(JobSpec(kind="bc", timeout_s=0.0,
                                    params={"sources_dense": roots}))
        assert late.wait(120) and late.state.value == "timeout", \
            (late.state, late.error)
        assert late.last_round == 1     # behind its first pull
        done = sched.submit(JobSpec(kind="bc",
                                    params={"sources_dense": roots}))
        assert done.wait(120) and done.state.value == "done", done.error
        first = max(done.result["levels"])      # one group of four
        real = B.bc

        def cancelling(snap, roots_, **kw):
            on_round = kw["on_round"]

            def hook(i):
                if i == first:      # the forward phase's last boundary
                    sched.cancel(job.id)
                return on_round(i)
            return real(snap, roots_, **dict(kw, on_round=hook))
        monkeypatch.setattr(B, "bc", cancelling)
        job = sched.submit(JobSpec(kind="bc",
                                   params={"sources_dense": roots}))
        assert job.wait(120)
        assert job.state.value == "cancelled", (job.state, job.error)
        assert job.last_round == first and job.result is None
        names = [s.name for s in sched.tracer.spans(job.id)]
        assert names.count("bc.forward") == 1
        assert "bc.backward" not in names and "bc.result" not in names
    finally:
        sched.close()


def test_admission_reserves_both_images_and_lets_the_work_go(case):
    n, src, dst, roots, _ref = case("graph500_s10")
    snap = snap_mod.from_arrays(n, src, dst)
    q_in = pp.pull_columns(snap.indptr_in, n)
    images = snapshot_csr_bytes(snap) + snapshot_pull_bytes(snap)
    work = snapshot_bc_work_bytes(snap)
    # sixteen roots at this n are groups of eight: a level of eight
    # roots (seven n-vectors and 16 B a column each) and sixteen deltas
    assert vg.shared_width(n, B.MAX_ROOTS) == 8
    assert work == B.work_bytes(n, q_in) \
        == 8 * (4 * n * 7 + 16 * q_in) + 4 * n * 16
    served = Served(n, src, dst)
    try:
        first = served.job({"kind": "bc", "sources": roots})
        # a PageRank job reads the same two images: resident already
        rank = served.job({"kind": "pagerank", "iterations": 2})
        assert first["status"] == rank["status"] == "done", first
        admits = [[s for s in served.sched.tracer.spans(e["job"])
                   if s.name == "job.admit"][0] for e in (first, rank)]
        ledger = served.sched.ledger
    finally:
        served.close()
    assert [a.attrs["bytes"] for a in admits] == [images + work, images]
    assert [a.attrs["sizing_passes"] for a in admits] == [2, 0]
    assert ledger.resident_bytes() == images        # the work left
    assert ledger.pinned_bytes() == 0
    served = Served(n, src, dst, hbm_budget_bytes=images + work - 1)
    try:
        env = served.job({"kind": "bc", "sources": roots})
        assert env["status"] == "failed" and "admission" in env["error"]
        assert served.sched.ledger.pinned_bytes() == 0
    finally:
        served.close()
