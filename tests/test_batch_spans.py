"""Spans inside a lane batch, one ``compile`` span for every executable,
and reading a stretch of time (ISSUE 25).

What is pinned here, all on the CPU:

* the span tree of one fused 2-hop batch: root, one ``member`` a
  request, the leaf phases in order, never overlapping one another;
* a ``compile`` span — key, static arguments, cache verdict — for a
  ``jit_once`` kernel called at a new ``c_cap`` and for an eager slice of
  a new shape, and none when either is called again;
* with ``TITAN_TPU_TRACING=0`` the answers are bit-equal and nothing is
  journaled; with the tracer on the batch makes the same ``jit_once``
  calls and moves the same bytes to the host as with it off;
* ``Tracer.window`` bounds, ``tracing.current`` and ``GET /trace?since=``.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import titan_tpu
from titan_tpu.obs import devprof, tracing
from titan_tpu.obs.tracing import Tracer, phase, scope
from titan_tpu.olap.serving.interactive import plan_from_wire
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.utils.metrics import MetricManager

LEAVES = ("admit", "bfs.seed", "bfs.plan", "bfs.sweep", "bfs.exhaust",
          "extract", "reply")
K = 4


@pytest.fixture(scope="module")
def social():
    g = titan_tpu.open("inmemory")
    rng = np.random.default_rng(7)
    n = 40
    tx = g.new_transaction()
    vs = [tx.add_vertex("person", name=f"p{i}") for i in range(n)]
    for a, b in zip(rng.integers(0, n, 120), rng.integers(0, n, 120)):
        if a != b:
            vs[int(a)].add_edge("knows", vs[int(b)])
    tx.commit()
    yield g
    g.close()


@pytest.fixture(scope="module")
def ids(social):
    out = sorted(v.id for v in social.traversal().V().to_list())
    social.rollback()
    return out


def fused_batch(sched, starts, terminal="count", direction="both"):
    """Submit ``len(starts)`` 2-hop queries at once until they ran as ONE
    batch; returns the responses in the order of ``starts``."""
    lane = sched.interactive()
    for _attempt in range(5):
        out = {}
        barrier = threading.Barrier(len(starts))

        def go(vid):
            barrier.wait()
            out[vid] = lane.submit(plan_from_wire(
                {"start": [vid], "dir": direction, "hops": 2,
                 "terminal": terminal}))

        threads = [threading.Thread(target=go, args=(v,)) for v in starts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert len(out) == len(starts)
        if {r["fused_k"] for r in out.values()} == {len(starts)}:
            return [out[v] for v in starts]
    raise AssertionError("the queries never fused into one batch")


def finished_tree(tracer, batch_id):
    """The batch's spans once its root has ended (the worker ends it
    moments after the last answer)."""
    deadline = time.time() + 10
    while time.time() < deadline:
        spans = tracer.spans(batch_id)
        if spans and spans[0].t_end is not None:
            return spans
        time.sleep(0.01)
    raise AssertionError(f"{batch_id}: root never ended")


@pytest.fixture(scope="module")
def traced_batch(social, ids):
    """One warm fused batch on a scheduler with the defaults (tracer and
    profiler on): (scheduler's tracer, batch id, responses)."""
    sched = JobScheduler(graph=social, autostart=False,
                         interactive_window_s=0.05)
    try:
        fused_batch(sched, ids[:K])             # compiles land here
        res = fused_batch(sched, ids[:K])
        spans = finished_tree(sched.tracer, res[0]["batch"])
        yield sched.tracer, res, spans
    finally:
        sched.close()


@pytest.fixture(scope="module")
def pulled_batch():
    """One warm fused ``out()`` batch: a directed chain's layout holds
    parents, so every level pulls; vertex 0, whose 80 parents (10
    chunks) no query reaches, outlasts the eight chunk rounds and goes
    to ``bfs.exhaust``."""
    g = titan_tpu.open("inmemory")
    tx = g.new_transaction()
    vs = [tx.add_vertex("person", name=f"q{i}") for i in range(90)]
    for v in vs[10:]:
        v.add_edge("knows", vs[0])
    for a, b in zip(vs[1:9], vs[2:10]):
        a.add_edge("knows", b)
    tx.commit()
    starts = sorted(v.id for v in g.traversal().V().to_list())[1:K + 1]
    g.rollback()
    sched = JobScheduler(graph=g, autostart=False,
                         interactive_window_s=0.05)
    try:
        fused_batch(sched, starts, direction="out")
        res = fused_batch(sched, starts, direction="out")
        yield finished_tree(sched.tracer, res[0]["batch"])
    finally:
        sched.close()
        g.close()


# -- the span tree of a batch ------------------------------------------------

def test_root_and_one_member_a_request(traced_batch):
    _tracer, res, spans = traced_batch
    root = spans[0]
    assert root.name == "interactive" and root.parent_id is None
    assert root.attrs["k"] == K and root.attrs["kind"] == "traverse"
    assert root.attrs["wall_ms"] > 0
    members = [s for s in spans if s.name == "member"]
    assert len(members) == K
    for m in members:
        assert m.parent_id == root.span_id
        assert m.attrs["tenant"] == "default"
        # submitted_at -> finish: begins before the root by its wait
        assert m.t_start <= root.t_start and m.t_end <= root.t_end
        assert m.attrs["wait_ms"] == pytest.approx(
            (root.t_start - m.t_start) * 1e3, abs=5.0)
    assert sorted(m.attrs["wait_ms"] for m in members) == sorted(
        r["wait_ms"] for r in res)


def test_leaf_phases_in_order_under_the_root(traced_batch):
    _tracer, _res, spans = traced_batch
    root = spans[0]
    leaves = [s for s in spans if s.name in LEAVES]
    names = [s.name for s in leaves]
    assert names[:2] == ["admit", "bfs.seed"]
    assert names[-2:] == ["extract", "reply"]
    # two levels of a 2-hop query, each a plan and (edges to sweep) a
    # chunk round; bfs.exhaust only where candidates were left over. A
    # level whose statistics came with the program before (the seed's
    # always; a push's where its rung hands on, which on 40 vertices
    # none does) has a plan span that holds the decision alone
    inner = [(s.name, s.attrs["level"]) for s in leaves[2:-2]]
    plans = [s for s in leaves if s.name == "bfs.plan"]
    assert [(s.attrs["level"], s.attrs["carried"]) for s in plans] == [
        (1, True), (2, False)]
    assert {lv for _n, lv in inner} == {1, 2}
    assert inner == sorted(inner, key=lambda x: x[1])
    for s in leaves:
        assert s.parent_id == root.span_id
        assert root.t_start <= s.t_start <= s.t_end <= root.t_end
    # leaves never nest in one another: each ends before the next starts
    for a, b in zip(leaves, leaves[1:]):
        assert a.t_end <= b.t_start


def test_phase_attributes(traced_batch):
    _tracer, _res, spans = traced_batch
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert by["admit"][0].attrs["k_runnable"] == K
    assert by["admit"][0].attrs["nbytes"] > 0
    assert "epoch" in by["admit"][0].attrs
    seed = by["bfs.seed"][0].attrs
    assert (seed["K"], seed["mode"]) == (K, "hops") and seed["n"] == 40
    for s in by["bfs.plan"]:
        assert s.attrs["replan"] is False
        assert s.attrs["frontier"] >= 1 and s.attrs["c_count"] >= 0
    assert by["bfs.plan"][0].attrs["frontier"] == K     # K single starts
    sweeps = by["bfs.sweep"]
    # `both`, no mask: a level whose frontier's chunks fit the ladder
    # is one push; K single starts of a chunk or two each do
    assert (sweeps[0].attrs["level"], sweeps[0].attrs["dir"]) == (1, "td")
    for s in sweeps:
        check_sweep(s)
    for s in by.get("bfs.exhaust", []):
        assert s.attrs["async"] is True and "sync_ms" not in s.attrs
    assert by["extract"][0].attrs["Kp"] == K
    assert by["reply"][0].attrs["d2h_bytes"] == 0       # count terminals
    for name in ("bfs.plan", "bfs.sweep", "extract"):
        for s in by.get(name, []):
            assert 0 <= s.attrs["sync_ms"] <= s.duration_ms + 1e-3
    cost = by["device_cost"][0].attrs
    assert "exec_ms" not in cost and cost["kernel_calls"] >= 2


def check_sweep(span):
    """A pushed level carries its rung and what it pushed; a chunk round
    of a pulled level its candidate cap and what the round left over."""
    a = span.attrs
    if a["dir"] == "td":
        assert 1 <= a["pairs"] <= a["mass"] <= a["p_cap"]
        assert a["list"] in ("carried", "scan") and a["handed"] >= -1
        assert not {"c_cap", "fuse", "c_count", "rem8"} & set(a)
    else:
        assert a["dir"] == "bu"
        assert a["c_cap"] >= 2 and a["fuse"] >= 1
        assert {"c_count", "rem8"} <= set(a)
        assert not {"p_cap", "mass", "pairs", "list", "handed"} & set(a)


def test_phase_attributes_of_a_pulled_batch(pulled_batch):
    by = {}
    for s in pulled_batch:
        by.setdefault(s.name, []).append(s)
    sweeps = by["bfs.sweep"]
    assert {s.attrs["level"] for s in sweeps} == {1, 2}
    assert {s.attrs["dir"] for s in sweeps} == {"bu"}
    for s in sweeps:
        check_sweep(s)
        assert 0 <= s.attrs["sync_ms"] <= s.duration_ms + 1e-3
    assert {s.attrs["level"] for s in by["bfs.exhaust"]} <= {1, 2}
    for s in by["bfs.exhaust"]:
        assert s.attrs["async"] is True and "sync_ms" not in s.attrs


def test_id_terminals_read_back_in_reply(social, ids):
    sched = JobScheduler(graph=social, autostart=False,
                         interactive_window_s=0.05)
    try:
        res = fused_batch(sched, ids[:2], terminal="id")
        spans = finished_tree(sched.tracer, res[0]["batch"])
        reply = next(s for s in spans if s.name == "reply")
        assert reply.attrs["d2h_bytes"] == 4 * sum(
            len(r["result"]) for r in res) > 0
    finally:
        sched.close()


# -- compile spans -----------------------------------------------------------

@pytest.fixture
def journal():
    """An enabled tracer and an installed profiler of the test's own."""
    tracer = Tracer()
    prof = devprof.DeviceCostProfiler(metrics=MetricManager()).install()
    yield tracer
    prof.uninstall()


def compile_spans(tracer, trace_id):
    return [s for s in tracer.spans(trace_id) or []
            if s.name == "compile"]


def test_compile_span_of_a_jit_once_kernel_at_a_new_cap(journal):
    import jax
    import jax.numpy as jnp

    from titan_tpu.utils.jitcache import jit_once

    def build():
        def spans_probe(x, *, c_cap, masked, tag="t"):
            return jnp.cumsum(x[:c_cap]) + (1 if masked else 0)
        return jax.jit(spans_probe,
                       static_argnames=("c_cap", "masked", "tag"))

    kern = jit_once("test_batch_spans.probe", build)
    x = jnp.arange(257, dtype=jnp.int32)
    kern(x, c_cap=8, masked=False).block_until_ready()  # outside a scope
    root = journal.start("batch-1", "interactive")
    with scope(journal, "batch-1", root):
        with phase("bfs.sweep", level=3):
            kern(x, c_cap=16, masked=True, tag="u").block_until_ready()
            kern(x, c_cap=16, masked=True, tag="u").block_until_ready()
    journal.end(root)
    (c,) = compile_spans(journal, "batch-1")
    sweep = next(s for s in journal.spans("batch-1")
                 if s.name == "bfs.sweep")
    assert c.parent_id == sweep.span_id and sweep.attrs["level"] == 3
    a = c.attrs
    assert a["key"] == "test_batch_spans.probe"
    assert (a["c_cap"], a["masked"], a["tag"]) == (16, True, "u")
    assert a["cache"] in ("hit", "miss")
    assert ("retrieval_ms" in a) == (a["cache"] == "hit")
    assert a["backend_ms"] > 0 and a["lower_ms"] > 0 and a["trace_ms"] > 0
    assert a["thread"] == threading.current_thread().name
    assert sweep.t_start <= c.t_start <= c.t_end <= sweep.t_end
    assert c.duration_ms == pytest.approx(
        a["trace_ms"] + a["lower_ms"] + a["backend_ms"], abs=0.01)
    # the call outside any scope went to the trace named ``compile``
    outside = [s for s in compile_spans(journal, "compile")
               if s.attrs["key"] == "test_batch_spans.probe"]
    assert [s.attrs["c_cap"] for s in outside] == [8]
    assert outside[0].parent_id is None


def test_compile_span_of_an_eager_slice(journal):
    import jax.numpy as jnp

    x = jnp.arange(1031, dtype=jnp.int32)   # a shape no other test has
    root = journal.start("batch-2", "interactive")
    with scope(journal, "batch-2", root):
        x[:509].block_until_ready()
        first = compile_spans(journal, "batch-2")
        x[:509].block_until_ready()
        assert compile_spans(journal, "batch-2") == first
    journal.end(root)
    keys = [s.attrs["key"] for s in first]
    assert "eager:dynamic_slice" in keys
    for s in first:
        assert s.parent_id == root.span_id      # no phase open: the root
        assert s.attrs["cache"] in ("hit", "miss", "off")
        assert "c_cap" not in s.attrs           # no shim, no statics


def test_no_compile_span_without_a_profiler():
    import jax.numpy as jnp

    saved = list(devprof._PROFILERS)
    devprof._PROFILERS.clear()
    try:
        tracer = Tracer()
        root = tracer.start("batch-3", "interactive")
        with scope(tracer, "batch-3", root):
            jnp.arange(1033, dtype=jnp.int32)[:511].block_until_ready()
        assert compile_spans(tracer, "batch-3") == []
    finally:
        devprof._PROFILERS.extend(saved)


def test_profiler_counts_keep_their_meaning(journal):
    """``compiles()`` still counts jit_once cache misses only: an eager
    program makes a span, not a count."""
    import jax.numpy as jnp

    prof = devprof.current()
    before = prof.compiles()
    jnp.arange(1039, dtype=jnp.int32)[:513].block_until_ready()
    assert prof.compiles() == before
    assert any(s.attrs["key"].startswith("eager:")
               for s in compile_spans(journal, "compile"))


# -- tracer off: same answers, same device work, nothing journaled -----------

def batch_cost(social, ids, **sched_kw):
    """(answers, jit_once calls by kernel, D2H bytes, tracer) of one warm
    fused batch under a scheduler made with ``sched_kw``."""
    prof = devprof.DeviceCostProfiler(metrics=MetricManager())
    sched = JobScheduler(graph=social, autostart=False, profiler=prof,
                         interactive_window_s=0.05, **sched_kw)
    prof.install()
    try:
        fused_batch(sched, ids[:K], terminal="id")      # warm
        calls0 = {k: v["calls"] for k, v in prof.kernel_stats().items()}
        d2h0 = prof.stats()["d2h_bytes"]
        res = fused_batch(sched, ids[:K], terminal="id")
        calls = {k: v["calls"] - calls0.get(k, 0)
                 for k, v in prof.kernel_stats().items()}
        return ([r["result"] for r in res],
                {k: v for k, v in calls.items() if v},
                prof.stats()["d2h_bytes"] - d2h0, sched.tracer)
    finally:
        prof.uninstall()
        sched.close()


def test_tracer_off_bit_equal_and_nothing_journaled(social, ids,
                                                    monkeypatch):
    on = batch_cost(social, ids)
    assert on[3].enabled and on[3].window(0.0)
    bystander = tracing.current()
    t_off = time.time()
    monkeypatch.setenv("TITAN_TPU_TRACING", "0")
    off = batch_cost(social, ids)
    assert not off[3].enabled
    assert off[3].window(0.0) == [] and off[3].spans("compile") is None
    assert tracing.current() is bystander       # a disabled one is not it
    assert bystander.window(t_off) == []        # and nobody else got them
    assert off[0] == on[0]                      # answers, bit for bit
    assert off[1] == on[1] and on[1]            # the same jit_once calls
    assert off[2] == on[2] > 0                  # the same bytes read back


def test_phase_without_a_tracer_is_the_shared_noop(monkeypatch):
    monkeypatch.setattr(tracing, "_CURRENT", None)
    ph = phase("bfs.plan", level=1, c_cap=4)
    assert ph is tracing.NULL_PHASE
    with ph as p, p.sync():
        assert p.set(c_count=1) is p
    ph.end()


def test_phase_outside_a_scope_only_annotates():
    tracer = Tracer()                   # enabled: the process has one
    assert tracing.current() is tracer
    with phase("bfs.plan", level=2) as ph:
        assert ph is not tracing.NULL_PHASE
        assert ph.sync() is tracing.NULL_PHASE
        ph.set(c_count=3)
    assert tracer.window(0.0) == []


def test_phase_may_end_early_and_a_disabled_scope_is_none():
    tracer = Tracer()
    root = tracer.start("b", "interactive")
    with scope(tracer, "b", root):
        with phase("admit") as admit:
            admit.set(k_runnable=1).end()
            assert tracing.current_span()[2] is root
            with phase("bfs.seed"):
                assert tracing.current_span()[2].name == "bfs.seed"
    admit_s, seed_s = tracer.spans("b")[1:]
    assert admit_s.t_end <= seed_s.t_start      # not nested in time
    assert tracing.current_span() is None
    off = Tracer(enabled=False)
    with scope(off, "b", None):
        assert tracing.current_span() is None


# -- a stretch of time -------------------------------------------------------

def ticking(start=100.0):
    t = [start]

    def clock():
        t[0] += 1.0
        return t[0]
    return clock


@pytest.mark.parametrize("t0, t1, want", [
    (0.0, None, ["a", "a.child", "b", "c"]),
    (102.0, 104.0, ["a.child"]),            # [t0, t1): by its own start
    (101.0, 102.0, ["a"]),
    (104.0, 106.0, ["b"]),
    (106.0, None, ["c"]),
    (200.0, None, []),
])
def test_window_bounds(t0, t1, want):
    tracer = Tracer(clock=ticking())
    a = tracer.start("A", "a")              # 101
    child = tracer.start("A", "a.child", parent=a, x=1)     # 102
    tracer.end(child)                       # 103
    b = tracer.start("B", "b")              # 104
    tracer.end(b)                           # 105
    tracer.event("C", "c", t0=106.0, t1=107.0)
    tracer.start("D", "open")               # never ended: not in a window
    tracer.end(a)
    got = tracer.window(t0, t1)
    assert [s["name"] for s in got] == want
    for s in got:
        assert {"trace", "span", "name", "start", "end",
                "duration_ms"} <= set(s)
    if "a.child" in want:
        c = next(s for s in got if s["name"] == "a.child")
        assert c["trace"] == "A" and c["parent"] == a.span_id
        assert c["attrs"] == {"x": 1}


def test_the_journal_holds_a_whole_run():
    """512 traces of 4,096 spans: a benchmark run makes under 200 lane
    batches of under 100 spans each, and the ``compile`` trace under a
    thousand."""
    tracer = Tracer()
    assert (tracer.max_traces, tracer.max_spans) == (512, 4096)
    for b in range(200):
        root = tracer.start(f"traverse-{b}", "interactive")
        for i in range(100):
            tracer.event(f"traverse-{b}", "bfs.sweep", parent=root)
        tracer.end(root)
    for i in range(1000):
        tracer.event("compile", "compile")
    assert len(tracer.window(0.0)) == 200 * 101 + 1000
    assert tracer.dropped("traverse-0") == tracer.dropped("compile") == 0


def _get(srv, path):
    try:
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}{path}", timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_get_trace_since(social, ids):
    from titan_tpu.server import GraphServer

    sched = JobScheduler(graph=social, autostart=False,
                         interactive_window_s=0.05)
    srv = GraphServer(social, port=0, scheduler=sched).start()
    try:
        t0 = time.time()
        res = fused_batch(sched, ids[:2])
        finished_tree(sched.tracer, res[0]["batch"])
        code, body = _get(srv, f"/trace?since={t0}")
        assert code == 200 and body["since"] == t0 and body["until"] is None
        mine = [s for s in body["spans"] if s["trace"] == res[0]["batch"]]
        assert {"interactive", "admit", "bfs.plan", "extract",
                "reply"} <= {s["name"] for s in mine}
        code, body = _get(srv, f"/trace?since={t0}&until={t0}")
        assert code == 200 and body["spans"] == []
        code, body = _get(srv, f"/trace?since={time.time() + 60}")
        assert code == 200 and body["spans"] == []
        assert _get(srv, "/trace?since=yesterday")[0] == 400
        assert _get(srv, "/trace?since=nan")[0] == 400
        assert _get(srv, f"/trace?since={t0}&until=inf")[0] == 400
        assert _get(srv, "/trace")[0] == 400
        code, tree = _get(srv, f"/trace?job={res[0]['batch']}")
        assert code == 200 and tree["spans"][0]["name"] == "interactive"
    finally:
        srv.stop()
        sched.close()
