"""``kernel`` spans (ISSUE 38, ``obs/devprof``'s watcher): every call
through the ``jit_once`` shim under an installed profiler and an enabled
tracer leaves ONE span under the phase that dispatched it, stamped when
its output became ready: ``device_ms = ready - max(dispatched, previous
ready)``. On the CPU: the spans, their attributes and the arithmetic
between two calls, never a time. A program that runs tens of
milliseconds stands in for a device program (the CPU backend dispatches
asynchronously too, so a second call queues behind the first).
"""

import functools
import inspect
import os
import re
import threading
import time

import numpy as np
import pytest

from titan_tpu.obs import devprof, tracing
from titan_tpu.utils import jitcache
from titan_tpu.utils.metrics import MetricManager

from test_served_pagerank import Served as PrServed, simple_undirected
from test_served_wcc import Served as WccServed, many_small

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WATCHER = "devprof-watcher"


def _heavy():
    """~50 ms on a CPU core; ``reps`` is static, as a kernel's caps."""
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("reps", "tag"))
        def heavy(x, reps: int, tag: str = "t"):
            out = jax.lax.fori_loop(0, reps,
                                    lambda i, y: jnp.sin(y @ y), x)
            return out, jnp.sum(out[:1, :1])
        return heavy
    return jitcache.jit_once("kspan_heavy", build)


def _light():
    def build():
        import jax

        @jax.jit
        def light(x):
            return x + 1.0
        return light
    return jitcache.jit_once("kspan_light", build)


@pytest.fixture
def x():
    import jax.numpy as jnp
    got = jnp.ones((400, 400), jnp.float32)
    _heavy()(got, reps=30)[0].block_until_ready()        # built
    _light()(got).block_until_ready()
    return got


@pytest.fixture
def alone():
    """No other test's profiler: what is counted here is this test's."""
    saved = list(devprof._PROFILERS)
    devprof._PROFILERS.clear()
    jitcache.set_profile_dispatch(None)
    devprof._WATCHER.stop(5.0)
    yield
    devprof._PROFILERS[:] = saved
    if saved:
        jitcache.set_profile_dispatch(devprof._dispatch)


def _kernels(tracer, trace_id):
    return [s for s in tracer.spans(trace_id) or [] if s.name == "kernel"]


def test_one_span_a_call_under_the_current_phase(x, alone):
    tracer, mm = tracing.Tracer(), MetricManager()
    root = tracer.start("t", "run")
    with devprof.DeviceCostProfiler(metrics=mm) as prof:
        with tracing.scope(tracer, "t", root):
            before = time.time()
            with tracing.phase("bfs.level", level=3) as ph:
                _heavy()(x, reps=30, tag="a")
                level = ph._span
            _light()(x)                  # under the root, no phase open
        assert devprof.drain(10.0)
        after = time.time()
        stats = prof.kernel_stats()
    heavy, light = _kernels(tracer, "t")
    assert heavy.parent_id == level.span_id
    assert light.parent_id == root.span_id
    a = heavy.attrs
    assert (a["key"], a["fn"]) == ("kspan_heavy", "heavy")
    assert (a["reps"], a["tag"]) == (30, "a")            # the statics
    assert a["stamped"] is True and a["queued_ms"] == 0.0
    assert a["dispatch_ms"] >= 0.0 and a["device_ms"] > 0.0
    # the span's extent IS the device interval, on time.time()
    assert before <= heavy.t_start <= heavy.t_end <= after
    assert (heavy.t_end - heavy.t_start) * 1e3 == \
        pytest.approx(a["device_ms"], abs=1e-3)
    assert light.attrs["key"] == "kspan_light"
    # device.exec.ms and kernel_stats()'s exec_s are the stamped time
    h = mm.histogram("device.exec.ms",
                     labels={"kernel": "kspan_heavy"}).to_dict()
    assert h["count"] == 1
    assert h["total"] == pytest.approx(
        (heavy.t_end - heavy.t_start) * 1e3, abs=1e-3)
    assert stats["kspan_heavy"]["exec_s"] == pytest.approx(
        heavy.t_end - heavy.t_start, abs=1e-6)
    assert mm.counter_value("device.exec.unstamped") == 0


def test_back_to_back_programs_queue_and_do_not_overlap(x, alone):
    tracer = tracing.Tracer()
    root = tracer.start("t", "run")
    with devprof.DeviceCostProfiler(metrics=MetricManager()):
        with tracing.scope(tracer, "t", root):
            t0 = time.time()
            _heavy()(x, reps=30)
            _heavy()(x, reps=30)
            dispatched_in = time.time() - t0
        assert devprof.drain(10.0)
    first, second = _kernels(tracer, "t")
    assert dispatched_in < (first.t_end - first.t_start)   # it was async
    assert second.attrs["queued_ms"] > 0.0
    assert first.t_end <= second.t_start <= second.t_end
    assert second.attrs["device_ms"] > 0.0


def test_a_deleted_output_is_unstamped_counted_and_raises_nothing(
        x, alone):
    """An output donated to the next program, or deleted, before the
    watcher reached it: ``stamped: false``, one count, its time left to
    the next stamped program; the caller's thread sees nothing."""
    tracer, mm = tracing.Tracer(), MetricManager()
    root = tracer.start("t", "run")
    with devprof.DeviceCostProfiler(metrics=mm):
        with tracing.scope(tracer, "t", root):
            _heavy()(x, reps=30)         # holds the watcher meanwhile
            gone = _light()(x)
            gone.delete()
            kept = _light()(x)
        assert devprof.drain(10.0)
    assert float(np.asarray(kept)[0, 0]) == 2.0
    heavy, lost, after = _kernels(tracer, "t")
    assert heavy.attrs["stamped"] and after.attrs["stamped"]
    assert lost.attrs["stamped"] is False
    assert lost.attrs["device_ms"] == 0.0
    assert lost.t_start == lost.t_end
    assert heavy.t_end <= after.t_start <= after.t_end
    assert mm.counter("device.exec.unstamped",
                      labels={"kernel": "kspan_light"}).count == 1
    assert mm.histogram("device.exec.ms",
                        labels={"kernel": "kspan_light"}).count == 1


def test_a_call_inside_an_outer_trace_is_unstamped(x, alone):
    """A shimmed kernel called while an outer ``jit`` traces returns
    tracers: nothing to wait on, so the call is counted unstamped, no
    tracer leaves its trace and the watcher lives on."""
    import jax

    mm = MetricManager()
    with devprof.DeviceCostProfiler(metrics=mm):
        got = jax.jit(lambda y: _light()(y) * 2.0)(x)
        _light()(x)
        assert devprof.drain(10.0)
        assert devprof._WATCHER.alive
    assert float(np.asarray(got)[0, 0]) == 4.0
    assert mm.counter("device.exec.unstamped",
                      labels={"kernel": "kspan_light"}).count == 1
    assert mm.histogram("device.exec.ms",
                        labels={"kernel": "kspan_light"}).count == 1


def test_a_full_queue_drops_the_stamp_and_never_blocks(x, alone,
                                                       monkeypatch):
    import queue

    tracer, mm = tracing.Tracer(), MetricManager()
    root = tracer.start("t", "run")
    monkeypatch.setattr(devprof._WATCHER, "_q", queue.Queue(1))
    with devprof.DeviceCostProfiler(metrics=mm):
        with tracing.scope(tracer, "t", root):
            _heavy()(x, reps=30)
            for _ in range(4):
                _light()(x)
        assert devprof.drain(10.0)
    assert mm.counter_value("device.exec.calls") == 5
    assert len(_kernels(tracer, "t")) \
        + mm.counter_value("device.exec.unstamped") == 5
    assert mm.counter_value("device.exec.unstamped") >= 2


def test_profiler_off_no_span_and_no_thread(x, alone):
    tracer = tracing.Tracer()
    root = tracer.start("t", "run")
    assert jitcache._PROFILE_DISPATCH is None
    with tracing.scope(tracer, "t", root):
        _heavy()(x, reps=30)
    assert devprof.drain(1.0)
    assert _kernels(tracer, "t") == []
    assert WATCHER not in [t.name for t in threading.enumerate()]
    # ... and the thread a profiler started ends with the last profiler
    with devprof.DeviceCostProfiler(metrics=MetricManager()):
        _light()(x)
        assert WATCHER in [t.name for t in threading.enumerate()]
    assert WATCHER not in [t.name for t in threading.enumerate()]


def test_no_scope_or_a_tracer_off_stamps_the_metric_alone(x, alone):
    """A call outside any scope, and one under the scope of a disabled
    tracer (which makes nothing current): stamped for the metric, no
    span anywhere, whatever other tracer the process has."""
    bystander, off, mm = tracing.Tracer(), tracing.Tracer(enabled=False), \
        MetricManager()
    with devprof.DeviceCostProfiler(metrics=mm):
        _light()(x)
        with tracing.scope(off, "t", off.start("t", "run")):
            _heavy()(x, reps=30)
        assert devprof.drain(10.0)
    assert bystander.window(0.0) == [] and off.window(0.0) == []
    assert mm.histogram("device.exec.ms",
                        labels={"kernel": "kspan_light"}).count == 1
    assert mm.histogram("device.exec.ms",
                        labels={"kernel": "kspan_heavy"}).count == 1


# -- served jobs: the spans under the phases of the two job cells ----------

#: the keys a phase may dispatch (``bfs.level`` by its ``dir``)
WCC_KEYS = {
    ("bfs.level", "head"): {"hybrid_head"},
    ("bfs.level", "td"): {"hybrid_td", "hybrid_frontier_of"},
    ("bfs.level", "bu"): {"hybrid_bu_start", "hybrid_bu_startL",
                          "hybrid_lead", "hybrid_bu_finish0",
                          "hybrid_bu_more", "hybrid_ex"},
    ("bfs.level", "end"): {"hybrid_endgame"},
    ("wcc.seed", None): {"wcc_seed_labels"},
    ("wcc.propagate", None): {"frontier_listplan_wcc",
                              "frontier_bandplan_wcc",
                              "frontier_pushlist_wcc"},
}


def _closed_journal(served, body):
    """One served job, then ``close()``: the envelope and the job's
    spans as the journal holds them once the scheduler has drained."""
    try:
        env = served.job(body)
        assert env["status"] == "done", env
    finally:
        served.close()
    spans = served.sched.tracer.spans(env["job"])
    return env, {s.span_id: s for s in spans}, \
        [s for s in spans if s.name == "kernel"]


def test_a_served_wcc_job_carries_its_kernels_under_its_phases():
    served = WccServed(*many_small(3000000601))
    env, by_id, kernels = _closed_journal(
        served, {"kind": "wcc", "timeout_s": 60})
    # (at this size a push's smallest output can be a buffer the next
    # push is given: a call or two may be unstamped, and is counted)
    lost = [k for k in kernels if not k.attrs["stamped"]]
    assert kernels and len(lost) <= \
        served.metrics.counter_value("device.exec.unstamped")
    assert {k.attrs["key"] for k in lost} <= {"frontier_pushlist_wcc"}
    seen = set()
    for k in kernels:
        parent = by_id[k.parent_id]
        where = (parent.name, (parent.attrs or {}).get("dir"))
        assert k.attrs["key"] in WCC_KEYS[where], (where, k.attrs)
        seen.add(where)
    levels = [s for s in by_id.values() if s.name == "bfs.level"]
    assert {("bfs.level", s.attrs["dir"]) for s in levels} <= seen
    assert ("wcc.propagate", None) in seen
    # close() drained: every profiled call has its stamp, and those
    # made under a scope their span
    m = served.metrics
    assert m.histogram("device.exec.ms").count \
        + m.counter_value("device.exec.unstamped") \
        == m.counter_value("device.exec.calls")
    everywhere = [s for s in served.sched.tracer.window(0.0)
                  if s["name"] == "kernel"]
    assert len(kernels) == len(everywhere) <= \
        m.counter_value("device.exec.calls")
    # the digest's device_ms is the stamped sum
    digest = tracing.trace_summary(served.sched.tracer, env["job"])
    assert digest["device_ms"] == pytest.approx(
        sum(k.attrs["device_ms"] for k in kernels), abs=1e-2)
    assert 0.0 < digest["device_ms"] and digest["run_ms"] > 0.0


def test_a_served_pagerank_job_carries_its_kernels_under_its_phases():
    n, src, dst = simple_undirected(5, n=1 << 9, m=1 << 12)
    _env, by_id, kernels = _closed_journal(
        PrServed(n, src, dst), {"kind": "pagerank", "iterations": 4})
    got = [(by_id[k.parent_id].name, k.attrs["key"]) for k in kernels]
    # (the cut to [n] is dispatched just before `pr.result` opens)
    assert got == [("pr.sweep", "pagerank_pull"),
                   ("pr.finish", "pagerank_finish")] * 4 \
        + [("run", "pagerank_result")]
    assert all(k.attrs["fn"] and k.attrs["stamped"] for k in kernels)
    # one device, one queue: the intervals never overlap
    assert all(a.t_end <= b.t_start for a, b in zip(kernels, kernels[1:]))
    (pull,) = {k.attrs["impl"] for k in kernels
               if k.attrs["key"] == "pagerank_pull"}
    assert pull == "xla"                 # the CPU's road, a static


# -- the naming table of docs/observability.md, held against the code ------

_ROW = re.compile(r"^\| `([A-Za-z0-9_<>]+)` \| `([A-Za-z0-9_]+)` \|", re.M)


def _doc_names() -> dict:
    with open(os.path.join(ROOT, "docs", "observability.md")) as f:
        text = f.read()
    table = text[text.index("### Kernel names"):]
    return dict(_ROW.findall(table[:table.index("\n#", 1)]))


def _built_names() -> dict:
    """``{key: fn}`` of every kernel the model modules build through
    ``jit_once`` from a builder without arguments (or with a ``kind``)."""
    from titan_tpu.models import (bfs_hybrid, frontier, pagerank,
                                  pagerank_pull)
    before = set(jitcache._JITS)
    built = {}
    for mod in (bfs_hybrid, frontier, pagerank, pagerank_pull):
        for name, fn in inspect.getmembers(mod, inspect.isfunction):
            if fn.__module__ != mod.__name__:
                continue
            src = inspect.getsource(fn)
            if "jit_once(" not in src and "_get(" not in src:
                continue
            params = list(inspect.signature(fn).parameters)
            shims = [fn()] if not params else \
                [fn(k) for k in ("sssp", "wcc")] if params == ["kind"] \
                else []
            for shim in shims:
                key = next(k for k, v in jitcache._JITS.items()
                           if v is shim)
                built[key] = shim.__name__
    assert before <= set(jitcache._JITS)
    return built


def test_the_docs_naming_table_is_the_codes():
    doc, built = _doc_names(), _built_names()
    assert len(built) > 25
    for key, fn in built.items():
        row = re.sub(r"_(sssp|wcc)$", "_<kind>", key) \
            if key.startswith("frontier_") else key
        assert doc.get(row) == fn, (key, fn, doc.get(row))
    # rows whose builder takes arguments: checked where they are built
    for key in set(doc) - set(built):
        shim = jitcache._JITS.get(key)
        if shim is not None:
            assert shim.__name__ == doc[key], key
    assert {"pagerank_pull", "hybrid_lead"} <= set(doc)
