"""Observability plane units: span tracer + Prometheus exposition.

The tracer contract (ISSUE r10): explicit spans with parent links and
an injectable clock (deterministic assertions, no sleeps), a bounded
ring-buffer journal per trace, bounded trace count, thread-safe writes,
and a disabled mode that records nothing. The exporter contract: every
registered metric renders as grammar-valid Prometheus text.
"""

import re
import threading

from titan_tpu.obs.promexport import (CONTENT_TYPE, render_prometheus,
                                      sanitize)
from titan_tpu.obs.tracing import (NULL_SPAN, TraceHandle, Tracer,
                                   trace_summary)
from titan_tpu.utils.metrics import MetricManager


class FakeClock:
    def __init__(self, t0: float = 100.0):
        self.t = t0

    def __call__(self) -> float:
        return self.t

    def tick(self, dt: float = 1.0) -> float:
        self.t += dt
        return self.t


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_tree_structure_and_durations():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.start("t1", "job", kind="bfs")
    clk.tick()
    child = tr.start("t1", "queue", parent=root)
    clk.tick(2.0)
    tr.end(child)
    tr.event("t1", "submit", parent=root)       # instant event
    clk.tick()
    tr.end(root, status="done")

    spans = tr.spans("t1")
    assert [s.name for s in spans] == ["job", "queue", "submit"]
    assert spans[1].parent_id == root.span_id
    assert spans[1].duration_ms == 2000.0
    assert spans[2].t_start == spans[2].t_end      # instant
    assert root.duration_ms == 4000.0
    assert root.attrs == {"kind": "bfs", "status": "done"}

    tree = tr.tree("t1")
    assert tree["dropped_spans"] == 0
    assert len(tree["spans"]) == 1                 # one root
    node = tree["spans"][0]
    assert node["name"] == "job"
    assert [c["name"] for c in node["children"]] == ["queue", "submit"]
    assert tr.tree("nope") is None


def test_event_with_explicit_host_timestamps():
    """The retroactive form the round seams use: wall time measured by
    the kernel's own boundary callbacks, stamped after the fact."""
    clk = FakeClock()
    tr = Tracer(clock=clk)
    s = tr.event("t", "round", t0=50.0, t1=53.5, level=3, frontier=17)
    assert s.t_start == 50.0 and s.t_end == 53.5
    assert s.duration_ms == 3500.0
    assert s.attrs == {"level": 3, "frontier": 17}
    # t0 only → window closes at the (injected) clock's now
    s2 = tr.event("t", "apply", t0=90.0)
    assert s2.t_start == 90.0 and s2.t_end == clk.t


def test_ring_buffer_drops_oldest_but_keeps_root():
    clk = FakeClock()
    tr = Tracer(clock=clk, max_spans=8)
    root = tr.start("t", "job")
    for i in range(20):
        tr.event("t", "round", parent=root, round=i)
    spans = tr.spans("t")
    assert len(spans) == 8
    assert spans[0] is root, "the root anchor must survive the ring"
    assert tr.dropped("t") == 13
    assert tr.tree("t")["dropped_spans"] == 13
    # orphaned children (parent dropped) still render as roots
    kept_rounds = [s.attrs["round"] for s in spans[1:]]
    assert kept_rounds == list(range(13, 20))


def test_trace_count_bounded_oldest_evicted():
    tr = Tracer(clock=FakeClock(), max_traces=3)
    for i in range(5):
        tr.start(f"t{i}", "job")
    assert tr.spans("t0") is None and tr.spans("t1") is None
    assert all(tr.spans(f"t{i}") is not None for i in (2, 3, 4))
    tr.discard("t3")
    assert tr.spans("t3") is None


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    s = tr.start("t", "job")
    assert s is NULL_SPAN
    assert s.set(x=1) is s
    tr.end(s)
    assert tr.event("t", "round") is NULL_SPAN
    with tr.span("t", "x") as sp:
        assert sp is NULL_SPAN
    assert tr.spans("t") is None and tr.tree("t") is None
    assert trace_summary(tr, "t") is None


def test_trace_handle_parent_switching_and_summary():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    root = tr.start("j", "job")
    h = TraceHandle(tr, "j", root)
    h.queue = h.start("queue")
    clk.tick(0.004)
    h.end(h.queue)
    h.attempt = h.start("attempt", attempt=1)
    assert h.parent is h.attempt
    fuse = h.start("fuse")
    clk.tick(0.001)
    h.end(fuse)
    run = h.start("run")
    clk.tick(0.25)
    for i in range(3):
        h.event("round", parent=run, round=i)
    h.end(run)
    h.end(h.attempt)
    tr.end(root)
    assert fuse.parent_id == h.attempt.span_id
    s = trace_summary(tr, "j")
    assert s["queue_ms"] == 4.0
    assert s["fuse_ms"] == 1.0
    # the wall of `run` is the host's; the device's time is read from
    # the trace's stamped `kernel` spans and absent where it has none
    assert s["run_ms"] == 250.0
    assert "device_ms" not in s
    assert s["rounds"] == 3
    for ms in (40.0, 2.5):
        h.event("kernel", parent=run, key="k", device_ms=ms)
    assert trace_summary(tr, "j")["device_ms"] == 42.5


def test_tracer_thread_safe_under_concurrent_writes():
    tr = Tracer()
    errs: list = []

    def writer(k):
        try:
            for i in range(200):
                tr.event(f"trace-{k % 4}", "round", round=i)
        except Exception as e:          # pragma: no cover - fail loud
            errs.append(repr(e))

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errs
    total = sum(len(tr.spans(f"trace-{i}")) + tr.dropped(f"trace-{i}")
                for i in range(4))
    assert total == 8 * 200


# ---------------------------------------------------------------------------
# prometheus exposition
# ---------------------------------------------------------------------------

# sample line grammar: name{labels} value  (exposition format 0.0.4)
_LABEL_PAIR = r"[a-zA-Z0-9_]+=\"([^\"\\]|\\.)*\""
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{" + _LABEL_PAIR + r"(," + _LABEL_PAIR + r")*\})? "
    r"[+-]?(\d+\.?\d*([eE][+-]?\d+)?|inf|nan)$")


def _assert_valid_exposition(text: str) -> list:
    lines = [ln for ln in text.splitlines() if ln]
    samples = []
    for ln in lines:
        if ln.startswith("#"):
            assert re.match(r"^# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ",
                            ln), ln
        else:
            assert _SAMPLE.match(ln), f"bad sample line: {ln!r}"
            samples.append(ln)
    return samples


def test_render_prometheus_all_three_kinds_valid():
    m = MetricManager()
    m.counter("serving.jobs.submitted").inc(42)
    m.timer("edgestore.getSlice.time").update(2_000_000)
    h = m.histogram("serving.job.latency_ms")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.update(v)
    text = render_prometheus(m)
    samples = _assert_valid_exposition(text)
    assert "serving_jobs_submitted 42" in samples
    assert "edgestore_getSlice_time_seconds_count 1" in samples
    assert "edgestore_getSlice_time_seconds_sum 0.002" in samples
    # nearest-rank over 4 samples: round(0.5 * 3) = 2 → s[2] = 3
    assert 'serving_job_latency_ms{quantile="0.5"} 3' in samples
    assert 'serving_job_latency_ms{quantile="0.95"} 4' in samples
    assert "serving_job_latency_ms_count 4" in samples
    assert "serving_job_latency_ms_sum 10" in samples
    assert text.endswith("\n")
    assert CONTENT_TYPE.startswith("text/plain; version=0.0.4")


def test_render_prometheus_empty_registry():
    assert render_prometheus(MetricManager()) == "\n"


def test_queue_depth_renders_as_gauge_not_counter():
    """serving.queue.depth is inc/dec bookkeeping — exporting it as a
    Prometheus counter would make rate()/increase() read every dequeue
    as a counter reset. The flag lives on the metric itself
    (``counter(name, gauge=True)``, set by the scheduler at startup —
    ISSUE 8 replaced promexport's name allowlist), and it is sticky:
    later unflagged get-or-create calls keep the gauge typing."""
    m = MetricManager()
    m.counter("serving.queue.depth", gauge=True).inc(3)
    m.counter("serving.queue.depth").inc(-1)     # sticky after this
    m.counter("serving.jobs.submitted").inc(3)
    text = render_prometheus(m)
    assert "# TYPE serving_queue_depth gauge" in text
    assert "serving_queue_depth 2" in text
    assert "# TYPE serving_jobs_submitted counter" in text


def test_sanitize_names():
    assert sanitize("serving.job.latency_ms") == "serving_job_latency_ms"
    assert sanitize("a b-c/d") == "a_b_c_d"
    assert sanitize("0zero") == "_0zero"
    assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$", sanitize("9!@#"))


def test_help_lines_from_description_registry():
    """ISSUE 8 satellite: ``# HELP`` text comes from promexport's
    per-name HELP registry and precedes the matching ``# TYPE``;
    undescribed names get TYPE but no HELP; the whole body still
    parses under the exposition grammar."""
    from titan_tpu.obs.promexport import HELP
    m = MetricManager()
    m.counter("serving.jobs.submitted").inc(1)
    m.counter("made.up.name").inc(1)
    m.histogram("serving.job.latency_ms").update(2.0)
    text = render_prometheus(m)
    _assert_valid_exposition(text)
    lines = text.splitlines()
    i_help = lines.index("# HELP serving_jobs_submitted "
                         + HELP["serving.jobs.submitted"])
    assert lines[i_help + 1] == "# TYPE serving_jobs_submitted counter"
    assert "# HELP serving_job_latency_ms " + \
        HELP["serving.job.latency_ms"] in lines
    assert "# TYPE made_up_name counter" in lines
    assert not any(ln.startswith("# HELP made_up_name") for ln in lines)
    # every HELP entry names a real metric family the registry can
    # create — entries must not rot as names churn (the doc-drift
    # guard covers the docs side; this pins the exposition side)
    for name, text_ in HELP.items():
        assert text_ and "\n" not in text_, name


def test_labeled_children_render_and_sum_to_parent():
    """Labeled children render as extra samples of the SAME family; the
    unlabeled parent sample equals their sum, and the parent lines are
    byte-identical to a registry that never used labels (ISSUE 8
    regression criterion for the no-tenant path)."""
    m = MetricManager()
    m.counter("serving.jobs.completed",
              labels={"tenant": "a", "kind": "bfs"}).inc(3)
    m.counter("serving.jobs.completed",
              labels={"tenant": "b", "kind": "bfs"}).inc(2)
    h = m.histogram("serving.job.latency_ms", labels={"tenant": "a"})
    for v in (1.0, 2.0, 3.0, 4.0):
        h.update(v)
    text = render_prometheus(m)
    samples = _assert_valid_exposition(text)
    assert "serving_jobs_completed 5" in samples
    assert ('serving_jobs_completed{kind="bfs",tenant="a"} 3'
            in samples)
    assert ('serving_jobs_completed{kind="bfs",tenant="b"} 2'
            in samples)
    assert 'serving_job_latency_ms{quantile="0.5"} 3' in samples
    # the summary's quantile pair lands LAST, after the child's own
    # sorted labels (promexport._labels extra convention)
    assert ('serving_job_latency_ms{tenant="a",quantile="0.95"} 4'
            in samples)
    assert 'serving_job_latency_ms_count{tenant="a"} 4' in samples
    # parent sample lines byte-identical to a never-labeled registry
    plain = MetricManager()
    plain.counter("serving.jobs.completed").inc(5)
    ph = plain.histogram("serving.job.latency_ms")
    for v in (1.0, 2.0, 3.0, 4.0):
        ph.update(v)
    plain_samples = _assert_valid_exposition(render_prometheus(plain))
    assert set(plain_samples) <= set(samples)


def test_gauges_render_with_children_and_escaping():
    m = MetricManager()
    m.gauge("serving.hbm.resident_bytes", fn=lambda: 1024)
    m.gauge("serving.slo.burn_rate", fn=lambda: 2.5,
            labels={"slo": 'we"ird\\na', "window": "300s"})
    text = render_prometheus(m)
    _assert_valid_exposition(text)
    assert "# TYPE serving_hbm_resident_bytes gauge" in text
    assert "serving_hbm_resident_bytes 1024" in text
    assert "# TYPE serving_slo_burn_rate gauge" in text
    # a children-only family (parent has no callback of its own) emits
    # NO unlabeled sample: the sum roll-up is meaningless for ratio
    # gauges like burn rates, so only the labeled children render
    assert "\nserving_slo_burn_rate 2.5" not in text
    assert ('serving_slo_burn_rate{slo="we\\"ird\\\\na",'
            'window="300s"} 2.5' in text)
    # programmatic roll-up read still available (additive families)
    assert m.gauge_value("serving.slo.burn_rate") == 2.5


# ---------------------------------------------------------------------------
# histogram quantile memo: scrape-vs-record (ISSUE 10 satellite)
# ---------------------------------------------------------------------------


def test_histogram_quantile_memo_invalidates_on_update():
    """The sorted reservoir is cached on the sample-count watermark:
    two reads without an update share ONE sort, any update (including
    reservoir replacement past max_samples) invalidates it."""
    from titan_tpu.utils.metrics import Histogram

    h = Histogram(max_samples=64)
    for v in range(10):
        h.update(float(v))
    first = h._sorted_samples()
    assert h._sorted_samples() is first          # memo hit: same list
    assert h.percentile(50) == 4.0 or h.percentile(50) == 5.0
    h.update(100.0)
    second = h._sorted_samples()
    assert second is not first                   # watermark moved
    assert h.to_dict()["max"] == 100.0
    # past max_samples every update still bumps count -> still fresh
    for v in range(200):
        h.update(float(v))
    assert len(h._sorted_samples()) == 64
    assert h._sorted_samples() == sorted(h.values())


def test_histogram_concurrent_scrape_vs_record_stress():
    """Prometheus scrapes (p50+p95 via to_dict / render) racing a
    recording thread must never throw, and every scrape must see a
    coherent sorted view (p50 <= p95, count monotone)."""
    m = MetricManager()
    h = m.histogram("serving.job.latency_ms")
    stop = threading.Event()
    errors = []

    def recorder():
        v = 0
        while not stop.is_set():
            h.update(float(v % 997))
            v += 1

    def scraper():
        last_count = 0
        while not stop.is_set():
            try:
                d = h.to_dict()
                assert d["p50"] <= d["p95"] <= d["max"] + 1e-9
                assert d["count"] >= last_count
                last_count = d["count"]
                text = render_prometheus(m)
                assert "serving_job_latency_ms" in text
            except Exception as e:               # pragma: no cover
                errors.append(e)
                return

    threads = [threading.Thread(target=recorder) for _ in range(2)] + \
              [threading.Thread(target=scraper) for _ in range(2)]
    for t in threads:
        t.start()
    import time as _time
    _time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(5)
    assert not errors, errors
    assert h.to_dict()["count"] > 0
