"""The five readers of the PageRank cell on a recorded run: a hand-made
journal of two jobs in the window (and one that began before it), the
samples' envelopes, and the graph's counts — and a program that writes
none of it, where each reports nothing."""

import pytest

import files
import spans

NEW = ["job_queue_ms", "job_exec_ms", "pr_iter_ms", "result_fetch_ms",
       "pr_iter_roofline"]
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def job(trace, start, iterations, dispatch_ms, drain_ms):
    """A job's phases as the asynchronous loop leaves them: every sweep
    and finish a dispatch, the device's time inside ``pr.result``."""
    t, out = start, [span(trace, "run", start, 0.0, kind="pagerank")]
    for it in range(1, iterations + 1):
        out.append(span(trace, "pr.sweep", t, dispatch_ms, it=it))
        t += dispatch_ms / 1e3
        out.append(span(trace, "pr.finish", t, 1.0, it=it))
        t += 1e-3
        out.append(span(trace, "round", t, 0.0, round=it))
    out.append(span(trace, "pr.result", t, drain_ms, bytes=4 * 1000,
                    sync_ms=drain_ms - 0.5))
    return out


# job-1: 10 iterations, 10 x (4 + 1) ms of dispatch then 1950 ms in the
# readback: 2000 ms, 200 ms an iteration. job-2: 10 x (9 + 1) + 2900 =
# 3000 ms, 300 an iteration. job-0 began before the window (its spans
# are not in it); job-3 was cancelled in its fourth sweep: no result.
JOURNAL = (job("job-0", T0 - 5.0, 10, 4.0, 1000.0)
           + job("job-1", T0 + 1.0, 10, 4.0, 1950.0)
           + job("job-2", T0 + 4.0, 10, 9.0, 2900.0)
           + job("job-3", T0 + 8.0, 3, 4.0, 0.0)[:-1]
           + [span("traverse-1", "bfs.sweep", T0 + 2.0, 5.0, level=1)])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 4.5 s of 5; 3 answered jobs in 10 s,
# so a job holds the device 0.9 / 0.3 = 3 s and an iteration of ten 0.3 s.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "pagerank", "iterations": 10}}},
    "trace": {"busy_s": 4.5, "window_s": 5.0},
    "samples": [
        sample(0, True, wait_ms=10.0, exec_ms=2100.0, fetch_ms=30.0),
        sample(1, True, wait_ms=2000.0, exec_ms=3100.0, fetch_ms=50.0),
        sample(2, True, wait_ms=2200.0, exec_ms=2500.0, fetch_ms=40.0),
        sample(3, False)]}


class Journal:
    def window(self, t0, t1=None):
        return [s for s in JOURNAL
                if s["start"] >= t0 and (t1 is None or s["start"] < t1)]


@pytest.fixture
def journal(monkeypatch):
    monkeypatch.setattr(spans, "journal", Journal)


@pytest.fixture
def a_chip(monkeypatch):
    """The device the peaks table knows, for the one reader that asks."""
    import jax

    class Device:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Device()])


def read(name, record=RECORD):
    return files.load_module("layer_metrics", name).read(record)


@pytest.mark.parametrize("name, want", [
    ("job_queue_ms", 2000.0),
    ("job_exec_ms", 2500.0),
    ("result_fetch_ms", 40.0),
    ("pr_iter_ms", 250.0),              # median of 200 and 300
    # 8 B x 20,000 slots + 12 B x 1,000 vertices = 172,000 B in the
    # device's 0.3 s an iteration (not the journal's 0.25), of 819 GB/s
    ("pr_iter_roofline", 100.0 * 172000 / (0.3 * 819e9)),
])
def test_reader_on_a_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_pr_iter_ms_job_by_job():
    per_job = files.load_module("layer_metrics", "pr_iter_ms").per_job
    got = per_job(Journal().window(T0, T0 + 10.0))
    assert got == pytest.approx([200.0, 300.0], rel=1e-9)


def test_the_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "pagerank_iteration").count
    assert count({"n": 1000, "edge_slots": 20000}) == {
        "ops": 44000, "bytes": 172000}
    # graph500-22 as generated (the configuration's file): 1.06 GB
    gen = files.load_json("configs", "graphalytics-g500-22.json")[
        "generated"]
    assert count({"n": gen["vertices"],
                  "edge_slots": gen["directed_edge_slots"]})["bytes"] \
        == 8 * 128302936 + 12 * 2396390 == 1055180168
    share = files.load_module("layer_metrics", "pr_iter_roofline").share
    assert share(819e9, 1000.0, 819e9) == pytest.approx(100.0)


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):       # the CPU of the sandbox
        read("pr_iter_roofline")


@pytest.mark.parametrize("trace", [
    None,                                   # --trace 0
    {"busy_s": 0.0, "window_s": 5.0},       # no operation on the device
])
def test_the_roofline_is_the_device_traces_alone(journal, a_chip, trace):
    """The journal's spans are there and the share is not read from
    them: without device time in a trace it reports nothing."""
    assert read("pr_iter_ms", dict(RECORD, trace=trace)) \
        == pytest.approx(250.0)
    assert read("pr_iter_roofline", dict(RECORD, trace=trace)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_writes_none_of_it(monkeypatch, name):
    """No journal (an older commit), no envelope fields, no graph counts:
    nothing is reported and nothing raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = {"window": RECORD["window"],
            "samples": [sample(0, True), sample(1, False)]}
    assert read(name, bare) is None


def test_no_job_in_the_window(monkeypatch, a_chip):
    class Empty:
        def window(self, t0, t1=None):
            return [span("traverse-1", "bfs.sweep", T0 + 2.0, 5.0)]
    monkeypatch.setattr(spans, "journal", Empty)
    assert read("pr_iter_ms") is None
    unanswered = dict(RECORD, samples=[sample(3, False)])
    assert read("pr_iter_roofline", unanswered) is None


def test_every_new_metric_is_declared_with_its_reader():
    bench = files.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = declared[name]
        assert m["workloads"] == ["g500-22.pr-c2"]
        assert m["moves"] == "throughput" and m["layer"] in layers | {
            "scheduler and batcher (olap/serving)"}
        assert callable(files.load_module("layer_metrics", name).read)
    assert declared["pr_iter_roofline"]["unit"] == "%"
    assert declared["pr_iter_roofline"]["source"] == "device_trace"
    assert declared["pr_iter_roofline"]["better"] == "higher"
