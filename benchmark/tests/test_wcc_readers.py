"""The seven readers of the WCC cell on a recorded run: a hand-made
journal of two jobs in the window (one that began before it, one that was
cancelled in its peel, one follower of a cohort), the samples' envelopes
and the graph's counts — and a program that writes none of it, where
each reports nothing."""

import pytest

import files
import spans

NEW = ["wcc_exec_ms", "wcc_peel_ms", "wcc_prop_ms", "bfs_pull_share",
       "wcc_job_roofline", "wcc_queue_ms", "wcc_fetch_ms"]
CELL = "g500-24.wcc-c2"
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def job(trace, start, steps, prop_ms, result_ms, k=1):
    """A job's phases: ``steps`` = [(dir, ms)] of the peel with 10 ms of
    host between two steps, then seed, propagation and readback."""
    t = start
    out = [span(trace, "fuse", start, 0.0, k=k, kind="wcc"),
           span(trace, "job.lease", start, 2.0),
           span(trace, "job.admit", start, 90.0 + 10.0 * k, bytes=99),
           span(trace, "run", start, 0.0, kind="wcc", k=k)]
    for level, (direction, ms) in enumerate(steps):
        out.append(span(trace, "bfs.level", t, ms, level=level,
                        dir=direction, sync_ms=ms - 1.0))
        t += (ms + 10.0) / 1e3
    out.append(span(trace, "wcc.seed", t, 1.0, levels=7, source_deg=99))
    t += 1e-3
    out.append(span(trace, "wcc.propagate", t, prop_ms, rounds=2,
                    sync_ms=prop_ms - 2.0))
    t += prop_ms / 1e3
    out.append(span(trace, "wcc.result", t, result_ms, bytes=4000,
                    sync_ms=result_ms))
    out.append(span(trace, "wcc.count", t + result_ms / 1e3, 20.0))
    return out


# job-1: head 100, td 200, bu 600, end 100 with three gaps of 10: the
# peel's extent 1030 ms, the pull 600 of 1000 = 60 %; propagation 300.
# job-2: head 100, bu 1500, bu 300, end 100: extent 2030, the pull 1800
# of 2000 = 90 %; propagation 500. job-0 began before the window; job-3
# was cancelled in its peel (no wcc.seed); job-4 is a cohort's follower.
JOURNAL = (
    job("job-0", T0 - 5.0, [("head", 50.0), ("end", 50.0)], 100.0, 10.0)
    + job("job-1", T0 + 1.0, [("head", 100.0), ("td", 200.0),
                              ("bu", 600.0), ("end", 100.0)], 300.0, 40.0)
    + job("job-2", T0 + 4.0, [("head", 100.0), ("bu", 1500.0),
                              ("bu", 300.0), ("end", 100.0)], 500.0, 60.0,
          k=2)
    + job("job-3", T0 + 8.0, [("head", 100.0), ("bu", 7000.0)],
          0.0, 0.0)[:6]
    + [span("job-4", "fuse", T0 + 4.0, 0.0, k=2, kind="wcc"),
       span("job-4", "run", T0 + 4.0, 0.0, kind="wcc", k=2),
       span("traverse-1", "bfs.sweep", T0 + 2.0, 5.0, level=1)])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 2.5 s of 5; 4 answered jobs in 10 s,
# so a job holds the device 0.5 / 0.4 = 1.25 s.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "wcc"}}},
    "trace": {"busy_s": 2.5, "window_s": 5.0},
    "samples": [
        sample(0, True, wait_ms=10.0, exec_ms=2100.0, fetch_ms=30.0),
        sample(1, True, wait_ms=2000.0, exec_ms=3100.0, fetch_ms=50.0),
        sample(2, True, wait_ms=2200.0, exec_ms=2500.0, fetch_ms=40.0),
        sample(3, True, wait_ms=2200.0, exec_ms=2700.0, fetch_ms=40.0),
        sample(4, False)]}


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
    ("wcc_exec_ms", 2600.0),            # median of 2100 2500 2700 3100
    ("wcc_peel_ms", 1530.0),            # median of 1030 and 2030
    ("wcc_prop_ms", 400.0),             # median of 300 and 500
    ("bfs_pull_share", 75.0),           # median of 60 and 90
    # 4 B x 20,000 slots + 12 B x 1,000 vertices = 92,000 B in the
    # device's 1.25 s a job, of 819 GB/s
    ("wcc_job_roofline", 100.0 * 92000 / (1.25 * 819e9)),
    ("wcc_queue_ms", 2100.0),           # median of 10 2000 2200 2200
    ("wcc_fetch_ms", 40.0),             # median of 30 40 40 50
])
def test_reader_on_a_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_the_printed_lines(journal, capsys):
    read("wcc_exec_ms")
    read("wcc_peel_ms")
    read("wcc_prop_ms")
    out = capsys.readouterr().out
    # job-3 and job-4 count among the cohorts, not among the peels
    assert "wcc jobs by cohort size (k: jobs): {1: 2, 2: 2}" in out
    # job-1, job-2 (k = 2: 110 ms) and job-3 were admitted in the window
    assert "host job.admit: median 100.0ms in 3 jobs" in out
    assert "host job.lease: median 2.0ms in 3 jobs" in out
    assert "host wcc.count: median 20.0ms in 2 jobs" in out
    assert "peel L0 head: median 100.0ms (sync 99.0ms) in 2 jobs" in out
    assert "peel L1 bu: median 1500.0ms" in out
    assert "peel L2 bu: median 450.0ms (sync 449.0ms) in 2 jobs" in out
    assert "propagation: median rounds 2.0, sync 398.0ms; wcc.result " \
           "median 50.0ms in 2 jobs" in out


def test_the_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "wcc_job").count
    assert count({"n": 1000, "edge_slots": 20000}) == {
        "ops": 22000, "bytes": 92000}
    # graph500-24 as generated (the configuration's file): 2.19 GB
    gen = files.load_json("configs", "graphalytics-g500-24.json")[
        "generated"]
    assert gen["directed_edge_slots"] == 2 * gen["edges"]
    assert count({"n": gen["vertices"],
                  "edge_slots": gen["directed_edge_slots"]})["bytes"] \
        == 4 * 520749364 + 12 * 8871268 == 2189452672


def test_a_device_that_is_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):       # the CPU of the sandbox
        read("wcc_job_roofline")


@pytest.mark.parametrize("trace", [
    None,                                   # --trace 0
    {"busy_s": 0.0, "window_s": 5.0},       # no operation on the device
])
def test_the_roofline_is_the_device_traces_alone(journal, a_chip, trace):
    assert read("wcc_peel_ms", dict(RECORD, trace=trace)) \
        == pytest.approx(1530.0)
    assert read("wcc_job_roofline", dict(RECORD, trace=trace)) is None


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_writes_none_of_it(monkeypatch, name):
    """No journal (an older commit), no envelope fields, no graph counts:
    nothing is reported and nothing raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = {"window": RECORD["window"],
            "samples": [sample(0, True), sample(1, False)]}
    assert read(name, bare) is None


@pytest.mark.parametrize("name", ["wcc_peel_ms", "wcc_prop_ms",
                                  "bfs_pull_share"])
def test_a_journal_without_the_jobs_phases(monkeypatch, name):
    """The parent's program: a journal, its ``run`` spans, no
    ``bfs.level`` and no ``wcc.*`` in it."""
    class Older:
        def window(self, t0, t1=None):
            return [span("job-1", "run", T0 + 1.0, 3000.0, kind="wcc"),
                    span("job-1", "round", T0 + 2.0, 5.0, round=0)]
    monkeypatch.setattr(spans, "journal", Older)
    assert read(name) is None


def test_every_new_metric_is_declared_with_its_reader():
    bench = files.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    # frontier_wcc and the propagation live in models/frontier.py
    frontier = "kernels (models/frontier.py, ops/compaction.py)"
    assert {n for n in NEW if declared[n]["layer"] == frontier} \
        == {"wcc_prop_ms", "wcc_job_roofline"}
    for name in NEW:
        m = declared[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "throughput" \
            and m["layer"] in layers | {frontier}
        assert callable(files.load_module("layer_metrics", name).read)
    assert declared["wcc_job_roofline"]["unit"] == "%"
    assert declared["wcc_job_roofline"]["source"] == "device_trace"
    assert declared["wcc_job_roofline"]["better"] == "higher"
    # the new entries close their lists, and no accepted list names the
    # new cell
    assert [m["name"] for m in bench["per_layer"]][-7:] == NEW
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "graphalytics-g500-24"
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", [])] == NEW


def test_the_mix_and_the_configuration():
    bench, cell, config, mix = files.cell_files(CELL)
    assert cell["chips"] == 1 and config["reduced"] == {}
    assert (config["scale"], config["edge_factor"]) == (24, 16)
    assert config["algorithm"] == {"name": "WCC"}
    assert config["graph_seed"] == files.load_json(
        "configs", "graphalytics-g500-22.json")["graph_seed"]
    assert (mix["driver"], mix["op"], mix["callers"]) == \
        ("closed_jobs", "wcc", 2)
    assert mix["request"] == {"path": "/jobs", "body": {
        "kind": "wcc", "timeout_s": 300}}
    assert (mix["poll_s"], mix["request_timeout_s"], mix["pools"],
            mix["result_array"], mix["trace_slice_s"]) == \
        (0.1, 300, {}, "labels", 5)
    entry = bench["configs"][-1]
    assert entry["source"] == config["source"] and entry["reduced"] == []
