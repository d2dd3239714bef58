"""The six readers of the CDLP cell on a recorded run: a hand-made
journal of two jobs in the window (and one that began before it, one of
another kind), the samples' envelopes and the graph's counts — and a
program that writes none of it, where each reports nothing."""

import pytest

import files
import spans

NEW = ["cdlp_exec_ms", "cdlp_round_ms", "cdlp_sort_ms", "cdlp_gather_ms",
       "cdlp_host_idle_ms", "cdlp_round_roofline"]
CELL = "g500-22.cdlp-c2"
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def kernel(trace, key, start, ms, **attrs):
    return span(trace, "kernel", start, ms, key=key, fn=key[5:],
                device_ms=ms, stamped=True, **attrs)


def job(trace, start, rounds, gather_ms, sort_ms, vote_ms, admit_ms):
    """A job: lease 2 ms, admission, then ``rounds`` rounds whose three
    programs follow each other on the device with no gap, the readback
    waiting for the last and 30 ms of transfer behind it, the count."""
    out = [span(trace, "job.lease", start, 2.0),
           span(trace, "job.admit", start + 0.002, admit_ms, bytes=777),
           span(trace, "run", start, 0.0, kind="cdlp")]
    t = start + 0.002 + admit_ms / 1e3
    for it in range(1, rounds + 1):
        out.append(span(trace, "cdlp.round", t, 1.0, it=it, impl="vmem"))
        for key, ms, attrs in (("cdlp_gather", gather_ms,
                                {"impl": "vmem"}),
                               ("cdlp_sort", sort_ms, {}),
                               ("cdlp_vote", vote_ms, {})):
            out.append(kernel(trace, key, t, ms, **attrs))
            t += ms / 1e3
    out.append(span(trace, "cdlp.result", t - 0.001, 31.0, bytes=4000,
                    sync_ms=31.0))
    out.append(span(trace, "cdlp.count", t + 0.030, 10.0))
    return out


# job-1: 2 rounds of 100 + 300 + 50: 450 ms a round; idle: lease 2 +
# admit 60 before the first program, 30 + 10 behind the last: 102 ms.
# job-2: 2 rounds of 120 + 340 + 60: 520 ms a round; idle 2 + 100 + 40:
# 142 ms. job-0 began before the window; the PageRank job is not ours.
JOURNAL = (
    job("job-0", T0 - 5.0, 2, 10.0, 10.0, 10.0, 5.0)
    + job("job-1", T0 + 1.0, 2, 100.0, 300.0, 50.0, 60.0)
    + job("job-2", T0 + 4.0, 2, 120.0, 340.0, 60.0, 100.0)
    + [span("job-9", "job.lease", T0 + 6.0, 2.0),
       span("job-9", "pr.sweep", T0 + 6.1, 1.0, it=1),
       kernel("job-9", "pagerank_pull", T0 + 6.1, 500.0, impl="vmem")])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 4 s of 5; 2 answered jobs in 10 s, so
# a job holds the device 0.8 / 0.2 = 4 s, a round (the mix asks 2) 2 s.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "cdlp", "iterations": 2}}},
    "trace": {"busy_s": 4.0, "window_s": 5.0},
    "samples": [
        sample(0, True, wait_ms=10.0, exec_ms=1100.0, fetch_ms=30.0),
        sample(1, True, wait_ms=900.0, exec_ms=1300.0, fetch_ms=50.0),
        sample(2, False)]}


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
    ("cdlp_exec_ms", 1200.0),           # median of 1100 and 1300
    ("cdlp_round_ms", 485.0),           # median of 450 and 520
    ("cdlp_sort_ms", 320.0),            # median of 300 300 340 340
    ("cdlp_gather_ms", 110.0),          # median of 100 100 120 120
    ("cdlp_host_idle_ms", 122.0),       # median of 102 and 142
    # 8 B x 20,000 slots + 8 B x 1,000 vertices = 168,000 B in the
    # device's 2 s a round, of 819 GB/s
    ("cdlp_round_roofline", 100.0 * 168000 / (2.0 * 819e9)),
])
def test_on_the_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_what_the_readers_print(journal, capsys):
    read("cdlp_exec_ms")
    read("cdlp_round_ms")
    read("cdlp_gather_ms")
    read("cdlp_host_idle_ms")
    out = capsys.readouterr().out
    assert "host job.admit: median 80.0ms in 2 jobs, bytes [777]" in out
    assert "host cdlp.result: median 31.0ms in 2 jobs, bytes [4000]" in out
    assert "host cdlp.count: median 10.0ms in 2 jobs" in out
    assert "kernel cdlp_sort (sort): 2 calls a job, median 320.00ms " \
           "a call, 640.0ms a job" in out
    assert "kernel cdlp_gather: 4 calls, impl ['vmem']" in out
    assert "idle under job.admit: median 80.0ms a job" in out
    assert "idle under cdlp.result: median 30.0ms a job" in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_journal_or_the_trace(monkeypatch, name):
    """The parent commit on this cell, or tracing off: no journal, no
    ``kernel`` span, no device plane: each reader reports nothing (the
    envelope's reader still reads the envelope) and none raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = dict(RECORD, trace=None)
    got = read(name, bare)
    assert got == (1200.0 if name == "cdlp_exec_ms" else None)


@pytest.mark.parametrize("name", NEW[1:5])
def test_a_journal_without_kernel_spans(monkeypatch, name):
    class Bare:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["name"] != "kernel"
                    and s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", Bare)
    assert read(name) is None


def test_the_rooflines_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "cdlp_round").count
    got = count({"n": 2_396_390, "edge_slots": 128_302_936})
    assert got["bytes"] == 1_045_594_608
    assert got["bytes"] == 8 * 128_302_936 + 8 * 2_396_390


def test_the_entries_in_benchmark_json():
    bench = files.benchmark_json()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
    assert mine["cdlp_round_roofline"]["unit"] == "%"
    assert mine["cdlp_round_roofline"]["source"] == "device_trace"
    assert {mine[k]["layer"] for k in ("cdlp_exec_ms",
                                       "cdlp_host_idle_ms")} == \
        {"scheduler and batcher (olap/serving)"}
    assert {mine[k]["layer"] for k in NEW[1:4] + NEW[5:]} == \
        {"kernels (models/cdlp.py, ops/segment.py)"}
    # no accepted list gained this cell
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
