"""The six readers of the LCC cell on a recorded run: a hand-made journal
of two jobs in the window (one that began before it, one of another
kind, the warm-up's image before it), the samples' envelopes and the
graph's counts — and a program that writes none of it, where each
reports nothing."""

import pytest

import files
import spans

NEW = ["lcc_exec_ms", "lcc_hub_ms", "lcc_tail_ms", "lcc_host_idle_ms",
       "lcc_image_s", "lcc_job_roofline"]
CELL = "g500-22.lcc-c2"
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def kernel(trace, key, start, ms):
    return span(trace, "kernel", start, ms, key=key, fn=key[4:],
                device_ms=ms, stamped=True)


def job(trace, start, pass_ms, col_ms, tail_ms, admit_ms, cache="hit",
        image_ms=1.0):
    """A job: lease 2 ms, admission, the image (reused), then two
    dispatches of the pass, one of the column sums, two classes of the
    tail and the finish, one behind the other on the device with no gap,
    the readback waiting for the last and 30 ms of transfer behind it,
    the count."""
    out = [span(trace, "job.lease", start, 2.0),
           span(trace, "job.admit", start + 0.002, admit_ms, bytes=777),
           span(trace, "run", start, 0.0, kind="lcc")]
    t = start + 0.002 + admit_ms / 1e3
    out.append(span(trace, "lcc.image", t, image_ms, hubs=64, bytes=555,
                    cache=cache))
    t += image_ms / 1e3
    out.append(span(trace, "lcc.hub", t, 1.0, level=1))
    for key, ms in (("lcc_pass", pass_ms), ("lcc_pass", pass_ms),
                    ("lcc_colsum", col_ms)):
        out.append(kernel(trace, key, t, ms))
        t += ms / 1e3
    out.append(span(trace, "lcc.tail", t, 1.0))
    for ms in (tail_ms, tail_ms):
        out.append(kernel(trace, "lcc_tail", t, ms))
        t += ms / 1e3
    out.append(kernel(trace, "lcc_finish", t, 20.0))
    t += 0.020
    out.append(span(trace, "lcc.result", t - 0.001, 31.0, bytes=8000,
                    sync_ms=31.0))
    out.append(span(trace, "lcc.count", t + 0.030, 10.0))
    return out


# job-1: hub 2 x 400 + 300 = 1,100 ms, tail 2 x 250 = 500 ms; idle: lease
# 2 + admit 60 + image 1 before the first program, 30 + 10 behind the
# last: 103 ms. job-2: hub 2 x 500 + 340 = 1,340, tail 2 x 300 = 600;
# idle 2 + 100 + 1 + 40 = 143 ms. job-0 began before the window and made
# the image (12.5 s); the PageRank job is not ours.
JOURNAL = (
    job("job-0", T0 - 50.0, 10.0, 10.0, 10.0, 5.0, cache="miss",
        image_ms=12500.0)
    + job("job-1", T0 + 1.0, 400.0, 300.0, 250.0, 60.0)
    + job("job-2", T0 + 4.0, 500.0, 340.0, 300.0, 100.0)
    + [span("job-9", "job.lease", T0 + 7.0, 2.0),
       span("job-9", "pr.sweep", T0 + 7.1, 1.0, it=1),
       kernel("job-9", "pagerank_pull", T0 + 7.1, 500.0)])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 4 s of 5; 2 answered jobs in 10 s, so
# a job holds the device 0.8 / 0.2 = 4 s.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "lcc"}}},
    "trace": {"busy_s": 4.0, "window_s": 5.0},
    "samples": [
        sample(0, True, wait_ms=10.0, exec_ms=2100.0, fetch_ms=30.0),
        sample(1, True, wait_ms=900.0, exec_ms=2500.0, fetch_ms=50.0),
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
    ("lcc_exec_ms", 2300.0),            # median of 2100 and 2500
    ("lcc_hub_ms", 1220.0),             # median of 1100 and 1340
    ("lcc_tail_ms", 550.0),             # median of 500 and 600
    ("lcc_host_idle_ms", 123.0),        # median of 103 and 143
    ("lcc_image_s", 12.5),              # the warm-up's miss alone
    # 4 B x 20,000 slots + 8 B x 1,000 vertices = 88,000 B in the
    # device's 4 s a job, of 819 GB/s
    ("lcc_job_roofline", 100.0 * 88000 / (4.0 * 819e9)),
])
def test_on_the_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_what_the_readers_print(journal, capsys):
    for name in NEW[:5]:
        read(name)
    out = capsys.readouterr().out
    assert "host job.admit: median 80.0ms in 2 jobs, bytes [777]" in out
    assert "host lcc.image: median 1.0ms in 2 jobs, bytes [555]" in out
    assert "host lcc.result: median 31.0ms in 2 jobs, bytes [8000]" in out
    assert "host lcc.count: median 10.0ms in 2 jobs" in out
    assert "kernel lcc_pass: median 900.0ms a job" in out
    assert "kernel lcc_colsum: median 320.0ms a job" in out
    assert "idle under job.admit: median 80.0ms a job" in out
    assert "idle under lcc.result: median 30.0ms a job" in out
    assert "lcc.image: built 1 x, bytes [555]" in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_journal_or_the_trace(monkeypatch, name):
    """The parent commit on this cell, or tracing off: no journal, no
    ``kernel`` span, no device plane: each reader reports nothing (the
    envelope's reader still reads the envelope) and none raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = dict(RECORD, trace=None)
    got = read(name, bare)
    assert got == (2300.0 if name == "lcc_exec_ms" else None)


@pytest.mark.parametrize("name", NEW[1:4])
def test_a_journal_without_kernel_spans(monkeypatch, name):
    class Bare:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["name"] != "kernel"
                    and s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", Bare)
    assert read(name) is None


def test_a_journal_of_another_kind_has_no_image(monkeypatch):
    class Other:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["trace"] == "job-9"]
    monkeypatch.setattr(spans, "journal", Other)
    assert read("lcc_image_s") is None
    assert read("lcc_host_idle_ms") is None


def test_the_rooflines_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "lcc_job").count
    got = count({"n": 2_396_390, "edge_slots": 128_302_936})
    assert got["bytes"] == 532_382_864
    assert got["bytes"] == 4 * 128_302_936 + 8 * 2_396_390


def test_the_entries_in_benchmark_json():
    bench = files.benchmark_json()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for name, m in mine.items():
        assert m["workloads"] == [CELL]
        assert m["moves"] == ("setup_s" if name == "lcc_image_s"
                              else "throughput")
    assert mine["lcc_job_roofline"]["unit"] == "%"
    assert mine["lcc_job_roofline"]["source"] == "device_trace"
    assert {mine[k]["layer"] for k in ("lcc_exec_ms",
                                       "lcc_host_idle_ms")} == \
        {"scheduler and batcher (olap/serving)"}
    assert {mine[k]["layer"] for k in NEW[1:3] + NEW[4:]} == \
        {"kernels (models/lcc.py, ops/)"}
    # no accepted list gained this cell
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
