"""The six readers of the BC cell on a recorded run: a hand-made journal
of two jobs in the window (one that began before it, one of another
kind), the samples' envelopes and the graph's counts — and a program that
writes none of it (the parent commit), where each reports nothing and
none raises."""

import pytest

import files
import spans

NEW = ["bc_exec_ms", "bc_forward_ms", "bc_backward_ms", "bc_pull_ms",
       "bc_host_idle_ms", "bc_level_roofline"]
CELL = "kron-s22.bc-c2"
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def kernel(trace, key, start, ms):
    return span(trace, "kernel", start, ms, key=key, fn=key[3:],
                device_ms=ms, stamped=True, impl="vmem")


def job(trace, start, pull_ms, levels, admit_ms):
    """A job of two roots: lease 2 ms, admission, then for each root its
    seed (1 ms), ``levels`` forward pulls and ``levels - 2`` backward,
    each followed by 1 ms of host before the next is dispatched; the
    result 2 ms on the device and 30 ms of transfer behind it."""
    out = [span(trace, "job.lease", start, 2.0),
           span(trace, "job.admit", start + 0.002, admit_ms, bytes=777),
           span(trace, "run", start, 0.0, kind="bc")]
    t = start + 0.002 + admit_ms / 1e3
    for root in (5, 9):
        t0 = t
        out.append(kernel(trace, "bc_seed", t, 1.0))
        t += 0.001
        for _ in range(levels):
            out.append(kernel(trace, "bc_forward_level", t, pull_ms))
            t += pull_ms / 1e3 + 0.001
        out.append(span(trace, "bc.forward", t0, (t - t0) * 1e3, root=root,
                        levels=levels, reached=90, impl="vmem"))
        t0 = t
        for _ in range(levels - 2):
            out.append(kernel(trace, "bc_backward_level", t, pull_ms))
            t += pull_ms / 1e3 + 0.001
        out.append(span(trace, "bc.backward", t0, (t - t0) * 1e3,
                        root=root, levels=levels - 2, impl="vmem"))
    out.append(kernel(trace, "bc_result", t, 2.0))
    out.append(span(trace, "bc.result", t, 32.0, bytes=4000, roots=2,
                    sync_ms=32.0))
    return out


# job-1: 5 levels at 100 ms: a root's forward phase 1 + 5 x 101 = 506 ms,
# its backward 3 x 101 = 303; two roots: 1,012 and 606. Idle: lease 2 +
# admit 60, a millisecond behind each of 16 pulls, 30 behind the result:
# 108 ms. job-2: 6 levels at 200 ms: forward 2 x (1 + 6 x 201) = 2,414,
# backward 2 x 4 x 201 = 1,608; idle 2 + 100 + 20 + 30 = 152 ms. job-0
# began before the window; the PageRank job is not ours.
JOURNAL = (
    job("job-0", T0 - 50.0, 10.0, 4, 5.0)
    + job("job-1", T0 + 1.0, 100.0, 5, 60.0)
    + job("job-2", T0 + 4.0, 200.0, 6, 100.0)
    + [span("job-9", "job.lease", T0 + 9.0, 2.0),
       span("job-9", "pr.sweep", T0 + 9.1, 1.0, it=1),
       kernel("job-9", "pagerank_pull", T0 + 9.1, 500.0)])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 4 s of 5; 2 answered jobs in 10 s, so
# a job holds the device 0.8 / 0.2 = 4 s; the jobs dispatched 16 and 20
# level programs: 18 the median, 4 / 18 s a level.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "bc"}}},
    "trace": {"busy_s": 4.0, "window_s": 5.0},
    "samples": [
        sample(0, True, wait_ms=10.0, exec_ms=1700.0, fetch_ms=30.0),
        sample(1, True, wait_ms=900.0, exec_ms=4200.0, fetch_ms=50.0),
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
    ("bc_exec_ms", 2950.0),             # median of 1700 and 4200
    ("bc_forward_ms", 1713.0),          # median of 1012 and 2414
    ("bc_backward_ms", 1107.0),         # median of 606 and 1608
    # 16 calls of 100 ms and 20 of 200: the median call is 200
    ("bc_pull_ms", 200.0),
    ("bc_host_idle_ms", 130.0),         # median of 108 and 152
    # 8 B x 20,000 slots + 16 B x 1,000 vertices = 176,000 B in the
    # device's 4 / 18 s a level, of 819 GB/s
    ("bc_level_roofline", 100.0 * 176000 / (4.0 / 18 * 819e9)),
])
def test_on_the_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_what_the_readers_print(journal, capsys):
    for name in NEW[:5]:
        read(name)
    out = capsys.readouterr().out
    assert "host job.admit: median 80.0ms in 2 jobs, bytes [777]" in out
    assert "host bc.result: median 32.0ms in 2 jobs, bytes [4000]" in out
    assert ("phase bc.forward: 4 roots in 2 jobs, median 856.5ms a root, "
            "levels [5, 6], reached 90..90") in out
    assert ("phase bc.backward: 4 roots in 2 jobs, median 553.5ms a "
            "root, levels [3, 4]") in out
    assert ("kernel bc_forward_level: median 200.00ms a call, 11 calls a "
            "job, impl ['vmem']") in out
    assert ("kernel bc_backward_level: median 200.00ms a call, 7 calls a "
            "job, impl ['vmem']") in out
    assert "idle under job.admit: median 80.0ms a job" in out
    assert "idle under bc.result: median 30.0ms a job" in out
    assert "idle under bc.forward: median " in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_journal_or_the_trace(monkeypatch, name):
    """The parent commit, or tracing off: no journal, no ``kernel``
    span, no device plane: each reader reports nothing (the envelope's
    reader still reads the envelope) and none raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = dict(RECORD, trace=None)
    got = read(name, bare)
    assert got == (2950.0 if name == "bc_exec_ms" else None)


@pytest.mark.parametrize("name", NEW[3:])
def test_a_journal_without_kernel_spans(monkeypatch, a_chip, name):
    class Bare:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["name"] != "kernel"
                    and s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", Bare)
    assert read(name) is None


@pytest.mark.parametrize("name", NEW[1:])
def test_a_journal_of_another_kind(monkeypatch, a_chip, name):
    """Another cell's traced run (the driver runs every reader a cell
    lists, and a later PR may list this cell's elsewhere): nothing."""
    class Other:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["trace"] == "job-9"
                    and s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", Other)
    assert read(name) is None


def test_the_rooflines_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "bc_level").count
    got = count({"n": 2_396_390, "edge_slots": 128_302_936})
    assert got["bytes"] == 1_064_765_728
    assert got["bytes"] == 8 * 128_302_936 + 16 * 2_396_390


def test_the_entries_in_benchmark_json():
    bench = files.benchmark_json()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
    assert mine["bc_level_roofline"]["unit"] == "%"
    assert mine["bc_level_roofline"]["source"] == "device_trace"
    assert {mine[k]["layer"] for k in ("bc_exec_ms", "bc_host_idle_ms")} \
        == {"scheduler and batcher (olap/serving)"}
    assert {mine[k]["layer"] for k in NEW[1:4] + NEW[5:]} == \
        {"kernels (models/bc.py, models/pagerank_pull.py)"}
    # no accepted list gained this cell
    for m in bench["per_layer"] + bench["end_to_end"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [])
