"""The eight readers of the BFS cell on a recorded run: a hand-made
journal of two jobs in the window (one that began before it, one of
another kind, a lane batch that sweeps too), the samples' envelopes and
the graph's counts — and a program that writes none of it (the parent
commit, whose batched run opens no scope), where each reports nothing
and none raises."""

import pytest

import files
import spans

NEW = ["bfs_exec_ms", "bfs_device_ms", "bfs_host_idle_ms", "bfs_push_ms",
       "bfs_pull_ms", "bfs_plan_ms", "bfs_padded_share",
       "bfs_job_roofline"]
CELL = "kron-s22.bfs-tree-c2"
T0 = 1000.0


def span(trace, name, start, ms, **attrs):
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def kernel(trace, key, start, ms, **statics):
    return span(trace, "kernel", start, ms, key=key, fn=key[8:],
                device_ms=ms, stamped=True, **statics)


def job(trace, start, admit_ms, push_ms, pull_ms, c_cap, candidates):
    """A job of four levels: lease 2 ms, admission, the seed (1 ms on
    the device, 2 in all); level 0 carried and pushed; level 1 planned
    (40 ms on the device) and pulled on ``c_cap``; level 2 planned and
    pulled on 4,096 with a stragglers' sweep behind it (3 ms on the
    device in a phase of 4); level 3's plan finds the frontier empty;
    every synced phase a millisecond longer than its program; the
    readback 30 ms."""
    out = [span(trace, "job.lease", start, 2.0),
           span(trace, "job.admit", start + 0.002, admit_ms, bytes=777),
           span(trace, "run", start, 0.0, k=1)]
    t = [start + 0.002 + admit_ms / 1e3]

    def phase(name, ms, key=None, device_ms=0.0, **attrs):
        if key is not None:
            out.append(kernel(trace, key, t[0], device_ms))
        out.append(span(trace, name, t[0], ms, **attrs))
        t[0] += ms / 1e3

    phase("bfs.seed", 2.0, "batched_seed", 1.0, K=1)
    phase("bfs.plan", 0.0, level=0, carried=True, sync_ms=0.0)
    phase("bfs.sweep", push_ms + 1.0, "batched_td", push_ms, level=0,
          dir="td", p_cap=4096, list="carried")
    phase("bfs.plan", 41.0, "batched_plan", 40.0, level=1, carried=False)
    phase("bfs.sweep", pull_ms + 1.0, "batched_bu", pull_ms, level=1,
          dir="bu", c_cap=c_cap, fuse=8, candidates=candidates)
    phase("bfs.plan", 41.0, "batched_plan", 40.0, level=2, carried=False)
    phase("bfs.sweep", 6.0, "batched_bu", 5.0, level=2, dir="bu",
          c_cap=4096, fuse=8, candidates=96)
    phase("bfs.exhaust", 4.0, "batched_ex", 3.0, level=2, c_cap=4096,
          p_cap=65536)
    phase("bfs.plan", 41.0, "batched_plan", 40.0, level=3, carried=False)
    phase("bfs.result", 30.0, bytes=4000, sync_ms=30.0)
    return out


# job-1: push 20 + 1 = 21; pull (100 + 1) + 6 + 4 = 111; plan 3 x 41 =
# 123; on the device 1 + 20 + 3 x 40 + 100 + 5 + 3 = 249; its extent 60 +
# 20 + 100 + 169 = 349, so idle 100. job-2: push 31, pull 211, plan 123,
# device 359, idle 140. job-0 began before the window; the PageRank job
# and the lane's batch are not ours.
JOURNAL = (
    job("job-0", T0 - 50.0, 5.0, 2.0, 10.0, 4096, 7)
    + job("job-1", T0 + 1.0, 60.0, 20.0, 100.0, 1 << 20, 900_000)
    + job("job-2", T0 + 4.0, 100.0, 30.0, 200.0, 1 << 21, 1_100_000)
    + [span("job-9", "job.lease", T0 + 9.0, 2.0),
       span("job-9", "pr.sweep", T0 + 9.1, 1.0, it=1),
       kernel("job-9", "pagerank_pull", T0 + 9.1, 500.0),
       span("traverse-3", "interactive", T0 + 2.0, 50.0),
       span("traverse-3", "bfs.sweep", T0 + 2.0, 40.0, level=1, dir="td",
            p_cap=8, list="carried"),
       kernel("traverse-3", "batched_td", T0 + 2.0, 39.0)])


def sample(i, ok, **envelope):
    return {"i": i, "ok": ok, "latency_ms": 5000.0, "envelope": envelope}


# The traced slice: the device busy 4 s of 5; 2 answered jobs in 10 s, so
# a job holds the device 0.8 / 0.2 = 4 s.
RECORD = {
    "window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
    "graph": {"n": 1000, "edge_slots": 20000},
    "mix": {"request": {"body": {"kind": "bfs"}}},
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


LANES = (1 << 20) + (1 << 21) + 2 * 4096
CANDIDATES = 900_000 + 1_100_000 + 2 * 96


@pytest.mark.parametrize("name, want", [
    ("bfs_exec_ms", 2950.0),            # median of 1700 and 4200
    ("bfs_device_ms", 304.0),           # median of 249 and 359
    ("bfs_host_idle_ms", 120.0),        # median of 100 and 140
    ("bfs_push_ms", 26.0),              # median of 21 and 31
    ("bfs_pull_ms", 161.0),             # median of 111 and 211
    ("bfs_plan_ms", 123.0),
    # the four pulled levels' rungs against what they held
    ("bfs_padded_share", 100.0 * (1.0 - CANDIDATES / LANES)),
    # 4 B x 20,000 slots + 8 B x 1,000 vertices = 88,000 B in the
    # device's 4 s a job, of 819 GB/s
    ("bfs_job_roofline", 100.0 * 88000 / (4.0 * 819e9)),
])
def test_on_the_recorded_run(journal, a_chip, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_what_the_readers_print(journal, capsys):
    for name in NEW[:7]:
        read(name)
    out = capsys.readouterr().out
    assert "host job.admit: median 80.0ms in 2 jobs, bytes [777]" in out
    assert "host bfs.result: median 30.0ms in 2 jobs, bytes [4000]" in out
    assert ("kernel batched_plan (plan): 3 calls a job, median 40.00ms a "
            "call, 120.0ms a job") in out
    assert "kernel batched_bu (bu): 2 calls a job" in out
    assert "idle under job.admit: median 80.0ms a job" in out
    assert "idle under bfs.result: median 30.0ms a job" in out
    assert "push p_cap=4096: 2 levels in 2 jobs, median 26.0ms" in out
    assert "push roads: {'carried': 2}" in out
    assert "pull c_cap=4096: 2 levels in 2 jobs, median 6.0ms" in out
    assert "pull c_cap=1048576: 1 levels in 2 jobs, median 101.0ms" in out
    assert "pull c_cap=2097152: 1 levels in 2 jobs, median 201.0ms" in out
    assert "exhaust (c_cap, p_cap): {(4096, 65536): 2}" in out
    assert "plan: 6 planned and 2 carried levels in 2 jobs" in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_journal_or_the_trace(monkeypatch, name):
    """Tracing off: no journal, no device plane: each reader reports
    nothing (the envelope's reader still reads the envelope) and none
    raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    bare = dict(RECORD, trace=None)
    got = read(name, bare)
    assert got == (2950.0 if name == "bfs_exec_ms" else None)


@pytest.mark.parametrize("name", NEW[1:7])
def test_the_parent_commit(monkeypatch, name):
    """The parent commit runs the kind and opens no scope round the
    batched loop: the job's trace holds its lease, its admission and its
    ``run``, no level phase and no ``kernel`` span: nothing."""
    class Parent:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL if s["start"] >= t0
                    and s["name"] in ("job.lease", "job.admit", "run")]
    monkeypatch.setattr(spans, "journal", Parent)
    assert read(name) is None


def test_a_ladderless_program_has_no_padded_share(monkeypatch):
    class NoCount:
        def window(self, t0, t1=None):
            return [dict(s, attrs={k: v for k, v in s["attrs"].items()
                                   if k != "candidates"})
                    for s in JOURNAL if s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", NoCount)
    assert read("bfs_padded_share") is None
    assert read("bfs_pull_ms") == pytest.approx(161.0)


@pytest.mark.parametrize("name", NEW[1:7])
def test_a_journal_of_another_kind(monkeypatch, a_chip, name):
    """Another cell's traced run (a job of another kind, a lane batch
    that sweeps): nothing."""
    class Other:
        def window(self, t0, t1=None):
            return [s for s in JOURNAL
                    if s["trace"] in ("job-9", "traverse-3")
                    and s["start"] >= t0]
    monkeypatch.setattr(spans, "journal", Other)
    assert read(name) is None


def test_the_rooflines_bytes_come_from_the_graph_alone():
    count = files.load_module("kernels", "bfs_job").count
    got = count({"n": 2_396_390, "edge_slots": 128_302_936})
    assert got["bytes"] == 532_382_864
    assert got["bytes"] == 4 * 128_302_936 + 8 * 2_396_390


def test_the_entries_in_benchmark_json():
    bench = files.benchmark_json()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in NEW}
    assert sorted(mine) == sorted(NEW)
    for m in mine.values():
        assert m["workloads"] == [CELL] and m["moves"] == "throughput"
    assert mine["bfs_job_roofline"]["unit"] == "%"
    assert mine["bfs_job_roofline"]["source"] == "device_trace"
    assert mine["bfs_padded_share"]["unit"] == "%"
    assert {mine[k]["layer"] for k in ("bfs_exec_ms", "bfs_host_idle_ms")} \
        == {"scheduler and batcher (olap/serving)"}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == "gap-kron-s22-bfs" and cell["chips"] == 1
    assert cell["traffic"] == "bfs-tree-jobs-c2"
