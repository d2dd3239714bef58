"""The six readers of the ``kernel`` spans (ISSUE 38) and
``kernel_spans.py``'s arithmetic on a hand-made journal: two WCC jobs and
a PageRank job in the window, one job that leased before it, a cohort's
follower, a lane batch; and a program that writes no such span, where
each reader reports nothing."""

import pytest

import files
import kernel_spans
import spans

NEW = ["job_device_ms", "job_host_idle_ms", "pr_pull_ms",
       "wcc_bu_wide_ms", "wcc_bu_rest_ms", "wcc_endgame_ms"]
T0 = 1000.0


def span(trace, name, start_ms, ms, **attrs):
    start = T0 + start_ms / 1e3
    return {"trace": trace, "span": 0, "name": name, "start": start,
            "end": start + ms / 1e3, "duration_ms": ms, "attrs": attrs}


def kernel(trace, key, fn, start_ms, ms, stamped=True, **statics):
    return span(trace, "kernel", start_ms, ms if stamped else 0.0,
                key=key, fn=fn, device_ms=ms if stamped else 0.0,
                queued_ms=0.0, dispatch_ms=0.2, stamped=stamped, **statics)


def wcc_job(trace, at, wide, finish, more, end):
    """lease 0-1, admit 1-101 (idle), the peel's steps back to back with
    2 ms of host after each readback, count 30 ms (idle), done."""
    t = at + 101.0
    out = [span(trace, "job.lease", at, 1.0),
           span(trace, "job.admit", at + 1.0, 100.0, bytes=9),
           span(trace, "run", at + 101.0, 0.0, kind="wcc")]
    out += [span(trace, "bfs.level", t, 52.0, dir="head", level=0),
            kernel(trace, "hybrid_head", "head", t + 1.0, 50.0)]
    t += 52.0
    bu = wide + finish + more + 4.0
    out += [span(trace, "bfs.level", t, bu, dir="bu", level=1),
            kernel(trace, "hybrid_bu_startL", "bu0a", t + 1.0, wide,
                   c_cap=1 << 24, lanes=2),
            kernel(trace, "hybrid_bu_finish0", "bu0b", t + 2.0 + wide,
                   finish, c_cap=1 << 20),
            kernel(trace, "hybrid_bu_more", "bu", t + 3.0 + wide + finish,
                   more, fuse=7)]
    t += bu
    out += [span(trace, "bfs.level", t, end + 2.0, dir="end", level=2),
            kernel(trace, "hybrid_endgame", "end", t + 1.0, end)]
    t += end + 2.0
    out += [span(trace, "wcc.count", t, 30.0),
            span(trace, "done", t + 30.0, 0.0)]
    return out


def pr_job(trace, at, pulls):
    """Dispatches only: sweep and finish spans of 0.3 ms, the kernels
    back to back behind them, the readback's phase covering the drain."""
    out = [span(trace, "job.lease", at, 1.0),
           span(trace, "job.admit", at + 1.0, 20.0),
           span(trace, "run", at + 21.0, 0.0, kind="pagerank")]
    t, host = at + 22.0, at + 21.0
    for it, ms in enumerate(pulls, 1):
        out += [span(trace, "pr.sweep", host, 0.3, it=it),
                span(trace, "pr.finish", host + 0.3, 0.3, it=it),
                kernel(trace, "pagerank_pull", "step", t, ms, impl="vmem"),
                kernel(trace, "pagerank_finish", "fin", t + ms, 1.0)]
        host += 0.6
        t += ms + 1.0
    out += [span(trace, "pr.result", host, t - host + 5.0, bytes=99),
            span(trace, "done", t + 5.0, 0.0)]
    return out


# wcc-1: device 50 + 500 + 300 + 100 + 600 = 1550; extent 0 -> 1689:
# idle 139 = admit 100 + lease 1 + count 30 + eight 1 ms links.
# wcc-2: device 50 + 700 + 300 + 200 + 700 = 1950; idle 139.
# pr-1: pulls 200 210 220 + 3 finishes = 633; extent 0 -> 660: idle 27 =
# lease 1 + admit 20 + 1 ms before the first pull + 5 in pr.result.
JOURNAL = (
    wcc_job("wcc-0", -500.0, 100.0, 100.0, 100.0, 100.0)
    + wcc_job("wcc-1", 1000.0, 500.0, 300.0, 100.0, 600.0)
    + wcc_job("wcc-2", 4000.0, 700.0, 300.0, 200.0, 700.0)
    + pr_job("pr-1", 7000.0, [200.0, 210.0, 220.0])
    + [span("wcc-3", "run", 4000.0, 0.0, kind="wcc", k=2),      # follower
       span("traverse-1", "bfs.sweep", 2000.0, 5.0, level=1),
       kernel("traverse-1", "batched_td", "btd", 2001.0, 2.0)])

RECORD = {"window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0},
          "samples": []}


class Journal:
    def __init__(self, journal=JOURNAL):
        self.journal = journal

    def window(self, t0, t1=None):
        return [s for s in self.journal
                if s["start"] >= t0 and (t1 is None or s["start"] < t1)]


@pytest.fixture
def journal(monkeypatch):
    monkeypatch.setattr(spans, "journal", Journal)


def read(name, record=RECORD):
    return files.load_module("layer_metrics", name).read(record)


@pytest.mark.parametrize("name, want", [
    ("job_device_ms", 1550.0),          # median of 633, 1550, 1950
    ("job_host_idle_ms", 139.0),        # median of 27, 139, 139
    ("pr_pull_ms", 210.0),              # median of 200 210 220
    ("wcc_bu_wide_ms", 600.0),          # median of 500 and 700
    ("wcc_bu_rest_ms", 450.0),          # median of 300 + 100, 300 + 200
    ("wcc_endgame_ms", 650.0),          # median of 600 and 700
])
def test_reader_on_a_recorded_run(journal, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_the_jobs_that_count(journal):
    """wcc-0 leased before the window (its later spans are in the list,
    its ``job.lease`` is not), wcc-3 is a follower, traverse-1 a lane
    batch: three jobs count, each from its lease on."""
    all_jobs = kernel_spans.read_jobs(RECORD)
    assert sorted(j[0]["trace"] for j in all_jobs) == \
        ["pr-1", "wcc-1", "wcc-2"]
    assert all(j[0]["name"] == "job.lease" for j in all_jobs)


def test_union_and_idle():
    assert kernel_spans.union([(3, 4), (0, 1), (1, 2), (5, 5), (3.5, 6)]) \
        == [(0, 2), (3, 6)]
    job = [span("j", "job.lease", 0.0, 1.0),
           kernel("j", "a", "a", 10.0, 5.0),
           kernel("j", "b", "b", 15.0, 5.0),       # back to back
           kernel("j", "c", "c", 30.0, 5.0),
           span("j", "done", 50.0, 0.0)]
    gaps = [(round((a - T0) * 1e3, 6), round((b - T0) * 1e3, 6))
            for a, b in kernel_spans.idle(job)]
    assert gaps == [(0.0, 10.0), (20.0, 30.0), (35.0, 50.0)]
    assert kernel_spans.idle_ms(job) == pytest.approx(35.0)
    assert kernel_spans.device_ms(job) == 15.0
    assert kernel_spans.device_ms(job, "a", "c") == 10.0


def test_idle_by_phase(journal):
    (wcc1,) = [j for j in kernel_spans.read_jobs(RECORD)
               if j[0]["trace"] == "wcc-1"]
    got = kernel_spans.idle_by_phase(wcc1)
    assert got.pop("job.admit") == pytest.approx(100.0)
    assert got.pop("job.lease") == pytest.approx(1.0)
    assert got.pop("wcc.count") == pytest.approx(30.0)
    # the links: 1 ms before each of five kernels, 1 ms after the last
    # kernel of each of the three steps, all inside a `bfs.level`
    assert got.pop("bfs.level") == pytest.approx(8.0)
    assert got == {}
    assert sum(kernel_spans.idle_by_phase(wcc1).values()) == \
        pytest.approx(kernel_spans.idle_ms(wcc1))
    # a stretch no phase covers
    bare = [span("j", "job.lease", 0.0, 1.0),
            kernel("j", "a", "a", 3.0, 5.0), span("j", "done", 8.0, 0.0)]
    assert kernel_spans.idle_by_phase(bare) == {
        "job.lease": pytest.approx(1.0),
        "(between phases)": pytest.approx(2.0)}


def test_the_table_by_key(journal):
    wcc = [j for j in kernel_spans.read_jobs(RECORD)
           if j[0]["trace"].startswith("wcc")]
    rows = {r[0]: r for r in kernel_spans.by_key(wcc)}
    assert rows["hybrid_endgame"] == ("hybrid_endgame", "end", 1, 650.0,
                                      650.0, 0)
    assert rows["hybrid_bu_startL"][1:5] == ("bu0a", 1, 600.0, 600.0)
    assert [r[0] for r in kernel_spans.by_key(wcc)][:2] == \
        ["hybrid_endgame", "hybrid_bu_startL"]          # dearest first
    # a key one job lacks counts 0 there; an unstamped call is counted
    # and has no time of its own
    extra = wcc[0] + [kernel("wcc-1", "hybrid_ex", "ex", 1690.0, 9.0),
                      kernel("wcc-1", "hybrid_ex", "ex", 1690.5, 9.0,
                             stamped=False)]
    ex = {r[0]: r for r in kernel_spans.by_key([extra, wcc[1]])}[
        "hybrid_ex"]
    assert ex == ("hybrid_ex", "ex", 1.0, 9.0, 4.5, 1)


def test_the_printed_lines(journal, capsys):
    read("job_device_ms")
    read("job_host_idle_ms")
    out = capsys.readouterr().out
    assert "kernel hybrid_bu_startL (bu0a): 1 calls a job, median " \
           "600.00ms a call, 500.0ms a job" in out     # 0, 500, 700
    assert "kernel pagerank_pull (step): 0 calls a job" in out
    assert "idle under job.admit: median 100.0ms a job" in out
    assert "idle under wcc.count: median 30.0ms a job" in out
    assert "idle under pr.result: median 0.0ms a job" in out


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_writes_no_kernel_span(monkeypatch, name):
    """No journal at all (an older commit, tracing off), and the parent's
    journal: every phase, no ``kernel`` in it. Nothing is reported and
    nothing raises."""
    monkeypatch.setattr(spans, "journal", lambda: None)
    assert read(name) is None
    older = [s for s in JOURNAL if s["name"] != "kernel"]
    monkeypatch.setattr(spans, "journal", lambda: Journal(older))
    assert read(name) is None


@pytest.mark.parametrize("name", ["pr_pull_ms", "wcc_bu_wide_ms",
                                  "wcc_bu_rest_ms", "wcc_endgame_ms"])
def test_a_cell_whose_jobs_lack_the_key(monkeypatch, name):
    """Kernel spans, none of the reader's keys among them."""
    lane_only = [span("j", "job.lease", 0.0, 1.0),
                 kernel("j", "batched_td", "btd", 2.0, 2.0)]
    monkeypatch.setattr(spans, "journal", lambda: Journal(lane_only))
    assert read(name) is None


def test_every_new_metric_is_declared_with_its_reader():
    bench = files.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    jobs = {"g500-22.pr-c2", "g500-24.wcc-c2"}
    for name in NEW:
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("ms", "lower", "program_span", "throughput")
        assert set(m["workloads"]) <= jobs
        files.load_module("layer_metrics", name)
    assert [m["name"] for m in bench["per_layer"]][-6:] == NEW
    assert set(declared["job_device_ms"]["workloads"]) == jobs
    assert declared["job_host_idle_ms"]["layer"] == \
        "scheduler and batcher (olap/serving)"
