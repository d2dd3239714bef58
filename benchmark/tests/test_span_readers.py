"""Each reader of the span journal on a hand-made list: two lane batches
in the window, a compile in one of them, the warm-up's compiles before
it — and a program without a journal, where every reader reports
nothing."""

import pytest

import files
import spans

NEW = ["compile_stall_ms", "warm_load_s", "warm_compile_s", "exec_plan_ms",
       "exec_sweep_ms", "exec_extract_ms", "exec_host_ms",
       "lane_busy_share"]
T0 = 1000.0                                 # the window: [1000, 1010]
RECORD = {"window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0}}


def span(trace, sid, name, start, ms, parent=None, **attrs):
    out = {"trace": trace, "span": sid, "name": name, "start": start,
           "end": start + ms / 1e3, "duration_ms": ms}
    if parent is not None:
        out["parent"] = parent
    if attrs:
        out["attrs"] = attrs
    return out


def batch(trace, base, start, plan_ms, sweep_ms, extract_ms, stall=None):
    """One batch: seed 4 ms, two plans, two sweeps, one exhaust 1 ms,
    extract, reply; every synced phase spends all but 2 ms blocked."""
    t, out = start, []

    def add(name, ms, **attrs):
        nonlocal t
        out.append(span(trace, base + len(out) + 1, name, t, ms,
                        parent=base, **attrs))
        t += ms / 1e3

    add("admit", 1.0, k_runnable=3)
    add("bfs.seed", 4.0, K=4)
    for level in (1, 2):
        add("bfs.plan", plan_ms, level=level, sync_ms=plan_ms - 2.0)
        add("bfs.sweep", sweep_ms, level=level, sync_ms=sweep_ms - 2.0)
    add("bfs.exhaust", 1.0, level=2, **{"async": True})
    if stall:
        out.append(span(trace, base + 50, "compile", t - 0.001, stall,
                        parent=out[-1]["span"], key="batched_ex",
                        c_cap=64, p_cap=512, masked=False, cache="miss",
                        backend_ms=stall - 1, trace_ms=0.5, lower_ms=0.5,
                        thread="serving-interactive"))
        t += stall / 1e3
    add("extract", extract_ms, Kp=4, sync_ms=extract_ms - 2.0)
    add("reply", 1.0, d2h_bytes=0)
    root = span(trace, base, "interactive", start, (t - start) * 1e3,
                k=3, kind="traverse")
    return [root] + out


WARM = [span("compile", 1, "compile", 900.0, 20000.0, key="batched_plan",
             cache="miss"),
        span("compile", 2, "compile", 930.0, 1500.0, key="batched_bu",
             cache="hit", retrieval_ms=900.0),
        span("compile", 3, "compile", 940.0, 30.0, key="eager:scatter",
             cache="off"),
        span("traverse-1", 4, "compile", 950.0, 500.0, key="batched_ex",
             cache="hit")]
IN_WINDOW = (batch("traverse-7", 100, T0 + 1.0, 400.0, 600.0, 300.0)
             + batch("traverse-8", 200, T0 + 5.0, 500.0, 700.0, 350.0,
                     stall=1200.0)
             # an empty batch (no runnable member): a root and no sweep
             + [span("traverse-9", 300, "interactive", T0 + 9.0, 1.0)])
STRADDLER = [span("traverse-6", 90, "interactive", T0 - 2.0, 2500.0)]


class Journal:
    def window(self, t0, t1=None):
        return [s for s in WARM + STRADDLER + IN_WINDOW
                if s["start"] >= t0 and (t1 is None or s["start"] < t1)]


@pytest.fixture
def journal(monkeypatch):
    monkeypatch.setattr(spans, "journal", Journal)


def read(name):
    return files.load_module("layer_metrics", name).read(RECORD)


@pytest.mark.parametrize("name, want", [
    ("compile_stall_ms", 1200.0),
    ("warm_load_s", 2.0),                   # the two hits
    ("warm_compile_s", 20.03),              # the miss and the ``off``
    ("exec_plan_ms", 900.0),                # median of 800 and 1000
    ("exec_sweep_ms", 1300.0),              # median of 1200 and 1400
    ("exec_extract_ms", 325.0),             # median of 300 and 350
    # seed 4 + exhaust 1 (the stalled batch's: 1 ms, the compile span is
    # not a phase) + 2 ms of each of the five synced phases
    ("exec_host_ms", 15.0),
    # the straddler's last 0.5 s, both batches whole, the empty one
    ("lane_busy_share", 100.0 * (0.5 + 2.307 + 3.957 + 0.001) / 10.0),
])
def test_reader_on_a_hand_made_journal(journal, name, want):
    assert read(name) == pytest.approx(want, rel=1e-9)


def test_compile_stall_prints_what_was_built_and_where(journal, capsys):
    read("compile_stall_ms")
    assert capsys.readouterr().out.strip() == (
        "compile batched_ex c_cap=64 masked=False p_cap=512 1200.0ms miss "
        "in traverse-8 bfs.exhaust L2")


def test_sweep_prints_the_median_of_each_level(journal, capsys):
    read("exec_sweep_ms")
    assert capsys.readouterr().out.splitlines() == [
        "phase bfs.sweep L1: median 650.0ms in 2 batches",
        "phase bfs.sweep L2: median 650.0ms in 2 batches"]


def test_warm_up_prints_where_its_compile_time_went(journal, capsys):
    read("warm_compile_s")
    assert capsys.readouterr().out.splitlines() == [
        "warm-up compiled 2 executables; most time in:",
        "  batched_plan: 1 x, 20.00s", "  eager:scatter: 1 x, 0.03s"]
    read("warm_load_s")
    assert capsys.readouterr().out.splitlines() == [
        "warm-up loaded 2 executables; most time in:",
        "  batched_bu: 1 x, 1.50s", "  batched_ex: 1 x, 0.50s"]


def test_a_compile_outside_any_phase_says_only_its_trace():
    c = span("compile", 9, "compile", 5.0, 12.5, key="eager:iota",
             cache="off", thread="http-3")
    assert spans.describe_compile(c, [c]) == (
        "compile eager:iota - 12.5ms off in compile")


@pytest.mark.parametrize("name", NEW)
def test_no_batch_in_the_window(monkeypatch, name):
    class Empty:
        def window(self, t0, t1=None):
            return []
    monkeypatch.setattr(spans, "journal", Empty)
    want = 0.0 if name in ("compile_stall_ms", "warm_load_s",
                           "warm_compile_s", "lane_busy_share") else None
    assert read(name) == want


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_a_journal_reports_nothing(monkeypatch, name):
    monkeypatch.setattr(spans, "journal", lambda: None)
    assert read(name) is None


def test_journal_of_a_program_that_has_none(monkeypatch):
    from titan_tpu.obs import tracing

    monkeypatch.setattr(tracing, "_CURRENT", None)      # tracing off
    assert spans.journal() is None
    monkeypatch.delattr(tracing, "current")             # an older commit
    assert spans.journal() is None


def test_every_new_metric_is_declared_with_its_reader():
    declared = {m["name"]: m for m in files.benchmark_json()["per_layer"]}
    for name in NEW:
        assert declared[name]["source"] == "program_span"
        assert callable(files.load_module("layer_metrics", name).read)
