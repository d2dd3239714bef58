"""``sweep_td_share`` on a hand-made journal: sweeps that say which way
they went, sweeps of a program that does not say, and no journal."""

import pytest

import files
import spans

T0 = 1000.0
RECORD = {"window": {"start": T0, "seconds": 8.0, "last_done": T0 + 10.0}}


def sweep(sid, start, level=1, trace="traverse-7", **attrs):
    return {"trace": trace, "span": sid, "name": "bfs.sweep",
            "start": start, "end": start + 0.01, "duration_ms": 10.0,
            "attrs": dict(attrs, level=level)}


def read(monkeypatch, journal):
    monkeypatch.setattr(spans, "journal", lambda: journal)
    return files.load_module("layer_metrics", "sweep_td_share").read(RECORD)


class Journal:
    def __init__(self, made):
        self.made = made

    def window(self, t0, t1=None):
        return [s for s in self.made
                if s["start"] >= t0 and (t1 is None or s["start"] < t1)]


@pytest.mark.parametrize("dirs, want", [
    (["td", "td", "td", "td"], 100.0),
    (["td", "bu", "bu", "td", "td"], 60.0),
    (["bu", "bu"], 0.0),
])
def test_share_of_the_windows_levels(monkeypatch, dirs, want):
    """One level each, in batches of two levels; a pulled level made
    three chunk rounds and counts once."""
    made = []
    for i, d in enumerate(dirs):
        for r in range(3 if d == "bu" else 1):
            made.append(sweep(10 * i + r, T0 + 1 + i + r / 10,
                              level=1 + i % 2, trace=f"traverse-{i // 2}",
                              dir=d, p_cap=4096))
    made.append(sweep(99, T0 - 5.0, dir="bu"))  # the warm-up's: not counted
    made.append({"trace": "traverse-7", "span": 98, "name": "bfs.plan",
                 "start": T0 + 2.0, "end": T0 + 2.01, "duration_ms": 10.0,
                 "attrs": {"level": 1}})
    assert read(monkeypatch, Journal(made)) == pytest.approx(want)


def test_a_program_that_does_not_say_reports_nothing(monkeypatch):
    made = [sweep(1, T0 + 1.0, c_cap=1024, fuse=8),
            sweep(2, T0 + 2.0, c_cap=1024, fuse=8)]
    assert read(monkeypatch, Journal(made)) is None


def test_no_sweep_in_the_window_and_no_journal(monkeypatch):
    assert read(monkeypatch, Journal([])) is None
    assert read(monkeypatch, None) is None


def test_declared_with_its_reader_in_both_cells():
    b = files.benchmark_json()
    m = next(m for m in b["per_layer"] if m["name"] == "sweep_td_share")
    assert m["source"] == "program_span" and m["unit"] == "%"
    assert m["moves"] == "latency_p50_ms" and m["better"] == "higher"
    assert m["workloads"] == [w["name"] for w in b["workloads"]]
    assert b["per_layer"][-1] is m              # appended, nothing moved
