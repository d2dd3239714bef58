"""``sweep_carried_share`` on a hand-made journal: pushes that say where
their frontier's pairs came from, pulled levels beside them, pushes of a
program that does not say, and no journal."""

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
    return files.load_module("layer_metrics",
                             "sweep_carried_share").read(RECORD)


class Journal:
    def __init__(self, made):
        self.made = made

    def window(self, t0, t1=None):
        return [s for s in self.made
                if s["start"] >= t0 and (t1 is None or s["start"] < t1)]


@pytest.mark.parametrize("roads, want", [
    (["carried", "carried", "carried", "carried"], 100.0),
    (["carried", "scan", "bu", "carried", "bu", "scan", "carried"], 60.0),
    (["scan", "scan"], 0.0),
])
def test_share_of_the_windows_pushed_levels(monkeypatch, roads, want):
    """One level each, in batches of two levels; a pulled level (three
    chunk rounds, no ``list``) is no pushed level and counts nowhere."""
    made = []
    for i, road in enumerate(roads):
        where = {"level": 1 + i % 2, "trace": f"traverse-{i // 2}"}
        if road == "bu":
            made += [sweep(10 * i + r, T0 + 1 + i + r / 10, dir="bu",
                           c_cap=1024, fuse=8, **where) for r in range(3)]
        else:
            made.append(sweep(10 * i, T0 + 1 + i, dir="td", p_cap=4096,
                              list=road, **where))
    made.append(sweep(99, T0 - 5.0, dir="td", list="scan"))  # the warm-up's
    made.append({"trace": "traverse-7", "span": 98, "name": "bfs.plan",
                 "start": T0 + 2.0, "end": T0 + 2.01, "duration_ms": 0.1,
                 "attrs": {"level": 1, "carried": True}})
    assert read(monkeypatch, Journal(made)) == pytest.approx(want)


def test_a_program_that_does_not_say_reports_nothing(monkeypatch):
    """The parent of the PR that brought the list: ``dir`` and no
    ``list``."""
    made = [sweep(1, T0 + 1.0, dir="td", p_cap=4096, mass=12, pairs=1),
            sweep(2, T0 + 2.0, level=2, dir="td", p_cap=4096, mass=90,
                  pairs=32)]
    assert read(monkeypatch, Journal(made)) is None


def test_no_push_in_the_window_and_no_journal(monkeypatch):
    pulled = [sweep(1, T0 + 1.0, dir="bu", c_cap=1024, fuse=8)]
    assert read(monkeypatch, Journal(pulled)) is None
    assert read(monkeypatch, Journal([])) is None
    assert read(monkeypatch, None) is None


def test_declared_with_its_reader_in_both_cells():
    b = files.benchmark_json()
    m = next(m for m in b["per_layer"] if m["name"] == "sweep_carried_share")
    assert m["source"] == "program_span" and m["unit"] == "%"
    assert m["moves"] == "latency_p50_ms" and m["better"] == "higher"
    assert m["workloads"] == [w["name"] for w in b["workloads"]]
