"""``sweep_fill_share`` on a hand-made journal: pushes on several rungs
that say what they weighed and what they ran on, pulled levels beside
them, pushes of a program that does not say, and no journal."""

import pytest

import files
import spans
from test_sweep_carried_share import RECORD, T0, Journal, sweep


def read(monkeypatch, journal):
    monkeypatch.setattr(spans, "journal", lambda: journal)
    return files.load_module("layer_metrics",
                             "sweep_fill_share").read(RECORD)


@pytest.mark.parametrize("levels, want", [
    # the median query: 4 and 1,761 columns on rung 2^12
    ([(4, 4096), (1761, 4096)], 100.0 * 1765 / 8192),
    # a hub start's second level on the top rung, and a rung twice its mass
    ([(4, 4096), (150_732, 1 << 21)], 100.0 * 150_736 / (4096 + (1 << 21))),
    ([(4, 4096), (150_732, 1 << 18)], 100.0 * 150_736 / (4096 + (1 << 18))),
    ([(4096, 4096), ("bu", None), (1 << 17, 1 << 17)], 100.0),
    ([(0, 4096)], 0.0),
])
def test_share_of_the_columns_paid_for(monkeypatch, levels, want):
    """One level each, in batches of two levels; a pulled level (three
    chunk rounds, no ``p_cap``) is no pushed level and counts nowhere;
    nor does the warm-up's push before the window."""
    made = []
    for i, (mass, cap) in enumerate(levels):
        where = {"level": 1 + i % 2, "trace": f"traverse-{i // 2}"}
        if mass == "bu":
            made += [sweep(10 * i + r, T0 + 1 + i + r / 10, dir="bu",
                           c_cap=1024, fuse=8, **where) for r in range(3)]
        else:
            made.append(sweep(10 * i, T0 + 1 + i, dir="td", p_cap=cap,
                              mass=mass, list="carried", **where))
    made.append(sweep(99, T0 - 5.0, dir="td", p_cap=1 << 21, mass=0,
                      list="scan"))
    made.append({"trace": "traverse-7", "span": 98, "name": "bfs.plan",
                 "start": T0 + 2.0, "end": T0 + 2.01, "duration_ms": 0.1,
                 "attrs": {"level": 1, "carried": True}})
    assert read(monkeypatch, Journal(made)) == pytest.approx(want)


def test_a_program_that_does_not_say_reports_nothing(monkeypatch):
    """A push that writes ``dir`` and no rung, or a rung and no mass."""
    made = [sweep(1, T0 + 1.0, dir="td", list="carried"),
            sweep(2, T0 + 2.0, level=2, dir="td", p_cap=4096, mass=90)]
    assert read(monkeypatch, Journal(made)) is None
    made = [sweep(1, T0 + 1.0, dir="td", p_cap=4096)]
    assert read(monkeypatch, Journal(made)) is None


def test_no_push_in_the_window_and_no_journal(monkeypatch):
    pulled = [sweep(1, T0 + 1.0, dir="bu", c_cap=1024, fuse=8)]
    assert read(monkeypatch, Journal(pulled)) is None
    assert read(monkeypatch, Journal([])) is None
    assert read(monkeypatch, None) is None


def test_declared_with_its_reader_in_both_cells():
    b = files.benchmark_json()
    m = next(m for m in b["per_layer"] if m["name"] == "sweep_fill_share")
    assert m["source"] == "program_span" and m["unit"] == "%"
    assert m["moves"] == "latency_p95_ms" and m["better"] == "higher"
    assert m["layer"] == next(
        x["layer"] for x in b["per_layer"] if x["name"] == "sweep_td_share")
    assert m["workloads"] == [w["name"] for w in b["workloads"]]
