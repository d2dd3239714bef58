"""bench.py Report contract: the headline is a one-shot latch.

VERDICT r5 weak #1: BENCH_r05's driver-parsed metric line read
``gods_2hop_p50_ms`` because a later stage's ``rep.headline(...)`` call
overwrote the scale-26 BFS TEPS headline. The latch makes the metric
line OWNED by whichever stage sets it first — the headline BFS stage,
which main() orders first and never budget-skips.
"""

import json

import bench


def test_headline_is_a_one_shot_latch(capsys):
    rep = bench.Report()
    rep.headline("graph500_scale26_bfs_teps", 1.568e8, "TEPS", 0.1568)
    # a later stage trying to claim the line is ignored
    rep.headline("gods_2hop_p50_ms", 0.137, "ms", 0.0)
    rep.emit()
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["metric"] == "graph500_scale26_bfs_teps"
    assert out["value"] == 1.568e8
    assert out["vs_baseline"] == 0.1568


def test_unlatched_report_is_incomplete():
    rep = bench.Report()
    assert rep.metric == "bench_incomplete"


def test_estimates_reprice_with_measured_h2d_rate():
    """Stage admission scales upload-heavy estimates by the observed
    H2D rate (VERDICT r5 weak #2: flat fast-day estimates admitted
    bfs_heavy into the external kill)."""
    old = bench._h2d_gbps
    try:
        bench._observe_h2d(9.0, 16.0)          # fast day: ~0.56 GB/s
        fast = bench._est("bfs_heavy")
        bench._observe_h2d(9.0, 480.0)         # a slow measured upload
        slow = bench._est("bfs_heavy")
        assert slow > fast
        # fixed-cost stages are unaffected by the measured H2D rate
        assert bench._est("ssspwcc") == bench._EST["ssspwcc"][0]
        # tiny/implausible observations are clamped, never zero/inf
        bench._observe_h2d(0.1, 1.0)           # too small to trust
        assert bench._est("bfs_heavy") == slow
    finally:
        bench._h2d_gbps = old
