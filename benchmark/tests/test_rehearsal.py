"""A scale-10 rehearsal of a whole run on the CPU, the look for a chip
stubbed: every cell end to end, and — the timed path broken underneath —
``correct`` comes out false."""

import json

import pytest

import files
import run

CELLS = [w["name"] for w in files.benchmark_json()["workloads"]]


def result_of(capsys, argv) -> dict:
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_a_run_end_to_end(small_bench, capsys, cell):
    res = result_of(capsys, ["--workload", cell, "--seed", "3000000019",
                             "--seconds", "3", "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    wanted = {m["name"] for m in small_bench["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == wanted
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_the_layers(small_bench, capsys, cell):
    res = result_of(capsys, ["--workload", cell, "--seed", "12",
                             "--seconds", "3", "--trace", "1"])
    assert res["correct"] is True
    # on the CPU there is no device plane, so the two device-trace
    # readers report nothing worth a name; every other reader reads
    wanted = {m["name"] for m in small_bench["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == wanted
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", CELLS)
def test_a_hop_count_altered_where_it_is_made(small_bench, capsys,
                                              monkeypatch, cell):
    from titan_tpu import server

    real = server.jsonify
    monkeypatch.setattr(
        server, "jsonify",
        lambda v: real(v) + 1 if isinstance(v, int) else real(v))
    res = result_of(capsys, ["--workload", cell, "--seed", "7",
                             "--seconds", "2", "--trace", "0"])
    assert res["correct"] is False


def test_a_fallback_is_a_failed_request(small_bench, capsys, monkeypatch):
    from titan_tpu.olap.serving.interactive import scheduler as lane

    def refuse(self, *a, **kw):
        raise lane.FallbackToInterpreter("rehearsal: lane refuses")

    res = None
    monkeypatch.setattr(lane.InteractiveLane, "_sweep", refuse)
    try:
        res = result_of(capsys, ["--workload", CELLS[0], "--seed", "8",
                                 "--seconds", "1", "--trace", "0"])
    except RuntimeError as e:         # the warm-up already refuses to go on
        assert "failed or fell back" in str(e)
    else:
        assert res["failed"] == res["attempted"] and not res["correct"]
