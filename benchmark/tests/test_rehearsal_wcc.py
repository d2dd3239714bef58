"""A scale-10 rehearsal of the WCC cell on the CPU, the look for a chip
stubbed: the traced run reports every layer of the cell but the roofline
share (the sandbox's trace has no device plane, so no device time to
divide by), one label altered where it is produced makes ``correct``
false, and the numbers compared are printed beside their limit."""

import json

import files
import run

CELL = "g500-24.wcc-c2"
NEW = {"wcc_exec_ms", "wcc_queue_ms", "wcc_fetch_ms", "wcc_peel_ms",
       "wcc_prop_ms", "bfs_pull_share", "wcc_job_roofline"}


def result_of(capsys, argv):
    assert run.main(argv) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_a_traced_run_reports_the_cells_layers(small_bench, capsys):
    res, out = result_of(capsys, ["--workload", CELL, "--seed",
                                  "3000000019", "--seconds", "2",
                                  "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    wanted = {m["name"] for m in small_bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert NEW | {"wire_ms", "compiles_in_window", "device_ms_per_req",
                  "device_idle_share", "graph_build_s",
                  "warm_s"} == wanted
    assert res["device"]["busy_s"] == 0     # no device plane on the CPU
    assert set(res["metrics"]) == wanted - {"wcc_job_roofline"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["wcc_peel_ms"]["value"] > 0
    assert res["metrics"]["wcc_prop_ms"]["value"] > 0
    assert res["metrics"]["wcc_fetch_ms"]["value"] > 0
    assert 0.0 <= res["metrics"]["bfs_pull_share"]["value"] <= 100.0
    # the peel's steps and the cohort sizes are printed for PERF.md
    assert "peel L0 head: median " in out
    assert "wcc jobs by cohort size (k: jobs): {1: " in out
    for name in ("job.lease", "job.admit", "wcc.count"):
        assert f"host {name}: median " in out
    assert "compare labels: mismatches=0 of " in out and "limit=0" in out


def test_one_label_altered_where_it_is_made(small_bench, capsys,
                                            monkeypatch):
    from titan_tpu.models import frontier

    real = frontier._wcc_readback

    def altered(out):
        labels = real(out).copy()
        labels[17] += 1                 # one label of n
        return labels

    monkeypatch.setattr(frontier, "_wcc_readback", altered)
    res, out = result_of(capsys, ["--workload", CELL, "--seed", "7",
                                  "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert f"compare labels: mismatches={res['attempted']} of " in out
