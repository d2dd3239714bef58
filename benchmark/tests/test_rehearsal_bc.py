"""A scale-10 rehearsal of the BC cell on the CPU, the look for a chip
stubbed: the traced run reports every layer of the cell but the roofline
share (the sandbox's trace has no device plane, so no device time to
divide by), every job of a run names other roots, one score altered
where it is produced makes ``correct`` false with 1 score out a job, the
stale-epoch control is not correct, and the mix asks for the
configuration's algorithm."""

import json

import pytest

import files
import run

CELL = "kron-s22.bc-c2"
NEW = {"bc_exec_ms", "bc_forward_ms", "bc_backward_ms", "bc_pull_ms",
       "bc_host_idle_ms", "bc_level_roofline"}


def result_of(capsys, argv):
    assert run.main(argv) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.out


def test_a_traced_run_reports_the_cells_layers(small_bench, capsys):
    res, out = result_of(capsys, ["--workload", CELL, "--seed",
                                  "3000000019", "--seconds", "3",
                                  "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    wanted = {m["name"] for m in small_bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    # the cell lists what this PR brought; a later entry may join them
    assert NEW <= wanted
    assert res["device"]["busy_s"] == 0     # no device plane on the CPU
    assert wanted - set(res["metrics"]) == {"bc_level_roofline"}
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["bc_exec_ms"] > 0 and metrics["bc_pull_ms"] > 0
    assert metrics["bc_forward_ms"] > 0 and metrics["bc_backward_ms"] > 0
    assert metrics["bc_forward_ms"] + metrics["bc_backward_ms"] \
        <= metrics["bc_exec_ms"]
    assert metrics["bc_host_idle_ms"] >= 0
    for key in ("bc_forward_level", "bc_backward_level"):
        assert f"kernel {key}: median " in out and "impl ['xla']" in out
    for name in ("job.lease", "job.admit", "bc.result"):
        assert f"host {name}: median " in out
    assert "phase bc.forward: " in out and "phase bc.backward: " in out
    assert "idle under " in out
    # at most two warm jobs of other roots: the second builds nothing
    # (the first neither where this process has built the cell before)
    assert "warm job 1: " in out and "warm job 3: " not in out
    assert "compare scores: mismatches=0 of " in out and "limit=0" in out


def test_the_end_to_end_run_reports_throughput_and_setup(small_bench,
                                                         capsys):
    res, _out = result_of(capsys, ["--workload", CELL, "--seed", "11",
                                   "--seconds", "1", "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"throughput", "setup_s"}
    assert res["metrics"]["throughput"]["value"] > 0


def test_one_score_altered_where_it_is_made(small_bench, capsys,
                                            monkeypatch):
    import numpy as np

    from titan_tpu.models import bc

    real = bc.bc
    at = {}

    def altered(snap, roots, **kw):
        scores, levels, reached = real(snap, roots, **kw)
        # neither the largest (all n would move) nor a zero
        v = int(np.flatnonzero((scores > 0) & (scores < 0.5))[0])
        scores = scores.copy()
        scores[v] *= np.float32(1.001)
        at["v"] = v
        return scores, levels, reached

    monkeypatch.setattr(bc, "bc", altered)
    res, out = result_of(capsys, ["--workload", CELL, "--seed", "7",
                                  "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False and "v" in at
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert f"compare scores: mismatches={res['attempted']} of " in out


def test_the_stale_epoch_control_is_not_correct(small_bench):
    import control

    _bench, _cell, config, mix = files.cell_files(CELL)
    out = control.control_run(config, mix, seed=5, stale_share=0.05)
    assert out["correct"] is False
    bad, of = out["compared"]["scores"]
    assert of == control.REQUESTS and bad >= of


def test_the_mix_asks_for_the_configurations_algorithm():
    from reference import bc as reference

    bench, cell, config, mix = files.cell_files(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bc-jobs-c2"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["scale"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/gap-kron-s22-bc.json"
    algorithm = config["algorithm"]
    assert reference.EPSILON == algorithm["epsilon"] == 1e-4
    body = mix["request"]["body"]
    assert body["kind"] == "bc" and body["timeout_s"] == 300
    # a trial's roots: one of each pool; the pools together GAP's picks
    assert body["sources"] == [{"draw": name} for name in sorted(
        mix["pools"])] and len(body["sources"]) \
        == algorithm["roots_per_trial"] \
        == config["published"]["roots_per_trial"]
    assert {p["size"] for p in mix["pools"].values()} \
        == {algorithm["trials"]} == {config["published"]["trials"]}
    assert {p["among"] for p in mix["pools"].values()} \
        == {"nonzero_degree"}
    assert (mix["driver"], mix["op"], mix["callers"], mix["poll_s"],
            mix["result_array"]) == \
        ("closed_jobs_keyed", "bc", 2, 0.1, "scores")
    assert mix["request_timeout_s"] == 300 and mix["trace_slice_s"] == 5
    # the graph of the three graph500-22 cells
    other = json.load(open(files.path(
        "configs", "graphalytics-g500-22.json")))
    for key in ("generator", "scale", "a", "b", "c", "edge_factor",
                "undirected", "graph_seed"):
        assert config[key] == other[key]
    for key in ("vertices", "edges", "directed_edge_slots"):
        assert config["generated"][key] == json.load(open(files.path(
            "configs", "graphalytics-g500-22-lcc.json")))["generated"][key]
    from titan_tpu.models import bc
    assert len(body["sources"]) <= bc.MAX_ROOTS
    # it reports throughput and set-up, and no latency percentile
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == \
            (m["name"] in ("throughput", "setup_s"))
