"""A scale-10 rehearsal of the CDLP cell on the CPU, the look for a chip
stubbed: the traced run reports every layer of the cell but the roofline
share (the sandbox's trace has no device plane, so no device time to
divide by), one label altered where it is produced makes ``correct``
false with 1 label out a job, the stale-epoch control is not correct, and
the mix asks for the configuration's algorithm."""

import json

import files
import run

CELL = "g500-22.cdlp-c2"
NEW = {"cdlp_exec_ms", "cdlp_round_ms", "cdlp_sort_ms", "cdlp_gather_ms",
       "cdlp_host_idle_ms", "cdlp_round_roofline"}


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
    # the cell lists what this PR brought; a later entry may join them
    assert NEW <= wanted
    assert res["device"]["busy_s"] == 0     # no device plane on the CPU
    assert wanted - set(res["metrics"]) == {"cdlp_round_roofline"}
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["cdlp_exec_ms"] > 0
    assert metrics["cdlp_sort_ms"] > 0 and metrics["cdlp_gather_ms"] > 0
    assert metrics["cdlp_round_ms"] > 0
    assert metrics["cdlp_host_idle_ms"] >= 0
    for key in ("cdlp_gather", "cdlp_sort", "cdlp_vote"):
        assert f"kernel {key} (" in out and ": 10 calls a job" in out
    assert "kernel cdlp_gather: " in out and "impl ['xla']" in out
    for name in ("job.lease", "job.admit", "cdlp.result", "cdlp.count"):
        assert f"host {name}: median " in out
    assert "idle under " in out
    assert "compare labels: mismatches=0 of " in out and "limit=0" in out


def test_the_end_to_end_run_reports_throughput_and_setup(small_bench,
                                                         capsys):
    res, _out = result_of(capsys, ["--workload", CELL, "--seed", "11",
                                   "--seconds", "1", "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"throughput", "setup_s"}
    assert res["metrics"]["throughput"]["value"] > 0


def test_one_label_altered_where_it_is_made(small_bench, capsys,
                                            monkeypatch):
    from titan_tpu.models import cdlp

    real = cdlp.cdlp

    def altered(snap, **kw):
        labels, rounds = real(snap, **kw)
        labels = labels.copy()
        labels[17] += 1                 # one label of n, behind the rounds
        return labels, rounds

    monkeypatch.setattr(cdlp, "cdlp", altered)
    res, out = result_of(capsys, ["--workload", CELL, "--seed", "7",
                                  "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert f"compare labels: mismatches={res['attempted']} of " in out


def test_the_stale_epoch_control_is_not_correct(small_bench):
    import control

    _bench, _cell, config, mix = files.cell_files(CELL)
    out = control.control_run(config, mix, seed=5, stale_share=0.05)
    assert out["correct"] is False
    bad, of = out["compared"]["labels"]
    assert of == control.REQUESTS and bad >= of


def test_the_mix_asks_for_the_configurations_algorithm():
    bench, cell, config, mix = files.cell_files(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "cdlp-jobs-c2"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert entry["source"] == config["source"]
    assert config["algorithm"] == {"name": "CDLP", "max-iterations": 10}
    body = mix["request"]["body"]
    assert body == {"kind": "cdlp", "timeout_s": 300,
                    "iterations": config["algorithm"]["max-iterations"]}
    assert (mix["driver"], mix["op"], mix["callers"], mix["poll_s"],
            mix["pools"], mix["result_array"]) == \
        ("closed_jobs", "cdlp", 2, 0.1, {}, "labels")
    assert mix["request_timeout_s"] == 300 and mix["trace_slice_s"] == 5
    # the same data set as the PageRank cell's, under another algorithm
    other = json.load(open(files.path(
        "configs", "graphalytics-g500-22.json")))
    for key in ("generator", "scale", "a", "b", "c", "edge_factor",
                "undirected", "graph_seed"):
        assert config[key] == other[key]
    # it reports throughput and set-up, and no latency percentile
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == \
            (m["name"] in ("throughput", "setup_s"))
