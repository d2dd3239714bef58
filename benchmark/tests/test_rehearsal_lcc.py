"""A scale-10 rehearsal of the LCC cell on the CPU, the look for a chip
stubbed and the hubs cut to 64 so that every part of the job has work:
the traced run reports every layer of the cell but the roofline share
(the sandbox's trace has no device plane, so no device time to divide
by), one count altered where it is produced makes ``correct`` false with
1 coefficient out a job, the stale-epoch control is not correct, and the
mix asks for the configuration's algorithm."""

import json

import pytest

import files
import run

CELL = "g500-22.lcc-c2"
NEW = {"lcc_exec_ms", "lcc_hub_ms", "lcc_tail_ms", "lcc_host_idle_ms",
       "lcc_image_s", "lcc_job_roofline"}


@pytest.fixture
def few_hubs(monkeypatch):
    from titan_tpu.models import lcc
    monkeypatch.setattr(lcc, "HUBS", 64)


def result_of(capsys, argv):
    assert run.main(argv) == 0
    out = capsys.readouterr().out
    return json.loads(out.strip().splitlines()[-1]), out


def test_a_traced_run_reports_the_cells_layers(small_bench, few_hubs,
                                               capsys):
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
    assert wanted - set(res["metrics"]) == {"lcc_job_roofline"}
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["lcc_exec_ms"] > 0
    assert metrics["lcc_hub_ms"] > 0 and metrics["lcc_tail_ms"] > 0
    assert metrics["lcc_host_idle_ms"] >= 0 and metrics["lcc_image_s"] > 0
    for key in ("lcc_pass", "lcc_colsum"):
        assert f"kernel {key}: median " in out
    for name in ("job.lease", "job.admit", "lcc.image", "lcc.result",
                 "lcc.count"):
        assert f"host {name}: median " in out
    assert "idle under " in out and "lcc.image: built 1 x" in out
    assert "compare lcc: mismatches=0 of " in out and "limit=0" in out


def test_the_end_to_end_run_reports_throughput_and_setup(small_bench,
                                                         capsys):
    """At the module's own hub count every vertex of the small graph is
    a hub: the pass alone answers."""
    res, _out = result_of(capsys, ["--workload", CELL, "--seed", "11",
                                   "--seconds", "1", "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"throughput", "setup_s"}
    assert res["metrics"]["throughput"]["value"] > 0


def test_one_count_altered_where_it_is_made(small_bench, few_hubs, capsys,
                                            monkeypatch):
    import numpy as np

    from titan_tpu.models import lcc

    real = lcc.lcc
    at = {}

    def altered(snap, **kw):
        counts, coeff = real(snap, **kw)
        v = int(np.flatnonzero(counts > 0)[0])
        d = float(np.diff(snap.indptr_in)[v])
        counts, coeff = counts.copy(), coeff.copy()
        counts[v] += 1              # one triangle too many at one vertex
        coeff[v] = 2.0 * counts[v] / (d * (d - 1.0))
        at["v"] = v
        return counts, coeff

    monkeypatch.setattr(lcc, "lcc", altered)
    res, out = result_of(capsys, ["--workload", CELL, "--seed", "7",
                                  "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False and "v" in at
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert f"compare lcc: mismatches={res['attempted']} of " in out


def test_the_stale_epoch_control_is_not_correct(small_bench):
    import control

    _bench, _cell, config, mix = files.cell_files(CELL)
    out = control.control_run(config, mix, seed=5, stale_share=0.05)
    assert out["correct"] is False
    bad, of = out["compared"]["lcc"]
    assert of == control.REQUESTS and bad >= of


def test_the_mix_asks_for_the_configurations_algorithm():
    from reference import lcc as reference

    bench, cell, config, mix = files.cell_files(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "lcc-jobs-c2"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert entry["source"] == config["source"]
    assert config["algorithm"] == {"name": "LCC", "epsilon": 1e-4}
    assert reference.EPSILON == config["algorithm"]["epsilon"]
    assert mix["request"]["body"] == {"kind": "lcc", "timeout_s": 300}
    assert (mix["driver"], mix["op"], mix["callers"], mix["poll_s"],
            mix["pools"], mix["result_array"]) == \
        ("closed_jobs", "lcc", 2, 0.1, {}, "lcc")
    assert mix["request_timeout_s"] == 300 and mix["trace_slice_s"] == 5
    # the same data set as the PageRank cell's, under another algorithm
    other = json.load(open(files.path(
        "configs", "graphalytics-g500-22.json")))
    for key in ("generator", "scale", "a", "b", "c", "edge_factor",
                "undirected", "graph_seed"):
        assert config[key] == other[key]
    from titan_tpu.models import lcc
    assert config["generated"]["hubs"] == lcc.HUBS
    assert config["generated"]["hub_table_bytes"] == \
        lcc.table_bytes(config["generated"]["vertices"], lcc.HUBS)
    # it reports throughput and set-up, and no latency percentile
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == \
            (m["name"] in ("throughput", "setup_s"))
