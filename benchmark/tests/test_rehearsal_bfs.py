"""A scale-10 rehearsal of the BFS cell on the CPU, the look for a chip
stubbed: the traced run reports every layer of the cell but the roofline
share (the sandbox's trace has no device plane, so no device time to
divide by), the warm-up's first job builds the whole set and its second
nothing, every job of a run names another source, one parent altered
where the answer is made makes ``correct`` false with 1 vertex out a job
by two of the four counts, the stale-epoch control is not correct, and
the mix asks for the configuration's algorithm: GAP's answer, the parent
array, held to GAP's rule."""

import json

import pytest

import files
import run

CELL = "kron-s22.bfs-tree-c2"
NEW = {"bfs_exec_ms", "bfs_device_ms", "bfs_host_idle_ms", "bfs_push_ms",
       "bfs_pull_ms", "bfs_plan_ms", "bfs_padded_share",
       "bfs_job_roofline"}


def result_of(capsys, argv):
    assert run.main(argv) == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.out


@pytest.fixture
def one_caller(monkeypatch):
    """The mix with one caller: at this size a job is milliseconds, so
    two callers' jobs now and then wait in the queue together and fuse
    into a cohort of two, whose programs (other shapes: K = 2) are built
    then and there. At the cell's size a job outlasts the other caller's
    fetch and check, and the queue never holds two (``PERF.md`` 7: K's
    buckets)."""
    real = files.load_json

    def load(*parts):
        got = real(*parts)
        return dict(got, callers=1) if parts[0] == "traffic" else got
    monkeypatch.setattr(files, "load_json", load)


def test_a_traced_run_reports_the_cells_layers(small_bench, one_caller,
                                               capsys):
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
    assert wanted - set(res["metrics"]) == {"bfs_job_roofline"}
    metrics = {k: v["value"] for k, v in res["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["bfs_exec_ms"] > 0 and metrics["bfs_device_ms"] > 0
    assert metrics["bfs_push_ms"] > 0 and metrics["bfs_pull_ms"] > 0
    assert metrics["bfs_plan_ms"] > 0
    assert metrics["bfs_push_ms"] + metrics["bfs_pull_ms"] \
        + metrics["bfs_plan_ms"] <= metrics["bfs_exec_ms"]
    assert metrics["bfs_host_idle_ms"] >= 0
    assert 0.0 <= metrics["bfs_padded_share"] < 100.0
    for key in ("batched_plan", "batched_td", "batched_bu"):
        assert f"kernel {key} (" in out
    for name in ("job.lease", "job.admit", "bfs.result"):
        assert f"host {name}: median " in out
    assert "push p_cap=" in out and "pull c_cap=" in out
    assert "plan: " in out and "idle under " in out
    # two warm jobs of other sources: the first builds the set (or finds
    # it built by this process), the second builds nothing
    assert "warm job 1: " in out and "warm job 3: " not in out
    for name in ("source", "reached", "depth", "edge"):
        assert f"compare {name}: mismatches=0 of " in out
    assert "limit=0" in out


def test_the_end_to_end_run_reports_throughput_and_setup(small_bench,
                                                         capsys):
    res, _out = result_of(capsys, ["--workload", CELL, "--seed", "11",
                                   "--seconds", "1", "--trace", "0"])
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"throughput", "setup_s"}
    assert res["metrics"]["throughput"]["value"] > 0


def test_one_parent_altered_where_the_answer_is_made(small_bench, capsys,
                                                     monkeypatch):
    """A reached vertex other than the source made its own parent: one
    vertex a job whose parent is not one level nearer (``depth``) and no
    neighbour (``edge``: the data set has no self-loop); the source's
    own parent and the reached set stand."""
    import numpy as np

    from titan_tpu.olap.serving import batcher

    real = batcher._bfs_result
    at = {}

    def altered(snap, dist_row, levels, inf, params, parent_row=None):
        parent_row = parent_row.copy()
        v = int(np.flatnonzero((dist_row > 0) & (dist_row < inf))[0])
        parent_row[v] = v
        at["v"] = v
        return real(snap, dist_row, levels, inf, params, parent_row)

    monkeypatch.setattr(batcher, "_bfs_result", altered)
    res, out = result_of(capsys, ["--workload", CELL, "--seed", "7",
                                  "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False and "v" in at
    assert res["failed"] == 0 and res["attempted"] >= 1
    for name in ("depth", "edge"):
        assert f"compare {name}: mismatches={res['attempted']} of " in out
    for name in ("source", "reached"):
        assert f"compare {name}: mismatches=0 of " in out


def test_the_stale_epoch_control_is_not_correct(small_bench):
    import control

    _bench, _cell, config, mix = files.cell_files(CELL)
    out = control.control_run(config, mix, seed=5, stale_share=0.05)
    assert out["correct"] is False
    # a tree of the stale epoch is made of edges the newest epoch still
    # has, and its source is its own parent; what it gets wrong is how
    # deep a vertex lies, and now and then whom it reaches (a vertex it
    # leaves without a parent is out by ``reached``, ``depth`` and
    # ``edge`` alike: no other vertex is out by ``edge``)
    bad, of = out["compared"]["depth"]
    assert of == control.REQUESTS and bad >= of
    assert out["compared"]["edge"][0] <= out["compared"]["reached"][0]
    assert out["compared"]["source"] == [0, of]


def test_the_mix_asks_for_the_configurations_algorithm():
    from reference import bfs as reference

    bench, cell, config, mix = files.cell_files(CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "bfs-tree-jobs-c2"
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["scale"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmark/configs/gap-kron-s22-bfs.json"
    for key in ("source", "published", "generated", "reduced", "assumed",
                "deployment", "guarantees"):
        assert config[key]
    algorithm = config["algorithm"]
    assert algorithm["name"] == "BFS" and algorithm["output"] == "parent"
    assert reference.UNREACHED == algorithm["unreached"]
    assert reference.NO_PARENT == algorithm["unreached_parent"] == -1
    assert reference.COMPARED == ("source", "reached", "depth", "edge")
    assert "BFSVerifier" in config["published"]["verifier"]
    from titan_tpu.models.bfs import INF
    assert int(INF) == algorithm["unreached"]
    body = mix["request"]["body"]
    assert body == {"kind": "bfs", "source": {"draw": "source"},
                    "parents": True, "timeout_s": 300}
    # one source a trial, the pool GAP's 64 picks
    assert list(mix["pools"]) == ["source"]
    assert mix["pools"]["source"] == {"size": 64,
                                      "among": "nonzero_degree"}
    assert algorithm["sources"] == config["published"]["trials"] == 64
    assert algorithm["sources_per_trial"] \
        == config["published"]["sources_per_trial"] == 1
    assert (mix["driver"], mix["op"], mix["callers"], mix["poll_s"],
            mix["result_array"]) == \
        ("closed_jobs_keyed", "bfs", 2, 0.1, "parent")
    assert mix["request_timeout_s"] == 300 and mix["trace_slice_s"] == 5
    # the graph of the graph500-22 cells
    other = json.load(open(files.path(
        "configs", "graphalytics-g500-22.json")))
    for key in ("generator", "scale", "a", "b", "c", "edge_factor",
                "undirected", "graph_seed"):
        assert config[key] == other[key]
    for key in ("vertices", "edges", "directed_edge_slots"):
        assert config["generated"][key] == other["generated"][key]
    # it reports throughput and set-up, and no latency percentile
    for m in bench["end_to_end"]:
        assert (CELL in m.get("workloads", [CELL])) == \
            (m["name"] in ("throughput", "setup_s"))


def test_every_job_names_another_source(small_bench):
    """The pool walked whole in a seeded order: the first 64 bodies of a
    seed name 64 distinct sources, and two seeds the same 64."""
    import numpy as np

    import loadgen
    from reference import csr

    _bench, _cell, config, mix = files.cell_files(CELL)
    n, src, dst, perm = loadgen.make_graph(config, 5)
    pools = loadgen.draw_pools(np.bincount(src, minlength=n), mix, config,
                               perm)
    assert len(set(pools["source"])) == 64
    indptr, _ = csr.structure(n, src, dst)
    assert all(indptr[s + 1] > indptr[s] for s in pools["source"])
    sent = [[loadgen.Bodies(mix, pools, seed).get(i)["source"]
             for i in range(64)] for seed in (5, 6)]
    assert sorted(sent[0]) == sorted(sent[1]) == sorted(pools["source"])
    assert sent[0] != sent[1]
