"""A scale-10 rehearsal of the job cell on the CPU, the look for a chip
stubbed: the traced run reports every layer of the cell but the roofline
share (the sandbox's trace has no device plane, so no device time to
divide by), one rank altered where it is produced makes ``correct``
false, and a program without the result plane fails in set-up after one
job."""

import json

import pytest

import files
import run

CELL = "g500-22.pr-c2"


def result_of(capsys, argv) -> dict:
    assert run.main(argv) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_traced_run_reports_the_cells_layers(small_bench, capsys):
    res = result_of(capsys, ["--workload", CELL, "--seed", "3000000019",
                             "--seconds", "2", "--trace", "1"])
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 2
    wanted = {m["name"] for m in small_bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    assert {"job_queue_ms", "job_exec_ms", "pr_iter_ms", "result_fetch_ms",
            "pr_iter_roofline", "wire_ms", "compiles_in_window"} <= wanted
    assert res["device"]["busy_s"] == 0     # no device plane on the CPU
    assert set(res["metrics"]) == wanted - {"pr_iter_roofline"}
    assert res["metrics"]["compiles_in_window"]["value"] == 0
    assert res["metrics"]["pr_iter_ms"]["value"] > 0


def test_one_rank_altered_where_it_is_made(small_bench, capsys,
                                           monkeypatch):
    from titan_tpu.models import frontier

    real = frontier.pagerank_dense

    def altered(*a, **kw):
        rank, its = real(*a, **kw)
        rank = rank.copy()
        rank[17] *= 1.0 + 5e-4          # one rank of n, five epsilons off
        return rank, its

    monkeypatch.setattr(frontier, "pagerank_dense", altered)
    res = result_of(capsys, ["--workload", CELL, "--seed", "7",
                             "--seconds", "1", "--trace", "0"])
    assert res["correct"] is False
    assert res["failed"] == 0 and res["attempted"] >= 1


def test_the_mismatches_are_printed_beside_their_limit(small_bench, capsys):
    assert run.main(["--workload", CELL, "--seed", "5", "--seconds", "1",
                     "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert "compare rank: mismatches=0 of " in out and "limit=0" in out


def test_a_program_without_the_result_plane_fails_in_set_up(
        small_bench, capsys, monkeypatch):
    from titan_tpu.olap.serving.jobs import Job

    real = Job.to_wire

    def older(self):
        out = real(self)
        out.pop("arrays", None)
        return out

    monkeypatch.setattr(Job, "to_wire", older)
    with pytest.raises(RuntimeError, match="no result plane"):
        run.main(["--workload", CELL, "--seed", "5", "--seconds", "1",
                  "--trace", "0"])
    assert "warm job" not in capsys.readouterr().out
