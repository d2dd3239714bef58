"""A scale-10 rehearsal of the two job cells on the CPU, the look for a
chip stubbed: the traced run prints the six readers of the ``kernel``
spans (ISSUE 38) in the cells that list them, with the table by key and
the idle time by phase. (That a job's device time and idle time add up
to its ``exec_ms`` is a chip reading: ``PERF.md`` 6, PR 38.)"""

import json

import pytest

import run

PR, WCC = "g500-22.pr-c2", "g500-24.wcc-c2"
WANTED = {
    PR: {"job_device_ms", "job_host_idle_ms", "pr_pull_ms"},
    WCC: {"job_device_ms", "job_host_idle_ms", "wcc_endgame_ms"},
}
#: listed in the cell, and silent at scale 10: no level of the peel
#: pulls there (the direction rule takes the endgame first)
SILENT = {PR: set(), WCC: {"wcc_bu_wide_ms", "wcc_bu_rest_ms"}}


@pytest.mark.parametrize("cell", [PR, WCC])
def test_a_traced_run_prints_the_kernel_readers(small_bench, capsys, cell):
    assert run.main(["--workload", cell, "--seed", "3000000019",
                     "--seconds", "2", "--trace", "1"]) == 0
    out = capsys.readouterr().out
    res = json.loads(out.strip().splitlines()[-1])
    assert res["correct"] is True and res["failed"] == 0
    listed = {m["name"] for m in small_bench["per_layer"][-6:]
              if cell in m["workloads"]}
    assert listed == WANTED[cell] | SILENT[cell]
    assert WANTED[cell] <= set(res["metrics"])
    assert not SILENT[cell] & set(res["metrics"])
    got = {n: res["metrics"][n]["value"] for n in WANTED[cell]}
    assert all(v > 0.0 for v in got.values())
    key = "pagerank_pull (step)" if cell == PR else "hybrid_endgame (end)"
    assert f"kernel {key}: " in out and "unstamped 0" in out
    assert "idle under job.admit: median " in out
