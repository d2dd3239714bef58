"""A served PageRank job, end to end on the CPU (ISSUE 33): ``POST /jobs``
-> poll -> ``GET /jobs/<id>/result/rank``, every rank held against the
benchmark's plain float64 reference (``benchmark/reference/pagerank.py``,
LDBC Graphalytics' formula, nothing of ``titan_tpu`` in it) by the
specification's epsilon rule; a directed graph with a dangling vertex, on
which the program (it leaks dangling mass) and the reference (it
redistributes it) are shown to differ — the reason the benchmark's set-up
refuses such a graph; and the job's spans and counters: ``pr.sweep``,
``pr.finish``, ``pr.result`` under ``run``, one ``device.pr.iterations``
an iteration, the readback's bytes under ``pagerank.result``.
"""

import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITERATIONS, DAMPING = 10, 0.85


def _reference_module(name: str):
    """``benchmark/reference/<name>.py`` by file: neither imports
    anything of the harness or of the program."""
    spec = importlib.util.spec_from_file_location(
        f"_bench_reference_{name}",
        os.path.join(ROOT, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _reference_module("pagerank")


def structure(n, src, dst):
    return _reference_module("csr").structure(n, src, dst)


def simple_undirected(seed: int, n: int = 1 << 11, m: int = 1 << 15):
    """A skewed simple undirected graph, both directions of each pair,
    no self-loop, no duplicate, no vertex without an edge."""
    rng = np.random.default_rng(seed)
    a = (rng.random(m) ** 3 * n).astype(np.int64)        # hubs near 0
    b = rng.integers(0, n, m)
    ring = np.arange(n)                  # a ring: every vertex has an edge
    a, b = np.concatenate([a, ring]), np.concatenate([b, (ring + 1) % n])
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    key = np.unique(lo[lo != hi] * n + hi[lo != hi])
    lo, hi = (key // n).astype(np.int32), (key % n).astype(np.int32)
    return n, np.concatenate([lo, hi]), np.concatenate([hi, lo])


class Served:
    def __init__(self, n, src, dst):
        self.metrics = MetricManager()
        snap = snap_mod.from_arrays(n, src, dst)
        self.sched = JobScheduler(snapshot=snap, metrics=self.metrics)
        self.http = GraphServer(None, port=0, scheduler=self.sched).start()
        self.base = f"http://{self.http.host}:{self.http.port}"

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=30) as r:
            return r.headers, r.read()

    def job(self, body):
        req = urllib.request.Request(
            self.base + "/jobs", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            job_id = json.loads(r.read())["job"]
        deadline = time.time() + 120
        while time.time() < deadline:
            env = json.loads(self.get(f"/jobs/{job_id}")[1])
            if env["status"] not in ("queued", "running"):
                return env
            time.sleep(0.02)
        raise AssertionError(f"job {job_id} did not finish")

    def rank(self, job_id):
        headers, raw = self.get(f"/jobs/{job_id}/result/rank")
        return np.frombuffer(raw, np.dtype(headers["X-Dtype"]))

    def close(self):
        self.http.stop()
        self.sched.close()


@pytest.mark.parametrize("seed", [3000000019, 12, 7])
def test_a_served_job_within_epsilon_of_the_reference(reference, seed):
    n, src, dst = simple_undirected(seed)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "pagerank", "iterations": ITERATIONS,
                          "damping": DAMPING, "timeout_s": 60})
        assert env["status"] == "done", env
        assert env["result"] == {"iterations": ITERATIONS}
        assert env["arrays"] == {"rank": {"dtype": "float32",
                                          "shape": [n]}}
        got = served.rank(env["job"])
        held = served.sched.get(env["job"]).result["rank"]
        assert got.tobytes() == held.tobytes()
    finally:
        served.close()
    want = reference.pagerank(*structure(n, src, dst), ITERATIONS, DAMPING)
    assert abs(want.sum() - 1.0) < 1e-12        # no vertex dangles
    assert reference.out_of_epsilon(got, want) == 0
    # the comparison is tight: the same ranks a part in a thousand off,
    # a short answer and a NaN are each seen
    assert reference.out_of_epsilon(got * 1.001, want) == n
    assert reference.out_of_epsilon(got[:-1], want) == n
    bad = got.copy()
    bad[5] = np.nan
    assert reference.out_of_epsilon(bad, want) == n
    one = got.copy()
    one[3] *= 1.0 + 3e-4
    assert reference.out_of_epsilon(one, want) == 1


def test_a_dangling_vertex_parts_program_and_reference(reference):
    """0 -> 1 -> 2, 0 -> 2, 3 -> 0, 2 -> 4 and vertex 4 with no out-edge:
    Graphalytics hands 4's rank to everybody, the program lets it leak."""
    from titan_tpu.models.frontier import pagerank_dense

    n = 5
    src = np.array([0, 1, 0, 3, 2], np.int32)
    dst = np.array([1, 2, 2, 0, 4], np.int32)
    want = reference.pagerank(*structure(n, src, dst), ITERATIONS, DAMPING)
    got, its = pagerank_dense(snap_mod.from_arrays(n, src, dst),
                              iterations=ITERATIONS, damping=DAMPING)
    assert its == ITERATIONS
    assert abs(want.sum() - 1.0) < 1e-12
    assert got.sum() < 0.9                       # mass leaked
    assert reference.out_of_epsilon(got, want) == n
    # ... and by the dangling term alone: with 2 -> 0 in place of 2 -> 4
    # and vertex 4 gone, no vertex dangles and the two agree
    dst[-1] = 0
    want = reference.pagerank(*structure(4, src, dst), ITERATIONS, DAMPING)
    got, _ = pagerank_dense(snap_mod.from_arrays(4, src, dst),
                            iterations=ITERATIONS, damping=DAMPING)
    assert reference.out_of_epsilon(got, want) == 0


def test_the_jobs_spans_and_counters():
    n, src, dst = simple_undirected(5, n=1 << 9, m=1 << 12)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "pagerank", "iterations": 4})
        assert env["status"] == "done", env
        spans = {s.span_id: s
                 for s in served.sched.tracer.spans(env["job"])}
    finally:
        served.close()
    by_name: dict = {}
    for s in spans.values():
        by_name.setdefault(s.name, []).append(s)
    (run,) = by_name["run"]
    sweeps, finishes = by_name["pr.sweep"], by_name["pr.finish"]
    (result,) = by_name["pr.result"]
    assert [s.attrs["it"] for s in sweeps] == [1, 2, 3, 4]
    assert [s.attrs["it"] for s in finishes] == [1, 2, 3, 4]
    leaves = sweeps + finishes + [result]
    assert all(s.parent_id == run.span_id for s in leaves)
    assert all(run.t_start <= s.t_start <= s.t_end <= run.t_end
               for s in leaves)
    # in order, never overlapping; the readback comes last
    ordered = sorted(leaves, key=lambda s: s.t_start)
    assert [s.name for s in ordered] == \
        ["pr.sweep", "pr.finish"] * 4 + ["pr.result"]
    assert all(a.t_end <= b.t_start for a, b in zip(ordered, ordered[1:]))
    assert result.attrs["bytes"] == 4 * n
    assert result.attrs["sync_ms"] >= 0.0
    # the host-stamped rounds stay beside them
    assert len(by_name["round"]) == 4
    m = served.metrics
    assert m.counter_value("device.pr.iterations") == 4
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "pagerank.result"}).count == 4 * n
