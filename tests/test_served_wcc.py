"""A served WCC job, end to end on the CPU (ISSUE 36): ``POST /jobs`` ->
poll -> ``GET /jobs/<id>/result/labels``, every label held against the
benchmark's plain reference (``benchmark/reference/wcc.py``: min-label
propagation with pointer jumping in numpy, nothing of ``titan_tpu`` in
it), exactly: on LDBC Graphalytics' graph500 (``benchmark/graphs/
graph500_simple.py``) at a small scale, and on a graph of many small
components under a relabelling. Then the job's spans and counters: one
``bfs.level`` a host step of the peel, ``wcc.seed``, ``wcc.propagate``,
``wcc.result``, all leaves under ``run``; ``device.bfs.levels{dir}``
summing to the peel's levels, ``device.wcc.rounds``, the readback's bytes
under ``wcc.result``; and ``components`` without a sort inside the job.
"""

import importlib.util
import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
DIRS = ("head", "td", "bu", "end")


def _by_file(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}", os.path.join(BENCH, kind, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """The benchmark's generator, structure and reference, by file. The
    generator finds its sibling through the harness's ``files`` module,
    which is importable only while this fixture holds the path."""
    sys.path.insert(0, BENCH)
    try:
        yield {"graph500_simple": _by_file("graphs", "graph500_simple"),
               "csr": _by_file("reference", "csr"),
               "wcc": _by_file("reference", "wcc")}
    finally:
        sys.path.remove(BENCH)
        sys.modules.pop("files", None)


def graph500(bench, scale: int, seed: int):
    config = {"scale": scale, "edge_factor": 16, "a": 0.57, "b": 0.19,
              "c": 0.19, "graph_seed": 20150814}
    n, src, dst, _perm = bench["graph500_simple"].generate(config, seed)
    return n, np.concatenate([src, dst]), np.concatenate([dst, src])


def many_small(seed: int, n: int = 3000):
    """Paths, stars and rings of 1 to 40 vertices, a few hundred of
    them, and some vertices with no edge at all, under a seeded
    relabelling: no component holds more than 2 % of the graph, so the
    peel takes one of them and the propagation has all the rest."""
    rng = np.random.default_rng(seed)
    src, dst, at = [], [], 0
    while at < n - 40:
        size = int(rng.integers(1, 41))
        ids = np.arange(at, at + size)
        shape = rng.integers(0, 3)
        if size > 1 and shape == 0:                     # path
            src += list(ids[:-1])
            dst += list(ids[1:])
        elif size > 1 and shape == 1:                   # star
            src += [ids[0]] * (size - 1)
            dst += list(ids[1:])
        elif size > 2:                                  # ring
            src += list(ids)
            dst += list(np.roll(ids, 1))
        at += size
    perm = rng.permutation(n)
    a, b = perm[np.asarray(src)], perm[np.asarray(dst)]
    return n, np.concatenate([a, b]).astype(np.int32), \
        np.concatenate([b, a]).astype(np.int32)


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

    def labels(self, job_id):
        headers, raw = self.get(f"/jobs/{job_id}/result/labels")
        shape = tuple(int(d) for d in headers["X-Shape"].split(",") if d)
        return np.frombuffer(raw, np.dtype(headers["X-Dtype"])) \
            .reshape(shape)

    def levels(self) -> dict:
        return {d: self.metrics.counter(
            "device.bfs.levels", labels={"dir": d, "list": "single"}).count
            for d in DIRS}

    def close(self):
        self.http.stop()
        self.sched.close()


def _spans_by_name(sched, job_id) -> dict:
    by_name: dict = {}
    for s in sched.tracer.spans(job_id):
        by_name.setdefault(s.name, []).append(s)
    return by_name


def serve_wcc(n, src, dst):
    """(envelope, labels over the wire, the job's spans by name, the
    counters) of one served WCC job."""
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "wcc", "timeout_s": 60})
        assert env["status"] == "done", env.get("error")
        got = served.labels(env["job"])
        held = served.sched.get(env["job"]).result["labels"]
        assert got.tobytes() == held.tobytes()
        by_name = _spans_by_name(served.sched, env["job"])
        counters = {
            "levels": served.levels(),
            "rounds": served.metrics.counter_value("device.wcc.rounds"),
            "d2h": served.metrics.counter(
                "device.xfer.d2h_bytes",
                labels={"site": "wcc.result"}).count}
    finally:
        served.close()
    return env, got, by_name, counters


@pytest.mark.parametrize("scale,seed", [(10, 3000000019), (11, 12),
                                        (12, 7)])
def test_graph500_labels_equal_the_reference(bench, scale, seed):
    n, src, dst = graph500(bench, scale, seed)
    env, got, _spans, _counters = serve_wcc(n, src, dst)
    assert env["arrays"] == {"labels": {"dtype": "int32", "shape": [n]}}
    want = bench["wcc"].components(*bench["csr"].structure(n, src, dst))
    assert bench["wcc"].mislabelled(got, want) == 0
    assert env["result"]["components"] == len(np.unique(want))
    # the comparison is exact: one altered label, a short answer
    one = got.copy()
    one[n // 2] += 1
    assert bench["wcc"].mislabelled(one, want) == 1
    assert bench["wcc"].mislabelled(got[:-1], want) == n


@pytest.mark.parametrize("seed", [3000000601, 5])
def test_many_small_components_under_a_relabelling(bench, seed):
    n, src, dst = many_small(seed)
    env, got, spans, counters = serve_wcc(n, src, dst)
    want = bench["wcc"].components(*bench["csr"].structure(n, src, dst))
    assert bench["wcc"].mislabelled(got, want) == 0
    count = len(np.unique(want))
    assert count > 100 and np.bincount(want).max() <= 40
    assert env["result"]["components"] == count
    # the peel took one small component; the propagation did the rest
    (prop,) = spans["wcc.propagate"]
    assert prop.attrs["rounds"] >= 2
    assert counters["rounds"] == prop.attrs["rounds"]


def test_components_are_counted_without_a_sort(bench, monkeypatch):
    """A label is its component's smallest vertex id, so the job counts
    the vertices that carry their own: ``np.unique`` is not called while
    the job runs."""
    n, src, dst = many_small(11, n=1200)

    def no_sort(*a, **kw):
        raise AssertionError("np.unique inside a WCC job")

    served = Served(n, src, dst)
    try:
        monkeypatch.setattr(np, "unique", no_sort)
        env = served.job({"kind": "wcc"})
        monkeypatch.undo()
        assert env["status"] == "done", env.get("error")
        got = served.labels(env["job"])
    finally:
        served.close()
    assert env["result"]["components"] == len(np.unique(got))


def test_the_jobs_spans_and_counters(bench):
    n, src, dst = graph500(bench, 11, 3)
    env, got, by_name, counters = serve_wcc(n, src, dst)
    (run,) = by_name["run"]
    levels = by_name["bfs.level"]
    (seed,) = by_name["wcc.seed"]
    (prop,) = by_name["wcc.propagate"]
    (result,) = by_name["wcc.result"]
    leaves = levels + [seed, prop, result]
    assert all(s.parent_id == run.span_id for s in leaves)
    assert all(run.t_start <= s.t_start <= s.t_end <= run.t_end
               for s in leaves)
    # leaves never nest: in order, none overlapping; peel, seed,
    # propagation, readback
    ordered = sorted(leaves, key=lambda s: s.t_start)
    assert [s.name for s in ordered] == \
        ["bfs.level"] * len(levels) + ["wcc.seed", "wcc.propagate",
                                       "wcc.result"]
    assert all(a.t_end <= b.t_start for a, b in zip(ordered, ordered[1:]))
    # a level's attributes: its direction and the caps it ran under
    assert levels[0].attrs["dir"] == "head" and levels[0].attrs["level"] == 0
    for s in levels:
        a = s.attrs
        assert a["dir"] in DIRS and a["sync_ms"] >= 0.0
        if a["dir"] in ("head", "td"):
            assert a["f_cap"] >= 2 and a["p_cap"] >= 2
        elif a["dir"] == "bu":
            assert a["c_cap"] >= 2 and a["split"] in (True, False)
            assert 1 <= a["rounds"] <= 8
        else:
            assert a["c_cap"] >= 2 and a["p_cap"] >= 2
    assert [s.attrs["level"] for s in levels] == \
        sorted(s.attrs["level"] for s in levels)
    # the counters: levels by direction sum to the peel's, which
    # `wcc.seed` carries; the job's rounds are levels + rounds
    assert sum(counters["levels"].values()) == seed.attrs["levels"]
    stepped = {d: sum(1 for s in levels if s.attrs["dir"] == d)
               for d in ("td", "bu")}
    assert {d: counters["levels"][d] for d in stepped} == stepped
    deg = np.bincount(src, minlength=n)
    assert seed.attrs["source_deg"] == deg.max()
    assert counters["rounds"] == prop.attrs["rounds"]
    assert env["result"]["rounds"] == \
        seed.attrs["levels"] + prop.attrs["rounds"]
    assert prop.attrs["sync_ms"] >= 0.0
    assert result.attrs["bytes"] == 4 * n == counters["d2h"]
    assert result.attrs["sync_ms"] >= 0.0
    # the propagation's host-stamped rounds stay beside the phase
    assert len(by_name["round"]) >= prop.attrs["rounds"]
    # the host's leaves outside `run`, under the attempt: the lease and
    # HBM admission before it, the component count after it
    (attempt,) = by_name["attempt"]
    (lease,) = by_name["job.lease"]
    (admit,) = by_name["job.admit"]
    (count,) = by_name["wcc.count"]
    assert all(s.parent_id == attempt.span_id
               for s in (lease, admit, count, run))
    assert lease.t_end <= admit.t_start <= admit.t_end <= run.t_start
    assert run.t_end <= count.t_start <= count.t_end
    assert admit.attrs["bytes"] > 0


def test_a_keyed_job_runs_alone_with_the_same_spans(bench, tmp_path):
    """A redispatch under an idempotency key may resume from a
    checkpoint, so it runs solo (``Batcher.run_single`` ->
    ``frontier_wcc``): the same labels, the same leaves under ``run``."""
    from titan_tpu.olap.serving.jobs import JobSpec

    n, src, dst = many_small(21, n=1500)
    want = bench["wcc"].components(*bench["csr"].structure(n, src, dst))
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics, checkpoint_dir=str(tmp_path))
    try:
        job = sched.submit(JobSpec(kind="wcc", idempotency_key="epoch-7"))
        assert job.wait(120) and job.state.value == "done", job.error
        by_name = _spans_by_name(sched, job.id)
    finally:
        sched.close()
    assert bench["wcc"].mislabelled(job.result["labels"], want) == 0
    assert job.result["components"] == len(np.unique(want))
    (run,) = by_name["run"]
    assert "k" not in run.attrs                 # not the cohort's run
    (prop,) = by_name["wcc.propagate"]
    (result,) = by_name["wcc.result"]
    leaves = by_name["bfs.level"] + by_name["wcc.seed"] + [prop, result]
    (count,) = by_name["wcc.count"]             # inside the solo run
    assert all(s.parent_id == run.span_id for s in leaves + [count])
    assert result.t_end <= count.t_start <= count.t_end <= run.t_end
    assert len(by_name["job.lease"]) == len(by_name["job.admit"]) == 1
    assert metrics.counter_value("device.wcc.rounds") == \
        prop.attrs["rounds"]
    assert metrics.counter("device.xfer.d2h_bytes",
                           labels={"site": "wcc.result"}).count == 4 * n


def test_two_queued_jobs_fuse_and_the_first_carries_the_phases(bench):
    """Two WCC jobs that wait together run as one cohort (``batch_k``
    2): one shared peel, one round loop; its phases journal under the
    first member's ``run``, each member gets its own labels."""
    import threading

    from titan_tpu.olap.serving.jobs import JobSpec

    n, src, dst = many_small(33, n=1500)
    want = bench["wcc"].components(*bench["csr"].structure(n, src, dst))
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics)
    gate = threading.Event()
    try:
        sched.submit(JobSpec(kind="callable",
                             params={"fn": lambda: gate.wait(60)}))
        jobs = [sched.submit(JobSpec(kind="wcc")) for _ in range(2)]
        gate.set()
        for job in jobs:
            assert job.wait(120) and job.state.value == "done", job.error
        first, second = (_spans_by_name(sched, j.id) for j in jobs)
    finally:
        sched.close()
    for job in jobs:
        assert job.batch_k == 2
        assert bench["wcc"].mislabelled(job.result["labels"], want) == 0
    assert first["run"][0].attrs["k"] == 2
    (prop,) = first["wcc.propagate"]
    assert prop.attrs["k"] == 2
    assert len(first["wcc.result"]) == 2 and len(first["bfs.level"]) >= 1
    assert not {"bfs.level", "wcc.propagate", "wcc.result"} & set(second)
    # the cohort is leased and admitted once, under its head; every
    # member's answer is counted under its own attempt
    assert len(first["job.lease"]) == len(first["job.admit"]) == 1
    assert not {"job.lease", "job.admit"} & set(second)
    assert len(first["wcc.count"]) == len(second["wcc.count"]) == 1
    # one peel for the two: the levels are counted once, the rounds a
    # member, the readback a member
    assert sum(metrics.counter(
        "device.bfs.levels", labels={"dir": d, "list": "single"}).count
        for d in DIRS) == first["wcc.seed"][0].attrs["levels"]
    assert metrics.counter_value("device.wcc.rounds") == \
        2 * prop.attrs["rounds"]
    assert metrics.counter("device.xfer.d2h_bytes",
                           labels={"site": "wcc.result"}).count == 8 * n


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_peel_that_pulls_levels_by_direction(bench, seed,
                                               force_bottom_up):
    """The head loop, the endgame and the split-lane threshold shrunk to
    nothing (conftest's ``force_bottom_up``): the peel steps level by
    level on the host, pushes, then pulls through the split-lane opener
    (the endgame takes over only once nothing is left unvisited); each
    step is one ``bfs.level`` with its direction's caps, and the levels
    counted by direction sum to the peel's."""
    from titan_tpu.models.frontier import frontier_wcc
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import Tracer, scope

    rng = np.random.default_rng(seed)
    n, m = 192, 900
    a = rng.integers(0, n, m).astype(np.int32)
    b = rng.integers(0, n, m).astype(np.int32)
    src, dst = np.concatenate([a, b]), np.concatenate([b, a])
    snap = snap_mod.from_arrays(n, src, dst)
    tracer, metrics = Tracer(), MetricManager()
    root = tracer.start("t", "run")
    with devprof.DeviceCostProfiler(metrics=metrics), \
            scope(tracer, "t", root):
        got, rounds = frontier_wcc(snap)
    tracer.end(root)
    want = bench["wcc"].components(*bench["csr"].structure(n, src, dst))
    assert bench["wcc"].mislabelled(got, want) == 0
    levels = [s for s in tracer.spans("t") if s.name == "bfs.level"]
    (seeded,) = [s for s in tracer.spans("t") if s.name == "wcc.seed"]
    dirs = [s.attrs["dir"] for s in levels]
    assert dirs[0] == "head" and {"td", "bu"} <= set(dirs)
    for s in levels:
        a_ = s.attrs
        if a_["dir"] == "bu":
            assert a_["split"] is True and a_["missed"] >= 0
            assert a_["c_cap"] >= 2 and 1 <= a_["rounds"] <= 8
            assert a_["exhaust"] >= 0 and a_["sync_ms"] >= 0.0
        elif a_["dir"] == "td":
            assert a_["f_cap"] >= 2 and a_["p_cap"] >= 2
    counted = {d: metrics.counter(
        "device.bfs.levels", labels={"dir": d, "list": "single"}).count
        for d in DIRS}
    assert counted["bu"] == dirs.count("bu")
    assert counted["td"] == dirs.count("td")
    assert sum(counted.values()) == seeded.attrs["levels"]
    assert rounds >= seeded.attrs["levels"]
