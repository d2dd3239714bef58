"""CDLP (ISSUE 40): LDBC Graphalytics' community detection by label
propagation as a served job, on the CPU. The program (``models/cdlp.py``
over PageRank's pull image, the vote of ``ops/segment.py``) against the
benchmark's plain reference (``benchmark/reference/cdlp.py``: the
specification's equations in numpy, nothing of ``titan_tpu`` in it) AND
against a ``collections.Counter`` count a vertex at a time, so that the
reference is itself checked: every label, exactly. Shapes worked by hand
(a tie, a vertex of degree 1, a star, two cliques joined by an edge, a
vertex whose every neighbour carries another label, a pair that flips
every round), seeded random graphs with a hub that straddles blocks, 0, 1
and 10 rounds; the Pallas gather in Pallas's interpreter; then the served
path: ``POST /jobs`` -> result plane, spans and counters, timeout and
cancel at a round's boundary, resume from a checkpoint bit-equal,
admission of the rounds' working set.
"""

import collections
import functools
import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.models import cdlp as C
from titan_tpu.models import pagerank_pull as pp
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving.hbm import (snapshot_cdlp_bytes,
                                        snapshot_csr_bytes,
                                        snapshot_pull_bytes)
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import segment
from titan_tpu.ops import vmem_gather as vg
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"_bench_reference_{name}",
        os.path.join(ROOT, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _reference("cdlp")


def structure(n, src, dst):
    return _reference("csr").structure(n, src, dst)


def by_counter(n, src, dst, iterations):
    """The specification read literally, a vertex at a time."""
    nbrs = [[] for _ in range(n)]
    for u, v in set(zip(src.tolist(), dst.tolist())):
        nbrs[v].append(u)
    labels = list(range(n))
    for _ in range(iterations):
        new = list(labels)
        for v in range(n):
            if nbrs[v]:
                count = collections.Counter(labels[u] for u in nbrs[v])
                most = max(count.values())
                new[v] = min(l for l, c in count.items() if c == most)
        labels = new
    return np.asarray(labels, np.int32)


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


def clique(ids):
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


# name -> (graph, {rounds: labels worked by hand})
BY_HAND = {
    # 0's neighbours 1, 2, 3 each carry their own label once: the
    # smallest wins; 1, 2, 3 have degree 1 and take 0's
    "a_tie_and_degree_one": (both_ways(4, [(0, 1), (0, 2), (0, 3)]),
                             {1: [1, 0, 0, 0], 2: [0, 1, 1, 1]}),
    # a pair flips every round: synchronous semantics show
    "a_pair_flips": (both_ways(2, [(0, 1)]),
                     {0: [0, 1], 1: [1, 0], 2: [0, 1], 3: [1, 0]}),
    # a star of six around 3, and vertex 6 with no edge keeps its label
    "a_star": (both_ways(7, [(3, v) for v in (0, 1, 2, 4, 5)]),
               {1: [3, 3, 3, 0, 3, 3, 6], 2: [0, 0, 0, 3, 0, 0, 6]}),
    # two 4-cliques joined by 3 - 4: each settles on its smallest id
    "two_cliques": (both_ways(8, clique([0, 1, 2, 3]) + clique([4, 5, 6, 7])
                              + [(3, 4)]),
                    {1: [1, 0, 0, 0, 3, 4, 4, 4],
                     2: [0, 0, 0, 0, 4, 4, 4, 4],
                     10: [0, 0, 0, 0, 4, 4, 4, 4]}),
    # after round 1 of this path 0-1-2-3-4, vertex 2's neighbours carry
    # 0 and 2, neither its own label 1
    "every_neighbour_another_label": (
        both_ways(5, [(0, 1), (1, 2), (2, 3), (3, 4)]),
        {1: [1, 0, 1, 2, 3], 2: [0, 1, 0, 1, 2]}),
}


@pytest.mark.parametrize("name,rounds", [
    (name, rounds) for name, (_g, want) in BY_HAND.items()
    for rounds in want])
def test_by_hand(reference, name, rounds):
    (n, src, dst), want = BY_HAND[name]
    assert by_counter(n, src, dst, rounds).tolist() == want[rounds]
    assert reference.propagate(*structure(n, src, dst),
                               rounds).tolist() == want[rounds]
    got, its = C.cdlp(snap_mod.from_arrays(n, src, dst), rounds)
    assert got.dtype == np.int32 and its == rounds
    assert got.tolist() == want[rounds]


def random_graph(seed: int, n: int, m: int, hub: int = 0):
    """A simple undirected graph, hubs near 0, some vertices without an
    edge; ``hub`` more edges tie vertex 1 to that many others, so that
    its columns straddle a block of the image."""
    rng = np.random.default_rng(seed)
    a = (rng.random(m) ** 2 * n).astype(np.int64)
    b = rng.integers(0, n, m)
    if hub:
        a = np.concatenate([a, np.ones(hub, np.int64)])
        b = np.concatenate([b, rng.permutation(n)[:hub]])
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    key = np.unique(lo[lo != hi] * n + hi[lo != hi])
    lo, hi = (key // n).astype(np.int32), (key % n).astype(np.int32)
    return n, np.concatenate([lo, hi]), np.concatenate([hi, lo])


GRAPHS = {"sparse": (41, 400, 700, 0), "dense": (42, 300, 6000, 0),
          "hub": (43, 9000, 12000, 8800)}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    seed, n, m, hub = GRAPHS[request.param]
    n, src, dst = random_graph(seed, n, m, hub)
    return request.param, n, src, dst, snap_mod.from_arrays(n, src, dst)


@pytest.mark.parametrize("rounds", [0, 1, 2, 10])
def test_program_reference_and_counter_agree(reference, graph, rounds):
    name, n, src, dst, snap = graph
    want = reference.propagate(*structure(n, src, dst), rounds)
    if name != "hub" or rounds <= 2:        # a vertex at a time is slow
        assert np.array_equal(by_counter(n, src, dst, rounds), want)
    got, its = C.cdlp(snap, rounds)
    assert its == rounds
    assert reference.mislabelled(got, want) == 0
    if rounds == 1:                 # every count is 1: the smallest id
        indptr, indices = structure(n, src, dst)
        has = np.diff(indptr) > 0
        assert np.array_equal(
            got[has], np.minimum.reduceat(indices, indptr[:-1][has]))
        assert np.array_equal(got[~has], np.flatnonzero(~has))


def test_the_hub_straddles_blocks_and_pad_lanes_do_not_vote(graph):
    name, n, _src, _dst, snap = graph
    im = pp.pull_image(snap)
    assert im["q_in"] % vg.BLOCK == 0
    idx = np.asarray(im["idx"]).reshape(8, -1)
    pads = int((idx == n + 1).sum())
    assert pads > 0 and pads == idx.size - len(snap.src)
    if name == "hub":
        assert im["seg_max"] > vg.BLOCK     # one vertex, several blocks
        assert im["q_in"] >= 3 * vg.BLOCK
    # the pad's label is its own id, above every vertex's: it sorts
    # behind a vertex's labels and its run does not count
    lanes = np.asarray(C._gather()(np.arange(n, dtype=np.int32),
                                   im["idx"], impl="xla", n_=n))
    assert (lanes.reshape(8, -1)[idx == n + 1] == n + 1).all()
    assert (lanes.reshape(8, -1)[idx <= n] < n).all()


def test_a_directed_graph_votes_over_its_in_edges(reference):
    """1 -> 0, 2 -> 0, 2 -> 3: vertex 0 hears 1 and 2, vertex 3 hears 2,
    nobody hears 0 or 3; 1 and 2 have no in-edge and keep their labels."""
    src = np.array([1, 2, 2], np.int32)
    dst = np.array([0, 0, 3], np.int32)
    got, _ = C.cdlp(snap_mod.from_arrays(4, src, dst), 3)
    assert got.tolist() == [1, 1, 2, 2]
    assert by_counter(4, src, dst, 3).tolist() == [1, 1, 2, 2]


# -- the vote itself ---------------------------------------------------------

def test_mode_vote_on_sorted_pairs():
    """Owners 0, 1, 3 (2 has no pair): the answer stands at an owner's
    last element; 7 is the pad and does not vote."""
    owner = np.array([0, 0, 0, 0, 0, 1, 1, 1, 3, 3], np.int32)
    label = np.array([2, 2, 4, 4, 7, 5, 7, 7, 7, 7], np.int32)
    best = np.asarray(segment.mode_vote(owner, label, pad=7))
    assert best[4] == 2         # 2 and 4 twice each: the smaller
    assert best[7] == 5         # two pads do not outvote one label
    assert best[9] == 7         # nothing voted


def test_seg_first_max_keeps_the_earlier_of_equals():
    score = np.array([1, 3, 3, 2, 5, 5, 0], np.int32)
    payload = np.arange(10, 17, dtype=np.int32)
    flags = np.array([1, 0, 0, 0, 1, 0, 0], bool)
    s, p = segment.seg_first_max(score, payload, flags)
    assert np.asarray(s).tolist() == [1, 3, 3, 3, 5, 5, 5]
    assert np.asarray(p).tolist() == [10, 11, 11, 11, 14, 14, 14]
    # ``max_len`` stops the passes at the longest segment
    s2, p2 = segment.seg_first_max(score, payload, flags, max_len=4)
    assert np.array_equal(s2, s) and np.array_equal(p2, p)


# -- the gather --------------------------------------------------------------

def test_the_kernel_gathers_the_same_lanes(graph, monkeypatch):
    """``impl="vmem"``: the Pallas kernel a lane at a time, the labels
    as float32 (exact below 2^24), in Pallas's interpreter here."""
    _name, n, _src, _dst, snap = graph
    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    im = pp.pull_image(snap)
    labels = np.random.default_rng(5).permutation(n).astype(np.int32)
    lanes = {impl: np.asarray(C._gather()(labels, im["idx"], impl=impl,
                                          n_=n))
             for impl in ("xla", "vmem")}
    assert lanes["vmem"].dtype == np.int32
    assert np.array_equal(lanes["vmem"], lanes["xla"])


def test_what_chooses_the_gather_and_the_table_keeps_labels_exact(
        monkeypatch):
    import inspect

    import jax

    seen = []
    monkeypatch.setattr(vg, "gather_impl",
                        lambda n: seen.append(n) or "xla")
    n, src, dst = random_graph(44, 200, 500)
    C.cdlp(snap_mod.from_arrays(n, src, dst), 1)
    assert seen == [n]              # asked once a job, with n alone
    monkeypatch.undo()
    # every label a table in VMEM can hold is an integer float32 keeps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    edge = vg.VMEM_TABLE_MAX // 4 - 2
    assert vg.gather_impl(edge) == "vmem" and edge + 1 <= 1 << 24
    assert vg.gather_impl(edge + 1) == "xla"
    src_text = inspect.getsource(C)
    assert "os.environ" not in src_text and "getenv" not in src_text
    assert list(inspect.signature(C.cdlp).parameters) == [
        "snap", "iterations", "on_round", "checkpoint", "resume",
        "overlay"]


# -- rounds, veto, resume ----------------------------------------------------

def test_a_veto_stops_at_a_rounds_boundary(graph):
    from titan_tpu.models.frontier import RoundInterrupted

    _name, _n, _src, _dst, snap = graph
    calls = []

    def veto(it):
        calls.append(it)
        return it < 2
    with pytest.raises(RoundInterrupted) as ei:
        C.cdlp(snap, 10, on_round=veto)
    assert ei.value.rounds == 2 and calls == [0, 1, 2]


@pytest.mark.parametrize("at", [1, 4, 9])
def test_resume_is_bit_equal(graph, at):
    _name, _n, _src, _dst, snap = graph
    kept = {}

    def checkpoint(it, state):
        kept[it] = np.asarray(state["labels"])

    straight, _ = C.cdlp(snap, 10, checkpoint=checkpoint)
    assert sorted(kept) == list(range(1, 11))
    assert kept[10].tobytes() == straight.tobytes()
    resumed, its = C.cdlp(snap, 10, resume={"labels": kept[at], "it": at})
    assert its == 10
    assert resumed.tobytes() == straight.tobytes()
    # a checkpoint at a round's boundary IS that many rounds' answer
    assert kept[at].tobytes() == C.cdlp(snap, at)[0].tobytes()


def test_a_live_overlay_is_refused():
    class Overlay:
        empty = False
    n, src, dst = random_graph(45, 100, 300)
    with pytest.raises(RuntimeError, match="compact the overlay"):
        C.cdlp(snap_mod.from_arrays(n, src, dst), 1, overlay=Overlay())


# -- the served path ---------------------------------------------------------

class Served:
    def __init__(self, n, src, dst, **sched):
        self.metrics = MetricManager()
        self.sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                                  metrics=self.metrics, **sched)
        self.http = GraphServer(None, port=0, scheduler=self.sched).start()
        self.base = f"http://{self.http.host}:{self.http.port}"

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=30) as r:
            return r.headers, r.read()

    def post(self, body):
        req = urllib.request.Request(
            self.base + "/jobs", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["job"]

    def job(self, body):
        job_id = self.post(body)
        deadline = time.time() + 120
        while time.time() < deadline:
            env = json.loads(self.get(f"/jobs/{job_id}")[1])
            if env["status"] not in ("queued", "running", "retrying"):
                return env
            time.sleep(0.02)
        raise AssertionError(f"job {job_id} did not finish")

    def labels(self, job_id):
        headers, raw = self.get(f"/jobs/{job_id}/result/labels")
        shape = tuple(int(d) for d in headers["X-Shape"].split(",") if d)
        return np.frombuffer(raw, np.dtype(headers["X-Dtype"])) \
            .reshape(shape)

    def close(self):
        self.http.stop()
        self.sched.close()


@pytest.mark.parametrize("seed,rounds", [(3000000019, 10), (12, 1), (7, 0)])
def test_a_served_job_equals_the_reference(reference, seed, rounds):
    n, src, dst = random_graph(seed, 1500, 5000, hub=1200)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": rounds,
                          "timeout_s": 60})
        assert env["status"] == "done", env
        got = served.labels(env["job"])
        held = served.sched.get(env["job"]).result["labels"]
        assert got.tobytes() == held.tobytes()
    finally:
        served.close()
    want = reference.propagate(*structure(n, src, dst), rounds)
    assert env["result"] == {"iterations": rounds,
                             "communities": len(np.unique(want))}
    assert env["arrays"] == {"labels": {"dtype": "int32", "shape": [n]}}
    assert reference.mislabelled(got, want) == 0
    # the comparison sees one label, and an answer of another length
    one = got.copy()
    one[3] = (one[3] + 1) % n
    assert reference.mislabelled(one, want) == 1
    assert reference.mislabelled(got[:-1], want) == n


def test_the_default_is_ten_rounds_and_an_unknown_kind_is_refused():
    n, src, dst = random_graph(46, 300, 900)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp"})
        assert env["status"] == "done" and \
            env["result"]["iterations"] == 10
        with pytest.raises(ValueError, match="cdlp"):
            served.sched.submit(JobSpec(kind="triangles"))
    finally:
        served.close()


def test_the_jobs_spans_and_counters():
    n, src, dst = random_graph(47, 600, 2000)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": 4})
        assert env["status"] == "done", env
        from titan_tpu.obs import devprof
        devprof.drain()
        spans = list(served.sched.tracer.spans(env["job"]))
        m = served.metrics
        text = served.get("/metrics")[1].decode()
    finally:
        served.close()
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (run,) = by_name["run"]
    rounds = by_name["cdlp.round"]
    (result,) = by_name["cdlp.result"]
    (count,) = by_name["cdlp.count"]
    assert [s.attrs["it"] for s in rounds] == [1, 2, 3, 4]
    assert {s.attrs["impl"] for s in rounds} == {"xla"}
    leaves = rounds + [result, count]
    assert all(s.parent_id == run.span_id for s in leaves)
    ordered = sorted(leaves, key=lambda s: s.t_start)
    assert [s.name for s in ordered] == \
        ["cdlp.round"] * 4 + ["cdlp.result", "cdlp.count"]
    assert all(a.t_end <= b.t_start for a, b in zip(ordered, ordered[1:]))
    assert result.attrs["bytes"] == 4 * n and result.attrs["sync_ms"] >= 0
    assert len(by_name["job.lease"]) == len(by_name["job.admit"]) == 1
    # a round's three programs, each a kernel span under its round
    kernels = by_name["kernel"]
    round_ids = {s.span_id for s in rounds}
    keys = [s.attrs["key"] for s in sorted(kernels,
                                           key=lambda s: s.t_start)]
    assert sorted(keys) == sorted(
        ["cdlp_gather", "cdlp_sort", "cdlp_vote"] * 4)
    assert all(s.parent_id in round_ids for s in kernels)
    assert {s.attrs.get("impl") for s in kernels
            if s.attrs["key"] == "cdlp_gather"} == {"xla"}
    q_in = pp.pull_columns(snap_mod.from_arrays(n, src, dst).indptr_in, n)
    assert m.counter_value("device.cdlp.rounds") == 4
    assert m.counter("device.cdlp.lanes",
                     labels={"impl": "xla"}).count == 4 * 8 * q_in
    assert m.counter("device.cdlp.lanes",
                     labels={"impl": "vmem"}).count == 0
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "cdlp.result"}).count == 4 * n
    for key in ("cdlp_gather", "cdlp_sort", "cdlp_vote"):
        assert m.counter("device.exec.unstamped",
                         labels={"kernel": key}).count == 0
    assert "device_cdlp_rounds" in text.replace(".", "_")


def test_timeout_and_cancel_at_a_rounds_boundary():
    n, src, dst = random_graph(48, 400, 1200)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics)
    try:
        late = sched.submit(JobSpec(kind="cdlp", timeout_s=0.0,
                                    params={"iterations": 5}))
        assert late.wait(120) and late.state.value == "timeout", \
            (late.state, late.error)
        assert late.last_round == 0         # before its first round
        # a cancel that arrives while the job runs takes effect before
        # the next round: the job's own hook asks for it after round 2
        real = C.cdlp

        def cancelling(snap, **kw):
            on_round = kw["on_round"]

            def hook(it):
                if it == 2:
                    sched.cancel(job.id)
                return on_round(it)
            return real(snap, **dict(kw, on_round=hook))
        C.cdlp = cancelling
        try:
            job = sched.submit(JobSpec(kind="cdlp",
                                       params={"iterations": 8}))
            assert job.wait(120)
        finally:
            C.cdlp = real
        assert job.state.value == "cancelled", (job.state, job.error)
        assert job.last_round == 2 and job.result is None
        assert metrics.counter_value("device.cdlp.rounds") == 2
    finally:
        sched.close()


def test_a_crashed_job_resumes_from_its_checkpoint_bit_equal(tmp_path):
    from titan_tpu.olap.recovery import FaultPlan

    n, src, dst = random_graph(49, 500, 1500)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics, checkpoint_dir=str(tmp_path))
    try:
        straight = sched.submit(JobSpec(kind="cdlp",
                                        params={"iterations": 7}))
        crashed = sched.submit(JobSpec(
            kind="cdlp", max_retries=1, checkpoint_every=1,
            params={"iterations": 7,
                    "faults": FaultPlan(crash_at_round=3)}))
        assert straight.wait(120) and crashed.wait(120)
    finally:
        sched.close()
    assert straight.state.value == crashed.state.value == "done", \
        (straight.error, crashed.error)
    assert crashed.attempt == 2
    assert crashed.result["labels"].tobytes() == \
        straight.result["labels"].tobytes()
    assert crashed.result["iterations"] == 7
    # rounds 1-3 before the crash, 4-7 behind the checkpoint, 7 straight
    assert metrics.counter_value("device.cdlp.rounds") == 3 + 4 + 7
    assert metrics.counter_value("serving.recovery.resumes") == 1


def test_admission_reserves_the_working_set_and_lets_it_go():
    n, src, dst = random_graph(50, 500, 1500)
    snap = snap_mod.from_arrays(n, src, dst)
    images = snapshot_csr_bytes(snap) + snapshot_pull_bytes(snap)
    work = snapshot_cdlp_bytes(snap)
    q_in = pp.pull_columns(snap.indptr_in, n)
    assert work == C.work_bytes(n, q_in) > 7 * (8 * q_in * 4)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": 2})
        assert env["status"] == "done", env
        (admit,) = [s for s in served.sched.tracer.spans(env["job"])
                    if s.name == "job.admit"]
        ledger = served.sched.ledger
    finally:
        served.close()
    assert admit.attrs["bytes"] == images + work
    assert ledger.resident_bytes() == images    # the working set left
    assert ledger.pinned_bytes() == 0
    # a budget that holds both images but not the rounds' working set
    # beside them refuses the job and leaves nothing pinned, where a
    # PageRank job over the same images is admitted
    served = Served(n, src, dst, hbm_budget_bytes=images + work - 1)
    try:
        env = served.job({"kind": "cdlp", "iterations": 2})
        assert env["status"] == "failed"
        assert "admission" in env["error"]
        assert served.sched.ledger.pinned_bytes() == 0
        ok = served.job({"kind": "pagerank", "iterations": 2})
        assert ok["status"] == "done", ok
    finally:
        served.close()


def test_a_second_tenants_image_leaves_no_room():
    """A pinned image of another tenant beside it: the job is refused at
    admission, not run into the device's memory."""
    n, src, dst = random_graph(51, 500, 1500)
    snap = snap_mod.from_arrays(n, src, dst)
    need = snapshot_csr_bytes(snap) + snapshot_pull_bytes(snap) \
        + snapshot_cdlp_bytes(snap)
    served = Served(n, src, dst, hbm_budget_bytes=need + 1000)
    try:
        served.sched.ledger.reserve("another-tenant", 2000)    # pinned
        env = served.job({"kind": "cdlp", "iterations": 1})
        assert env["status"] == "failed" and "admission" in env["error"]
        served.sched.ledger.release("another-tenant")
        env = served.job({"kind": "cdlp", "iterations": 1})
        assert env["status"] == "done", env
    finally:
        served.close()
