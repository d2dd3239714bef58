"""The uniform PageRank iteration as a pull (ISSUE 35,
``models/pagerank_pull.py``): the Pallas gather in Pallas's interpreter
on the CPU and XLA's gather over the same image, against a float64
numpy PageRank with the program's semantics (dangling mass leaks); the
pad lanes; resume; and what the program reports of it — the
``device.pr.gather_lanes`` counter, ``pr.sweep``'s attributes, no eager
program in a snapshot's first job, both images in HBM admission.
"""

import functools

import numpy as np
import pytest

from test_served_pagerank import Served as _Served, simple_undirected
from titan_tpu.models import pagerank_pull as pp
from titan_tpu.models.frontier import pagerank_dense
from titan_tpu.olap.serving.hbm import (snapshot_csr_bytes,
                                        snapshot_pull_bytes)
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import vmem_gather as vg

ITERATIONS, DAMPING = 10, 0.85
IMPLS = ("xla", "vmem")


@pytest.fixture(autouse=True)
def kernel_in_the_interpreter(monkeypatch):
    """The CPU has no Mosaic: wherever a test asks for the kernel
    (``impl="vmem"``), Pallas's interpreter runs it."""
    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))


def pagerank64(n, src, dst, iterations, damping):
    """The program's PageRank in float64: rank' = (1-d)/n + d * the sum
    over in-edges of rank[u]/outdeg[u]; a dangling vertex's mass leaks."""
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    rank = np.full(n, 1.0 / n)
    for _ in range(iterations):
        contrib = np.where(outdeg > 0, rank / np.maximum(outdeg, 1), 0.0)
        acc = np.zeros(n)
        np.add.at(acc, dst, contrib[src])
        rank = (1.0 - damping) / n + damping * acc
    return rank


def directed_toy():
    """0 -> 1 -> 2, 0 -> 2, 3 -> 0, 2 -> 4: vertex 4 dangles, vertex 3
    has no in-edge."""
    return (5, np.array([0, 1, 0, 3, 2], np.int32),
            np.array([1, 2, 2, 0, 4], np.int32))


def kron_with_hub(scale: int = 12, copies: int = 5):
    """A symmetric Kronecker graph (R-MAT, A .57 B .19 C .19, edge
    factor 16) plus a hub tied ``copies`` times to every vertex: 5 x
    4,096 in-edges are 2,560 columns, so the hub's columns straddle
    two block boundaries at least."""
    rng = np.random.default_rng(35)
    n, m = 1 << scale, 16 << scale
    a = np.zeros(m, np.int64)
    b = np.zeros(m, np.int64)
    for bit in range(scale):
        r = rng.random(m)            # quadrants A | B | C | D
        a |= (r >= 0.76).astype(np.int64) << bit
        b |= (((r >= 0.57) & (r < 0.76)) | (r >= 0.95)).astype(np.int64) \
            << bit
    hub = 1234
    others = np.tile(np.delete(np.arange(n), hub), copies)
    a = np.concatenate([a, np.full(len(others), hub)])
    b = np.concatenate([b, others])
    keep = a != b
    a, b = a[keep].astype(np.int32), b[keep].astype(np.int32)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


GRAPHS = {"directed-toy": directed_toy, "kron12-hub": kron_with_hub,
          "undirected": lambda: simple_undirected(35)}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    n, src, dst = GRAPHS[request.param]()
    return request.param, n, src, dst, snap_mod.from_arrays(n, src, dst)


def run(snap, monkeypatch, impl, **kw):
    monkeypatch.setattr(vg, "gather_impl", lambda n: impl)
    return pagerank_dense(snap, iterations=ITERATIONS, damping=DAMPING,
                          **kw)


@pytest.mark.parametrize("impl", IMPLS)
def test_the_pull_against_float64(graph, impl, monkeypatch):
    name, n, src, dst, snap = graph
    got, its = run(snap, monkeypatch, impl)
    want = pagerank64(n, src, dst, ITERATIONS, DAMPING)
    assert its == ITERATIONS and got.shape == (n,)
    assert np.abs(got - want).max() <= 2e-6 * want.max()
    assert (np.abs(got - want) <= 1e-5 * want).all()
    if name == "directed-toy":
        assert got.sum() < 0.9                   # vertex 4's mass leaked
        # no in-edge: the teleport term alone, as the finish computes it
        assert got[3] == (np.float32(1.0) - np.float32(DAMPING)) / n


def test_the_hub_straddles_blocks_and_the_cover_is_padded():
    n, src, dst = kron_with_hub()
    snap = snap_mod.from_arrays(n, src, dst)
    im = pp.pull_image(snap)
    deg_in = np.diff(snap.indptr_in)
    real = int((-(-deg_in // 8)).sum()) + 1
    assert real % vg.BLOCK != 0            # case (c) of ISSUE 35
    assert im["q_in"] % vg.BLOCK == 0 and \
        0 < im["q_in"] - real < vg.BLOCK
    assert im["q_in"] == pp.pull_columns(snap.indptr_in, n)
    assert im["seg_max"] == -(-deg_in.max() // 8) > 2 * vg.BLOCK
    # cached on the snapshot, dropped with the other layouts
    assert pp.pull_image(snap) is im
    snap._invalidate_layout_caches()
    assert not hasattr(snap, "_pull_csr")
    held = sum(int(im[k].nbytes)
               for k in ("idx", "first", "last", "has", "deg"))
    assert held == pp.pull_image_bytes(n, im["q_in"]) \
        == snapshot_pull_bytes(snap)


@pytest.mark.parametrize("impl", IMPLS)
def test_pad_lanes_read_zero(graph, impl):
    """With every vertex contributing 1.0 — and the SINK's rank set to
    1.0 too — a vertex's sum is its in-degree exactly: the pad lanes
    (index n + 1) and the sink read 0.0."""
    import jax.numpy as jnp

    _name, n, _src, _dst, snap = graph
    im = pp.pull_image(snap)
    ones = jnp.ones((n + 1,), jnp.float32)
    deg = jnp.ones((n + 1,), jnp.float32).at[n].set(0.0)
    acc = pp.pull_step()(ones, deg, im["idx"], im["first"], im["last"],
                         im["has"], impl=impl, seg_max=im["seg_max"])
    assert np.array_equal(np.asarray(acc),
                          np.diff(snap.indptr_in).astype(np.float32))


def test_the_kernel_and_xla_agree(graph):
    import jax.numpy as jnp

    _name, n, _src, _dst, snap = graph
    im = pp.pull_image(snap)
    rng = np.random.default_rng(3)
    rank = jnp.asarray(np.concatenate(
        [rng.random(n, np.float32) / n, np.zeros(1, np.float32)]))
    accs = [np.asarray(pp.pull_step()(
        rank, im["deg"], im["idx"], im["first"], im["last"], im["has"],
        impl=impl, seg_max=im["seg_max"])) for impl in IMPLS]
    assert np.abs(accs[0] - accs[1]).max() <= 1e-6 * accs[0].max()


@pytest.mark.parametrize("impl", IMPLS)
def test_resume_is_bit_equal(graph, impl, monkeypatch):
    _name, _n, _src, _dst, snap = graph
    kept = {}

    def checkpoint(it, state):
        if it == 4:
            kept["rank"] = np.asarray(state["rank"])

    straight, _ = run(snap, monkeypatch, impl, checkpoint=checkpoint)
    resumed, its = run(snap, monkeypatch, impl,
                       resume={"rank": kept["rank"], "it": 4})
    assert its == ITERATIONS
    assert resumed.tobytes() == straight.tobytes()


def test_who_keeps_the_window_sweep(monkeypatch):
    """A caller that hands the out-layout dict, and the personalised
    oracle, never reach the pull."""
    from titan_tpu.models.bfs_hybrid import build_chunked_csr

    def never(_n):
        raise AssertionError("the pull was chosen")

    monkeypatch.setattr(vg, "gather_impl", never)
    n, src, dst = simple_undirected(36, n=1 << 9, m=1 << 12)
    snap = snap_mod.from_arrays(n, src, dst)
    by_dict, _ = pagerank_dense(build_chunked_csr(snap), iterations=3)
    reset = np.zeros(n, np.float32)
    reset[5] = 1.0
    pagerank_dense(snap, iterations=3, reset=reset)
    assert not hasattr(snap, "_pull_csr")
    monkeypatch.setattr(vg, "gather_impl", lambda _n: "xla")
    pulled, _ = pagerank_dense(snap, iterations=3)
    assert np.abs(pulled - by_dict).max() <= 1e-6 * by_dict.max()


def test_what_chooses_the_gather(monkeypatch):
    """The backend and the table's size, nothing else."""
    import inspect

    import jax

    assert vg.gather_impl(2_396_390) == "xla"           # tier 1: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert vg.gather_impl(2_396_390) == "vmem"          # 9.6 MB
    assert vg.gather_impl(1 << 26) == "xla"             # scale 26: 268 MB
    edge = vg.VMEM_TABLE_MAX // 4 - 2
    assert vg.gather_impl(edge) == "vmem"
    assert vg.gather_impl(edge + 1) == "xla"
    # n and the values a vertex (tests/test_shared_pull.py): no flag
    assert list(inspect.signature(vg.gather_impl).parameters) \
        == ["n", "width"]
    for mod in (pp, vg):
        src = inspect.getsource(mod)
        assert "os.environ" not in src and "getenv" not in src


# -- what the program reports ----------------------------------------------

class Served(_Served):
    """``test_served_pagerank``'s server, the scheduler's options open."""

    def __init__(self, n, src, dst, **sched):
        from titan_tpu.olap.serving.scheduler import JobScheduler
        from titan_tpu.server import GraphServer
        from titan_tpu.utils.metrics import MetricManager

        self.metrics = MetricManager()
        self.sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                                  metrics=self.metrics, **sched)
        self.http = GraphServer(None, port=0, scheduler=self.sched).start()
        self.base = f"http://{self.http.host}:{self.http.port}"


def served_job(n, src, dst, iterations=4, **sched):
    served = Served(n, src, dst, **sched)
    try:
        env = served.job({"kind": "pagerank", "iterations": iterations})
        spans = list(served.sched.tracer.spans(env["job"]))
        return served, env, spans
    finally:
        served.close()


def test_the_counter_and_the_sweeps_attributes():
    n, src, dst = simple_undirected(37, n=1 << 9, m=1 << 12)
    served, env, spans = served_job(n, src, dst)
    assert env["status"] == "done", env
    q_in = pp.pull_columns(snap_mod.from_arrays(n, src, dst).indptr_in, n)
    m = served.metrics
    assert m.counter("device.pr.gather_lanes",
                     labels={"impl": "xla"}).count == 4 * 8 * q_in
    assert m.counter("device.pr.gather_lanes",
                     labels={"impl": "vmem"}).count == 0
    assert m.counter_value("device.pr.iterations") == 4
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (run_span,) = by_name["run"]
    assert len(by_name["pr.sweep"]) == len(by_name["pr.finish"]) == 4
    assert len(by_name["pr.result"]) == 1
    for s in by_name["pr.sweep"]:
        assert s.parent_id == run_span.span_id
        assert s.attrs["windows"] == q_in // vg.BLOCK
        assert s.attrs["impl"] == "xla"


def test_a_snapshots_first_job_runs_no_eager_program():
    """Every executable the first ``pagerank`` job of a snapshot builds
    or loads comes from ``jit_once``: the ``compile`` spans carry its
    keys and none reads ``eager:``, and ``DeviceCostProfiler.compiles()``
    counts as many."""
    n, src, dst = simple_undirected(38, n=(1 << 9) + 37, m=1 << 12)
    served = Served(n, src, dst)
    try:
        prof = served.sched.profiler
        before = prof.compiles()
        env = served.job({"kind": "pagerank", "iterations": 3})
        assert env["status"] == "done", env
        built = prof.compiles() - before
        spans = [s for trace in (env["job"], "compile")
                 for s in served.sched.tracer.spans(trace) or ()
                 if s.name == "compile"]
    finally:
        served.close()
    keys = sorted(s.attrs["key"] for s in spans)
    assert keys == ["pagerank_finish", "pagerank_pull", "pagerank_result"]
    assert built == 3


def test_admission_counts_both_images():
    n, src, dst = simple_undirected(39, n=1 << 9, m=1 << 12)
    snap = snap_mod.from_arrays(n, src, dst)
    both = snapshot_csr_bytes(snap) + snapshot_pull_bytes(snap)
    served, env, _spans = served_job(n, src, dst)
    assert env["status"] == "done", env
    ledger = served.sched.ledger
    assert ledger.resident_bytes() == both
    assert ledger.pinned_bytes() == 0
    # a budget that holds the forward image alone refuses the job and
    # leaves nothing pinned
    served, env, _spans = served_job(
        n, src, dst, hbm_budget_bytes=both - 1)
    assert env["status"] == "failed" and "admission" in env["error"]
    assert served.sched.ledger.pinned_bytes() == 0
