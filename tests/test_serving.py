"""OLAP serving layer: batched multi-source execution + scheduler paths.

The acceptance contract (ISSUE r7): >= 8 concurrent same-snapshot BFS
jobs fuse into ONE batched [K, n] device run whose per-job rows are
bit-equal to K sequential single-source runs, with cancellation /
deadline / admission / timeout paths covered and per-job latency +
batch-occupancy metrics exported through utils/metrics.
"""

import time

import numpy as np
import pytest

from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving.hbm import HBMLedger, chunked_csr_bytes
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager


# ONE vertex-count across the file: the batched/hybrid kernels compile
# per power-of-two capacity bucket, and CPU XLA compiles dominate this
# suite's runtime — distinct random n per test would recompile
# everything (tier-1 is serial and budgeted)
_N = 192


def _sym_snapshot(seed: int, n: int = _N, m: int = 900):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


@pytest.fixture(scope="module")
def snap_main():
    return _sym_snapshot(42)


@pytest.fixture
def metrics():
    return MetricManager()     # isolated registry (not the singleton)


def _await_counter(metrics, name, want, timeout=10.0):
    """Job.wait() fires at the state transition (inside the batch); the
    worker finalizes counters just after — poll briefly before
    asserting."""
    deadline = time.time() + timeout
    while time.time() < deadline and metrics.counter_value(name) < want:
        time.sleep(0.01)
    return metrics.counter_value(name)


# --------------------------------------------------------------------------
# batched kernel: bit-equality property + early-exit masks
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "seed", [0, pytest.param(1, marks=pytest.mark.slow)])
def test_batched_bfs_bit_equal_to_sequential(seed):
    """Property: every row of a K-way batched run equals the sequential
    single-source hybrid BFS from that source (duplicate sources
    included — BFS distances are canonical). Random edges per seed; a
    sparse second graph (m < n) exercises multi-component frontiers and
    isolated-ish sources."""
    from titan_tpu.models.bfs_hybrid import (frontier_bfs_batched,
                                             frontier_bfs_hybrid)

    snap = _sym_snapshot(seed, m=900 if seed == 0 else 150)
    rng = np.random.default_rng(100 + seed)
    nz = np.flatnonzero(snap.out_degree > 0)
    # K = 8 everywhere in this suite: each distinct K is a separate
    # XLA compile of the three batched kernels (CPU compiles dominate)
    K = 8
    sources = [int(s) for s in rng.choice(nz, size=K, replace=True)]
    dist, levels, completed = frontier_bfs_batched(snap, sources)
    assert completed.all()
    assert dist.shape == (K, snap.n)
    for k, s in enumerate(sources):
        ref, _ = frontier_bfs_hybrid(snap, s)
        assert (dist[k] == np.asarray(ref)).all(), f"job {k} source {s}"


def test_batched_bfs_on_level_early_exit_mask():
    """A job dropped via the on_level keep mask stops exactly at that
    level (its dist stays partial, completed=False) while the surviving
    jobs finish bit-equal to sequential runs."""
    from titan_tpu.models.bfs import INF
    from titan_tpu.models.bfs_hybrid import (frontier_bfs_batched,
                                             frontier_bfs_hybrid)

    n = 50   # path graph: distances grow one level at a time
    es = np.arange(n - 1, dtype=np.int32)
    ed = es + 1
    snap = snap_mod.from_arrays(n, np.concatenate([es, ed]),
                                np.concatenate([ed, es]))
    seen = []

    def on_level(level, nf):
        seen.append((level, nf.tolist()))
        if level >= 2:
            return np.array([False, True])
        return None

    dist, levels, completed = frontier_bfs_batched(
        snap, [0, n - 1], on_level=on_level)
    assert not completed[0] and completed[1]
    assert levels[0] == 2
    # job 0 explored exactly levels 0 and 1 before the drop
    assert dist[0][0] == 0 and dist[0][2] == 2
    assert (dist[0][3:] >= int(INF)).all()
    ref, _ = frontier_bfs_hybrid(snap, n - 1)
    assert (dist[1] == np.asarray(ref)).all()
    # the callback saw per-job frontier counts every level
    assert seen[0][0] == 0 and seen[0][1] == [1, 1]


def test_batched_bfs_rejects_bad_sources(snap_main):
    from titan_tpu.models.bfs_hybrid import frontier_bfs_batched

    snap = snap_main
    with pytest.raises(IndexError):
        frontier_bfs_batched(snap, [0, snap.n + 5])
    with pytest.raises(ValueError):
        frontier_bfs_batched(snap, [])


# --------------------------------------------------------------------------
# scheduler: fusion, terminal paths, metrics
# --------------------------------------------------------------------------

def test_scheduler_fuses_eight_plus_jobs_and_results_match(metrics, snap_main):
    """>= 8 queued same-snapshot BFS jobs execute as ONE batch (every
    job reports the same batch_k >= 8) and each result is bit-equal to
    its sequential reference; latency/queue/occupancy metrics land in
    the registry."""
    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid

    snap = snap_main
    nz = np.flatnonzero(snap.out_degree > 0)
    K = 8
    sched = JobScheduler(snapshot=snap, metrics=metrics, autostart=False)
    try:
        jobs = [sched.submit(JobSpec(kind="bfs",
                                     params={"source_dense": int(s)}))
                for s in nz[:K]]
        assert metrics.counter_value("serving.queue.depth") == K
        sched.start()
        for job in jobs:
            assert job.wait(60), job
            assert job.state.value == "done", (job, job.error)
        assert all(j.batch_k >= 8 for j in jobs), [j.batch_k for j in jobs]
        for job in jobs:
            ref, _ = frontier_bfs_hybrid(
                snap, int(job.spec.params["source_dense"]))
            assert (job.result["dist"] == np.asarray(ref)).all()
            assert job.result["reached"] == int(
                (np.asarray(ref) < (1 << 30)).sum())
        # metrics: occupancy recorded the fused width; per-job latency
        assert _await_counter(metrics, "serving.jobs.completed", K) == K
        occ = metrics.histogram("serving.batch.occupancy")
        assert occ.count >= 1 and occ.max >= 8
        lat = metrics.histogram("serving.job.latency_ms")
        assert lat.count == K and lat.percentile(50) > 0 \
            and lat.percentile(95) >= lat.percentile(50)
        assert metrics.counter_value("serving.queue.depth") == 0
    finally:
        sched.close()


def test_scheduler_cancel_deadline_admission_timeout(metrics, snap_main):
    snap = snap_main
    src = int(np.flatnonzero(snap.out_degree > 0)[0])
    sched = JobScheduler(snapshot=snap, metrics=metrics, autostart=False)
    try:
        # cancellation while queued: immediate terminal state
        c = sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": src}))
        assert sched.cancel(c.id)
        assert c.state.value == "cancelled"
        # deadline already passed: EXPIRED, never runs
        e = sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": src},
                                 deadline=time.time() - 1))
        assert e.state.value == "expired"
        # timeout_s=0 trips the level-boundary check on the first level
        t = sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": src},
                                 timeout_s=0.0))
        sched.start()
        assert t.wait(60) and t.state.value == "timeout", (t.state,
                                                           t.error)
        assert metrics.counter_value("serving.jobs.cancelled") == 1
        assert metrics.counter_value("serving.jobs.expired") == 1
        assert _await_counter(metrics, "serving.jobs.timeout", 1) == 1
    finally:
        sched.close()
    # admission: a budget smaller than the graph image rejects the job
    # with an explanatory error instead of running it
    sched2 = JobScheduler(snapshot=snap, metrics=metrics,
                          hbm_budget_bytes=64)
    try:
        a = sched2.submit(JobSpec(kind="bfs",
                                  params={"source_dense": src}))
        assert a.wait(60) and a.state.value == "failed"
        assert "admission" in a.error
    finally:
        sched2.close()


@pytest.mark.slow
def test_single_execution_kinds_and_round_interrupt(metrics, snap_main):
    """Non-BFS kinds execute through the scheduler; the frontier kinds
    honor cancellation/timeout at ROUND boundaries via
    _frontier_run's on_round veto (the single-execution analog of the
    batched level mask). Slow tier: compiles the sssp/wcc/pagerank
    kernel sets on top of the BFS ones — the tier-1 serial budget is
    knife-edge and the BFS cancellation/timeout/admission acceptance
    paths are covered by the fast tests above."""
    from titan_tpu.models.frontier import (RoundInterrupted,
                                           frontier_sssp)

    snap = snap_main
    src = int(np.flatnonzero(snap.out_degree > 0)[0])
    # direct kernel contract: a vetoing on_round raises with the round
    calls = []

    def veto(rounds):
        calls.append(rounds)
        return rounds < 1
    with pytest.raises(RoundInterrupted) as ei:
        frontier_sssp(snap, src, on_round=veto)
    assert ei.value.rounds == 1 and calls == [0, 1]

    sched = JobScheduler(snapshot=snap, metrics=metrics)
    try:
        s = sched.submit(JobSpec(kind="sssp",
                                 params={"source_dense": src}))
        w = sched.submit(JobSpec(kind="wcc"))
        p = sched.submit(JobSpec(kind="pagerank",
                                 params={"iterations": 3}))
        t = sched.submit(JobSpec(kind="sssp",
                                 params={"source_dense": src},
                                 timeout_s=0.0))
        pt = sched.submit(JobSpec(kind="pagerank", timeout_s=0.0,
                                  params={"iterations": 5}))
        for job in (s, w, p, t, pt):
            assert job.wait(120), job
        assert s.state.value == "done" and s.result["reached"] >= 1
        assert w.state.value == "done" and w.result["components"] >= 1
        assert p.state.value == "done" and p.result["iterations"] == 3
        assert t.state.value == "timeout", (t.state, t.error)
        assert pt.state.value == "timeout", (pt.state, pt.error)
    finally:
        sched.close()


def test_scheduler_unknown_kind_and_unknown_source(metrics, snap_main):
    snap = snap_main
    sched = JobScheduler(snapshot=snap, metrics=metrics)
    try:
        with pytest.raises(ValueError):
            sched.submit(JobSpec(kind="nope"))
        j = sched.submit(JobSpec(kind="bfs", params={}))   # no source
        assert j.wait(60) and j.state.value == "failed"
        assert "source" in j.error
    finally:
        sched.close()


def test_malformed_jobs_never_kill_the_worker(metrics, snap_main):
    """One stuck caller must never wedge the queue: malformed params
    (None source, junk targets, junk max_levels) fail THEIR job — or
    degrade to None target entries — and the worker keeps serving."""
    snap = snap_main
    src = int(np.flatnonzero(snap.out_degree > 0)[0])
    sched = JobScheduler(snapshot=snap, metrics=metrics)
    try:
        bad1 = sched.submit(JobSpec(kind="bfs",
                                    params={"source": None}))
        bad2 = sched.submit(JobSpec(kind="bfs",
                                    params={"source_dense": src,
                                            "max_levels": "soon"}))
        soft = sched.submit(JobSpec(kind="bfs",
                                    params={"source_dense": src,
                                            "targets": ["abc", src]}))
        good = sched.submit(JobSpec(kind="bfs",
                                    params={"source_dense": src}))
        for j in (bad1, bad2, soft, good):
            assert j.wait(60), j
        assert bad1.state.value == "failed" and "source" in bad1.error
        assert bad2.state.value == "failed"
        # junk target degrades to None; the job itself succeeds
        assert soft.state.value == "done"
        assert soft.result["targets"]["abc"] is None
        assert soft.result["targets"][str(src)] == 0
        # the worker survived all of it
        assert good.state.value == "done", (good.state, good.error)
    finally:
        sched.close()


def test_batch_key_separates_incompatible_jobs():
    """Only jobs that can share ONE fused round loop may batch: kind,
    snapshot parameters AND the kind's cohort-wide knobs must agree (a
    tight level cap must not truncate batchmates, nor ride past its
    own). Since ISSUE 19 SSSP and WCC are batchable too — into
    PER-ALGORITHM cohorts whose keys can never collide with another
    kind's (the kind leads every key)."""
    from titan_tpu.olap.serving.kinds import batch_key

    base = batch_key(JobSpec(kind="bfs"))
    assert base is not None
    assert batch_key(JobSpec(kind="bfs")) == base
    assert batch_key(JobSpec(kind="bfs",
                             params={"max_levels": 3})) != base
    assert batch_key(JobSpec(kind="bfs", directed=True)) != base
    assert batch_key(JobSpec(kind="bfs", labels=("knows",))) != base
    # sssp/wcc fuse among themselves, never with bfs or each other
    sssp = batch_key(JobSpec(kind="sssp"))
    wcc = batch_key(JobSpec(kind="wcc"))
    assert sssp is not None and wcc is not None
    assert len({base, sssp, wcc}) == 3
    assert batch_key(JobSpec(kind="sssp")) == sssp
    # SSSP mode knobs are cohort-wide: differing knobs must not fuse
    assert batch_key(JobSpec(kind="sssp",
                             params={"delta": 0.3})) != sssp
    assert batch_key(JobSpec(kind="sssp",
                             params={"quantile_mass": 64})) != sssp
    assert batch_key(JobSpec(kind="sssp",
                             params={"max_rounds": 7})) != sssp
    # junk knob values: run (and fail) alone, never poison a cohort
    assert batch_key(JobSpec(kind="sssp",
                             params={"delta": "wat"})) is None
    # pagerank stays single-execution
    assert batch_key(JobSpec(kind="pagerank")) is None


def test_hbm_ledger_eviction_and_pinning():
    evicted = []
    led = HBMLedger(budget_bytes=1000, on_evict=evicted.append)
    led.reserve("a", 400)
    led.unpin("a")
    led.reserve("b", 500)
    led.unpin("b")
    led.reserve("c", 600)          # must evict the largest idle (b)
    assert evicted == ["b"]
    # a (400) + c (600) fill the budget; c is pinned, a idle
    from titan_tpu.olap.serving.hbm import AdmissionError
    with pytest.raises(AdmissionError):
        led.reserve("d", 700)      # even evicting a leaves c+700 > 1000
    assert chunked_csr_bytes(0, 1) == 8 * 4 + 12


# --------------------------------------------------------------------------
# engine-level batched DenseProgram execution
# --------------------------------------------------------------------------

def test_engine_run_batched_matches_run_single(snap_main):
    """K BFS DensePrograms as one [K, n] vmapped while_loop — per-job
    outputs and iteration counts bit-equal to run_single."""
    from titan_tpu.models.bfs import BFS
    from titan_tpu.olap.tpu.engine import run_single, run_single_batched

    snap = snap_main
    nz = np.flatnonzero(snap.out_degree > 0)
    prog = BFS(max_iterations=100)
    params = [{"source_dense": int(s)} for s in nz[:4]]
    batched = run_single_batched(prog, snap, params)
    for p, res in zip(params, batched):
        ref = run_single(prog, snap, p)
        assert (res["dist"] == ref["dist"]).all()
        assert res.iterations == ref.iterations
    with pytest.raises(TypeError):
        run_single_batched(prog, snap, [{"source_dense": "zero"}])


def test_computer_run_async_delegates_to_scheduler():
    """The host computer's async hook: run_async queues the BSP run
    behind the serving scheduler and returns a waitable handle whose
    result is the usual HostComputerResult."""
    import titan_tpu
    from titan_tpu.core.defs import Direction
    from titan_tpu.olap.api import VertexProgram
    from titan_tpu.olap.computer import HostGraphComputer

    class DegreeProgram(VertexProgram):
        def execute(self, vertex, messenger, memory):
            vertex.set_state("deg", vertex.degree(Direction.OUT))

        def terminate(self, memory):
            return True

    g = titan_tpu.open("inmemory")
    try:
        tx = g.new_transaction()
        vs = [tx.add_vertex("node", name=f"v{i}") for i in range(4)]
        for a, b in [(0, 1), (1, 2), (2, 3)]:
            vs[a].add_edge("link", vs[b])
        vids = [v.id for v in vs]
        tx.commit()
        snap = snap_mod.build(g)
        sched = JobScheduler(snapshot=snap)
        try:
            comp = HostGraphComputer(g, num_threads=2)
            job = comp.run_async(DegreeProgram(), sched)
            assert job.wait(60) and job.state.value == "done", job.error
            res = job.result["value"]
            assert res.state_of(vids[0])["deg"] == 1
            assert res.state_of(vids[3])["deg"] == 0
        finally:
            sched.close()
    finally:
        g.close()


# --------------------------------------------------------------------------
# SSSP/WCC cohorts (ISSUE 19): bit-equality + per-algorithm fusion
# --------------------------------------------------------------------------

@pytest.mark.parametrize("K", [1, 4, 8])
def test_batched_sssp_bit_equal_to_sequential(K, snap_main):
    """Property: every member of a K-way SSSP cohort (shared round
    loop, one stacked plan sync per round) equals the sequential run
    from its source — distances AND round counts, duplicates
    included."""
    from titan_tpu.models.frontier import (frontier_sssp,
                                           frontier_sssp_batched)

    snap = snap_main
    rng = np.random.default_rng(200 + K)
    nz = np.flatnonzero(snap.out_degree > 0)
    sources = [int(s) for s in rng.choice(nz, size=K, replace=True)]
    outs, rounds, stopped = frontier_sssp_batched(snap, sources)
    assert stopped == [None] * K
    for k, s in enumerate(sources):
        ref, ref_rounds = frontier_sssp(snap, s)
        assert rounds[k] == ref_rounds, f"member {k} source {s}"
        assert (np.asarray(outs[k]) == np.asarray(ref)).all(), \
            f"member {k} source {s}"


def test_batched_sssp_delta_mode_bit_equal(snap_main):
    """Cohort-wide mode knobs (delta-stepping here) produce the same
    per-member trajectory the sequential kernel walks under the same
    knobs — the contract behind the batch key pinning them."""
    from titan_tpu.models.frontier import (frontier_sssp,
                                           frontier_sssp_batched)

    snap = snap_main
    nz = np.flatnonzero(snap.out_degree > 0)
    sources = [int(s) for s in nz[:4]]
    outs, rounds, _ = frontier_sssp_batched(snap, sources, delta=0.3)
    for k, s in enumerate(sources):
        ref, ref_rounds = frontier_sssp(snap, s, delta=0.3)
        assert rounds[k] == ref_rounds
        assert (np.asarray(outs[k]) == np.asarray(ref)).all()


@pytest.mark.parametrize("K", [1, 4])
def test_batched_wcc_bit_equal_to_sequential(K, snap_main):
    from titan_tpu.models.frontier import (frontier_wcc,
                                           frontier_wcc_batched)

    snap = snap_main
    ref, ref_rounds = frontier_wcc(snap)
    outs, rounds, stopped = frontier_wcc_batched(snap, K)
    assert stopped == [None] * K
    for k in range(K):
        assert rounds[k] == ref_rounds
        assert (np.asarray(outs[k]) == np.asarray(ref)).all()


def test_batched_sssp_mixed_early_exit(snap_main):
    """A member vetoed mid-cohort (the serving layer's cancel/timeout
    hook) drops at exactly that round — out None, stopped set — while
    every survivor still finishes bit-equal to sequential."""
    from titan_tpu.models.frontier import (frontier_sssp,
                                           frontier_sssp_batched)

    snap = snap_main
    nz = np.flatnonzero(snap.out_degree > 0)
    sources = [int(s) for s in nz[:4]]

    def on_round(k, rounds):
        return not (k == 1 and rounds >= 2)

    outs, rounds, stopped = frontier_sssp_batched(
        snap, sources, on_round=on_round)
    assert outs[1] is None and stopped[1] == 2
    for k in (0, 2, 3):
        assert stopped[k] is None
        ref, ref_rounds = frontier_sssp(snap, sources[k])
        assert rounds[k] == ref_rounds
        assert (np.asarray(outs[k]) == np.asarray(ref)).all()


def test_scheduler_mixed_stream_fuses_per_algorithm(metrics, snap_main):
    """A mixed BFS/SSSP/WCC submit stream fuses into PER-ALGORITHM
    cohorts: each kind's fresh jobs share one batch (batch_k = that
    kind's count), kinds never cross-fuse, and every result is
    bit-equal to its sequential reference."""
    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
    from titan_tpu.models.frontier import frontier_sssp, frontier_wcc

    snap = snap_main
    nz = np.flatnonzero(snap.out_degree > 0)
    sched = JobScheduler(snapshot=snap, metrics=metrics,
                         autostart=False)
    try:
        bfs = [sched.submit(JobSpec(kind="bfs",
                                    params={"source_dense": int(s)}))
               for s in nz[:4]]
        sssp = [sched.submit(JobSpec(kind="sssp",
                                     params={"source_dense": int(s)}))
                for s in nz[:4]]
        wcc = [sched.submit(JobSpec(kind="wcc")) for _ in range(3)]
        sched.start()
        for job in bfs + sssp + wcc:
            assert job.wait(120), job
            assert job.state.value == "done", (job, job.error)
        # per-algorithm fusion, never cross-kind: batch_k equals the
        # kind's own cohort width exactly
        assert [j.batch_k for j in bfs] == [4] * 4
        assert [j.batch_k for j in sssp] == [4] * 4
        assert [j.batch_k for j in wcc] == [3] * 3
        for job in bfs:
            ref, _ = frontier_bfs_hybrid(
                snap, int(job.spec.params["source_dense"]))
            assert (job.result["dist"] == np.asarray(ref)).all()
        for job in sssp:
            ref, ref_rounds = frontier_sssp(
                snap, int(job.spec.params["source_dense"]))
            assert job.result["rounds"] == ref_rounds
            assert (job.result["dist"] == np.asarray(ref)).all()
        wref, wrounds = frontier_wcc(snap)
        for job in wcc:
            assert job.result["rounds"] == wrounds
            assert (job.result["labels"] == np.asarray(wref)).all()
    finally:
        sched.close()
