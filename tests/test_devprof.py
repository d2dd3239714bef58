"""Device-cost profiler (ISSUE 10, titan_tpu/obs/devprof).

Three contracts on the repo-shared n=192/m=900/seed-42 smoke shape:

1. **Compile-bucket regression guard**: after one warm pass, running
   every smoke workload — BFS, batched BFS K in {1, 8}, SSSP, WCC,
   the device epoch merge — under the profiler compiles EXACTLY ZERO
   new XLA shape buckets. A silent recompile regression (per-call
   retrace, weak-type flip-flop, a static argument that stopped
   hashing) fails here in CI instead of burning chip time.
2. **Bit-equality**: kernel results are identical with profiling on
   or off — the profiler never touches the device computation.
3. **Overhead**: smoke-shape BFS with profiling ON completes within
   1.15x of OFF (same guard style as the PR 6 tracing bound; reps are
   summed so the multiplicative bound dominates the noise floor).

ONE vertex count and K set across the file — each distinct (kernel,
static shape) is an XLA compile and CPU compiles dominate tier-1.
"""

import numpy as np
import pytest

from titan_tpu.models.bfs_hybrid import (build_chunked_csr,
                                         frontier_bfs_batched,
                                         frontier_bfs_hybrid)
from titan_tpu.models.frontier import frontier_sssp, frontier_wcc
from titan_tpu.obs import devprof
from titan_tpu.olap.live.compactor import EpochCompactor
from titan_tpu.olap.live.overlay import DeltaOverlay
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils import jitcache
from titan_tpu.utils.metrics import MetricManager

#: the repo-shared smoke shape (tests/test_serving.py's bucket)
N, M, SEED = 192, 900, 42


def _sym_snapshot(seed: int = SEED, n: int = N, m: int = M):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


@pytest.fixture(scope="module")
def snap():
    return _sym_snapshot()


@pytest.fixture
def clean_profilers():
    """Truly-OFF baseline: schedulers elsewhere in the suite install
    process-wide profilers and may not have uninstalled; park them for
    the duration so on-vs-off comparisons measure THIS test's
    profiler."""
    saved = list(devprof._PROFILERS)
    devprof._PROFILERS.clear()
    jitcache.set_profile_dispatch(None)
    yield
    devprof._PROFILERS[:] = saved
    if saved:
        jitcache.set_profile_dispatch(devprof._dispatch)


def _overlay(snap):
    """The exact mutation shape test_live_compact_device.py parametrizes
    (adds=120/removes=40/dead-add) so the eager merge ops share its
    compile buckets."""
    rng = np.random.default_rng(SEED)
    src = rng.integers(0, snap.n, 120).astype(np.int32)
    dst = rng.integers(0, snap.n, 120).astype(np.int32)
    labs = rng.integers(0, 3, 120).astype(np.int32)
    ov = DeltaOverlay(snap, min_cap=64)
    ov.append_edges(src, dst, labs)
    ov.remove_edge(int(snap.src[0]), int(snap.dst[0]), None)
    return ov


def _workloads(snap):
    """name -> thunk for every smoke workload the guard pins."""
    rng = np.random.default_rng(7)
    nz = np.flatnonzero(snap.out_degree > 0)
    s8 = [int(s) for s in rng.choice(nz, size=8, replace=True)]

    def merge():
        ov = _overlay(snap)
        build_chunked_csr(snap)
        merged, mode = EpochCompactor().compact(snap, ov)
        assert mode == "device"

    return [
        ("bfs", lambda: frontier_bfs_hybrid(snap, int(nz[0]))),
        ("bfs_batched_k1", lambda: frontier_bfs_batched(
            snap, [int(nz[0])])),
        ("bfs_batched_k8", lambda: frontier_bfs_batched(snap, s8)),
        ("sssp", lambda: frontier_sssp(snap, int(nz[0]))),
        ("wcc", lambda: frontier_wcc(snap)),
        ("epoch_merge", merge),
    ]


def test_zero_recompiles_on_warm_smoke_shapes(snap):
    """THE compile-bucket pin: one warm pass, then every workload under
    the profiler compiles exactly zero new static shape buckets — and
    every dispatch is observed (calls > 0, all cache hits)."""
    for _name, fn in _workloads(snap):
        fn()                                   # warm pass (may compile)
    mm = MetricManager()
    with devprof.DeviceCostProfiler(metrics=mm) as prof:
        for name, fn in _workloads(snap):
            before = prof.compiles()
            fn()
            assert prof.compiles() == before, (
                f"workload {name!r} recompiled on the warm smoke "
                f"shape: {prof.compile_log()[-3:]}")
    stats = prof.stats()
    assert stats["compiles"] == 0
    assert stats["calls"] > 0
    assert stats["cache_hits"] == stats["calls"]
    # per-kernel fingerprints: the interception saw the kernel library,
    # not just one entry point
    kernels = prof.kernel_stats()
    for expected in ("hybrid_head", "batched_plan",
                     "frontier_bandplan_sssp", "frontier_listplan_wcc",
                     "ops.epoch_merge"):
        assert expected in kernels, (expected, sorted(kernels))
    # ... and landed on the labeled metric families
    assert mm.counter_value("device.exec.calls") == stats["calls"]
    assert mm.counter_value("device.compile.count") == 0
    assert mm.counter_value(
        "device.exec.calls",
        labels={"kernel": "batched_plan"}) > 0


def test_bottom_up_path_zero_recompiles_and_kernel_labels(
        snap, force_bottom_up):
    """The bottom-up chain, which the smoke shape never reaches under
    the default thresholds: its kernels register through jit_once like
    every other, so they carry the same warm-shape contract — one warm
    pass, then zero new compile buckets — and show up under the
    device.exec.* {kernel} labels the decision plane reads."""
    rng = np.random.default_rng(7)
    nz = np.flatnonzero(snap.out_degree > 0)
    s8 = [int(s) for s in rng.choice(nz, size=8, replace=True)]
    workloads = [lambda: frontier_bfs_hybrid(snap, int(nz[0])),
                 lambda: frontier_bfs_batched(snap, s8)]
    for fn in workloads:
        fn()                                   # warm pass (may compile)
    mm = MetricManager()
    with devprof.DeviceCostProfiler(metrics=mm) as prof:
        for fn in workloads:
            fn()
        assert prof.compiles() == 0, (
            f"bottom-up path recompiled warm: {prof.compile_log()[-3:]}")
    kernels = prof.kernel_stats()
    for kern in ("hybrid_bu_startL", "batched_bu"):
        assert kern in kernels, sorted(kernels)
        assert mm.counter_value("device.exec.calls",
                                labels={"kernel": kern}) > 0


def test_compile_miss_counts_once_per_new_bucket(snap):
    """A genuinely new static shape bucket counts exactly one compile,
    and repeating it counts a cache hit — the hit/miss split the guard
    above relies on. K=3 exists nowhere else in the suite, so the
    batched kernels are cold for it (one compile per batched kernel
    dispatched), and a second identical call compiles nothing."""
    nz = np.flatnonzero(snap.out_degree > 0)
    s3 = [int(nz[0])] * 3
    with devprof.DeviceCostProfiler(metrics=MetricManager()) as prof:
        frontier_bfs_batched(snap, s3)
        cold = prof.stats()
        frontier_bfs_batched(snap, s3)
        warm = prof.stats()
    assert cold["compiles"] >= 1
    assert warm["compiles"] == cold["compiles"], "K=3 recompiled warm"
    log = prof.compile_log()
    assert len(log) == cold["compiles"]
    assert all(e["kernel"] for e in log)


def test_results_bit_equal_with_profiling(snap, clean_profilers):
    """Profiling must never perturb the computation: batched BFS and
    SSSP produce bit-identical outputs with the profiler installed."""
    rng = np.random.default_rng(7)
    nz = np.flatnonzero(snap.out_degree > 0)
    s8 = [int(s) for s in rng.choice(nz, size=8, replace=True)]
    d_off, lv_off, c_off = frontier_bfs_batched(snap, s8)
    sp_off, _ = frontier_sssp(snap, int(nz[0]))
    with devprof.DeviceCostProfiler(metrics=MetricManager()):
        d_on, lv_on, c_on = frontier_bfs_batched(snap, s8)
        sp_on, _ = frontier_sssp(snap, int(nz[0]))
    assert (np.asarray(d_on) == np.asarray(d_off)).all()
    assert np.array_equal(np.asarray(lv_on), np.asarray(lv_off))
    assert (c_on == c_off).all()
    assert (np.asarray(sp_on) == np.asarray(sp_off)).all()


def test_profiling_overhead_within_bound(snap, clean_profilers):
    """Acceptance bound (ISSUE 10): smoke-shape BFS with profiling ON
    within 1.15x of OFF. Reps are summed so the multiplicative bound,
    not the timer floor, decides; the additive term absorbs the box's
    scheduling noise (PR 6 guard style)."""
    import time

    rng = np.random.default_rng(7)
    nz = np.flatnonzero(snap.out_degree > 0)
    s8 = [int(s) for s in rng.choice(nz, size=8, replace=True)]
    frontier_bfs_batched(snap, s8)              # warm
    reps = 6
    t0 = time.time()
    for _ in range(reps):
        frontier_bfs_batched(snap, s8)
    off_s = time.time() - t0
    with devprof.DeviceCostProfiler(metrics=MetricManager()):
        t0 = time.time()
        for _ in range(reps):
            frontier_bfs_batched(snap, s8)
        on_s = time.time() - t0
    assert on_s <= off_s * 1.15 + 0.5, (
        f"profiling overhead blew the bound: on={on_s:.3f}s "
        f"off={off_s:.3f}s")


def test_transfer_seams_count_bytes(clean_profilers):
    """H2D/D2H seams land on device.xfer.* with per-site children: a
    fresh snapshot's chunked-CSR upload (same shape — no new compiles)
    and the batched result readback."""
    fresh = _sym_snapshot(SEED)             # device cache empty
    mm = MetricManager()
    with devprof.DeviceCostProfiler(metrics=mm) as prof:
        nz = np.flatnonzero(fresh.out_degree > 0)
        frontier_bfs_batched(fresh, [int(nz[0])])
    stats = prof.stats()
    assert stats["h2d_bytes"] > 0 and stats["d2h_bytes"] > 0
    assert mm.counter_value("device.xfer.h2d_bytes",
                            labels={"site": "bfs.chunked_csr"}) > 0
    assert mm.counter_value("device.xfer.d2h_bytes",
                            labels={"site": "bfs.dist"}) > 0
    assert mm.counter_value("device.xfer.h2d_bytes") \
        == stats["h2d_bytes"]


def test_window_isolates_a_stage(snap, clean_profilers):
    """ProfileWindow deltas: activity before open() is excluded, the
    windowed workload's calls/bytes are included."""
    with devprof.DeviceCostProfiler(metrics=MetricManager()) as prof:
        nz = np.flatnonzero(snap.out_degree > 0)
        frontier_bfs_batched(snap, [int(nz[0])])    # outside
        w = prof.window()
        frontier_bfs_batched(snap, [int(nz[0])])
        delta = w.close()
    assert delta["calls"] > 0
    assert delta["calls"] < prof.stats()["calls"]
    assert delta["wall_s"] >= 0
    assert delta["compiles"] == 0                   # warm shape


def test_uninstall_restores_the_bare_path(snap, clean_profilers):
    """With no profiler installed the shim is one global load + None
    check: dispatch cleared, nothing recorded."""
    prof = devprof.DeviceCostProfiler(metrics=MetricManager())
    prof.install()
    assert prof.installed and jitcache._PROFILE_DISPATCH is not None
    prof.uninstall()
    assert not prof.installed and jitcache._PROFILE_DISPATCH is None
    before = prof.stats()["calls"]
    nz = np.flatnonzero(snap.out_degree > 0)
    frontier_bfs_batched(snap, [int(nz[0])])
    assert prof.stats()["calls"] == before


def test_two_profilers_fan_out(snap, clean_profilers):
    """Measurement happens once and fans out to every installed
    profiler (a bench window beside the scheduler's)."""
    a = devprof.DeviceCostProfiler(metrics=MetricManager()).install()
    b = devprof.DeviceCostProfiler(metrics=MetricManager()).install()
    try:
        nz = np.flatnonzero(snap.out_degree > 0)
        frontier_bfs_batched(snap, [int(nz[0])])
    finally:
        a.uninstall()
        b.uninstall()
    assert a.stats()["calls"] == b.stats()["calls"] > 0


def test_two_profilers_over_one_registry_count_an_event_once(
        snap, clean_profilers):
    """A scheduler that was never closed leaves its profiler installed,
    and the next one's shares the process-wide default registry with it:
    every event still counts ONCE on that registry (``chip_smoke.py``
    read its WCC job's endgame twice behind such a leak: ISSUE 49),
    while each profiler's own totals take every call."""
    shared = MetricManager()
    leaked = devprof.DeviceCostProfiler(metrics=shared).install()
    mine = devprof.DeviceCostProfiler(metrics=shared).install()
    apart = devprof.DeviceCostProfiler(metrics=MetricManager()).install()
    try:
        nz = np.flatnonzero(snap.out_degree > 0)
        frontier_bfs_batched(snap, [int(nz[0])])
        devprof.count_pull_rung(4096)
        devprof.count_d2h("bfs.result", 100)
        devprof.count_frontier_test("end", "xla")
        devprof.drain()
    finally:
        for prof in (leaked, mine, apart):
            prof.uninstall()
    calls = mine.stats()["calls"]
    assert calls == leaked.stats()["calls"] == apart.stats()["calls"] > 0
    for metrics in (shared, apart.metrics):
        assert sum(c.count for c in (
            metrics.counter("device.exec.calls", labels={"kernel": k})
            for k in mine.kernel_stats())) == calls
        assert metrics.counter("device.bfs.pull_rung",
                               labels={"c_cap": "4096"}).count >= 1
        assert metrics.counter("device.xfer.d2h_bytes",
                               labels={"site": "bfs.result"}).count == 100
        assert metrics.counter_value(
            "device.bfs.frontier_test",
            {"prog": "end", "impl": "xla"}) == 1
    assert mine.stats()["d2h_bytes"] == leaked.stats()["d2h_bytes"] > 100
