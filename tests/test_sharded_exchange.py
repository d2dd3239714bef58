"""Sharded BFS exchange rebuild (ISSUE 13): fused per-level dispatch,
explicit shardings, mesh-aware batch placement.

Property suite for the rebuilt sharded data plane:

* bit-equality of the fused sharded BFS vs the single-chip hybrid on
  the in-process 8-device mesh AND on 1/2-device meshes in subprocesses
  (``XLA_FLAGS=--xla_force_host_platform_device_count={1,2}`` must be
  pinned before jax initializes, so those run out of process — the
  pattern the multihost dryrun uses; the main session keeps its
  conftest-forced 8 devices);
* the per-level dispatch budget: ≤ 2 ``device.exec.calls`` per level
  (1 fused kernel + at most one exchange-cap retry), asserted through
  the DeviceCostProfiler, plus ZERO new compile buckets on the warm
  smoke shape;
* the sparse exchange invariant (caps track the actual per-chip
  discovery maxima — O(frontier) communication);
* mesh-aware batched placement (``parallel/partition.place_batched_csr``
  + ``JobScheduler(mesh=)``): [K, n] cohorts bit-equal over the mesh,
  HBM ledger charged the PER-DEVICE share;
* ``parallel/mesh.global_sum``'s explicit axis-environment check: a
  misspelled axis name raises instead of silently summing per shard.

Shared shape discipline: the module's graphs reuse two fixed shapes
(an rmat scale-9 sym graph and the n=255/m=900/seed-42 serving shape)
so XLA compile buckets are shared across tests (tier-1 is
compile-bound; see tests/conftest.py).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from titan_tpu.models import bfs_hybrid_sharded as S
from titan_tpu.models.bfs import frontier_bfs
from titan_tpu.models.bfs_hybrid import (build_chunked_csr,
                                         frontier_bfs_batched,
                                         frontier_bfs_hybrid)
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.olap.tpu.rmat import rmat_edges
from titan_tpu.parallel.mesh import vertex_mesh


def sym_snap_from(src, dst, n):
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


@pytest.fixture(scope="module")
def rmat9():
    src, dst = rmat_edges(9, 8, seed=3)
    snap = sym_snap_from(src, dst, 1 << 9)
    source = int(np.flatnonzero(snap.out_degree > 0)[0])
    d_ref, lv_ref = frontier_bfs_hybrid(snap, source)
    return snap, source, np.asarray(d_ref), lv_ref


@pytest.fixture(scope="module")
def serving_snap():
    """The n=255/m=900 shape: n+1 = 256 divides over 8 devices, so the
    mesh-placed [K, n+1] state genuinely shards."""
    rng = np.random.default_rng(42)
    n, m = 255, 900
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    return sym_snap_from(src, dst, n)


# ------------------------------------------------------- fused sharded BFS

def test_fused_sharded_bit_equal_and_dispatch_budget_8dev(rmat9):
    """Bit-equality on the 8-device mesh, then the ISSUE-13 acceptance
    bound via the device-cost profiler: a WARM sharded run (kernels
    compiled by the first pass) spends ≤2 device dispatches per level
    on the shx_* kernels and mints ZERO new XLA compile buckets."""
    from titan_tpu.obs.devprof import DeviceCostProfiler

    snap, source, d_ref, lv_ref = rmat9
    mesh = vertex_mesh(8)
    d_sh, lv = S.frontier_bfs_hybrid_sharded(snap, source, mesh)
    assert (np.asarray(d_sh) == d_ref).all()
    assert lv == lv_ref
    # every level was ONE fused dispatch (+ rare retry)
    assert S.LAST_PROFILE, "comm-profile instrumentation missing"
    assert all(p["dispatches"] == 1 + p["retries"]
               for p in S.LAST_PROFILE)
    # warm pass under the profiler
    prof = DeviceCostProfiler()
    with prof:
        d_sh, _lv = S.frontier_bfs_hybrid_sharded(snap, source, mesh)
    assert (np.asarray(d_sh) == d_ref).all()
    disp = [p["dispatches"] for p in S.LAST_PROFILE]
    assert max(disp) <= 2, f"per-level dispatch budget blown: {disp}"
    shx = {k: v for k, v in prof.kernel_stats().items()
           if k.startswith("shx_")}
    assert shx, "sharded kernels did not run through the profiler shim"
    assert sum(v["calls"] for v in shx.values()) == sum(disp)
    # warm shape: no new static shape buckets (found_guess seeds from
    # the source degree, so the cap trail is deterministic per graph)
    assert prof.compiles() == 0, prof.compile_log()


def test_exchange_stays_sparse_on_path():
    """O(frontier) invariant: a path graph's frontier is ONE vertex per
    level, so every exchange cap stays tiny regardless of n — and the
    per-shard edge arrays are genuinely partitioned."""
    n = 96
    src = np.arange(n - 1, dtype=np.int32)
    snap = sym_snap_from(src, src + 1, n)
    mesh = vertex_mesh(8)
    d_sh, levels = S.frontier_bfs_hybrid_sharded(snap, 0, mesh)
    d_ref, _ = frontier_bfs(snap, 0)
    assert (np.asarray(d_sh) == d_ref).all()
    assert levels in (n - 1, n)
    assert S.LAST_EXCHANGE_CAPS and max(S.LAST_EXCHANGE_CAPS) <= 8 < n
    sh = S.shard_chunked_csr(build_chunked_csr(snap), 8)
    assert sh["dstT_sh"].shape[0] == 8
    assert sh["q_max"] <= sh["q_total"]
    assert sh["layout"].num_shards == 8
    assert sh["layout"].balance() >= 1.0


_CHILD = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import sys
ndev = int(sys.argv[1])
assert jax.device_count() == ndev, (jax.device_count(), ndev)
from titan_tpu.utils.jitcache import enable_compile_cache
enable_compile_cache()
try:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
except Exception:
    pass
from titan_tpu.models import bfs_hybrid_sharded as S
from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.olap.tpu.rmat import rmat_edges
from titan_tpu.parallel.mesh import vertex_mesh
src, dst = rmat_edges(8, 8, seed=5)
snap = snap_mod.from_arrays(1 << 8, np.concatenate([src, dst]),
                            np.concatenate([dst, src]))
source = int(np.flatnonzero(snap.out_degree > 0)[0])
d_ref, lv_ref = frontier_bfs_hybrid(snap, source)
mesh = vertex_mesh(ndev)
d_sh, lv = S.frontier_bfs_hybrid_sharded(snap, source, mesh)
assert (np.asarray(d_sh) == np.asarray(d_ref)).all(), "dist diverged"
assert lv == lv_ref, (lv, lv_ref)
disp = [p["dispatches"] for p in S.LAST_PROFILE]
assert max(disp) <= 2, disp
print(f"SHARDED_CHILD_OK ndev={ndev} levels={lv} max_disp={max(disp)}")
"""


@pytest.mark.parametrize("ndev", [
    pytest.param(1, marks=pytest.mark.slow), 2])
def test_sharded_bit_equal_forced_devices_subprocess(ndev):
    """1- and 2-device meshes need their own processes: the forced
    host device count is an XLA init-time flag, and this session is
    pinned to 8 (conftest). Same pattern as the multihost dryrun.
    Tier-1 budget note: the 1-device case rides the slow tier; tier-1
    keeps the genuinely-multi-device forced-2 case (8 runs in-process
    above)."""
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        kept + [f"--xla_force_host_platform_device_count={ndev}"])
    for k in list(env):
        if k.startswith("TPU_"):
            env.pop(k)
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, str(ndev)], cwd=here, env=env,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert f"SHARDED_CHILD_OK ndev={ndev}" in proc.stdout, proc.stdout


# ------------------------------------------- mesh-aware batch placement

def test_mesh_placed_batched_cohort_bit_equal(serving_snap):
    """place_batched_csr + the UNCHANGED batched kernels: a [K, n]
    cohort over the 8-device mesh is bit-equal to the single-device
    run, with the dist state genuinely sharded P(None, "v")."""
    from titan_tpu.parallel.partition import place_batched_csr

    snap = serving_snap
    mesh = vertex_mesh(8)
    sources = [0, 5, 9, 11]
    d_ref, lv_ref, comp_ref = frontier_bfs_batched(snap, sources)
    placed = place_batched_csr(snap, mesh)
    assert "_state_sharding" in placed       # 256 % 8 == 0
    assert placed["dstT"].shape[1] % 8 == 0  # column pad to D multiple
    d_m, lv_m, comp_m = frontier_bfs_batched(placed, sources)
    assert (d_m == d_ref).all()
    assert (lv_m == lv_ref).all() and (comp_m == comp_ref).all()
    # placement is cached per mesh on the graph dict
    assert place_batched_csr(snap, mesh) is placed


def test_scheduler_mesh_cohort_and_per_device_ledger(serving_snap):
    """JobScheduler(mesh=): the fused cohort runs placed, results stay
    bit-equal per job, and the HBM ledger charges the PER-DEVICE share
    of the sharded image, not the whole thing."""
    from titan_tpu.olap.api import JobSpec
    from titan_tpu.olap.serving.hbm import (meshed_snapshot_csr_bytes,
                                            snapshot_csr_bytes)
    from titan_tpu.olap.serving.scheduler import JobScheduler

    snap = serving_snap
    mesh = vertex_mesh(8)
    per_dev = meshed_snapshot_csr_bytes(snap, 8)
    assert per_dev < snapshot_csr_bytes(snap)
    sched = JobScheduler(snapshot=snap, mesh=mesh)
    try:
        sources = [0, 5, 9, 11]
        jobs = [sched.submit(JobSpec(kind="bfs",
                                     params={"source_dense": s}))
                for s in sources]
        for j in jobs:
            assert j.wait(180), "mesh cohort did not finish"
        assert all(j.state.value == "done" for j in jobs)
        for j, s in zip(jobs, sources):
            d_ref, _ = frontier_bfs_hybrid(snap, s)
            assert (j.result["dist"] == np.asarray(d_ref)).all()
        assert sched.ledger.resident_bytes() == per_dev
        assert sched._dump_config()["mesh_devices"] == 8
    finally:
        sched.close()


# --------------------------------------------------- global_sum axis check

def test_global_sum_explicit_axis_check():
    """parallel/mesh.global_sum (ISSUE 13 satellite): under the "v"
    mesh it psums the FULL vertex axis; under a mesh whose axis names
    don't include "v" it RAISES (the old NameError swallow silently
    returned a per-shard sum for misspelled axis names); with no axis
    bound it is a plain sum."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from titan_tpu.parallel.mesh import (axis_bound, bound_axes,
                                         global_sum, shard_map_compat)

    x = jnp.arange(16.0)
    # no mesh: plain sum, no axis bound
    assert not axis_bound() and bound_axes() == ()
    assert float(global_sum(x)) == float(x.sum())

    mesh = vertex_mesh(8)
    f = shard_map_compat(lambda s: global_sum(s), mesh=mesh,
                         in_specs=(P("v"),), out_specs=P())
    assert float(jax.jit(f)(x)) == float(x.sum())   # FULL sum, per shard 2 elems

    wrong = Mesh(np.array(jax.devices()[:8]), ("x",))
    g = shard_map_compat(lambda s: global_sum(s), mesh=wrong,
                         in_specs=(P("x"),), out_specs=P())
    with pytest.raises(ValueError, match="bound mapped axes"):
        jax.jit(g)(x)


def test_block_layout_descriptor():
    """parallel/partition.BlockLayout: the one layout definition the
    sharded CSR carries — bounds cover [0, n], caps match the packed
    arrays, describe() is json-able."""
    import json

    from titan_tpu.parallel.partition import BlockLayout, block_layout

    n = 1 << 9
    rng = np.random.default_rng(7)
    degc = rng.integers(0, 5, n).astype(np.int64)
    colstart = np.zeros(n + 1, np.int64)
    np.cumsum(degc, out=colstart[1:])
    lay = block_layout(colstart, degc.astype(np.int32), n, 8)
    assert isinstance(lay, BlockLayout)
    assert lay.bounds[0] == 0 and lay.bounds[-1] == n
    assert len(lay.bounds) == 9
    lo, hi = lay.block_window(0)
    assert 0 == lo < hi <= n
    assert hi - lo <= lay.b_max
    assert max(lay.shard_chunks) < lay.q_max
    json.dumps(lay.describe())
