#!/usr/bin/env bash
# Sharded-exchange smoke (ISSUE 13): run the fused sharded BFS over a
# forced 8-virtual-device CPU mesh, assert bit-equality against the
# single-chip hybrid, the ≤2-dispatch-per-level budget, and the sparse
# (O(frontier)) exchange — ONE command for a future chip day's sanity
# pass before any timed run. The in-CI twin of this flow lives in
# tests/test_sharded_exchange.py; this script proves it standalone with
# a fresh process's XLA_FLAGS pinning.
#
# Usage: scripts/sharded_smoke.sh   (CPU-safe; ~1-2 min incl. compiles)
set -euo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS=cpu \
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
exec python - <<'EOF'
import numpy as np

import jax
jax.config.update("jax_platforms", "cpu")
assert jax.device_count() >= 8, (
    f"wanted 8 forced host devices, got {jax.device_count()}")

from titan_tpu.utils.jitcache import enable_compile_cache
enable_compile_cache()

from titan_tpu.models import bfs_hybrid_sharded as S
from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
from titan_tpu.obs.devprof import DeviceCostProfiler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.olap.tpu.rmat import rmat_edges
from titan_tpu.parallel.mesh import vertex_mesh

scale = 10
src, dst = rmat_edges(scale, 8, seed=2)
snap = snap_mod.from_arrays(1 << scale,
                            np.concatenate([src, dst]),
                            np.concatenate([dst, src]))
source = int(np.flatnonzero(snap.out_degree > 0)[0])
mesh = vertex_mesh(8)

d_ref, lv_ref = frontier_bfs_hybrid(snap, source)
d_cold, lv = S.frontier_bfs_hybrid_sharded(snap, source, mesh)
assert (np.asarray(d_cold) == np.asarray(d_ref)).all() and lv == lv_ref, \
    "sharded BFS diverged from the single-chip hybrid"

# warm run under the profiler: the per-level dispatch budget
prof = DeviceCostProfiler()
with prof:
    d_sh, lv = S.frontier_bfs_hybrid_sharded(snap, source, mesh)
assert (np.asarray(d_sh) == np.asarray(d_ref)).all()
levels = len(S.LAST_PROFILE)
disp = [p["dispatches"] for p in S.LAST_PROFILE]
assert max(disp) <= 2, f"dispatch budget blown: {disp}"
calls = sum(v["calls"] for k, v in prof.kernel_stats().items()
            if k.startswith("shx_"))
assert calls == sum(disp), (calls, disp)
assert prof.compiles() == 0, \
    f"warm run minted {prof.compiles()} new compile buckets"

# sparse exchange: path graph — frontier is 1 vertex/level, caps stay tiny
n = 96
psnap = snap_mod.from_arrays(
    n, np.concatenate([np.arange(n - 1), np.arange(1, n)]),
    np.concatenate([np.arange(1, n), np.arange(n - 1)]))
d_p, _ = S.frontier_bfs_hybrid_sharded(psnap, 0, mesh)
d_pr, _ = frontier_bfs_hybrid(psnap, 0)
assert (np.asarray(d_p) == np.asarray(d_pr)).all()
assert max(S.LAST_EXCHANGE_CAPS) <= 8 < n, S.LAST_EXCHANGE_CAPS

print(f"SHARDED_SMOKE_OK scale={scale} levels={levels} "
      f"dispatches_per_level_max={max(disp)} "
      f"path_exchange_cap_max={max(S.LAST_EXCHANGE_CAPS)}")
EOF
