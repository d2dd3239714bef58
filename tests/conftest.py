"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths
(parallel/, olap/tpu/) are exercised without TPU hardware — the same trick
the driver's dryrun uses. The config update after import pins the CPU.
"""

import os

import pytest

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache (.bench_cache/xla): tier-1 is budgeted
# and DOMINATED by XLA compiles, not compute — a warm cache cuts the
# suite by minutes. Threshold 0:
# test-scale kernels compile fast individually but number in the
# hundreds, so even sub-second entries pay for themselves.
from titan_tpu.utils.jitcache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)



@pytest.fixture
def force_bottom_up(monkeypatch):
    """Every BFS level that can pull does, at toy scale: the head loop,
    the endgame and the split-lane threshold of the single-source
    driver shrink to nothing (in production the opener engages above
    2^21 candidates), and a pushed column costs the batched direction
    rule more than any pull."""
    import titan_tpu.models.bfs_hybrid as H

    monkeypatch.setattr(H, "SPLIT_LANE_MIN", 2)
    monkeypatch.setattr(H, "END_C_CAP", 0)
    monkeypatch.setattr(H, "END_P_CAP", 0)
    monkeypatch.setattr(H, "HEAD_F_CAP", 1)
    monkeypatch.setattr(H, "TD_BU_COST", 1 << 30)
