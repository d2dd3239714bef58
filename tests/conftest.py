"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths
(parallel/, olap/tpu/) are exercised without TPU hardware — the same trick
the driver's dryrun uses. The config update after import pins the CPU.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent XLA compile cache, shared with bench.py (.bench_cache/xla):
# serial-CPU tier-1 is budgeted (870 s) and DOMINATED by XLA compiles,
# not compute — a warm cache cuts the suite by minutes. Threshold 0:
# test-scale kernels compile fast individually but number in the
# hundreds, so even sub-second entries pay for themselves.
from titan_tpu.utils.jitcache import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

