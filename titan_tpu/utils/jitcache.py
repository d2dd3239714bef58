"""Process-level cache for lazily built jitted functions.

Kernels are built once per process and keyed by name so (a) jax is only
imported when a kernel is first needed and (b) every call site reuses the
same function object — defining jits per call would recompile every
shape bucket on every run.
"""

from __future__ import annotations

from typing import Callable, Optional

_JITS: dict = {}

# device-cost observability seam (titan_tpu/obs/devprof, ISSUE 10):
# every kernel fetched through jit_once is wrapped in a shim that hands
# the call to the installed profile dispatch — (key, raw_fn, args,
# kwargs) -> result — which counts compiles per static shape bucket
# (cache hit vs miss via the jit's _cache_size delta), per-call wall
# time and compile time. The dispatch lives here as a plain module
# global so utils/ never imports obs/: devprof sets it on install and
# clears it when the last profiler uninstalls, leaving the off-path at
# ONE global load + None check per kernel call.
_PROFILE_DISPATCH: Optional[Callable] = None


def set_profile_dispatch(dispatch: Optional[Callable]) -> None:
    """Install (or clear, with None) the process-wide profile dispatch
    used by every jit_once shim. Owned by titan_tpu/obs/devprof."""
    global _PROFILE_DISPATCH
    _PROFILE_DISPATCH = dispatch


def _profile_shim(key: str, raw):
    """Wrap a freshly built kernel so the active profiler (if any) sees
    every call. The raw jitted function stays reachable as
    ``__wrapped__`` (tests and the dispatch read ``_cache_size`` off
    it)."""

    def shim(*args, **kwargs):
        dispatch = _PROFILE_DISPATCH
        if dispatch is None:
            return raw(*args, **kwargs)
        return dispatch(key, raw, args, kwargs)

    shim.__name__ = getattr(raw, "__name__", key)
    shim.__wrapped__ = raw
    return shim


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache — the ONE place the
    program sets its directory. Where ``JAX_COMPILATION_CACHE_DIR`` is
    in the environment JAX reads it itself and no directory is set
    here; otherwise the cache lives at the fixed in-checkout path
    ``<repo>/.bench_cache/xla`` (the path is part of the cache key, so
    it never moves). Every entry-point process (chip_smoke.py,
    ``python -m titan_tpu.server``, the experiments, the tests) calls
    this before building kernels."""
    import os

    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".bench_cache", "xla")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1)


def jit_once(key: str, builder: Callable):
    """Return the cached jitted function for ``key``, building it with
    ``builder()`` on first use. The cached function is profile-shimmed
    (see ``_profile_shim``) — a no-op unless a device-cost profiler is
    installed."""
    fn = _JITS.get(key)
    if fn is None:
        fn = _profile_shim(key, builder())
        _JITS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# device-scalar pool
# ---------------------------------------------------------------------------
# Every host->device transfer of a bare scalar costs a full host↔device
# round trip, and they do NOT pipeline. Host-driven loops that pass
# jnp.int32(...) per call silently pay this on EVERY dispatch, which
# dominated SSSP/PageRank rounds. Reused scalar values (loop levels,
# slice indices, window starts, thresholds) must come from this pool so
# each distinct value is shipped ONCE per process.

_SCALARS: dict = {}
_SCALAR_SHARDING = None


def set_scalar_sharding(sharding) -> None:
    """Multihost mode: materialize pooled scalars as GLOBAL (replicated)
    arrays under ``sharding`` — process-local device scalars cannot feed
    a process-spanning jit. Pass None to return to single-process mode.
    Clears the pool (existing entries carry the old placement)."""
    global _SCALAR_SHARDING
    _SCALAR_SHARDING = sharding
    _SCALARS.clear()


def dev_scalar(value, dtype: str = "int32"):
    """A cached device scalar for ``value`` (ship-once semantics)."""
    key = (dtype, value)
    got = _SCALARS.get(key)
    if got is None:
        import numpy as np

        import jax
        import jax.numpy as jnp
        if _SCALAR_SHARDING is not None:
            arr = np.asarray(value, dtype=dtype)
            got = jax.make_array_from_callback(
                (), _SCALAR_SHARDING, lambda idx: arr)
        else:
            got = jnp.asarray(value, dtype=getattr(jnp, dtype))
        _SCALARS[key] = got
    return got
