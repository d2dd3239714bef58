"""Device-mesh helpers + the explicit-sharding compile layer.

The OLAP engine shards per-vertex state over a 1D mesh axis ``"v"``
(vertex blocks); frontier/state exchange rides ICI via ``all_gather``
inside ``shard_map`` (SURVEY §2.8: the TPU-native replacement for the
reference's storage-mediated data movement).

Since the sharded-exchange rebuild (ISSUE 13) this module is also the
compile seam for explicit shardings:

* :func:`mesh_jit` — the compile-once helper (SNIPPETS [1] pattern):
  build a mesh-bound kernel exactly once per (name, mesh), jit it with
  its OUTPUT shardings pinned as ``NamedSharding``s so XLA never
  re-infers placement across levels, and register it through
  ``utils/jitcache`` so the device-cost profiler shims it like every
  other kernel;
* :func:`vertex_mesh` — caches the mesh per device count, so every
  call site holding "the 8-device mesh" holds the SAME hashable object
  and static-argument jit caches never fork on mesh identity;
* :func:`bound_axes` / :func:`axis_bound` — explicit axis-environment
  introspection. ``global_sum`` used to swallow ``NameError`` to
  detect "axis not bound", which also swallowed genuinely misspelled
  axis names into a silent per-shard sum; now a bound-but-different
  axis environment raises loudly.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

VERTEX_AXIS = "v"


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled
    (check_vma) — the engine kernels return deliberately-replicated
    pmax'd stats next to sharded state, which the checker rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


#: mesh cache: one Mesh object per device count (device order is
#: process-stable), so jit caches keyed on the mesh — static arguments
#: and mesh_jit's registry alike — never fork on object identity
_MESHES: dict = {}


def vertex_mesh(num_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    if num_devices is None or num_devices <= 0:
        num_devices = len(devs)
    if num_devices > len(devs):
        raise ValueError(f"requested {num_devices} devices, have {len(devs)}")
    got = _MESHES.get(num_devices)
    if got is None or got.devices.size != num_devices:
        got = Mesh(np.array(devs[:num_devices]), (VERTEX_AXIS,))
        _MESHES[num_devices] = got
    return got


def bound_axes() -> tuple:
    """Names of the mapped axes bound in the CURRENT trace (inside a
    shard_map/pmap body: that map's axis names; top level: empty).

    Raises (does NOT return empty) when the axis-environment API is
    missing — a jax upgrade that renames it must surface as a loud
    error at the call site, never as a silent "no axis bound" that
    degrades ``global_sum`` into a per-shard sum (the failure mode the
    old NameError swallow had, which this module exists to close)."""
    try:
        from jax._src import core
        env = core.get_axis_env()
    except Exception as e:
        raise RuntimeError(
            "parallel.mesh.bound_axes: this jax version does not "
            "expose jax._src.core.get_axis_env() — update the axis-"
            "environment probe here (silently assuming 'no axis "
            "bound' would turn sharded global reductions into "
            f"per-shard sums): {type(e).__name__}: {e}") from e
    return tuple(env.axis_sizes)


def axis_bound(name: str = VERTEX_AXIS) -> bool:
    """True iff mapped axis ``name`` is bound in the current trace."""
    return name in bound_axes()


def global_sum(x, axis: str = VERTEX_AXIS):
    """Sum across the FULL vertex axis from inside a DenseProgram
    callback: shard-local sum + psum over the mesh when executing under
    shard_map, plain sum on a single device (no axis bound there).
    Programs with global reductions (e.g. HITS normalization) must use
    this instead of jnp.sum, or sharded runs silently normalize per
    shard.

    The "am I sharded?" test is an EXPLICIT axis-environment check
    (:func:`axis_bound`), not a swallowed NameError: executing under a
    mesh whose axis names don't include ``axis`` raises — a misspelled
    axis name must never degrade into a silent per-shard sum."""
    import jax.numpy as jnp
    total = jnp.sum(x)
    bound = bound_axes()
    if axis in bound:
        return jax.lax.psum(total, axis)
    if bound:
        raise ValueError(
            f"global_sum over axis {axis!r}, but the bound mapped axes "
            f"are {bound} — a per-shard sum here would be silently "
            "wrong; pass the mesh axis this program is sharded over")
    return total


def state_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(VERTEX_AXIS))


def edge_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(VERTEX_AXIS, None))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# compile-once helper over explicit shardings (SNIPPETS [1] pattern)
# ---------------------------------------------------------------------------

def mesh_key(mesh: Mesh) -> str:
    """A stable fingerprint for jit-cache keys: axis layout + device
    ids (NOT id(mesh) — equal meshes must share compiled kernels)."""
    ids = ",".join(str(d.id) for d in mesh.devices.flat)
    ax = ",".join(f"{n}{s}" for n, s in zip(mesh.axis_names, mesh.shape.values()))
    return f"{ax}[{ids}]"


def mesh_jit(name: str, mesh: Mesh, builder, *, out_specs,
             static_argnames=(), donate_argnums=()):
    """Compile-once, donor-aware jit with pinned OUTPUT shardings.

    ``builder(mesh)`` returns the python callable (typically a
    shard_map-wrapped per-shard body closed over the mesh). It is
    called once per (name, mesh); the result is jitted with
    ``out_shardings`` materialized from ``out_specs`` (a PartitionSpec
    pytree) as ``NamedSharding``s on ``mesh``, so every level dispatch
    lands its outputs exactly where the next level's inputs are pinned
    — XLA never re-infers or reshuffles placement between dispatches.
    Inputs are pinned at the data instead (see
    ``partition.place_shards``): committed arrays carry their sharding
    through jit, and pinning uploads once beats re-specifying per call.

    The compiled function registers through ``utils/jitcache.jit_once``
    (key ``<name>@<mesh fingerprint>``), so the device-cost profiler
    shims it exactly like the single-chip kernels — ``device.exec.calls
    {kernel=<name>@...}`` is the per-level dispatch-budget evidence."""
    from titan_tpu.utils.jitcache import jit_once

    key = f"{name}@{mesh_key(mesh)}"

    def build():
        fn = builder(mesh)
        out_shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec), out_specs,
            is_leaf=lambda s: isinstance(s, P))
        return jax.jit(fn, out_shardings=out_shardings,
                       static_argnames=tuple(static_argnames),
                       donate_argnums=tuple(donate_argnums))

    return jit_once(key, build)
