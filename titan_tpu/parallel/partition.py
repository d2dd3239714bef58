"""Edge/vertex partitioning + explicit data placement for multi-chip
execution.

The TPU-native replacement for the reference's data-placement machinery
(reference: titan-core SURVEY §2.7 — partition bits in ids shard rows across
the cluster; vertex cuts spread hot rows): vertices are block-partitioned
into D contiguous dense ranges (dense order is partition-major, so storage
partitions and device shards coincide); edges go to the shard that OWNS THE
DESTINATION vertex (pull layout), each shard keeping global source indices.
A superstep then needs exactly one all-gather of vertex state over ICI plus
a local gather + segment-combine — no shuffle.

All shards are padded to identical static shapes (XLA requirement): padded
edges point at a per-shard sink row (local index == block) and are masked
with the combine identity.

Sharded-exchange rebuild (ISSUE 13) additions:

* :class:`BlockLayout` — the vertex-block layout descriptor: one object
  carrying the edge-balanced block bounds, per-shard padded widths and
  the int32 safety facts, shared by the sharded BFS, the multihost
  loader and the comm-profile reporting so the layout has exactly one
  definition;
* :func:`place_shards` / :func:`place_replicated` — explicit
  ``NamedSharding`` placement of the per-shard device arrays (uploaded
  ONCE, committed, so no per-dispatch resharding);
* :func:`exchange_found` — the shard_map-level sparse exchange
  primitive: compact each shard's newly-found vertex ids to a static
  cap and all-gather ONLY those lists — O(frontier) communication, the
  replicated-dist merge without an n-scale all-reduce;
* :func:`place_batched_csr` — mesh placement for the serving plane's
  batched ``[K, n]`` cohorts: the chunked CSR's columns shard over
  ``"v"`` and the dist state rides a ``P(None, "v")`` sharding (K
  replicated), so K-way plan amortization and sharding compose through
  the UNCHANGED batched kernels (GSPMD partitions them from the input
  placements).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from titan_tpu.olap.tpu.snapshot import GraphSnapshot

# kept in sync with parallel/mesh.VERTEX_AXIS (a string constant; the
# mesh module imports jax at module scope, which this module defers)
VERTEX_AXIS = "v"

_ALIGN = 1024  # pad edge blocks to multiples of this (8×128 tiles)


@dataclass
class ShardedCSR:
    n: int                      # true vertex count
    n_pad: int                  # D * block
    block: int                  # vertices per shard
    num_shards: int
    e_block: int                # edges per shard (padded, static)
    src_global: np.ndarray      # [D, e_block] int32
    dst_local: np.ndarray       # [D, e_block] int32 in [0, block]; block = sink
    valid: np.ndarray           # [D, e_block] bool
    last_idx: np.ndarray        # [D, block+1] int32 scan metadata (ops/segment)
    seg_has: np.ndarray         # [D, block+1] bool
    edge_values: dict = field(default_factory=dict)  # name -> [D, e_block]


def shard_csr(snap: GraphSnapshot, num_shards: int,
              align: int = _ALIGN) -> ShardedCSR:
    n = snap.n
    block = -(-max(n, 1) // num_shards)          # ceil
    block = -(-block // 8) * 8                   # sublane-align vertex blocks
    n_pad = block * num_shards

    # snapshot edges are dst-sorted: shard boundaries via searchsorted
    bounds = np.searchsorted(snap.dst, np.arange(0, n_pad + 1, block))
    counts = np.diff(bounds)
    e_block = int(max(counts.max() if len(counts) else 0, 1))
    e_block = -(-e_block // align) * align

    src_g = np.zeros((num_shards, e_block), dtype=np.int32)
    dst_l = np.full((num_shards, e_block), block, dtype=np.int32)  # sink
    valid = np.zeros((num_shards, e_block), dtype=bool)
    last_idx = np.zeros((num_shards, block + 1), dtype=np.int32)
    seg_has = np.zeros((num_shards, block + 1), dtype=bool)
    evs = {name: np.zeros((num_shards, e_block), dtype=np.asarray(v).dtype)
           for name, v in snap.edge_values.items()}
    from titan_tpu.ops.segment import segment_metadata
    for d in range(num_shards):
        lo, hi = bounds[d], bounds[d + 1]
        m = hi - lo
        src_g[d, :m] = snap.src[lo:hi]
        dst_l[d, :m] = snap.dst[lo:hi] - d * block
        valid[d, :m] = True
        for name, v in snap.edge_values.items():
            evs[name][d, :m] = v[lo:hi]
        # scan metadata over the local (block+1)-segment layout (sink last)
        indptr_l = np.zeros(block + 2, dtype=np.int64)
        np.add.at(indptr_l, dst_l[d] + 1, 1)
        np.cumsum(indptr_l, out=indptr_l)
        li, sh = segment_metadata(indptr_l)
        last_idx[d] = li[:block + 1]
        seg_has[d] = sh[:block + 1]
    return ShardedCSR(n, n_pad, block, num_shards, e_block, src_g, dst_l,
                      valid, last_idx, seg_has, evs)


# ---------------------------------------------------------------------------
# vertex-block layout descriptors (ISSUE 13)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockLayout:
    """The vertex-block layout of a D-way mesh: edge-balanced
    contiguous vertex ranges over the chunk prefix, with the padded
    per-shard widths every kernel cap derives from.

    ``bounds`` is always ``num_shards + 1`` long (degenerate trailing
    shards own empty ranges, exactly like the packed arrays they
    describe). ``b_max``/``q_max`` are the padded per-shard vertex and
    chunk-column widths; ``q_max`` includes the +1 local sink column
    and is int32-guarded at construction (per-shard LOCAL column
    indices are int32). ``shard_chunks`` is the per-shard edge-chunk
    mass — the edge-balance evidence the comm profile reports.
    ``nunv_cap`` bounds the per-shard count of expandable vertices —
    the first bottom-up level's candidate cap, before any exchange
    stats exist."""

    n: int
    num_shards: int
    bounds: tuple                # [num_shards + 1] dense vertex cuts
    b_max: int                   # padded vertices per shard
    q_max: int                   # padded chunk columns per shard (+sink)
    shard_chunks: tuple          # per-shard chunk mass (live shards)
    nunv_cap: int

    @property
    def live_shards(self) -> int:
        return len(self.shard_chunks)

    def balance(self) -> float:
        """max/min chunk mass over live shards (1.0 = perfect)."""
        if not self.shard_chunks:
            return 1.0
        return max(self.shard_chunks) / max(min(self.shard_chunks), 1)

    def block_window(self, d: int) -> tuple:
        """(lo, hi) dense vertex range owned by shard ``d``."""
        return int(self.bounds[d]), int(self.bounds[d + 1])

    def describe(self) -> dict:
        return {"n": self.n, "num_shards": self.num_shards,
                "b_max": self.b_max, "q_max": self.q_max,
                "shard_chunks": list(self.shard_chunks),
                "balance_max_over_min": round(self.balance(), 3),
                "nunv_cap": self.nunv_cap}


def block_layout(colstart: np.ndarray, degc_all: np.ndarray, n: int,
                 num_shards: int) -> BlockLayout:
    """Plan the edge-balanced vertex-block layout (the ONE descriptor
    construction — single-host sharding and the multihost host-sharded
    loader both come through here via
    ``bfs_hybrid_sharded.plan_shard_cuts``)."""
    from titan_tpu.models.bfs_hybrid_sharded import (plan_shard_cuts,
                                                     shard_unvisited_cap)

    bounds, b_max, q_max = plan_shard_cuts(colstart, n, num_shards)
    d_eff = len(bounds) - 1
    bounds_full = np.zeros(num_shards + 1, np.int64)
    bounds_full[:len(bounds)] = bounds
    bounds_full[len(bounds):] = n
    chunks = tuple(int(colstart[bounds[d + 1]] - colstart[bounds[d]])
                   for d in range(d_eff))
    return BlockLayout(int(n), int(num_shards),
                       tuple(int(b) for b in bounds_full),
                       int(b_max), int(q_max), chunks,
                       shard_unvisited_cap(degc_all, bounds))


# ---------------------------------------------------------------------------
# explicit NamedSharding placement (ISSUE 13)
# ---------------------------------------------------------------------------

def _placeable(a):
    """Host data stays a numpy array so ``device_put`` ships each shard
    straight to its own device; going through ``jnp.asarray`` first
    would land the whole stacked image on the first device and reshard
    it from there. Arrays already on device pass through."""
    import jax

    return a if isinstance(a, jax.Array) else np.asarray(a)


def place_shards(mesh, *arrays):
    """Commit per-shard arrays (leading dim = num_shards) onto the
    mesh with explicit ``NamedSharding(mesh, P("v", None, ...))`` —
    uploaded ONCE to their final placement, so no kernel dispatch ever
    pays a host round trip or a device reshuffle to put shard d's rows
    on device d. Returns the placed arrays in order."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = []
    for a in arrays:
        a = _placeable(a)
        spec = P(VERTEX_AXIS, *([None] * (a.ndim - 1)))
        out.append(jax.device_put(a, NamedSharding(mesh, spec)))
    return out


def place_replicated(mesh, *arrays):
    """Commit arrays fully replicated (``P()``) across the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = NamedSharding(mesh, P())
    return [jax.device_put(_placeable(a), sh) for a in arrays]


# ---------------------------------------------------------------------------
# the sparse exchange primitive (ISSUE 13)
# ---------------------------------------------------------------------------

def exchange_found(newly_mask, found_cap: int, n: int,
                   axis: str = VERTEX_AXIS):
    """The shard_map-level frontier exchange: compact this shard's
    newly-found vertex mask into a ``found_cap``-sized id list
    (ops.compaction — no n-wide nonzero) and all-gather ONLY those
    lists over the mesh axis. Communication is O(frontier), not O(n):
    D × found_cap int32 ids per level versus the n-element dist
    all-reduce the round-1 design paid (256 MB × levels at scale 26).

    The all-gather is issued HERE, before the caller's merge/stat
    reductions consume it, so XLA can overlap the collective with the
    n-scale stat compute that follows (the overlap model,
    docs/performance.md).

    Must be called INSIDE a shard_map body with ``axis`` bound. Returns
    ``(all_ids [D, found_cap] int32 with fill n+1, found_max)`` where
    ``found_max`` is the pmax'd true per-shard discovery count — the
    caller's overflow check (``found_max > found_cap`` ⇒ retry with the
    exact cap; the merged result is discarded)."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.ops.compaction import compact_ids

    cnt = newly_mask.sum().astype(jnp.int32)
    found_max = jax.lax.pmax(cnt, axis)
    _, ids = compact_ids(newly_mask, found_cap, n + 1)
    all_ids = jax.lax.all_gather(ids, axis)          # [D, found_cap]
    return all_ids, found_max


# ---------------------------------------------------------------------------
# mesh placement for batched [K, n] cohorts (ISSUE 13, serving plane)
# ---------------------------------------------------------------------------

def batched_state_sharding(mesh):
    """The ``[K, n+1]`` dist placement for mesh-placed batched runs:
    vertex axis sharded over ``"v"``, K replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, VERTEX_AXIS))


def place_batched_csr(snap_or_graph, mesh) -> dict:
    """Chunked-CSR graph dict placed for a multi-device mesh: ``dstT``'s
    chunk columns shard over ``"v"`` (each device holds ~1/D of the
    edge image — the arrays that dominate HBM), the small per-vertex
    arrays replicate, and ``_state_sharding`` tells
    ``frontier_bfs_batched`` to pin its ``[K, n+1]`` dist to
    ``P(None, "v")`` (K replicated). The batched kernels themselves are
    UNCHANGED — committed input placements carry through jit and GSPMD
    partitions the sweep, which is what lets K-way plan amortization
    and sharding compose without a second kernel library.

    ``dstT`` is column-padded to a multiple of D (extra all-pad sink
    columns — this jax requires divisible shard extents); the padded
    columns behave exactly like the existing sink column (pad gathers
    clamp to the never-written ``dist[n]``). The state sharding is
    attached only when ``n + 1`` divides over the mesh; otherwise the
    state replicates (correct either way — GSPMD still shards the edge
    sweep) and the dict records ``_state_replicated_why``.

    Cached on the graph dict per mesh. Single-process meshes only (the
    serving plane is one process; multihost cohorts would need
    host-sharded loading, which is the sharded-BFS path's job)."""
    import jax

    from titan_tpu.models.bfs_hybrid import build_chunked_csr

    if jax.process_count() > 1:
        raise NotImplementedError(
            "place_batched_csr is single-process (the serving plane); "
            "multihost placement goes through parallel/multihost")
    g = snap_or_graph if isinstance(snap_or_graph, dict) \
        else build_chunked_csr(snap_or_graph)
    cache = g.get("_meshed")
    if cache is not None and cache[0] == mesh:
        return cache[1]
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = g["n"]
    D = int(mesh.devices.size)
    host = g.get("_host", {})
    dstT_h = host.get("dstT")
    if dstT_h is None:
        dstT_h = np.asarray(g["dstT"])
    q = dstT_h.shape[1]
    q_pad = -(-q // D) * D
    if q_pad != q:
        dstT_h = np.concatenate(
            [dstT_h, np.full((8, q_pad - q), n + 1, np.int32)], axis=1)
    from titan_tpu.obs import devprof
    devprof.count_h2d("parallel.batched_csr", dstT_h.nbytes)
    placed = dict(g)
    placed["dstT"] = jax.device_put(
        dstT_h, NamedSharding(mesh, P(None, VERTEX_AXIS)))
    placed["colstart"], placed["degc"], placed["deg"] = place_replicated(
        mesh, g["colstart"], g["degc"], g["deg"])
    if (n + 1) % D == 0:
        placed["_state_sharding"] = batched_state_sharding(mesh)
    else:
        placed["_state_replicated_why"] = (
            f"n+1 = {n + 1} does not divide over {D} devices; dist "
            "replicates (edge sweep still sharded)")
    placed["_mesh"] = mesh
    placed.pop("_meshed", None)
    g["_meshed"] = (mesh, placed)
    return placed


