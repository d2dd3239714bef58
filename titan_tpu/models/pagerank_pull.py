"""The uniform PageRank sweep as a PULL over the in-edges, its random
reads served from a table in VMEM by one Pallas program.

``rank'[v] = (1-d)/n + d * sum over u -> v of rank[u]/outdeg[u]`` read
from v's side: exact for any graph, directed or not, no test for
symmetry. Three pieces, one program an iteration (``pagerank_pull``
under ``jit_once``):

* **the image** (:func:`pull_image`, host, once a snapshot): the
  in-edge chunked CSR ``srcT [8, q_in]`` (pad = n + 1, a table entry
  that reads 0.0) — the snapshot's arrays are dst-sorted, so
  ``snap.src`` / ``snap.indptr_in`` ARE its payload and index, laid out
  by ``bfs_hybrid.layout_slot_positions`` like every other chunked
  layout — stored flat, row after row, the column count rounded up to
  a whole block so that no block reads past it. With it, from the same
  pass, what the segment sum needs: each column's first-of-its-vertex
  flag and each vertex's last column.
* **the gather**: ``colsum[q]`` = the sum of ``contrib`` over column
  q's eight in-neighbours. On a TPU whose VMEM holds the table, the
  Pallas kernel below: a block's eight rows of indices arrive in SMEM,
  ``contrib`` sits whole in VMEM as ``[R, 128]``, an edge costs the
  scalar core a load, a shift and an address, the vector units a
  one-row load, a compare and a select; a column's eight rows are
  summed and the MXU sums each of a tile's 128 rows into its lane.
  Elsewhere (the CPU, a table that outgrows VMEM) XLA's gather over
  the same image, ``contrib[srcT].sum(0)``.
* **the segment sum**: a vertex's columns are consecutive, so
  ``ops/segment.seg_scan`` sums inside a vertex in a fixed tree order
  (no prefix-sum differences: a float32 cumsum over 17 M columns loses
  the small ranks) and one sorted gather reads each vertex's last
  column.

What chooses the gather is what the code can observe — the backend and
the table's size — never a flag, an argument or the environment.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.utils.jitcache import jit_once

#: columns a grid step: 8 x 1,024 indices = 32 KiB of SMEM a buffer
PULL_BLOCK = 1024
#: the largest table the kernel asks VMEM for (a v5e has 128 MiB); a
#: larger graph (2^26 vertices: 268 MB) takes XLA's gather
VMEM_TABLE_MAX = 64 << 20


def table_rows(n: int) -> int:
    """Rows of the ``[R, 128]`` table: entries 0..n+1 (n the sink, n+1
    the pad), zeros from n up."""
    return -(-(n + 2) // 128)


def pull_columns(indptr_in: np.ndarray, n: int) -> int:
    """Columns of the pull image, BEFORE it is built (admission sizes
    it from the in-degrees): sum(ceil(deg/8)) + 1 sink column, rounded
    up to a whole block."""
    deg = np.diff(np.asarray(indptr_in[:n + 1], np.int64))
    q = int((-(-deg // 8)).sum()) + 1
    return -(-q // PULL_BLOCK) * PULL_BLOCK


def pull_image_bytes(n: int, q_in: int) -> int:
    """Device bytes of the pull image: indices [8 x q_in] int32, a flag
    a column, last column + has-columns + out-degree a vertex."""
    return q_in * 8 * 4 + q_in + n * 4 + n + (n + 1) * 4


def pull_image(snap) -> dict:
    """Host-side (cached on the snapshot as ``_pull_csr``, dropped with
    the other layouts): the in-edge image of the module docstring, on
    device. ``idx`` int32 [8 * q_in] (``srcT``, row after row),
    ``first`` bool [q_in], ``last`` int32 [n], ``has`` bool [n], ``deg``
    float32 [n + 1] (OUT-degrees, 0 for the sink), ``seg_max`` the most
    columns a vertex has."""
    cached = getattr(snap, "_pull_csr", None)
    if cached is not None:
        return cached
    import jax.numpy as jnp

    from titan_tpu.models.bfs_hybrid import layout_slot_positions
    from titan_tpu.obs import devprof
    from titan_tpu.ops.segment import segment_metadata

    n = snap.n
    deg_in = np.diff(snap.indptr_in).astype(np.int64)
    pos, colstart, degc = layout_slot_positions(snap.indptr_in, deg_in, n)
    q_in = pull_columns(snap.indptr_in, n)
    if q_in >= (1 << 28):
        raise NotImplementedError(
            "the pull image indexes slots in int32; shard below 2^28 "
            "columns")
    flat = np.full(q_in * 8, n + 1, np.int32)
    flat[pos] = snap.src
    idx = np.ascontiguousarray(flat.reshape(q_in, 8).T).reshape(-1)
    last, has = segment_metadata(colstart)
    first = np.zeros(q_in, bool)
    first[colstart[:n][has]] = True
    first[int(colstart[n]):] = True          # sink and pad columns
    outdeg = np.concatenate(
        [snap.out_degree.astype(np.float32), np.zeros(1, np.float32)])
    devprof.count_h2d("pagerank.pull_image", pull_image_bytes(n, q_in))
    out = {
        "idx": jnp.asarray(idx),
        "first": jnp.asarray(first),
        "last": jnp.asarray(np.maximum(last, 0)),
        "has": jnp.asarray(has),
        "deg": jnp.asarray(outdeg),
        "seg_max": int(degc.max()) if n else 1,
        "q_in": q_in,
        "n": n,
    }
    snap._pull_csr = out
    return out


def gather_impl(n: int) -> str:
    """``"vmem"`` on a TPU whose VMEM can hold the table, else
    ``"xla"``: decided by the backend and the table's size alone."""
    import jax

    if jax.default_backend() == "tpu" \
            and table_rows(n) * 512 <= VMEM_TABLE_MAX:
        return "vmem"
    return "xla"


def _colsum_xla(idx, table):
    """XLA's gather over the same image: ``contrib[srcT].sum(0)``."""
    return table.reshape(-1)[idx.reshape(8, -1)].sum(axis=0)


def _colsum_vmem(idx, table, interpret: bool = False):
    """The Pallas gather: colsum [q_in] from the table in VMEM
    (``interpret``: Pallas's interpreter, for the tests on the CPU)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q_in = idx.shape[0] // 8
    blocks = q_in // PULL_BLOCK
    tiles = PULL_BLOCK // 128
    unroll = 32                  # columns of straight-line code

    def kernel(*refs):
        rows, (tab_ref, out_ref, sums_ref) = refs[:8], refs[8:]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        ones = jnp.ones((8, 128), jnp.float32)

        def tile(t, carry):
            def sub(s, carry):
                base = t * 128 + s * unroll
                for c in range(unroll):
                    vs = []
                    for k in range(8):
                        # a row of the indices a view: its base is the
                        # loop's invariant and the column's offset is
                        # shared by the eight, so an edge costs the
                        # scalar core a load, a shift and an address
                        i = rows[k][base + c]
                        row = tab_ref[pl.ds(i >> 7, 1), :]
                        # the lane is split off on the vector side: the
                        # scalar core's two slots are the kernel's wall
                        hit = (jnp.full((1, 128), i, jnp.int32) & 127) \
                            == lane
                        vs.append(jnp.where(hit, row, 0.0))
                    while len(vs) > 1:
                        vs = [vs[j] + vs[j + 1]
                              for j in range(0, len(vs), 2)]
                    sums_ref[pl.ds(s * unroll + c, 1), :] = vs[0]
                return carry

            jax.lax.fori_loop(0, 128 // unroll, sub, 0)
            # row c holds column c's eight values at their own lanes:
            # the MXU sums every row into lane c of one output row
            sums = jax.lax.dot_general(
                ones, sums_ref[...], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            out_ref[pl.ds(t, 1), :] = sums[0:1, :]
            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((q_in // 128, 128), jnp.float32),
        grid=(blocks,),
        in_specs=[pl.BlockSpec((PULL_BLOCK,),
                               lambda b, k=k: (k * blocks + b,),
                               memory_space=pltpu.SMEM)
                  for k in range(8)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tiles, 128), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=table.shape[0] * 512 + (16 << 20),
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*([idx] * 8), table)
    return out.reshape(-1)


def pull_step():
    """``pagerank_pull``: one iteration's sums, ``acc`` [n] from
    ``rank`` [n + 1]. ``contrib`` is computed here, from ``rank`` and
    the out-degrees, by the expressions ``pagerank_finish`` uses."""
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.segment import seg_scan

        @functools.partial(jax.jit,
                           static_argnames=("impl", "seg_max"))
        def step(rank, deg, idx, first, last, has, impl: str,
                 seg_max: int):
            contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
            n1 = contrib.shape[0]                       # n + 1
            rows = table_rows(n1 - 1)
            table = jnp.pad(contrib, (0, rows * 128 - n1)) \
                .reshape(rows, 128)
            colsum = (_colsum_vmem if impl == "vmem"
                      else _colsum_xla)(idx, table)
            run = seg_scan(colsum, first, "sum", max_len=seg_max)
            return jnp.where(has, run[last], 0.0)
        return step
    return jit_once("pagerank_pull", build)
