"""A random gather served from a table in VMEM: one Pallas program.

``out[q]`` = the sum of ``table`` at column q's ``rows`` indices, for an
index image ``[rows, Q]`` stored flat, row after row. The table
(float32, entries 0 .. n + 1: n the sink, n + 1 the pad, both 0.0) sits
whole in VMEM as ``[R, 128]``; a block's rows of indices arrive in
SMEM; an index costs the scalar core a load, a shift and an address,
the vector units a one-row load, a compare and a select; a column's
rows are summed pairwise and the MXU sums each of a tile's 128 rows
into its lane. XLA's gather serves such reads an element at a time
(124 M lanes/s on a v5e against this kernel's 713 M: PERF.md 6, PR 35).

Three callers: the uniform PageRank pull (``models/pagerank_pull.py``:
``contrib`` over the eight in-edges of a column), the dense bottom-up
opener (``models/bfs_hybrid.py``: the frontier as a 0/1 table over each
vertex's leading lanes) and CDLP's rounds (``models/cdlp.py``: at
``rows=1`` a column is one index and its sum the gathered value itself,
the labels as float32). ``gather_impl`` says whether the
kernel can serve a table of n vertices — the backend and the table's
size, what the code can observe — never a flag, an argument or the
environment.
"""

from __future__ import annotations

#: columns a grid step: 8 rows x 1,024 indices = 32 KiB of SMEM a buffer
BLOCK = 1024
#: the largest table the kernel asks VMEM for (a v5e has 128 MiB); a
#: larger graph (2^26 vertices: 268 MB) takes XLA's gather
VMEM_TABLE_MAX = 64 << 20


def table_rows(n: int) -> int:
    """Rows of the ``[R, 128]`` table: entries 0..n+1 (n the sink, n+1
    the pad), zeros from n up."""
    return -(-(n + 2) // 128)


def as_table(values):
    """``values`` [n + 1] (entries 0..n, n the sink) as the ``[R, 128]``
    table the gather reads, zeros from n + 1 up (the pad reads 0)."""
    import jax.numpy as jnp

    n1 = values.shape[0]
    rows = table_rows(n1 - 1)
    return jnp.pad(values, (0, rows * 128 - n1)).reshape(rows, 128)


def padded_columns(q: int) -> int:
    """``q`` columns rounded up to whole blocks: the width an index
    image is stored at, so that no block reads past it."""
    return -(-q // BLOCK) * BLOCK


def gather_impl(n: int) -> str:
    """``"vmem"`` on a TPU whose VMEM can hold the table, else
    ``"xla"``: decided by the backend and the table's size alone."""
    import jax

    if jax.default_backend() == "tpu" \
            and table_rows(n) * 512 <= VMEM_TABLE_MAX:
        return "vmem"
    return "xla"


def colsum_vmem(idx, table, interpret: bool = False, rows: int = 8):
    """The Pallas gather: ``out[q] = sum over k < rows of
    table[idx[k * Q + q]]``, float32 [Q], from the table in VMEM.
    ``idx`` int32 [rows * Q], row after row, Q a multiple of ``BLOCK``;
    ``rows`` a power of two (the pairwise sum below); ``interpret``:
    Pallas's interpreter, for the tests on the CPU."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert rows > 0 and rows & (rows - 1) == 0, rows
    assert idx.shape[0] % (rows * BLOCK) == 0, (idx.shape, rows)
    q_in = idx.shape[0] // rows
    blocks = q_in // BLOCK
    tiles = BLOCK // 128
    unroll = 32                  # columns of straight-line code

    def kernel(*refs):
        views, (tab_ref, out_ref, sums_ref) = refs[:rows], refs[rows:]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        ones = jnp.ones((8, 128), jnp.float32)

        def tile(t, carry):
            def sub(s, carry):
                base = t * 128 + s * unroll
                for c in range(unroll):
                    vs = []
                    for k in range(rows):
                        # a row of the indices a view: its base is the
                        # loop's invariant and the column's offset is
                        # shared by the rows, so an edge costs the
                        # scalar core a load, a shift and an address
                        i = views[k][base + c]
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
            # row c holds column c's values at their own lanes:
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
        in_specs=[pl.BlockSpec((BLOCK,),
                               lambda b, k=k: (k * blocks + b,),
                               memory_space=pltpu.SMEM)
                  for k in range(rows)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tiles, 128), lambda b: (b, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=table.shape[0] * 512 + (16 << 20),
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*([idx] * rows), table)
    return out.reshape(-1)
