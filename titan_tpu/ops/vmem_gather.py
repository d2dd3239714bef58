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

**The price is the index, not the table** (PERF.md 6, PR 47): an entry
may hold ``width`` values (a power of two up to ``MAX_WIDTH``) side by
side in adjacent lanes, a row then 128 / width entries. An index is
still one load, one shift (by 7 - log2 width), one address, one row
load, one compare (of the lane's ENTRY, a loop invariant) and one
select, of which ``width`` lanes survive; the MXU contracts a tile's
lanes against a 0/1 selector a value where it contracts against ones,
and the sums come out ``[width, Q]``. A pass over 139.6 M indices reads
188 ms at width 1, 190 at 4, 193 at 8 (PERF.md 5). Width 1 traces the
program it was before there was a width (tests/test_shared_pull.py).

Four callers: the uniform PageRank pull (``models/pagerank_pull.py``:
``contrib`` over the eight in-edges of a column), the dense bottom-up
opener (``models/bfs_hybrid.py``: the frontier as a 0/1 table over each
vertex's leading lanes), CDLP's rounds (``models/cdlp.py``: at
``rows=1`` a column is one index and its sum the gathered value itself,
the labels as float32), all at width 1, and the levels of a BC job
(``models/bc.py`` through ``pagerank_pull.pull_sum``: the masked tables
of a group of roots side by side, ``shared_width`` of them).
``gather_impl`` says whether the kernel can serve a table of n vertices
and ``width`` values each — the backend and the table's size, what the
code can observe — never a flag, an argument or the environment.
"""

from __future__ import annotations

#: columns a grid step: 8 rows x 1,024 indices = 32 KiB of SMEM a buffer
BLOCK = 1024
#: the largest table the kernel asks VMEM for (a v5e has 128 MiB); a
#: larger graph (2^26 vertices: 268 MB) takes XLA's gather
VMEM_TABLE_MAX = 64 << 20
#: the most values an entry of the table holds: the rows of the MXU's
#: left operand, one a value
MAX_WIDTH = 8


def table_rows(n: int, width: int = 1) -> int:
    """Rows of the ``[R, 128]`` table: entries 0..n+1 (n the sink, n+1
    the pad), ``width`` values an entry, zeros from n up."""
    return -(-(n + 2) * width // 128)


def as_table(values):
    """``values`` [n + 1] (entries 0..n, n the sink) as the ``[R, 128]``
    table the gather reads, zeros from n + 1 up (the pad reads 0).
    ``values`` [w, n + 1] (w values an entry, the values major: w minor
    would pad every value to a row of lanes on the chip): the table of
    ``colsum_vmem``'s ``width`` w, flat row-major over (entry, value),
    so that a row holds 128 / w entries and an entry's w values stand
    in adjacent lanes."""
    import jax.numpy as jnp

    if values.ndim == 1:
        n1 = values.shape[0]
        rows = table_rows(n1 - 1)
        return jnp.pad(values, (0, rows * 128 - n1)).reshape(rows, 128)
    width, n1 = values.shape
    rows, per = table_rows(n1 - 1, width), 128 // width
    return jnp.pad(values, ((0, 0), (0, rows * per - n1))) \
        .reshape(width, rows, per).transpose(1, 2, 0).reshape(rows, 128)


def padded_columns(q: int) -> int:
    """``q`` columns rounded up to whole blocks: the width an index
    image is stored at, so that no block reads past it."""
    return -(-q // BLOCK) * BLOCK


def _fits(n: int, width: int) -> bool:
    return table_rows(n, width) * 512 <= VMEM_TABLE_MAX


def gather_impl(n: int, width: int = 1) -> str:
    """``"vmem"`` on a TPU whose VMEM can hold the table of ``width``
    values a vertex, else ``"xla"``: decided by the backend and the
    table's size alone."""
    import jax

    if jax.default_backend() == "tpu" and _fits(n, width):
        return "vmem"
    return "xla"


def shared_width(n: int, most: int) -> int:
    """The most values a vertex that one gather serves: the largest
    power of two that ``most``, ``MAX_WIDTH`` and the table's cap at
    this ``n`` allow (1 where a single value outgrows the cap)."""
    width = 1
    while 2 * width <= min(most, MAX_WIDTH) and _fits(n, 2 * width):
        width *= 2
    return width


def colsum_vmem(idx, table, interpret: bool = False, rows: int = 8,
                width: int = 1):
    """The Pallas gather: ``out[q] = sum over k < rows of
    table[idx[k * Q + q]]``, float32 [Q], from the table in VMEM.
    ``idx`` int32 [rows * Q], row after row, Q a multiple of ``BLOCK``;
    ``rows`` a power of two (the pairwise sum below); ``interpret``:
    Pallas's interpreter, for the tests on the CPU.

    ``width`` (a power of two, at most ``MAX_WIDTH``; static) values an
    entry, as ``as_table`` lays a ``[width, n + 1]`` array: a VMEM row
    holds 128 / width entries, an index selects its entry's ``width``
    adjacent lanes of ONE row, and the MXU contracts a tile's lanes
    against a 0/1 selector a value, so the sums are float32 [width, Q],
    value r's in row r. An index costs what it costs at width 1."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert rows > 0 and rows & (rows - 1) == 0, rows
    assert 0 < width <= MAX_WIDTH and width & (width - 1) == 0, width
    assert idx.shape[0] % (rows * BLOCK) == 0, (idx.shape, rows)
    q_in = idx.shape[0] // rows
    blocks = q_in // BLOCK
    tiles = BLOCK // 128
    unroll = 32                  # columns of straight-line code
    log_w = width.bit_length() - 1
    row_shift, entry_mask = 7 - log_w, (128 >> log_w) - 1

    def kernel(*refs):
        views, tab_ref = refs[:rows], refs[rows]
        out_refs, sums_ref = refs[rows + 1:-1], refs[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        # the entry a lane belongs to, and the rows of the MXU's left
        # operand: value r of every entry into output row r
        entry = lane >> log_w if log_w else lane
        if width == 1:
            sel = jnp.ones((8, 128), jnp.float32)
        else:
            sel = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
                   & (width - 1)
                   == jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
                   ).astype(jnp.float32)

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
                        row = tab_ref[pl.ds(i >> row_shift, 1), :]
                        # the lane is split off on the vector side: the
                        # scalar core's two slots are the kernel's wall
                        hit = (jnp.full((1, 128), i, jnp.int32)
                               & entry_mask) == entry
                        vs.append(jnp.where(hit, row, 0.0))
                    while len(vs) > 1:
                        vs = [vs[j] + vs[j + 1]
                              for j in range(0, len(vs), 2)]
                    sums_ref[pl.ds(s * unroll + c, 1), :] = vs[0]
                return carry

            jax.lax.fori_loop(0, 128 // unroll, sub, 0)
            # row c holds column c's values at their own lanes: the MXU
            # sums every row's lanes of value r into lane c of row r
            sums = jax.lax.dot_general(
                sel, sums_ref[...], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            for r, out_ref in enumerate(out_refs):
                out_ref[pl.ds(t, 1), :] = sums[r:r + 1, :]
            return carry

        jax.lax.fori_loop(0, tiles, tile, 0)

    one = jax.ShapeDtypeStruct((q_in // 128, 128), jnp.float32)
    outs = pl.pallas_call(
        kernel,
        out_shape=[one] * width,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((BLOCK,),
                               lambda b, k=k: (k * blocks + b,),
                               memory_space=pltpu.SMEM)
                  for k in range(rows)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((tiles, 128), lambda b: (b, 0),
                                memory_space=pltpu.VMEM)] * width,
        scratch_shapes=[pltpu.VMEM((128, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=table.shape[0] * 512 + (16 << 20),
            dimension_semantics=("arbitrary",)),
        interpret=interpret)(*([idx] * rows), table)
    if width == 1:
        return outs[0].reshape(-1)
    return jnp.stack([out.reshape(-1) for out in outs])
