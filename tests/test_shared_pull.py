"""A gather of ``width`` values an index (ISSUE 47): ``ops/vmem_gather``'s
table that holds w values a vertex side by side in one VMEM row, the
kernel in Pallas's interpreter and XLA's road over the same image
against numpy, at every width and at ``rows`` 8 and 1; width 1 the
program it was at PR 46 (the same jaxpr, the same bits); what prices the
width; ``seg_scan`` with a leading axis; ``pull_sum`` over ``[w, n + 1]``
tables row by row what it gives one table alone.
"""

import functools
import inspect

import numpy as np
import pytest

from titan_tpu.models import pagerank_pull as pp
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import vmem_gather as vg
from titan_tpu.ops.segment import seg_scan

WIDTHS = (1, 2, 4, 8)
N = 1000                          # entries 0..N: N the sink, N + 1 the pad


def _parents_colsum_vmem(idx, table, interpret: bool = False, rows: int = 8):
    """``colsum_vmem`` as it stood at PR 46 (commit a78e4cd, word for
    word): one value an index."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BLOCK = vg.BLOCK
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
                        i = views[k][base + c]
                        row = tab_ref[pl.ds(i >> 7, 1), :]
                        hit = (jnp.full((1, 128), i, jnp.int32) & 127) \
                            == lane
                        vs.append(jnp.where(hit, row, 0.0))
                    while len(vs) > 1:
                        vs = [vs[j] + vs[j + 1]
                              for j in range(0, len(vs), 2)]
                    sums_ref[pl.ds(s * unroll + c, 1), :] = vs[0]
                return carry

            jax.lax.fori_loop(0, 128 // unroll, sub, 0)
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


def indices(rows: int, width: int, seed: int = 47) -> np.ndarray:
    """``rows`` x one block of indices into entries 0..N + 1. Column 0
    is all sinks, column 1 all pads, column 2 holds two rows in one
    lane group (entries of one table row, ``128 / width`` apart in
    nothing but their row: the same entry twice, and its neighbour),
    column 3 the first and the last entry."""
    idx = np.random.default_rng(seed).integers(
        0, N + 2, size=(rows, vg.BLOCK)).astype(np.int32)
    idx[:, 0], idx[:, 1] = N, N + 1
    per = 128 // width
    idx[:, 2] = (5 * per + 3 + np.arange(rows) // 2 % 2)[:rows]
    idx[:, 3] = np.where(np.arange(rows) % 2, N - 1, 0)
    return idx


def values(width: int, seed: int = 7) -> np.ndarray:
    """float32 [width, N + 1], the sink's column 0.0."""
    out = np.random.default_rng(seed).random((width, N + 1)) \
        .astype(np.float32)
    out[:, N] = 0.0
    return out


def want(vals: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """numpy's column sums, float64 [width, Q]: the pad reads 0."""
    return np.pad(vals, ((0, 0), (0, 1)))[:, idx].sum(
        axis=1, dtype=np.float64)


@pytest.mark.parametrize("rows", [8, 1])
@pytest.mark.parametrize("width", WIDTHS)
def test_the_kernel_and_xlas_road_against_numpy(width, rows):
    import jax.numpy as jnp

    vals, idx = values(width), indices(rows, width)
    table = vg.as_table(jnp.asarray(vals if width > 1 else vals[0]))
    assert table.shape == (vg.table_rows(N, width), 128)
    # an entry's values stand side by side, flat row-major
    flat = np.asarray(table).reshape(-1)
    assert np.array_equal(flat[:(N + 1) * width], vals.T.reshape(-1))
    assert not flat[(N + 1) * width:].any()
    got = np.asarray(vg.colsum_vmem(
        jnp.asarray(idx.reshape(-1)), table, interpret=True, rows=rows,
        width=width))
    assert got.shape == ((width, vg.BLOCK) if width > 1 else (vg.BLOCK,))
    got = got.reshape(width, -1)
    exact = want(vals, idx)
    assert np.allclose(got, exact, rtol=1e-6, atol=0)
    # the sink and the pad read an exact 0; one index a column is the
    # value itself
    assert not got[:, :2].any()
    if rows == 1:
        assert np.array_equal(got, exact.astype(np.float32))
    else:
        by_xla = np.asarray(pp._colsum_xla(jnp.asarray(idx.reshape(-1)),
                                           table, width=width))
        assert np.allclose(by_xla.reshape(width, -1), exact, rtol=1e-6,
                           atol=0)
        assert np.allclose(by_xla.reshape(width, -1), got, rtol=1e-6,
                           atol=0)


@pytest.mark.parametrize("rows", [8, 1])
def test_width_1_is_the_parents_program(rows):
    """PageRank, CDLP and every frontier test of the single-source
    family run the kernel at width 1: it traces the jaxpr it traced at
    PR 46 and gives the same bits."""
    import jax
    import jax.numpy as jnp

    idx = jnp.asarray(indices(rows, 1).reshape(-1))
    table = vg.as_table(jnp.asarray(values(1)[0]))
    now, then = (str(jax.make_jaxpr(functools.partial(
        fn, interpret=True, rows=rows))(idx, table))
        for fn in (vg.colsum_vmem, _parents_colsum_vmem))
    assert now == then
    got = vg.colsum_vmem(idx, table, interpret=True, rows=rows)
    old = _parents_colsum_vmem(idx, table, interpret=True, rows=rows)
    assert np.asarray(got).tobytes() == np.asarray(old).tobytes()
    # and the table of one value a vertex is laid as it was
    assert np.array_equal(
        np.asarray(table).reshape(-1)[:N + 1], values(1)[0])


def test_what_prices_the_width(monkeypatch):
    """The backend and ``width`` tables' bytes against the cap, nothing
    else; the widest table is what the cap, the selector's eight rows
    and the values asked for allow."""
    import jax

    n22 = 2_396_390
    assert vg.gather_impl(n22, 4) == "xla"              # tier 1: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [vg.gather_impl(n22, w) for w in WIDTHS] \
        == ["vmem", "vmem", "vmem", "xla"]              # 4 x 9.59 MB
    assert vg.table_rows(n22, 4) * 512 == 38_342_656
    assert vg.table_rows(n22) == 18_722 and vg.table_rows(n22, 4) == 74_888
    for w in WIDTHS:
        edge = vg.VMEM_TABLE_MAX // (4 * w) - 2
        assert vg.gather_impl(edge, w) == "vmem"
        assert vg.gather_impl(edge + 1, w) == "xla"
        assert vg.shared_width(edge, 16) == w
        assert vg.shared_width(edge + 1, 16) == max(w // 2, 1)
    assert [vg.shared_width(n22, most) for most in (1, 2, 3, 4, 5, 16)] \
        == [1, 2, 2, 4, 4, 4]
    assert [vg.shared_width(1 << 12, most) for most in (1, 3, 5, 8, 16)] \
        == [1, 2, 4, 8, 8]
    assert vg.shared_width(1 << 26, 16) == 1            # past the cap alone
    assert list(inspect.signature(vg.gather_impl).parameters) \
        == ["n", "width"]
    assert list(inspect.signature(vg.shared_width).parameters) \
        == ["n", "most"]
    assert "os.environ" not in inspect.getsource(vg)


def _parents_seg_scan(values, flags, combine: str, max_len=None):
    """``ops/segment.seg_scan`` as it stood at PR 46 (word for word)."""
    import jax.numpy as jnp

    from titan_tpu.ops.segment import _COMBINE_FN, combine_identity

    op = _COMBINE_FN[combine]
    ident = combine_identity(combine, values.dtype)
    e = values.shape[0]
    if max_len is not None:
        e = min(e, max_len)
    d = 1
    while d < e:
        pv = jnp.concatenate([jnp.full((d,), ident, values.dtype), values[:-d]])
        pf = jnp.concatenate([jnp.ones((d,), bool), flags[:-d]])
        values = jnp.where(flags, values, op(values, pv))
        flags = flags | pf
        d <<= 1
    return values


@pytest.mark.parametrize("combine", ["sum", "min", "max"])
def test_seg_scan_with_a_leading_axis(combine):
    """Every leading index is scanned as it would be alone, over the
    one ``flags``; one array alone traces the operations it traced at
    PR 46 (LCC's finish, PageRank's and CDLP's sums run it so)."""
    import jax
    import jax.numpy as jnp

    shapes = (jax.ShapeDtypeStruct((700,), jnp.float32),
              jax.ShapeDtypeStruct((700,), jnp.bool_))
    now, then = (str(jax.make_jaxpr(functools.partial(
        fn, combine=combine, max_len=64))(*shapes))
        for fn in (seg_scan, _parents_seg_scan))
    assert now == then

    rng = np.random.default_rng(3)
    e = 700
    vals = rng.random((4, e)).astype(np.float32)
    flags = rng.random(e) < 0.1
    flags[0] = True
    both = np.asarray(seg_scan(jnp.asarray(vals), jnp.asarray(flags),
                               combine, max_len=64))
    cube = np.asarray(seg_scan(jnp.asarray(vals.reshape(2, 2, e)),
                               jnp.asarray(flags), combine, max_len=64))
    for r in range(4):
        alone = np.asarray(seg_scan(jnp.asarray(vals[r]),
                                    jnp.asarray(flags), combine,
                                    max_len=64))
        assert both[r].tobytes() == alone.tobytes()
        assert cube[r // 2, r % 2].tobytes() == alone.tobytes()


@pytest.mark.parametrize("impl", ["xla", "vmem"])
@pytest.mark.parametrize("width", WIDTHS)
def test_pull_sum_over_tables_side_by_side(width, impl, monkeypatch):
    """``pull_sum`` of ``[w, n + 1]``: row r what ``table[r]`` gives
    alone. On XLA's road to the bit (XLA sums a gathered ``[8, Q, w]``
    over its leading axis in the order it sums ``[8, Q]``); under the
    kernel within rounding: at width w a column's lanes stand w apart,
    so two in-neighbours that shared no lane at width 1 may share a lane
    group, and the MXU then sums the column's lanes in another order."""
    import jax.numpy as jnp

    from test_pagerank_pull import kron_with_hub

    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    n, src, dst = kron_with_hub(scale=11, copies=3)
    im = pp.pull_image(snap_mod.from_arrays(n, src, dst))
    tables = np.random.default_rng(11).random((width, n + 1)) \
        .astype(np.float32)
    tables[:, n] = 0.0
    tables[0, : n // 2] = 0.0                 # a masked level
    image = (im["idx"], im["first"], im["last"], im["has"])
    got = np.asarray(pp.pull_sum(jnp.asarray(tables), *image, impl,
                                 im["seg_max"]))
    assert got.shape == (width, n)
    for r in range(width):
        alone = np.asarray(pp.pull_sum(jnp.asarray(tables[r]), *image,
                                       impl, im["seg_max"]))
        exact = np.zeros(n)
        np.add.at(exact, dst, tables[r][src].astype(np.float64))
        assert np.allclose(alone, exact, rtol=1e-5, atol=0)
        if impl == "xla":
            assert got[r].tobytes() == alone.tobytes()
        else:
            assert np.allclose(got[r], alone, rtol=1e-6, atol=0)
            assert np.array_equal(got[r] == 0, alone == 0)
