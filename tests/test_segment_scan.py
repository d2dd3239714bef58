"""The segmented scan of ops/segment.py (``seg_scan``, in three cells'
programs, and ``sorted_segment_combine``, the road ``segment_combine``
takes off the CPU) against numpy's ``reduceat`` over the run starts."""

import jax.numpy as jnp
import numpy as np
import pytest

from titan_tpu.ops.segment import (seg_scan, segment_combine,
                                   segment_metadata,
                                   sorted_segment_combine)

_REDUCEAT = {"sum": np.add, "min": np.minimum, "max": np.maximum}


def _random_segments(e=1000, n=37, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    if np.issubdtype(dtype, np.integer):
        vals = rng.integers(0, 100, e).astype(dtype)
    else:
        vals = rng.uniform(-5, 5, e).astype(dtype)
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr[1:], seg, 1)
    indptr = np.cumsum(indptr)
    return vals, seg, indptr, n


def _scan_by_numpy(vals, flags, combine):
    """The inclusive segmented scan, a segment at a time."""
    starts = np.flatnonzero(flags)
    ends = np.append(starts[1:], len(vals))
    acc = _REDUCEAT[combine].accumulate
    return np.concatenate([acc(vals[a:b]) for a, b in zip(starts, ends)])


@pytest.mark.parametrize("combine", ["sum", "min", "max"])
@pytest.mark.parametrize("e", [128, 256])
def test_scan_matches_numpy(combine, e):
    """Sizes of one and two rows of 128: the last element of every
    segment is ``reduceat``'s answer, every other the running one."""
    vals, seg, _, _ = _random_segments(e=e, n=11, seed=e)
    flags = np.concatenate([[True], seg[1:] != seg[:-1]])
    got = np.asarray(seg_scan(jnp.asarray(vals), jnp.asarray(flags),
                              combine))
    np.testing.assert_allclose(got, _scan_by_numpy(vals, flags, combine),
                               rtol=1e-5, atol=1e-5)
    starts = np.flatnonzero(flags)
    lasts = np.append(starts[1:], e) - 1
    np.testing.assert_allclose(
        got[lasts], _REDUCEAT[combine].reduceat(vals, starts),
        rtol=1e-5, atol=1e-5)


def test_scan_carry_across_many_blocks():
    # one giant segment spanning every row of 128: pure carry chain
    e = 1024
    vals = np.ones(e, np.float32)
    flags = np.zeros(e, bool)
    flags[0] = True
    got = np.asarray(seg_scan(jnp.asarray(vals), jnp.asarray(flags),
                              "sum"))
    np.testing.assert_allclose(got, np.arange(1, e + 1, dtype=np.float32))


def _combine_by_numpy(vals, indptr, combine, ident):
    """A value a segment, ``ident`` for an empty one."""
    has = indptr[1:] > indptr[:-1]
    out = np.full(len(has), ident, vals.dtype)
    out[has] = _REDUCEAT[combine].reduceat(vals, indptr[:-1][has])
    return out


@pytest.mark.parametrize("combine,ident", [("sum", 0.0), ("min", np.inf)])
def test_segment_combine_matches_numpy(combine, ident):
    vals, seg, indptr, n = _random_segments(e=900, n=53, seed=3)
    last_idx, seg_has = segment_metadata(indptr)
    got = np.asarray(sorted_segment_combine(
        jnp.asarray(vals), jnp.asarray(seg), jnp.asarray(last_idx),
        jnp.asarray(seg_has), combine))
    np.testing.assert_allclose(
        got, _combine_by_numpy(vals, indptr, combine, ident),
        rtol=1e-5, atol=1e-5)


def test_int32_min_identity():
    vals = np.array([5, 3, 9, 2], np.int32)
    flags = np.array([True, False, True, False])
    got = np.asarray(seg_scan(jnp.asarray(vals), jnp.asarray(flags),
                              "min"))
    np.testing.assert_array_equal(got, [5, 3, 9, 2])


def test_segment_combine_ignores_the_old_variable(monkeypatch):
    """``TITAN_TPU_SEGMENT_KERNEL`` chose the road until PR 45; a value
    it used to refuse is now not read at all."""
    monkeypatch.setenv("TITAN_TPU_SEGMENT_KERNEL", "no-such-kernel")
    vals, seg, indptr, n = _random_segments(e=300, n=17, seed=5)
    last_idx, seg_has = segment_metadata(indptr)
    got = np.asarray(segment_combine(
        jnp.asarray(vals), jnp.asarray(seg), n, "sum",
        last_idx=jnp.asarray(last_idx), seg_has=jnp.asarray(seg_has)))
    np.testing.assert_allclose(
        got, _combine_by_numpy(vals, indptr, "sum", 0.0),
        rtol=1e-5, atol=1e-5)
