"""Segment-reduction kernels — the SpMV primitive of the OLAP engine.

Three implementations of "combine per-edge messages by destination",
selected by ``TITAN_TPU_SEGMENT_KERNEL`` (see PERF_NOTES.md for the full
measurement story — beware XLA constant-folding jit-captured inputs;
only argument-passed benchmarks are real):

* ``scan`` (DEFAULT on non-CPU backends when segment metadata is present):
  sorted-segment Hillis-Steele scan + static last-index gather. At real
  scale (268M edges, v5e, readback-synced): scan 330ms + last-gather 270ms
  vs 3 275ms for the scatter path — ~5× faster.
* ``native`` (and the CPU default): ``jax.ops.segment_*`` scatter — XLA's
  TPU scatter lowering runs at a flat ~100M elem/s, but it is the right
  path on CPU and for unsorted segments.
* ``pallas`` (opt-in): one-pass streamed scan (ops/pallas_segment.py),
  currently lane-shift-bound, ~par with the XLA scan; retained as the
  kernel substrate for future tuning.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_OPS = {
    "sum": jax.ops.segment_sum,
    "min": jax.ops.segment_min,
    "max": jax.ops.segment_max,
}

_COMBINE_FN = {
    "sum": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
}


def combine_identity(combine: str, dtype):
    if combine == "sum":
        return jnp.zeros((), dtype=dtype)
    if combine == "min":
        return jnp.array(jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
                         else jnp.inf, dtype=dtype)
    if combine == "max":
        return jnp.array(jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                         else -jnp.inf, dtype=dtype)
    raise ValueError(f"unknown combine {combine!r}")


def segment_metadata(indptr: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Static per-segment scan metadata from a CSR indptr: the index of each
    segment's LAST edge and whether the segment is non-empty."""
    indptr = np.asarray(indptr, dtype=np.int64)
    last_idx = (indptr[1:] - 1).astype(np.int32)
    seg_has = indptr[1:] > indptr[:-1]
    return last_idx, seg_has


def seg_scan(values, flags, combine: str, max_len: Optional[int] = None):
    """Inclusive segmented scan (Hillis-Steele): ``flags[i]`` marks the first
    element of a segment; returns per-position running combine within the
    segment. log₂(E) vectorized passes; everything static-shaped.
    ``max_len``: the longest segment, where the caller knows it — the
    passes stop at log₂ of it."""
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


def sorted_segment_combine(values, seg_ids, last_idx, seg_has, combine: str):
    """Scan-based segment combine for dst-sorted edges with static metadata."""
    flags = jnp.concatenate([jnp.ones((1,), bool), seg_ids[1:] != seg_ids[:-1]])
    r = seg_scan(values, flags, combine)
    ident = combine_identity(combine, values.dtype)
    out = r[jnp.maximum(last_idx, 0)]
    return jnp.where(seg_has, out, ident)


def segment_combine(values, segment_ids, num_segments: int, combine: str,
                    indices_are_sorted: bool = True,
                    last_idx=None, seg_has=None):
    import os
    kernel = os.environ.get("TITAN_TPU_SEGMENT_KERNEL", "scan")
    if kernel not in ("scan", "native", "pallas"):
        raise ValueError(
            f"TITAN_TPU_SEGMENT_KERNEL={kernel!r}: expected scan|native|pallas")
    has_meta = last_idx is not None and seg_has is not None
    if has_meta and kernel == "pallas" and jax.default_backend() == "tpu":
        from titan_tpu.ops.pallas_segment import \
            pallas_sorted_segment_combine
        return pallas_sorted_segment_combine(
            values, segment_ids, last_idx, seg_has, combine)
    if has_meta and kernel == "scan" and jax.default_backend() != "cpu":
        return sorted_segment_combine(values, segment_ids, last_idx, seg_has,
                                      combine)
    try:
        op = _OPS[combine]
    except KeyError:
        raise ValueError(f"unknown combine {combine!r}") from None
    return op(values, segment_ids, num_segments=num_segments,
              indices_are_sorted=indices_are_sorted)
