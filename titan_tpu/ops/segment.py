"""Segment-reduction kernels — the SpMV primitive of the OLAP engine.

Two implementations of "combine per-edge messages by destination";
``segment_combine`` chooses from what it can observe (see PERF_NOTES.md
for the full measurement story — beware XLA constant-folding
jit-captured inputs; only argument-passed benchmarks are real):

* the sorted scan, where segment metadata is given and the backend is
  not the CPU: sorted-segment Hillis-Steele scan + static last-index
  gather. At real scale (268M edges, v5e, readback-synced): scan 330ms +
  last-gather 270ms vs 3 275ms for the scatter path — ~5× faster.
* ``jax.ops.segment_*`` scatter otherwise — XLA's TPU scatter lowering
  runs at a flat ~100M elem/s, but it is the right path on CPU and for
  unsorted segments.
"""

from __future__ import annotations

import functools
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
    """Inclusive segmented scan (Hillis-Steele) along the LAST axis:
    ``flags[i]`` marks the first element of a segment; returns
    per-position running combine within the segment. ``values`` [e], or
    [..., e] for several arrays over the one ``flags`` [e] (each leading
    index is scanned as it would be alone). log₂(E) vectorized passes;
    everything static-shaped. ``max_len``: the longest segment, where
    the caller knows it — the passes stop at log₂ of it."""
    op = _COMBINE_FN[combine]
    ident = combine_identity(combine, values.dtype)
    lead, e = values.shape[:-1], values.shape[-1]
    if max_len is not None:
        e = min(e, max_len)
    d = 1
    while d < e:
        pv = jnp.concatenate([jnp.full(lead + (d,), ident, values.dtype),
                              values[..., :-d]], axis=-1)
        pf = jnp.concatenate([jnp.ones((d,), bool), flags[:-d]])
        values = jnp.where(flags, values, op(values, pv))
        flags = flags | pf
        d <<= 1
    return values


#: elements a row of the two-level scans below: a vector register's lanes
_ROW = 128


def _back(x, d: int, fill, axis: int):
    """``x`` moved ``d`` places on along ``axis``, ``fill`` behind it."""
    pad = list(x.shape)
    pad[axis] = d
    kept = jax.lax.slice_in_dim(x, 0, x.shape[axis] - d, axis=axis)
    return jnp.concatenate([jnp.full(pad, fill, x.dtype), kept], axis=axis)


def running_max(x):
    """Inclusive running maximum of ``x`` (integers, none below 0):
    Hillis-Steele passes, a long array on two levels (inside rows of 128,
    then over the rows' last elements), which costs 7 passes over it.
    ``lax.cummax`` ran three times as long over 1.4e8 elements, and the
    chip's compiler takes 40 s to build one of any size (PERF.md 6, PR
    40)."""
    e = x.shape[0]
    two_level = e % _ROW == 0 and e > _ROW
    rows = x.reshape(e // _ROW, _ROW) if two_level else x[None, :]
    d = 1
    while d < rows.shape[1]:
        rows = jnp.maximum(rows, _back(rows, d, 0, 1))
        d <<= 1
    if two_level:
        carried = _back(running_max(rows[:, -1]), 1, 0, 0)
        rows = jnp.maximum(rows, carried[:, None])
    return rows.reshape(e)


def _first_max_passes(score, payload, flags, axis: int, length: int):
    """Hillis-Steele passes of the segmented first-max along ``axis``, as
    many as a segment of ``length`` elements needs. Returns (score,
    payload, flags): ``flags`` then says whether a segment began at or
    before the position, as far back as the passes looked."""
    ident = combine_identity("max", score.dtype)
    back = functools.partial(_back, axis=axis)
    d = 1
    while d < min(score.shape[axis], length):
        ps, pp = back(score, d, ident), back(payload, d, 0)
        take = ~flags & (ps >= score)
        score = jnp.where(take, ps, score)
        payload = jnp.where(take, pp, payload)
        flags = flags | back(flags, d, False)
        d <<= 1
    return score, payload, flags


def seg_first_max(score, payload, flags, max_len: Optional[int] = None):
    """Inclusive segmented scan of the FIRST largest ``score`` so far in
    the segment, with the ``payload`` that stands beside it (a tie keeps
    the earlier element): the segmented arg-max, ``seg_scan``'s passes
    over a pair. Returns (score, payload) per position; a segment's
    answer stands at its last element. ``max_len``: the longest segment,
    where the caller knows it.

    A long array is scanned on two levels, which costs 7 passes over it
    and not log2(``max_len``): inside rows of 128 elements; then over the
    rows' last elements (a row in which a segment begins hands nothing
    of the rows before it on); then every element before its row's
    first segment start takes what the rows before carried in."""
    e = score.shape[0]
    length = e if max_len is None else min(e, max_len)
    if e % _ROW or e <= _ROW:
        return _first_max_passes(score, payload, flags, 0, length)[:2]
    rows = e // _ROW
    s, p, f = _first_max_passes(
        score.reshape(rows, _ROW), payload.reshape(rows, _ROW),
        flags.reshape(rows, _ROW), 1, length)
    cs, cp, _cf = _first_max_passes(s[:, -1], p[:, -1], f[:, -1], 0,
                                    -(-length // _ROW) + 1)
    cs = _back(cs, 1, combine_identity("max", score.dtype), 0)
    cp = _back(cp, 1, 0, 0)
    take = ~f & (cs[:, None] >= s)
    return (jnp.where(take, cs[:, None], s).reshape(e),
            jnp.where(take, cp[:, None], p).reshape(e))


def mode_vote(owner, label, pad, max_len: Optional[int] = None):
    """The most-frequent-label vote, the one combiner here that is no
    semiring: a multiset's mode cannot be folded element by element, so
    the caller hands the (owner, label) pairs SORTED by owner, then
    label, and equal labels stand in runs. A run's length is known at
    its last element (its position less the run's first, carried there
    by ``running_max``); ``seg_first_max`` over an owner's runs keeps the
    longest, and of equally long runs the first, which is the smallest
    label. Elements labelled ``pad`` do not vote. Returns int32 per
    position: at an owner's LAST element, the smallest of its most
    frequent labels (``pad`` where nothing voted)."""
    first = owner != _back(owner, 1, -1, 0)
    start = first | (label != _back(label, 1, -1, 0))
    pos = jnp.arange(label.shape[0], dtype=jnp.int32)
    run0 = running_max(jnp.where(start, pos, 0))
    ends = jnp.concatenate([start[1:], jnp.ones((1,), bool)])
    count = jnp.where(ends & (label != pad), pos - run0 + 1, 0)
    _score, best = seg_first_max(count, label, first, max_len=max_len)
    return best


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
    if last_idx is not None and seg_has is not None \
            and jax.default_backend() != "cpu":
        return sorted_segment_combine(values, segment_ids, last_idx, seg_has,
                                      combine)
    try:
        op = _OPS[combine]
    except KeyError:
        raise ValueError(f"unknown combine {combine!r}") from None
    return op(values, segment_ids, num_segments=num_segments,
              indices_are_sorted=indices_are_sorted)
