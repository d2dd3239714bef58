"""R1/R2 negative fixture: a pallas kernel whose Python control flow
runs on keyword-only compile-time constants. ``pallas_call`` passes
only the refs, positionally, so the seam must classify kwonly params
(bound through ``functools.partial``) as static — the ``while d <
block`` unroll ladder idiom. Never imported."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ok_kernel(v_ref, o_ref, *, block, masked):
    v = v_ref[...]
    d = 1
    while d < block:                  # static unroll ladder — legal
        v = v + jnp.pad(v[:, :-d], ((0, 0), (d, 0)))
        d <<= 1
    if masked:                        # static config branch — legal
        v = v * 2
    if v_ref.shape[0] > 1:            # static shape metadata — legal
        v = v + 1
    o_ref[...] = v


def run(x):
    kern = functools.partial(_ok_kernel, block=128, masked=False)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x)
