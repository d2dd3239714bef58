"""Probe: cost of 4-row vs 8-row column gathers on the chunked CSR, and
whether an XLA slice of the big dstT fuses into the gather or
materializes a copy. Decides the split-lane bitmap-test design
(PERF_NOTES r4 follow-up).

Run from repo root: python experiments/lane_split_probe.py [scale]
"""
import sys
import time

import numpy as np


def bench(fn, *args, reps=3):
    import jax
    fn(*args)[0].block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        out = fn(*args)
        _ = np.asarray(out[0][:1])          # force completion (D2H)
        best = min(best, time.time() - t0)
    return best


def main(scale=23):
    import jax
    import jax.numpy as jnp

    from titan_tpu.olap.tpu import graph500

    hg = graph500.load_or_build(scale, 16, seed=2, verbose=False)
    dstT_h = hg["dstT"]
    q = dstT_h.shape[1]
    dstT = jnp.asarray(dstT_h)
    lo = jnp.asarray(dstT_h[:4])
    m = 1 << 22                           # 4.2M column fetches
    rng = np.random.default_rng(0)
    cols = jnp.asarray(rng.integers(0, q, m).astype(np.int32))

    @jax.jit
    def take8(dstT, cols):
        return (jnp.take(dstT, cols, axis=1).sum(axis=0),)

    @jax.jit
    def take4_slice(dstT, cols):
        return (jnp.take(dstT[:4], cols, axis=1).sum(axis=0),)

    @jax.jit
    def take4_sep(lo, cols):
        return (jnp.take(lo, cols, axis=1).sum(axis=0),)

    t8 = bench(take8, dstT, cols)
    t4s = bench(take4_slice, dstT, cols)
    t4p = bench(take4_sep, lo, cols)
    print(f"cols={m}: take8 {t8:.3f}s  take4(slice of dstT) {t4s:.3f}s  "
          f"take4(separate lo array) {t4p:.3f}s", flush=True)

    # bitmap test rate at [4, m] vs [8, m] for the same parents
    from titan_tpu.models.bfs_hybrid import _bit_of
    nbytes = (1 << scale) // 8 + 2
    fbits = jnp.asarray(rng.integers(0, 255, nbytes).astype(np.uint8))

    @jax.jit
    def test8(fbits, dstT, cols):
        p = jnp.take(dstT, cols, axis=1)
        return (_bit_of(fbits, jnp.clip(p, 0, nbytes * 8 - 9))
                .any(axis=0),)

    @jax.jit
    def test4(fbits, lo, cols):
        p = jnp.take(lo, cols, axis=1)
        return (_bit_of(fbits, jnp.clip(p, 0, nbytes * 8 - 9))
                .any(axis=0),)

    tt8 = bench(test8, fbits, dstT, cols)
    tt4 = bench(test4, fbits, lo, cols)
    print(f"fetch+test8 {tt8:.3f}s  fetch+test4 {tt4:.3f}s", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 23)
