"""Does random-gather rate depend on TABLE size at the 100MB+ scale?

Round-2 notes measured ~112M elem/s with tables up to 8M entries (32MB).
The scale-26 BU hit test gathers 268M elements from a 268MB table and
runs ~2x slower per element than that rate predicts. Hypothesis: big
tables are HBM-latency-bound; a bitmap (8.4MB) restores the fast regime.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp

    from titan_tpu.utils.jitcache import enable_compile_cache
    enable_compile_cache()

    E = 1 << 27                        # 134M gathers per trial
    rng = np.random.default_rng(0)

    @jax.jit
    def g_direct(tab, idx):
        return (jnp.take(tab, idx) == 3).sum()

    @jax.jit
    def g_bitmap(bits, idx):
        w = jnp.take(bits, idx >> 5)
        return ((w >> (idx & 31)) & 1).sum()

    idx_host = rng.integers(0, 1 << 26, E, dtype=np.int32)

    for logn in (21, 23, 26):          # 8MB, 32MB, 268MB tables
        n = 1 << logn
        tab = jnp.zeros((n,), jnp.int32)
        idx = jnp.asarray(idx_host % n)
        r = g_direct(tab, idx); _ = np.asarray(r)       # warm
        t0 = time.time()
        for _ in range(2):
            r = g_direct(tab, idx)
        _ = np.asarray(r)
        dt = (time.time() - t0) / 2
        print(f"direct gather, table 2^{logn} ({4*n>>20}MB): "
              f"{dt:.3f}s = {E/dt/1e6:.0f}M/s", flush=True)

    for logn in (26,):                 # bitmap for a 2^26 vertex set
        n = 1 << logn
        bits = jnp.zeros((n >> 5,), jnp.uint32)
        idx = jnp.asarray(idx_host % n)
        r = g_bitmap(bits, idx); _ = np.asarray(r)
        t0 = time.time()
        for _ in range(2):
            r = g_bitmap(bits, idx)
        _ = np.asarray(r)
        dt = (time.time() - t0) / 2
        print(f"bitmap gather, 2^{logn} bits ({n>>23}MB words): "
              f"{dt:.3f}s = {E/dt/1e6:.0f}M/s", flush=True)


main()
