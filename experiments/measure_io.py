"""Measure host↔device H2D/D2H bandwidth + native csr_build rate (sizing the
scale-26 bench pipeline)."""
import time

import numpy as np
import jax
import jax.numpy as jnp

print("devices:", jax.devices())

# H2D bandwidth: 1GB int32
x = np.arange(1 << 28, dtype=np.int32)
t0 = time.time()
d = jnp.asarray(x)
d.block_until_ready()
t1 = time.time()
print(f"H2D 1GB: {t1-t0:.2f}s = {1.0/(t1-t0):.2f} GB/s")

# D2H bandwidth
t0 = time.time()
y = np.asarray(d)
t1 = time.time()
print(f"D2H 1GB: {t1-t0:.2f}s = {1.0/(t1-t0):.2f} GB/s")
del d, y

# native csr_build rate at 268M edges
from titan_tpu import native
print("native available:", native.available)
rng = np.random.default_rng(0)
E = 1 << 28
n = 1 << 23
src = rng.integers(0, n, E, dtype=np.int32)
dst = rng.integers(0, n, E, dtype=np.int32)
t0 = time.time()
order, indptr, out_degree = native.csr_build(src, dst, n)
t1 = time.time()
print(f"csr_build E=268M: {t1-t0:.2f}s = {E/(t1-t0)/1e6:.0f}M edges/s")
t0 = time.time()
s2 = native.gather_i32(src, order)
t1 = time.time()
print(f"gather_i32 E=268M: {t1-t0:.2f}s")
