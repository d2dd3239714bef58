"""Probe Mosaic capabilities/speeds for dynamic gather/scatter on TPU.

Run:  python experiments/probe_pallas_gather.py

All timed functions reduce to ONE scalar on device so the forced D2H sync
moves 4 bytes, not the result array.
"""
from __future__ import annotations

import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

R = 4096          # gather table rows (tab = R x 128 int32 = 2 MB VMEM)
CHUNK = 1024      # idx rows per grid step
STEPS = 512       # grid steps
M = CHUNK * STEPS * 128   # total gathered elements (67M)


def timed(fn, *args, reps=3):
    out = fn(*args)
    np.asarray(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        np.asarray(fn(*args))
        best = min(best, time.time() - t0)
    return best


def call(kernel, out_shape, nin, tab_spec=False):
    in_specs = []
    if tab_spec:
        in_specs.append(pl.BlockSpec((R, 128), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
    for _ in range(nin - (1 if tab_spec else 0)):
        in_specs.append(pl.BlockSpec((CHUNK, 128), lambda i: (i, 0),
                                     memory_space=pltpu.VMEM))
    return lambda *a: pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(STEPS,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((CHUNK, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
    )(*a)


# ---------------------------------------------------------------- 0: stream
def copy_kernel(idx_ref, out_ref):
    out_ref[:] = idx_ref[:]


@jax.jit
def stream_copy(idx):
    out = call(copy_kernel,
               jax.ShapeDtypeStruct((CHUNK * STEPS, 128), jnp.int32), 1)(idx)
    return out[::CHUNK * 8].sum()


# ---------------------------------------------------------------- 1: gather
def gather_kernel(tab_ref, idx_ref, out_ref):
    out_ref[:] = jnp.take_along_axis(tab_ref[:], idx_ref[:], axis=0)


@jax.jit
def lane_gather(tab, idx):
    out = call(gather_kernel,
               jax.ShapeDtypeStruct((CHUNK * STEPS, 128), jnp.int32), 2,
               tab_spec=True)(tab, idx)
    return out[::CHUNK * 8].sum()


def lane_gather_check(tab, idx):
    return call(gather_kernel,
                jax.ShapeDtypeStruct((CHUNK * STEPS, 128), jnp.int32), 2,
                tab_spec=True)(tab, idx)


# ---------------------------------------------------------------- 2: shuffle
def shuffle_kernel(v_ref, idx_ref, out_ref):
    out_ref[:] = jnp.take_along_axis(v_ref[:], idx_ref[:], axis=1)


@jax.jit
def lane_shuffle(v, idx):
    out = call(shuffle_kernel,
               jax.ShapeDtypeStruct((CHUNK * STEPS, 128), jnp.int32), 2)(
                   v, idx)
    return out[::CHUNK * 8].sum()


# ------------------------------------------------------- 3: scatter variants
def make_scatter_kernel(mode):
    def scatter_kernel(idx_ref, val_ref, acc_ref, out_ref):
        del out_ref
        lanes = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, 128), 1)
        if mode == "set":
            acc_ref[idx_ref[:], lanes] = val_ref[:]
        elif mode == "at_set":
            acc_ref[:] = acc_ref[:].at[idx_ref[:], lanes].set(val_ref[:])
        elif mode == "at_max":
            acc_ref[:] = acc_ref[:].at[idx_ref[:], lanes].max(val_ref[:])
        elif mode == "at_add":
            acc_ref[:] = acc_ref[:].at[idx_ref[:], lanes].add(val_ref[:])
    return scatter_kernel


def lane_scatter(mode):
    @jax.jit
    def f(idx, val):
        out = pl.pallas_call(
            make_scatter_kernel(mode),
            out_shape=jax.ShapeDtypeStruct((CHUNK, 128), jnp.int32),
            grid=(STEPS,),
            in_specs=[
                pl.BlockSpec((CHUNK, 128), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((CHUNK, 128), lambda i: (i, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((CHUNK, 128), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.VMEM((R, 128), jnp.int32)],
        )(idx, val)
        return out[::8].sum()
    return f


def main():
    print("devices:", jax.devices())
    rng = np.random.default_rng(0)
    tab = jnp.asarray(rng.integers(0, 1 << 20, (R, 128), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, R, (CHUNK * STEPS, 128),
                                   dtype=np.int32))
    sidx = jnp.asarray(rng.integers(0, 128, (CHUNK * STEPS, 128),
                                    dtype=np.int32))
    val = jnp.asarray(rng.integers(0, 100, (CHUNK * STEPS, 128),
                                   dtype=np.int32))

    t = timed(stream_copy, idx)
    print(f"0 stream copy:    {t*1e3:8.1f} ms  {M/t/1e9:8.2f} G elem/s")

    try:
        t = timed(lane_gather, tab, idx)
        out = np.asarray(lane_gather_check(tab, idx)[:2048])
        ref = np.asarray(tab)[np.asarray(idx[:2048]),
                              np.arange(128)[None, :]]
        ok = np.array_equal(out, ref)
        print(f"1 lane gather:    {t*1e3:8.1f} ms  {M/t/1e9:8.2f} G elem/s"
              f"  correct={ok}")
    except Exception:  # noqa: BLE001
        print("1 lane gather FAILED:")
        traceback.print_exc(limit=2)

    try:
        t = timed(lane_shuffle, val, sidx)
        print(f"2 lane shuffle:   {t*1e3:8.1f} ms  {M/t/1e9:8.2f} G elem/s")
    except Exception:  # noqa: BLE001
        print("2 lane shuffle FAILED:")
        traceback.print_exc(limit=2)

    for mode in ("set", "at_set", "at_max", "at_add"):
        try:
            t = timed(lane_scatter(mode), idx, val)
            print(f"3 scatter {mode:7s}{t*1e3:8.1f} ms  "
                  f"{M/t/1e9:8.2f} G elem/s")
        except Exception as e:  # noqa: BLE001
            print(f"3 scatter {mode} FAILED: {str(e)[:200]}")


if __name__ == "__main__":
    main()
