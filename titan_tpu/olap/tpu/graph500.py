"""Graph500 benchmark-graph pipeline: generate, build, cache, upload.

Reading a multi-gigabyte image back from the device is the transfer to
avoid, so the benchmark graph is generated and CSR-built on the HOST (native C++:
``tt_rmat_gen`` + ``tt_sym_chunked_csr``), cached on disk, and uploaded
once per process; the BFS then reads back only scalar stats. At scale 26
the symmetrized graph is exactly 2^31 directed edges — one over the int32
limit — so the builder dedups per-vertex adjacency (and drops self-loops),
which is standard Graph500 practice; TEPS accounting still uses the
PRE-dedup degrees (``deg_orig``), per the official TEPS definition
(counts every input edge tuple incl. multiples and self-loops).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".bench_cache")


def load_or_build(scale: int, edge_factor: int = 16, seed: int = 2,
                  cache_dir: str | None = None, verbose: bool = True
                  ) -> dict:
    """Host-side chunked Graph500 CSR, disk-cached.

    Returns numpy dict: ``dstT`` int32 [8, Q] (transposed 8-aligned
    chunked CSR, pad = n+1), ``colstart`` int32 [n+1], ``deg`` int32 [n]
    (post-dedup), ``deg_orig`` int32 [n], plus ``n``, ``q_total``,
    ``m_input`` (generated directed edge count before symmetrization).
    """
    from titan_tpu import native

    cache_dir = cache_dir or DEFAULT_CACHE
    tag = f"g500_s{scale}_ef{edge_factor}_seed{seed}"
    meta_path = os.path.join(cache_dir, tag + ".json")
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        # the native and numpy generators produce DIFFERENT edge sets for
        # the same (scale, ef, seed); a numpy-built cache is upgraded once
        # the native module appears so benchmark identity stays stable
        if not (native.available
                and meta.get("generator", "native") == "numpy"):
            out = {k: np.load(os.path.join(cache_dir, f"{tag}_{k}.npy"),
                              mmap_mode="r")
                   for k in ("dstT", "colstart", "deg", "deg_orig")}
            out.update(meta)
            return out

    n = 1 << scale
    m = n * edge_factor
    t0 = time.time()
    if native.available:
        src, dst = native.rmat_gen(m, scale, seed=seed)
        t1 = time.time()
        flat, colstart64, deg, deg_orig = native.sym_chunked_csr(src, dst,
                                                                 n)
        del src, dst
    else:
        # pure-numpy fallback (no C++ toolchain): fine for CI scales,
        # far too slow for scale 26
        from titan_tpu.olap.tpu.rmat import rmat_edges
        src, dst = rmat_edges(scale, edge_factor, seed=seed)
        t1 = time.time()
        flat, colstart64, deg, deg_orig = _sym_chunked_csr_numpy(src, dst,
                                                                 n)
        del src, dst
    t2 = time.time()
    q_total = flat.shape[0]
    # the kernels index COLUMNS (q_total) and vertices only — never flat
    # slot positions — so int32 safety needs q_total < 2^31, not slots;
    # scale-26 has ~2.26B slots but only ~282M columns
    if q_total >= (1 << 31):
        raise NotImplementedError(
            f"chunked CSR has {q_total} columns >= 2^31; needs sharding")
    dstT = np.ascontiguousarray(flat.T)
    del flat
    colstart = colstart64.astype(np.int32)
    t3 = time.time()
    if verbose:
        print(f"graph500 s{scale}: gen {t1-t0:.1f}s build {t2-t1:.1f}s "
              f"transpose {t3-t2:.1f}s  q_total={q_total} "
              f"dedup_edges={int(colstart64[-1])*8 - int(((8 - deg % 8) % 8).sum())}")
    meta = {"n": n, "q_total": int(q_total), "m_input": m,
            "generator": "native" if native.available else "numpy",
            "scale": scale, "edge_factor": edge_factor, "seed": seed,
            "e_dedup": int(deg.sum(dtype=np.int64)),
            "e_sym": int(deg_orig.sum(dtype=np.int64))}
    os.makedirs(cache_dir, exist_ok=True)
    for k, v in (("dstT", dstT), ("colstart", colstart), ("deg", deg),
                 ("deg_orig", deg_orig)):
        np.save(os.path.join(cache_dir, f"{tag}_{k}.npy"), v)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    out = {"dstT": dstT, "colstart": colstart, "deg": deg,
           "deg_orig": deg_orig}
    out.update(meta)
    return out


def _sym_chunked_csr_numpy(src, dst, n: int):
    """Numpy mirror of native.sym_chunked_csr (symmetrize, per-vertex
    sort-dedup incl. self-loop drop, 8-aligned chunk layout)."""
    v = np.concatenate([src, dst]).astype(np.int64)
    w = np.concatenate([dst, src]).astype(np.int64)
    deg_orig = np.bincount(v, minlength=n).astype(np.int32)
    packed = np.unique(v * (n + 1) + w)
    pv = (packed // (n + 1)).astype(np.int64)
    pw = (packed % (n + 1)).astype(np.int64)
    keep = pv != pw
    pv, pw = pv[keep], pw[keep]
    deg = np.bincount(pv, minlength=n).astype(np.int32)
    degc = -(-deg.astype(np.int64) // 8)
    colstart64 = np.zeros(n + 1, np.int64)
    np.cumsum(degc, out=colstart64[1:])
    q_total = int(colstart64[-1]) + 1
    flat = np.full(q_total * 8, n + 1, np.int32)
    starts8 = colstart64[:n] * 8
    pos = np.repeat(starts8 - np.concatenate(
        [[0], np.cumsum(deg.astype(np.int64))])[:n], deg) \
        + np.arange(len(pw), dtype=np.int64)
    flat[pos] = pw
    return flat.reshape(q_total, 8), colstart64, deg, deg_orig


def pipelined_upload(arr, chunk_cols: int = 1 << 24):
    """Host->HBM upload of a [8, Q] (or any 2D) array in column chunks,
    overlapping disk/memory page-in with the transfer (SURVEY 2.7 PP row:
    DataPuller->Processor pipelining, restructured as async H2D).

    jnp.asarray of a 9GB memmap serializes page-in with the copy
    (~0.4 GB/s observed); chunked dispatch lets jax's async transfers
    overlap the next chunk's page-in. Each chunk lands in a donated
    device buffer via dynamic_update_slice, so peak device memory is
    size + one chunk."""
    import functools

    import jax
    import jax.numpy as jnp

    rows, cols = arr.shape
    if cols <= chunk_cols:
        return jnp.asarray(np.asarray(arr))

    # `at` is a traced operand (NOT static): one compile serves every
    # chunk — a static index would recompile per chunk, minutes of
    # compile time for a 9GB upload
    @functools.partial(jax.jit, donate_argnums=(0,))
    def place(buf, chunk, at):
        return jax.lax.dynamic_update_slice(
            buf, chunk, (jnp.int32(0), at))

    buf = jnp.zeros((rows, cols), arr.dtype)
    for c0 in range(0, cols, chunk_cols):
        if c0 + chunk_cols > cols:
            # final short chunk: shift the window back so the shape stays
            # static; the overlap rewrites identical real data (padding
            # with zeros instead would clobber the previous chunk's tail)
            c0 = cols - chunk_cols
        chunk = np.ascontiguousarray(arr[:, c0:c0 + chunk_cols])
        buf = place(buf, jnp.asarray(chunk), jnp.int32(c0))
    return buf


def to_device(host_graph: dict) -> dict:
    """Upload a ``load_or_build`` result as a hybrid-BFS device graph
    (the dict form ``frontier_bfs_hybrid`` accepts)."""
    import jax.numpy as jnp

    n = host_graph["n"]
    deg = np.asarray(host_graph["deg"])
    degc = -(-deg // 8)
    return {
        "dstT": pipelined_upload(host_graph["dstT"]),
        "colstart": jnp.asarray(np.asarray(host_graph["colstart"])),
        "degc": jnp.asarray(
            np.concatenate([degc, [0]]).astype(np.int32)),
        "deg": jnp.asarray(
            np.concatenate([deg, [0]]).astype(np.int32)),
        "q_total": host_graph["q_total"],
        "n": n,
    }


def device_degrees(deg_orig: np.ndarray, chunk: int = 4096):
    """Upload (once) the pre-dedup degrees padded to a chunk multiple,
    for reachable_edge_sum."""
    import jax.numpy as jnp

    pad = (-len(deg_orig)) % chunk
    return jnp.asarray(np.concatenate(
        [np.asarray(deg_orig, np.int32), np.zeros(pad, np.int32)]))


def _parts_fn():
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n_", "inf", "chunk"))
    def parts(dist, deg_pad, n_: int, inf: int, chunk: int):
        reach = dist[:n_] < inf
        pad = (-n_) % chunk
        rp = jnp.concatenate(
            [reach, jnp.zeros((pad,), bool)]).reshape(-1, chunk)
        dp = deg_pad.reshape(-1, chunk)
        psums = jnp.where(rp, dp, 0).sum(axis=1, dtype=jnp.int32)
        return psums, reach.sum(dtype=jnp.int32)
    return parts


def reachable_edge_sum(dist_dev, deg_orig, inf: int,
                       chunk: int = 4096, deg_dev=None) -> tuple[int, int]:
    """Graph500 TEPS numerator on device: sum of PRE-dedup degrees over
    reachable vertices (and the reachable count). The total exceeds int32
    and x64 is disabled, so the device produces per-chunk int32 partial
    sums (each < 2^31) and the host adds them exactly. Pass ``deg_dev``
    (from device_degrees) to amortize the upload across calls."""
    from titan_tpu.utils.jitcache import jit_once
    parts = jit_once("graph500_reachable_parts", _parts_fn)
    n = len(deg_orig)
    if deg_dev is None:
        deg_dev = device_degrees(deg_orig, chunk)
    psums, nreach = parts(dist_dev, deg_dev, n_=n, inf=inf, chunk=chunk)
    return int(np.asarray(psums, dtype=np.int64).sum()), int(nreach)
