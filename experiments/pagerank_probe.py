"""Chip probe (PR 33, PR 35): what a PageRank job costs and how close
float32 comes, on the benchmark's ``graphalytics-g500-22``.

    python experiments/pagerank_probe.py [--scale 22] [--iterations 10]

Builds the served image as ``benchmark/run.py`` does and the pull image
the first job of a snapshot makes, then **the gather probe** (PR 35),
medians of 5 on the cell's own in-edges: XLA's gather over the pull
image, the Pallas kernel with the table in VMEM (their rates in lanes a
second, and the largest difference between the two), the segment sum
and the whole ``pagerank_pull`` program. Then ``frontier.pagerank_dense``
three times (the first builds or loads its executables) with the
host's dispatch time and the whole time to the rank on the host apart,
and the rank held against the benchmark's float64 reference: the
largest relative error, the vertices outside Graphalytics' epsilon
(1e-4), and the worst by degree band.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def gather_probe(im: dict, n: int) -> dict:
    """Medians of 5, on the image's own indices: XLA's gather and the
    kernel (lanes a second), the segment sum, the whole pull."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from titan_tpu.models import pagerank_pull as pp
    from titan_tpu.ops import vmem_gather as vg
    from titan_tpu.ops.segment import seg_scan

    def median_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))

    rows = vg.table_rows(n)
    flat = np.zeros(rows * 128, np.float32)
    flat[:n] = np.random.default_rng(7).random(n, np.float32) / n
    table = jnp.asarray(flat.reshape(rows, 128))
    lanes = 8 * im["q_in"]
    out: dict = {"lanes": lanes, "impl": vg.gather_impl(n)}
    xla = jax.jit(pp._colsum_xla)
    out["xla_ms"] = median_ms(xla, im["idx"], table)
    out["xla_lanes_per_s"] = lanes / out["xla_ms"] * 1e3
    want = xla(im["idx"], table)
    if out["impl"] == "vmem":
        vmem = jax.jit(vg.colsum_vmem)
        out["vmem_ms"] = median_ms(vmem, im["idx"], table)
        out["vmem_lanes_per_s"] = lanes / out["vmem_ms"] * 1e3
        got = vmem(im["idx"], table)
        out["vmem_vs_xla_max_rel"] = float(
            jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    @jax.jit
    def segsum(colsum, first, last, has):
        run = seg_scan(colsum, first, "sum", max_len=im["seg_max"])
        return jnp.where(has, run[last], 0.0)

    out["segment_sum_ms"] = median_ms(segsum, want, im["first"],
                                      im["last"], im["has"])
    rank = jnp.asarray(np.concatenate([flat[:n], [0.0]]).astype(np.float32))
    out["pull_ms"] = median_ms(
        lambda: pp.pull_step()(rank, im["deg"], im["idx"], im["first"],
                               im["last"], im["has"], impl=out["impl"],
                               seg_max=im["seg_max"]))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--seed", type=int, default=3000000401)
    ap.add_argument("--config", default="graphalytics-g500-22")
    args = ap.parse_args()

    import numpy as np

    import files
    import loadgen
    from reference import csr

    config = dict(files.load_json("configs", args.config + ".json"),
                  scale=args.scale)
    t0 = time.time()
    n, src, dst, _perm = loadgen.make_graph(config, args.seed)
    print(f"graph n={n} directed_edges={len(src)} "
          f"{time.time() - t0:.1f}s", flush=True)

    want: dict = {}

    def reference():
        t = time.time()
        indptr, indices = csr.structure(n, src, dst)
        ref = files.load_module("reference", "pagerank")
        want["rank"] = ref.pagerank(indptr, indices, args.iterations, 0.85)
        want["degree"] = np.diff(indptr)
        want["s"] = time.time() - t

    th = threading.Thread(target=reference)
    th.start()

    import jax

    from titan_tpu.models import pagerank_pull as pp
    from titan_tpu.models.bfs_hybrid import build_chunked_csr
    from titan_tpu.models.frontier import pagerank_dense
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.utils.jitcache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    t0 = time.time()
    snap = snap_mod.from_arrays(n, src, dst)
    g = build_chunked_csr(snap)
    print(f"snapshot q_total={g['q_total']} {time.time() - t0:.1f}s",
          flush=True)
    t0 = time.perf_counter()
    im = pp.pull_image(snap)
    jax.block_until_ready(im["idx"])
    print(f"pull image (once a snapshot, in its first job) "
          f"q_in={im['q_in']} seg_max={im['seg_max']} "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"gather_probe": gather_probe(im, n)}), flush=True)
    runs = []
    for i in range(3):
        t0 = time.perf_counter()
        rank, its = pagerank_dense(snap, iterations=args.iterations,
                                   damping=0.85, return_device=True)
        t1 = time.perf_counter()
        rank.block_until_ready()
        t2 = time.perf_counter()
        got = np.asarray(rank)
        t3 = time.perf_counter()
        runs.append({"dispatch_s": t1 - t0, "device_wait_s": t2 - t1,
                     "readback_s": t3 - t2, "job_s": t3 - t0,
                     "iter_ms": (t2 - t0) * 1e3 / its})
        print(f"run {i}: {json.dumps(runs[-1])}", flush=True)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    th.join()
    ref = want["rank"]
    rel = np.abs(got.astype(np.float64) - ref) / np.abs(ref)
    deg = want["degree"]
    bands = {}
    for lo in (1, 10, 100, 1000, 10000, 100000):
        m = (deg >= lo) & (deg < lo * 10)
        if m.any():
            bands[f"deg>={lo}"] = [int(m.sum()), float(rel[m].max())]
    print(json.dumps({
        "n": n, "directed_edges": int(len(src)),
        "q_total": int(g["q_total"]), "max_degree": int(deg.max()),
        "reference_s": want["s"], "runs": runs,
        "max_rel_err": float(rel.max()),
        "out_of_epsilon": int((rel > 1e-4).sum()),
        "rel_err_by_degree": bands, "sum": float(got.sum()),
        "memory_peak_bytes": peak}), flush=True)


if __name__ == "__main__":
    main()
