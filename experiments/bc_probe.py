"""Chip probe (PR 46; PR 47: the shared pull): a job of the BC kind at
``gap-kron-s22-bc``'s size, from the gather up.

    python experiments/bc_probe.py [--seed 3000004601]

It builds the cell's own graph (the benchmark's generator and
relabelling), draws the cell's pools and takes the first trial's four
roots as the cell's driver renders them. Then, in the order a change to
the shared pull has to be judged:

1. **the kernel alone** (``ops/vmem_gather.colsum_vmem``) over the
   cell's pull image at width 1, 2 and 4, and at 8 over the same image
   folded onto 2^21 - 2 vertices (what 64 MiB holds eight wide): ms a
   pass, M indices/s, each call awaited, median of 5; beside it what
   lays the table (``as_table``) and, at width 4, the table's entries
   held against numpy's. An index should cost what it costs at width 1:
   a pass that grows with the width says the vector side binds.
2. **the two level programs** at width 1 and at the group's, the
   four roots' first levels, each call awaited.
3. **whole jobs** as the batcher runs them (``models/bc.bc``: the roots
   in groups that share every pull) beside the same roots one at a time
   (``vmem_gather.shared_width`` held to 1 for those calls: PR 46's
   job), their scores compared.

The four roots' scores are held against the plain reference
(``benchmark/reference/bc.py``, float64): the scores outside the epsilon
rule and the largest relative error (the first reading of the rule's
limit), and beside them the reference's own scores rounded to bfloat16,
the precision below the configuration's (the second reading: it has to
come out NOT correct). One job runs under the profiler, its device
operations by their own time (``benchmark/trace_reduce.py``'s
reduction). Prints one JSON line a finding and writes everything to
``chiprun_out/bc_probe.json``.

``--cpu --scale 12`` rehearses off the chip (counts, never times; the
kernel in Pallas's interpreter over the image's first block).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.join(ROOT, "experiments"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000004601)
    ap.add_argument("--scale", type=int, default=None,
                    help="another Kronecker scale than the cell's 22")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    import files
    import loadgen
    from bu_dense_probe import traced_ops
    from reference import csr
    from titan_tpu.models import bc as B
    from titan_tpu.models.pagerank_pull import pull_image
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.ops import vmem_gather as vg
    from titan_tpu.utils.jitcache import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    _bench, _cell, config, mix = files.cell_files("kron-s22.bc-c2")
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    t0 = time.time()
    n, src, dst, perm = loadgen.make_graph(config, args.seed)
    pools = loadgen.draw_pools(np.bincount(src, minlength=n), mix, config,
                               perm)
    body = loadgen.Bodies(mix, pools, args.seed).get(0)
    roots = [int(r) for r in body["sources"]]
    snap = snap_mod.from_arrays(n, src, dst)
    indptr, indices = csr.structure(n, src, dst)
    del src, dst
    im = pull_image(snap)
    width = vg.shared_width(n, len(roots))
    impl = vg.gather_impl(n, width)
    image = (im["idx"], im["first"], im["last"], im["has"])
    print(f"graph: n={n} q_in={im['q_in']} roots={roots} width={width} "
          f"impl={impl} in {time.time() - t0:.1f} s", flush=True)
    out: dict = {"n": n, "q_in": im["q_in"], "roots": roots, "width": width,
                 "impl": impl,
                 "device": f"{device.platform}:{device.device_kind}"}

    def awaited(fn):
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn())
        return got, round((time.perf_counter() - t0) * 1e3, 3)

    def median_of_5(fn):
        first = awaited(fn)[1]
        return first, round(float(np.median(
            [awaited(fn)[1] for _ in range(5)])), 3)

    # 1. the kernel alone, by width
    if args.cpu:
        colsum = functools.partial(vg.colsum_vmem, interpret=True)
        idx = im["idx"].reshape(8, -1)[:, :vg.BLOCK].reshape(-1)
    else:
        colsum, idx = vg.colsum_vmem, im["idx"]
    lay = jax.jit(vg.as_table)
    rng = np.random.default_rng(args.seed)
    kernel = []
    for w in (1, 2, 4, 8):
        # the widest table the cap holds at this n, else the image folded
        # onto as many vertices as it holds
        n_w = n if vg.shared_width(n, w) == w \
            else vg.VMEM_TABLE_MAX // (4 * w) - 2
        folded = idx if n_w == n else jax.block_until_ready(idx % (n_w + 2))
        values = rng.random((w, n_w + 1), dtype=np.float32)
        values[:, n_w] = 0.0
        dev = jnp.asarray(values if w > 1 else values[0])
        gather = jax.jit(functools.partial(colsum, width=w))
        table, _ = awaited(lambda: lay(dev))
        _, table_ms = median_of_5(lambda: lay(dev))
        first_ms, ms = median_of_5(lambda: gather(folded, table))
        row = {"width": w, "n": n_w, "table_ms": table_ms,
               "first_ms": first_ms, "ms": ms,
               "m_indices_a_s": round(folded.shape[0] / ms / 1e3, 1)}
        if w == width or args.cpu:
            flat = np.asarray(table).reshape(-1)
            row["table_is_numpys"] = bool(np.array_equal(
                flat[:(n_w + 1) * w], values.T.reshape(-1))
                and not flat[(n_w + 1) * w:].any())
            sums = np.asarray(gather(folded, table)).reshape(w, -1)
            at = rng.integers(0, sums.shape[1], 4096)
            lanes = np.asarray(folded).reshape(8, -1)[:, at]
            want = np.pad(values, ((0, 0), (0, 1)))[:, lanes] \
                .sum(axis=1, dtype=np.float64)
            row["largest_relative_error"] = float(
                (np.abs(sums[:, at] - want) / np.maximum(want, 1e-30)).max())
        kernel.append(row)
        print(json.dumps({"kernel": row}), flush=True)
        del table, dev, folded
    out["kernel"] = kernel

    # 2. the level programs, at width 1 and at the group's
    seed = B._seed()
    forward = B._level("bc_forward_level", B.forward_level)
    backward = B._level("bc_backward_level", B.backward_level)
    levels = []
    for w in sorted({1, width}):
        statics = {"impl": vg.gather_impl(n, w), "seg_max": im["seg_max"],
                   "width": w}
        state = seed(jnp.asarray(roots[:w], jnp.int32), n_=n)
        fwd_first, fwd = median_of_5(lambda: forward(
            state[0], state[1], jnp.int32(1), *image, **statics))
        depth, sigma, delta = state
        for d in (1, 2, 3):
            depth, sigma, _ = forward(depth, sigma, jnp.int32(d), *image,
                                      **statics)
        fwd3 = median_of_5(lambda: forward(
            depth, sigma, jnp.int32(4), *image, **statics))[1]
        bwd_first, bwd = median_of_5(lambda: backward(
            depth, sigma, delta, jnp.int32(3), *image, **statics))
        levels.append({"width": w, "forward_first_ms": fwd_first,
                       "forward_ms": fwd, "forward_level4_ms": fwd3,
                       "backward_first_ms": bwd_first, "backward_ms": bwd})
        print(json.dumps({"level": levels[-1]}), flush=True)
    out["levels"] = levels

    # 3. whole jobs, as the batcher runs them, and a root at a time
    def jobs(count: int):
        ms = []
        for _ in range(count):
            t0 = time.perf_counter()
            got = B.bc(snap, roots)
            ms.append(round((time.perf_counter() - t0) * 1e3, 1))
        return got, ms

    (answer, by_root, reached), shared_ms = jobs(4)
    grouped = vg.shared_width
    vg.shared_width = lambda _n, _most: 1
    try:
        (alone, by_root1, _), alone_ms = jobs(3)
    finally:
        vg.shared_width = grouped
    pulls = max(by_root) + max(max(by_root) - 2, 0)
    out["jobs"] = {
        "shared_ms": shared_ms, "one_at_a_time_ms": alone_ms,
        "levels": by_root, "reached": reached, "pulls_shared": pulls,
        "pulls_one_at_a_time": sum(lv + max(lv - 2, 0) for lv in by_root),
        "same_levels": by_root == by_root1,
        "scores_bit_equal": bool(np.array_equal(answer, alone)),
        "scores_largest_relative_difference": float(
            (np.abs(answer - alone) / np.maximum(alone, 1e-30)).max())}
    print(json.dumps({"jobs": out["jobs"]}), flush=True)

    # the two readings of the rule's limit
    reference = files.load_module("reference", "bc")
    t0 = time.time()
    ref = reference.prepare(n, indptr, indices, {"trial": roots}, mix)
    want = ref.answer(body)["result"]
    low = want.astype(ml_dtypes.bfloat16).astype(np.float32)
    out["reference_s"] = round(time.time() - t0, 1)
    out["rule"] = {
        "epsilon": reference.EPSILON,
        "positive_scores": int((want > 0).sum()),
        "outside": reference.outside(answer, want),
        "largest_relative_error": reference.worst(answer, want),
        "one_at_a_time_outside": reference.outside(alone, want),
        "bfloat16_outside": reference.outside(low, want),
        "bfloat16_largest_relative_error": reference.worst(low, want)}
    print(json.dumps({"reference_s": out["reference_s"],
                      "rule": out["rule"]}), flush=True)

    out["trace"] = traced_ops(lambda: B.bc(snap, roots), top=20)
    print(json.dumps({"trace": out["trace"]}), flush=True)
    stats = device.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["work_bytes_admitted"] = B.work_bytes(n, im["q_in"])
    print(json.dumps({k: out[k] for k in ("peak_bytes_in_use",
                                          "work_bytes_admitted")}),
          flush=True)
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "bc_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
