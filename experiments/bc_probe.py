"""Chip probe (PR 46): a job of the BC kind at ``gap-kron-s22-bc``'s
size, by program, and the level loop on the host beside the same levels
inside one ``lax.while_loop`` a phase.

    python experiments/bc_probe.py [--seed 3000004601]

It builds the cell's own graph (the benchmark's generator and
relabelling), draws the cell's pools and takes the first trial's four
roots as the cell's driver renders them. Then, for each root, every
level program of ``models/bc.py`` one at a time, each call awaited
(``bc_forward_level`` and ``bc_backward_level`` by level, ``bc_seed``,
``bc_result``); whole jobs as the batcher runs them (``models/bc.bc``:
the loop on the host, one scalar read back a forward level); and the
same two phases of a root as ONE program each, the levels inside a
``lax.while_loop`` (built here from the same level bodies: no scalar
comes back, no boundary for a veto), first call (the build) and median.
The four roots' scores are held against the plain reference
(``benchmark/reference/bc.py``, float64): the scores outside the epsilon
rule and the largest relative error (the first reading of the rule's
limit), and beside them the reference's own scores rounded to bfloat16,
the precision below the configuration's (the second reading: it has to
come out NOT correct). One root runs under the profiler, its device
operations by their own time (``benchmark/trace_reduce.py``'s
reduction). Prints one JSON line a finding and writes everything to
``chiprun_out/bc_probe.json``.

``--cpu --scale 12`` rehearses off the chip (counts, never times).
"""

from __future__ import annotations

import argparse
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
    from titan_tpu.ops.vmem_gather import gather_impl
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
    impl = gather_impl(n)
    image = (im["idx"], im["first"], im["last"], im["has"])
    statics = {"impl": impl, "seg_max": im["seg_max"]}
    print(f"graph: n={n} q_in={im['q_in']} roots={roots} impl={impl} in "
          f"{time.time() - t0:.1f} s", flush=True)
    out: dict = {"n": n, "q_in": im["q_in"], "roots": roots, "impl": impl,
                 "device": f"{device.platform}:{device.device_kind}"}

    def awaited(fn):
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn())
        return got, round((time.perf_counter() - t0) * 1e3, 3)

    seed = B._seed()
    forward = B._level("bc_forward_level", B.forward_level)
    backward = B._level("bc_backward_level", B.backward_level)
    # the builds, then every level of every root awaited
    state, out["seed_first_ms"] = awaited(
        lambda: seed(jnp.asarray(roots[0], jnp.int32), n_=n))
    _, out["forward_first_ms"] = awaited(lambda: forward(
        state[0], state[1], jnp.int32(1), *image, **statics))
    _, out["backward_first_ms"] = awaited(lambda: backward(
        *state, jnp.int32(2), *image, **statics))
    by_root, deltas = [], []
    for root in roots:
        (depth, sigma, delta), seed_ms = awaited(
            lambda: seed(jnp.asarray(root, jnp.int32), n_=n))
        fwd, joined_by_level, d = [], [1], 0
        while True:
            d += 1
            (depth, sigma, joined), ms = awaited(lambda: forward(
                depth, sigma, jnp.int32(d), *image, **statics))
            fwd.append(ms)
            if not int(joined):
                break
            joined_by_level.append(int(joined))
        bwd = []
        for k in range(d - 1, 1, -1):
            delta, ms = awaited(lambda: backward(
                depth, sigma, delta, jnp.int32(k), *image, **statics))
            bwd.append(ms)
        deltas.append(delta)
        by_root.append({"root": root, "levels": d, "seed_ms": seed_ms,
                        "level_sizes": joined_by_level,
                        "forward_ms": fwd, "backward_ms": bwd,
                        "largest_sigma": float(sigma.max()),
                        "largest_delta": float(delta.max()),
                        "with_a_dependency": int((delta > 0).sum())})
        print(json.dumps({"root": by_root[-1]}), flush=True)
    out["by_root"] = by_root
    scores, out["result_first_ms"] = awaited(
        lambda: B._result()(tuple(deltas)))
    _, out["result_ms"] = awaited(lambda: B._result()(tuple(deltas)))
    pulls = [ms for r in by_root for ms in r["forward_ms"] + r["backward_ms"]]
    out["pull_ms_median"] = round(float(np.median(pulls)), 3)
    out["pulls_a_job"] = len(pulls)
    print(json.dumps({k: out[k] for k in (
        "seed_first_ms", "forward_first_ms", "backward_first_ms",
        "result_first_ms", "result_ms", "pull_ms_median", "pulls_a_job")}),
        flush=True)

    # whole jobs, as the batcher runs them: the loop on the host
    jobs = []
    for _ in range(3):
        t0 = time.perf_counter()
        answer, levels, reached = B.bc(snap, roots)
        jobs.append(round((time.perf_counter() - t0) * 1e3, 1))
    out["job_ms_host_loop"] = jobs
    out["levels"], out["reached"] = levels, reached
    print(json.dumps({"job_ms_host_loop": jobs, "levels": levels,
                      "reached": reached}), flush=True)

    # the same phases with the levels inside one while_loop each (the
    # image handed in: a closure would bake 0.6 GB of constants into
    # the executable, as PR 46's first probe did: 164 s to build)
    @jax.jit
    def forward_loop(root, *image):
        depth, sigma, delta = B._seed().__wrapped__(root, n_=n)

        def go(carry):
            depth, sigma, d, _joined = carry
            depth, sigma, joined = B.forward_level(
                depth, sigma, d + 1, *image, **statics)
            return depth, sigma, d + 1, joined
        depth, sigma, d, _ = jax.lax.while_loop(
            lambda c: c[3] > 0, go,
            (depth, sigma, jnp.int32(0), jnp.int32(1)))
        return depth, sigma, delta, d

    @jax.jit
    def backward_loop(depth, sigma, delta, d, *image):
        def go(carry):
            delta, k = carry
            return B.backward_level(depth, sigma, delta, k, *image,
                                    **statics), k - 1
        return jax.lax.while_loop(lambda c: c[1] > 1, go,
                                  (delta, d - 1))[0]

    def job_in_loops():
        got = []
        for root in roots:
            depth, sigma, delta, d = forward_loop(
                jnp.asarray(root, jnp.int32), *image)
            got.append(backward_loop(depth, sigma, delta, d, *image))
        return np.asarray(B._result()(tuple(got)))

    loops = []
    for _ in range(4):
        t0 = time.perf_counter()
        looped = job_in_loops()
        loops.append(round((time.perf_counter() - t0) * 1e3, 1))
    out["job_ms_while_loop"] = {"first": loops[0], "then": loops[1:]}
    out["loops_agree"] = bool(np.array_equal(looped, answer))
    print(json.dumps({"job_ms_while_loop": out["job_ms_while_loop"],
                      "loops_agree": out["loops_agree"]}), flush=True)

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
        "bfloat16_outside": reference.outside(low, want),
        "bfloat16_largest_relative_error": reference.worst(low, want)}
    print(json.dumps({"reference_s": out["reference_s"],
                      "rule": out["rule"]}), flush=True)

    def one_root():
        B.bc(snap, roots[:1])

    out["trace"] = traced_ops(one_root, top=16)
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
