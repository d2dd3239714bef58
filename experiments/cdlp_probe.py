"""Chip probe (PR 40): a round of CDLP at ``graphalytics-g500-22-cdlp``'s
size, by program.

    python experiments/cdlp_probe.py [--seed 3000004001]

It builds the cell's own graph (the benchmark's generator and
relabelling) and its pull image, then times the three programs of a
round (``models/cdlp.py``) one at a time, each call awaited, median of 5:
``cdlp_gather`` under what ``vmem_gather.gather_impl`` chooses and under
XLA's gather (the same lanes from both, checked on the device),
``cdlp_sort``, ``cdlp_vote`` (call B also timed the kernel at 1,024, 4,096
and 8,192 indices a grid step: 452.8, 449.8 and 449.6 ms, so the step's
width stayed what it was). Round 1 is held against the host: with
every label distinct the vote is the smallest neighbour id (one
``minimum.reduceat`` over the snapshot's in-edges). Then whole jobs of 10
rounds as the batcher runs them (no sync between rounds), and one round
under the profiler with its device operations by their own time
(``benchmark/trace_reduce.py``'s reduction). Prints one JSON line a
finding and writes everything to ``chiprun_out/cdlp_probe.json``.

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
    ap.add_argument("--seed", type=int, default=3000004001)
    ap.add_argument("--scale", type=int, default=None,
                    help="another Kronecker scale than the cell's 22")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import files
    import loadgen
    from bu_dense_probe import traced_ops
    from titan_tpu.models import cdlp as C
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
    _bench, _cell, config, mix = files.cell_files("g500-22.cdlp-c2")
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    rounds = int(mix["request"]["body"]["iterations"])
    t0 = time.time()
    n, src, dst, _perm = loadgen.make_graph(config, args.seed)
    snap = snap_mod.from_arrays(n, src, dst)
    del src, dst
    im = pull_image(snap)
    lanes_wide = 8 * im["q_in"]
    print(f"graph: n={n} q_in={im['q_in']} lanes={lanes_wide} "
          f"seg_max={im['seg_max']} in {time.time() - t0:.1f} s", flush=True)
    gather, sort, vote = C._gather(), C._sort(), C._vote()
    labels0 = jnp.arange(n, dtype=jnp.int32)

    def timed(fn, reps: int = 6):
        """(last result, first call's ms, median ms of the others)."""
        ts, out = [], None
        for _ in range(reps):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            ts.append((time.perf_counter() - t0) * 1e3)
        return out, round(ts[0], 1), round(float(np.median(ts[1:])), 3)

    out: dict = {"n": n, "q_in": im["q_in"], "lanes": lanes_wide,
                 "device": f"{device.platform}:{device.device_kind}"}
    impl = gather_impl(n)
    got = {}
    for how in dict.fromkeys((impl, "xla")):
        got[how], first, ms = timed(
            lambda: gather(labels0, im["idx"], impl=how, n_=n))
        out[f"gather_{how}"] = {"first_ms": first, "ms": ms,
                                "lanes_per_s": round(lanes_wide / ms * 1e3)}
        print(json.dumps({f"gather_{how}": out[f"gather_{how}"]}),
              flush=True)
    out["gathers_agree"] = all(bool(jnp.array_equal(g, got["xla"]))
                               for g in got.values())

    # the sort donates its lanes: a fresh copy a call, made outside the
    # clock
    copies = [jnp.array(got[impl], copy=True) for _ in range(4)]
    jax.block_until_ready(copies)
    ts = []
    for lanes in copies:
        t0 = time.perf_counter()
        owner, by_label = jax.block_until_ready(sort(im["first"], lanes))
        ts.append((time.perf_counter() - t0) * 1e3)
    del copies
    out["sort"] = {"first_ms": round(ts[0], 1),
                   "ms": round(float(np.median(ts[1:])), 3)}
    print(json.dumps({"sort": out["sort"]}), flush=True)
    labels1, first, ms = timed(lambda: vote(
        owner, by_label, labels0, im["last"], im["has"],
        seg_max=im["seg_max"], n_=n))
    out["vote"] = {"first_ms": first, "ms": ms}
    print(json.dumps({"vote": out["vote"]}), flush=True)

    # round 1 against the host: every label distinct, so every count is
    # 1 and the smallest neighbour id wins
    indptr = np.asarray(snap.indptr_in[:n + 1], np.int64)
    has = np.diff(indptr) > 0
    want = np.arange(n, dtype=np.int32)
    want[has] = np.minimum.reduceat(np.asarray(snap.src, np.int32),
                                    indptr[:-1][has])
    out["round1_out"] = int((np.asarray(labels1) != want).sum())
    print(json.dumps({"gathers_agree": out["gathers_agree"],
                      "round1_out": out["round1_out"]}), flush=True)
    del owner, by_label, got

    # whole jobs, as the batcher runs them
    jobs = []
    for _ in range(3):
        t0 = time.perf_counter()
        answer, its = C.cdlp(snap, iterations=rounds)
        jobs.append(round((time.perf_counter() - t0) * 1e3, 1))
    out["job_ms"] = jobs
    out["communities"] = int(len(np.unique(answer)))
    print(json.dumps({"job_ms": jobs, "rounds": its,
                      "communities": out["communities"]}), flush=True)

    def one_round():
        lanes = gather(labels1, im["idx"], impl=impl, n_=n)
        o, s = sort(im["first"], lanes)
        return jax.block_until_ready(vote(
            o, s, labels1, im["last"], im["has"], seg_max=im["seg_max"],
            n_=n))

    out["trace"] = traced_ops(one_round, top=24)
    print(json.dumps({"trace": out["trace"]}), flush=True)
    stats = device.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["work_bytes_admitted"] = C.work_bytes(n, im["q_in"])
    print(json.dumps({k: out[k] for k in ("peak_bytes_in_use",
                                          "work_bytes_admitted")}),
          flush=True)
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "cdlp_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
