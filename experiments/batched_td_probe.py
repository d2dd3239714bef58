"""Chip probe (PR 26, PR 29, PR 31): what one level of the batched BFS
costs in each direction and on each road, on the benchmark's two graphs
— the measurement behind ``bfs_hybrid.TD_RUNG_SHIFTS`` and
``TD_BU_COST``, and behind handing the frontier forward.

    python experiments/batched_td_probe.py [--scale 20]
        [--rungs 18,19,20] [--push-only]

``--rungs`` keeps the rungs of those powers of two; ``--push-only``
times ``td-scan`` and ``td-last`` alone, on the executables the rule
serves with (PR 31: what a rung costs, against its width).

Per graph and batch size K: the top-down step at each rung of the
ladder with a frontier that fills about 0.8 of the rung (hops mode), on
the scan road (``td-scan``: the frontier listed from dist, n wide, then
pushed, nothing handed on: the step as PR 26 had it), from a list in
hand with the next level's list and statistics made (``td-carried``)
and from a list in hand with nothing handed on (``td-last``); what the
dedup's claim array costs made in the program (``claim-fill``) against
kept and reset (``claim-reset``, a second 8 x p_cap scatter); the seed
and the extract; one bottom-up level over the same state (plan + the
eight fused rounds, the exhaustive sweep left out); and whole BFS runs
(mode="bfs", K = 8) with the rule as it is and with the push held off
by a layout marked directed. Times are medians of 5 after one
unmeasured call; the first call's time (compile or cache load) is
printed beside them.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def timed(fn, reps: int = 5):
    t0 = time.perf_counter()
    fn()
    first = time.perf_counter() - t0
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return statistics.median(out) * 1e3, first * 1e3


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--graphs", default="gap-kron-s20,gap-urand-s20")
    ap.add_argument("--rungs", default="",
                    help="log2 of the rungs to time (default: all)")
    ap.add_argument("--push-only", action="store_true")
    args = ap.parse_args()
    rungs = {1 << int(e) for e in args.rungs.split(",") if e}

    import jax
    import jax.numpy as jnp
    import numpy as np

    import files
    import loadgen
    from titan_tpu.models import bfs_hybrid as bh
    from titan_tpu.models.bfs import _next_pow2
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.ops.compaction import CLAIM_SENTINEL, claim_reset
    from titan_tpu.utils.jitcache import dev_scalar, enable_compile_cache

    enable_compile_cache()
    print("device", jax.devices()[0].platform, jax.devices()[0].device_kind,
          flush=True)
    rows = []
    for name in args.graphs.split(","):
        config = dict(files.load_json("configs", name + ".json"),
                      scale=args.scale)
        n, src, dst, _perm = loadgen.make_graph(config, 7)
        snap = snap_mod.from_arrays(n, src, dst)
        del src, dst
        g = bh.build_chunked_csr(snap)
        degc = np.asarray(g["_host"]["degc"])[:n]
        rng = np.random.default_rng(26)
        order = rng.permutation(np.flatnonzero(degc > 0))
        csum = np.cumsum(degc[order])
        btd, bplan, bstep = bh._batched_td(), bh._batched_plan(), \
            bh._batched_bu()
        blist, bseed, bext = bh._batched_list(), bh._batched_seed(), \
            bh.hop_extract()
        caps = bh._td_caps(g)
        cap_n = _next_pow2(n)
        claim_fill = jax.jit(lambda size: jnp.full(
            (size,), CLAIM_SENTINEL, jnp.int32), static_argnums=0)
        claim_reset_ = jax.jit(claim_reset, donate_argnums=0)

        def row(**kw):
            rows.append(dict(kw, graph=name))
            print(rows[-1], flush=True)

        for K in (1, 4, 16):
            active = jnp.ones((K,), bool)
            srcs = order[:K].astype(np.int32)

            def seed():
                bseed(srcs, dev_scalar(1), n_=n, cap=caps[-1],
                      expand=True)[0].block_until_ready()

            dist2 = bseed(srcs, dev_scalar(1), n_=n, cap=caps[-1],
                          expand=True)[0]

            def extract():
                np.asarray(bext(dist2, np.full(K, 2, np.int32), n_=n)[1])

            for label, fn in (("seed", seed), ("extract", extract)):
                if not args.push_only:
                    ms, first = timed(fn)
                    row(K=K, dir=label, ms=round(ms, 2),
                        first_ms=round(first, 1))
            for i, cap in enumerate(caps):
                if rungs and cap not in rungs:
                    continue
                # a frontier of about 0.8 of the rung, split over K jobs
                take = int(np.searchsorted(csum, 0.8 * cap))
                init = np.zeros((K, n + 1), np.int32)
                for k in range(K):
                    init[k, order[k:take:K]] = 1
                mass = int(degc[order[:take]].sum())
                base = jnp.asarray(init)

                def listed():
                    return blist(base, active, dev_scalar(1),
                                 dev_scalar(i), g["degc"], caps=caps,
                                 n_=n)

                def push(lst, want):
                    out = btd(base + 0, *lst, active, dev_scalar(1),
                              dev_scalar(want), g["dstT"], g["colstart"],
                              g["degc"], p_cap=cap, n_=n, expand=True,
                              lists=lists)
                    return np.asarray(out[4])

                # the dedup on every rung it could run on (fewer lanes
                # than K x n), whatever _td_lists says: its measurement
                lists = bh._td_lists(cap, n) if args.push_only \
                    else 8 * cap < K * n
                held = listed()
                handed = int(push(held, 1)[2])
                for label, fn in (
                        ("td-scan", lambda: push(listed(), 0)),
                        ("td-carried", lambda: push(held, 1)),
                        ("td-last", lambda: push(held, 0))):
                    if args.push_only and label == "td-carried":
                        continue
                    ms, first = timed(fn)
                    row(K=K, dir=label, p_cap=cap, mass=mass,
                        handed=handed, rule=bh._td_lists(cap, n),
                        ms=round(ms, 2), first_ms=round(first, 1))
                if not lists or args.push_only:
                    continue
                # the claim array of the dedup: made in the program, or
                # kept between levels and reset at the keys it touched
                keys = jnp.asarray(rng.integers(
                    0, K * (n + 1), (8, cap)).astype(np.int32))
                kept = [claim_fill(K * (n + 1))]

                def fill():
                    claim_fill(K * (n + 1)).block_until_ready()

                def reset():
                    kept[0] = claim_reset_(kept[0], keys)
                    kept[0].block_until_ready()

                for label, fn in (("claim-fill", fill),
                                  ("claim-reset", reset)):
                    ms, first = timed(fn)
                    row(K=K, dir=label, p_cap=cap, ms=round(ms, 3),
                        first_ms=round(first, 1))
            if args.push_only:
                continue
            # one bottom-up level over a 16-vertex-a-job frontier
            init = np.zeros((K, n + 1), np.int32)
            for k in range(K):
                init[k, order[k * 16:(k + 1) * 16]] = 1
            base = jnp.asarray(init)
            fbits, cand, stats = bplan(base, active, dev_scalar(1),
                                       g["degc"], c_cap=cap_n, n_=n,
                                       expand=True)
            c_count = int(np.asarray(stats)[0])
            c_cap2 = min(_next_pow2(max(c_count, 2)), cap_n)
            if cand.shape[0] < cap_n:
                cand = jnp.concatenate([cand, jnp.full(
                    (cap_n - cand.shape[0],), n + 1, cand.dtype)])
            off = jnp.zeros((cap_n,), jnp.int32)
            prog = jnp.asarray([c_count, 0], jnp.int32)

            def plan():
                np.asarray(bplan(base, active, dev_scalar(1), g["degc"],
                                 c_cap=cap_n, n_=n, expand=True)[2])

            def bu():
                out = bstep(base + 0, fbits, cand[:c_cap2], off[:c_cap2],
                            prog, dev_scalar(1), g["dstT"], g["colstart"],
                            g["degc"], jnp.zeros((1,), jnp.uint8),
                            c_cap=c_cap2, n_=n, fuse=bh.BU_CHUNK_ROUNDS,
                            masked=False, expand=True)
                np.asarray(out[3])

            for label, fn in (("plan", plan), ("bu", bu)):
                ms, first = timed(fn)
                row(K=K, dir=label, c_count=c_count, ms=round(ms, 2),
                    first_ms=round(first, 1))
        if args.push_only:
            continue
        # whole BFS runs, the job batcher's mode
        srcs = [int(v) for v in order[:8]]
        for label, layout in (("rule", g), ("held-off",
                                            dict(g, directed=True))):
            def run():
                bh.frontier_bfs_batched(layout, srcs, mode="bfs",
                                        return_device=True)[0] \
                    .block_until_ready()

            ms, first = timed(run, reps=3)
            row(K=8, dir="bfs-" + label, ms=round(ms, 1),
                first_ms=round(first, 1))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "batched_td_probe.json"),
              "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
