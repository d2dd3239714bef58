"""Chip probe (PR 40; the row sort beside the global one: PR 44): a round
of CDLP at ``graphalytics-g500-22-cdlp``'s size, by program.

    python experiments/cdlp_probe.py [--seed 3000004001]

It builds the cell's own graph (the benchmark's generator and
relabelling) and CDLP's row image (``models/cdlp.cdlp_image``; its rows,
widths and ``pad_share`` printed), then times the three programs of a
round (``models/cdlp.py``) one at a time, each call awaited, median of 5:
``cdlp_gather`` under what ``vmem_gather.gather_impl`` chooses and under
XLA's gather (the same lanes from both, checked on the device),
``cdlp_sort`` and, alone, the ``lax.sort`` of each class of its rows on
the same gathered lanes, ``cdlp_vote`` (PR 40's call B also timed the
kernel at 1,024, 4,096 and 8,192 indices a grid step: 452.8, 449.8 and
449.6 ms, so the step's width stayed what it was). Beside them, on the
same machine, what a round paid before PR 44: the gather over PageRank's
pull image and ONE global ``lax.sort`` of its lanes as (owner, label)
pairs, two keys. Round 1 is held against the host: with every label
distinct the vote is the smallest neighbour id (one ``minimum.reduceat``
over the snapshot's in-edges). Then whole jobs of 10 rounds as the
batcher runs them (no sync between rounds), and one round under the
profiler with its device operations by their own time
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
    im = C.cdlp_image(snap)
    lanes_wide = im["lanes"]
    statics = C.sort_statics(im)
    print(f"graph: n={n} in {time.time() - t0:.1f} s; image: "
          f"lanes={lanes_wide} rows={statics['rows']} "
          f"width={statics['width']} keys={im['keys']} "
          f"label_bits={im['label_bits']} pad_share={statics['pad_share']}",
          flush=True)
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

    out: dict = {"n": n, "lanes": lanes_wide, "image": statics,
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
    lanes1 = got[impl]
    del got

    # the sort of each class of rows alone, on the gathered lanes: one
    # word a lane (or the pair), along the rows
    key = im["key_hi"] | lanes1.astype(jnp.uint32)
    at = 0
    for name, (rows, width) in zip(("small", "wide"), im["classes"]):
        size = rows * 8 * width
        if rows:
            part = jax.block_until_ready(
                key[at:at + size].reshape(rows, 8 * width))
            row_sort = jax.jit(lambda x: jax.lax.sort(
                x, dimension=1, is_stable=False))
            _, first, ms = timed(lambda: row_sort(part))
            out[f"row_sort_{name}"] = {
                "shape": [rows, 8 * width], "first_ms": first, "ms": ms,
                "ms_per_m_lanes": round(ms / size * 1e6, 4)}
            print(json.dumps({f"row_sort_{name}": out[f"row_sort_{name}"]}),
                  flush=True)
            del part
        at += size
    del key
    (owner, by_label), first, ms = timed(
        lambda: sort(im["key_hi"], lanes1, **statics))
    out["sort"] = {"first_ms": first, "ms": ms}
    print(json.dumps({"sort": out["sort"]}), flush=True)
    labels1, first, ms = timed(lambda: vote(
        owner, by_label, labels0, im["last_lane"], im["has"],
        max_len=im["max_len"], n_=n))
    out["vote"] = {"first_ms": first, "ms": ms}
    print(json.dumps({"vote": out["vote"]}), flush=True)
    del owner, by_label, lanes1

    # what a round paid before PR 44, on this machine: the gather over
    # PageRank's pull image, then ONE sort of every lane of it as
    # (owner, label) pairs
    pull = pull_image(snap)

    @jax.jit
    def global_sort(first, lanes):
        owner = jnp.tile(jnp.cumsum(first, dtype=jnp.int32) - 1, 8)
        return jax.lax.sort((owner, lanes), num_keys=2, is_stable=False)

    old_lanes, first, ms = timed(
        lambda: gather(labels0, pull["idx"], impl=impl, n_=n))
    out["gather_pull_image"] = {"first_ms": first, "ms": ms,
                                "lanes": 8 * pull["q_in"]}
    _, first, ms = timed(lambda: global_sort(pull["first"], old_lanes),
                         reps=4)
    out["sort_global"] = {"first_ms": first, "ms": ms,
                          "lanes": 8 * pull["q_in"]}
    print(json.dumps({k: out[k] for k in ("gather_pull_image",
                                          "sort_global")}), flush=True)
    del old_lanes, pull
    del snap._pull_csr

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
        o, s = sort(im["key_hi"], lanes, **statics)
        return jax.block_until_ready(vote(
            o, s, labels1, im["last_lane"], im["has"],
            max_len=im["max_len"], n_=n))

    out["trace"] = traced_ops(one_round, top=24)
    print(json.dumps({"trace": out["trace"]}), flush=True)
    stats = device.memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["work_bytes_admitted"] = C.work_bytes(n, lanes_wide)
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
