"""Chip probe (PR 49, PR 50): a job of the ``bfs`` kind at
``gap-kron-s22-bfs``'s size, by level, as the cell's callers send it,
WITH its BFS tree and without, side by side: what the parent plane
costs a job, by direction.

    python experiments/bfs_probe.py [--seed 3000004901] [--sources 8]

It builds the cell's own graph (the benchmark's generator and
relabelling), draws the cell's pool and takes its first sources. Then:

1. **served**: the program's scheduler and HTTP server with their
   defaults (what ``benchmark/run.py`` starts), the sources one at a
   time through ``POST /jobs``, each as the cell sends it (``"parents":
   true``) and again without the flag, polled and fetched over the
   result plane: a job's ``exec_ms``, the fetch, the executables it
   built (``devprof``'s count, and each ``compile`` span by key, statics
   and seconds), its parents held to GAP's rule and its depths to the
   serial BFS's by ``benchmark/reference/bfs.py`` (with the milliseconds
   that check took: it runs between two of a caller's jobs), and its
   levels (``bfs.plan`` / ``bfs.sweep`` / ``bfs.exhaust``: direction,
   caps, ms), the time by direction summed, and its ``kernel`` spans by
   key.
2. **direct**: the same sources through ``frontier_bfs_batched`` at
   K = 1 on this thread under a scope of the probe's own, with parents
   and without: by level the direction, the rung (``p_cap`` or
   ``c_cap``), the counts and the milliseconds (every level is awaited
   by its own readback), and the difference by direction.
3. **rule** (``--td-bu-costs 0.125,0.5,1,2``): the direct loop with
   parents again under other values of the direction rule's
   ``TD_BU_COST`` (a pushed chunk column against a candidate-round of
   the pull; the same programs on the same ladders, so nothing builds):
   a job's time and its time by direction under each. 1 is the rule as
   it stands since PR 50 (a BFS level's pull weighed at ONE chunk
   round); 0.125 is the rule of before (weighed at eight, as when a
   pulled level fused eight rounds into one dispatch).
4. **single** (``--single n``, default none): the first n sources
   through the single-source family (``frontier_bfs_hybrid``), awaited,
   its second call timed: ROADMAP D2 (a)'s other number.

Prints one JSON line a finding and writes everything to
``chiprun_out/bfs_probe.json`` (``--out``). ``--cpu --scale 12``
rehearses off the chip (counts, never times).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

LEVEL_PHASES = ("bfs.plan", "bfs.sweep", "bfs.exhaust")


def level_rows(spans) -> list:
    """One row a level phase of a list of span dicts, in time order."""
    rows = []
    for s in sorted(spans, key=lambda s: s["start"]):
        if s["name"] not in LEVEL_PHASES:
            continue
        a = s.get("attrs") or {}
        row = {"phase": s["name"], "level": a.get("level"),
               "ms": round(s["duration_ms"] or 0.0, 2)}
        for k in ("dir", "p_cap", "c_cap", "fuse", "mass", "list",
                  "pairs", "handed", "c_count", "rem8", "frontier",
                  "carried", "replan", "sync_ms"):
            if k in a:
                row[k] = a[k]
        rows.append(row)
    return rows


def by_direction(rows) -> dict:
    """{plan | td | bu | exhaust: summed ms} of ``level_rows``' rows."""
    out: dict = {}
    for r in rows:
        what = {"bfs.plan": "plan", "bfs.exhaust": "exhaust"}.get(
            r["phase"], r.get("dir"))
        out[what] = round(out.get(what, 0.0) + r["ms"], 2)
    return out


def kernel_rows(spans) -> dict:
    """{key: [calls, summed device_ms]} of a list's ``kernel`` spans."""
    out: dict = {}
    for s in spans:
        if s["name"] == "kernel":
            a = s.get("attrs") or {}
            k = out.setdefault(a.get("key", "?"), [0, 0.0])
            k[0] += 1
            k[1] = round(k[1] + a.get("device_ms", 0.0), 2)
    return out


def compile_rows(spans) -> list:
    rows = []
    for s in spans:
        if s["name"] == "compile":
            a = dict(s.get("attrs") or {})
            rows.append({"key": a.pop("key", "?"),
                         "cache": a.pop("cache", "?"),
                         "s": round((s["duration_ms"] or 0.0) / 1e3, 2),
                         "statics": {k: v for k, v in sorted(a.items())
                                     if not k.endswith("_ms")
                                     and k != "thread"}})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000004901)
    ap.add_argument("--scale", type=int, default=None,
                    help="another Kronecker scale than the cell's 22")
    ap.add_argument("--sources", type=int, default=8)
    ap.add_argument("--single", type=int, default=0,
                    help="sources also run through the single-source "
                         "family")
    ap.add_argument("--td-bu-costs", default="0.125,0.5,1,2",
                    help="values of TD_BU_COST the rule part tries")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "bfs_probe.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import numpy as np

    import files
    import loadgen
    from reference import bfs as ref
    from reference import csr
    from titan_tpu.models import bfs_hybrid as H
    from titan_tpu.obs import devprof, tracing
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.server import GraphServer
    from titan_tpu.utils.jitcache import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    closed_jobs = files.load_module("drivers", "closed_jobs")
    _bench, _cell, config, mix = files.cell_files("kron-s22.bfs-tree-c2")
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    t0 = time.time()
    n, src, dst, perm = loadgen.make_graph(config, args.seed)
    pools = loadgen.draw_pools(np.bincount(src, minlength=n), mix, config,
                               perm)
    sources = [int(s) for s in pools["source"][:args.sources]]
    snap = snap_mod.from_arrays(n, src, dst)
    indptr, indices = csr.structure(n, src, dst)
    del src, dst
    g = H.build_chunked_csr(snap)
    into = ref.transposed(indptr, indices)
    with ThreadPoolExecutor(ref.WORKERS) as workers:
        want = dict(zip(sources, workers.map(
            lambda s: ref.depths(indptr, indices, s), sources)))
    out: dict = {"n": n, "q_total": int(g["q_total"]), "sources": sources,
                 "td_caps": list(H._td_caps(g)),
                 "device": f"{device.platform}:{device.device_kind}"}
    if hasattr(H, "_bu_caps"):
        out["bu_caps"] = [list(c) for c in H._bu_caps(g)]
    print(f"graph: n={n} q_total={g['q_total']} sources={sources} in "
          f"{time.time() - t0:.1f} s", flush=True)
    for s in sources:
        sizes = np.bincount(want[s][want[s] < ref.UNREACHED]).tolist()
        print(json.dumps({"source": s, "degree": int(indptr[s + 1]
                                                     - indptr[s]),
                          "level_sizes": sizes}), flush=True)

    # 1. served: one job a source through POST /jobs
    sched = JobScheduler(snapshot=snap)
    http_srv = GraphServer(None, port=0, scheduler=sched).start()
    http = loadgen.Http(f"http://{http_srv.host}:{http_srv.port}")
    tracer = tracing.current()
    prof = sched.profiler
    served = []

    def held(s, parent=None, dist=None) -> dict:
        """What the reference reads in an answer, and how long it took."""
        t0 = time.time()
        got = {} if parent is None else ref.broken(
            into, want[s], s, parent)
        check_ms = round((time.time() - t0) * 1e3, 1)
        if dist is not None:
            got["depths_differ"] = int((np.asarray(dist) != want[s]).sum())
        return {"broken": got, "check_ms": check_ms}

    for s in sources:
        for parents in (True, False):
            body = loadgen.render(mix["request"]["body"], {"source": s})
            if not parents:
                del body["parents"]
            before = prof.compiles() if prof is not None else 0
            t0 = time.time()
            env = closed_jobs.await_job(http, mix, body)
            t_done = time.time()
            name = "parent" if parents else "dist"
            array, fetch_s = closed_jobs.fetch_result(
                http, dict(mix, result_array=name), env)
            devprof.drain()
            spans = tracer.window(t0, time.time()) \
                if tracer is not None else []
            mine = [x for x in spans if x["trace"] == env["job"]]
            rows = level_rows(mine)
            row = {"part": "served", "source": s, "parents": parents,
                   "exec_ms": env.get("exec_ms"),
                   "queue_ms": env.get("queue_ms"),
                   "wall_ms": round((t_done - t0) * 1e3, 1),
                   "fetch_ms": round(fetch_s * 1e3, 1),
                   "result": env.get("result"),
                   "compiles": (prof.compiles() - before)
                   if prof is not None else None,
                   **(held(s, parent=array) if parents
                      else held(s, dist=array)),
                   "built": compile_rows(spans),
                   "by_direction": by_direction(rows),
                   "result_ms": [round(x["duration_ms"], 1) for x in mine
                                 if x["name"] == "bfs.result"],
                   "levels": rows, "kernels": kernel_rows(mine)}
            served.append(row)
            print(json.dumps(row), flush=True)
    out["served"] = served
    both = {p: [r for r in served if r["parents"] is p and not r["compiles"]]
            for p in (True, False)}
    if both[True] and both[False]:
        med = {p: float(np.median([r["exec_ms"] for r in rows]))
               for p, rows in both.items()}
        out["served_tree_cost_ms"] = round(med[True] - med[False], 1)
        print(json.dumps({"served exec_ms, median of the jobs that built "
                          "nothing": {"parents": med[True],
                                      "depths": med[False],
                                      "the tree costs": out[
                                          "served_tree_cost_ms"]}}),
              flush=True)

    # 2. direct: the loop at K = 1 under a scope of the probe's own
    direct = []
    for i, s in enumerate(sources):
        pair = {}
        for parents in (False, True):
            trace_id = f"probe-{i}-{int(parents)}"
            root = tracer.start(trace_id, "run", source=s)
            t0 = time.time()
            with tracing.scope(tracer, trace_id, root):
                got, levels, _done = H.frontier_bfs_batched(
                    snap, [s], parents=parents)
            wall = time.time() - t0
            tracer.end(root)
            devprof.drain()
            mine = [x for x in tracer.window(t0 - 1.0, time.time())
                    if x["trace"] == trace_id]
            rows = level_rows(mine)
            dist, parent = got if parents else (got, None)
            row = {"part": "direct", "source": s, "parents": parents,
                   "wall_ms": round(wall * 1e3, 1),
                   "levels_run": int(levels[0]),
                   **held(s, parent=None if parent is None else parent[0],
                          dist=dist[0]),
                   "by_direction": by_direction(rows),
                   "levels": rows, "kernels": kernel_rows(mine)}
            pair[parents] = row
            direct.append(row)
            print(json.dumps(row), flush=True)
        cost = {d: round(pair[True]["by_direction"].get(d, 0.0)
                         - pair[False]["by_direction"].get(d, 0.0), 2)
                for d in pair[True]["by_direction"]}
        print(json.dumps({"source": s, "the tree costs, by direction":
                          cost}), flush=True)
    out["direct"] = direct

    # 3. the direction rule's one constant, same programs
    rule = []
    costs = [float(c) for c in args.td_bu_costs.split(",") if c]
    was = H.TD_BU_COST
    for cost in costs:
        H.TD_BU_COST = cost
        for i, s in enumerate(sources):
            trace_id = f"probe-rule-{cost}-{i}"
            root = tracer.start(trace_id, "run", source=s)
            t0 = time.time()
            with tracing.scope(tracer, trace_id, root):
                (dist, parent), levels, _done = H.frontier_bfs_batched(
                    snap, [s], parents=True)
            wall = time.time() - t0
            tracer.end(root)
            devprof.drain()
            mine = [x for x in tracer.window(t0 - 1.0, time.time())
                    if x["trace"] == trace_id]
            rows = level_rows(mine)
            row = {"part": "rule", "td_bu_cost": cost, "source": s,
                   "wall_ms": round(wall * 1e3, 1),
                   **held(s, parent=parent[0], dist=dist[0]),
                   "by_direction": by_direction(rows),
                   "dirs": [(r["level"], r.get("dir"),
                             r.get("p_cap") or r.get("c_cap"), r["ms"])
                            for r in rows if r["phase"] == "bfs.sweep"]}
            rule.append(row)
            print(json.dumps(row), flush=True)
        walls = [r["wall_ms"] for r in rule if r["td_bu_cost"] == cost]
        print(json.dumps({"td_bu_cost": cost, "wall_ms of the sources":
                          walls, "sum": round(sum(walls), 1)}), flush=True)
    H.TD_BU_COST = was
    out["rule"] = rule

    # 4. the single-source family, same graph, same sources
    single = []
    for s in sources[:args.single]:
        times = []
        for _ in range(2):
            t0 = time.time()
            dist, levels = H.frontier_bfs_hybrid(snap, s,
                                                 return_device=True)
            jax.block_until_ready(dist)
            times.append(round((time.time() - t0) * 1e3, 1))
        row = {"part": "single", "source": s, "first_ms": times[0],
               "ms": times[1], "levels_run": int(levels),
               **held(s, dist=np.asarray(dist)[:n])}
        single.append(row)
        print(json.dumps(row), flush=True)
    out["single"] = single

    http_srv.stop()
    sched.close()
    if prof is not None:
        out["compiles"] = prof.stats()
        print(json.dumps({"compiles": out["compiles"]}), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
