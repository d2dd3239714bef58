"""Chip probe (PR 39): the split-lane opener at
``graphalytics-g500-24``'s heavy level, and at thinner ones.

    python experiments/bu_dense_probe.py [--seed 3000003901]

It builds the cell's own graph (the benchmark's generator and
relabelling), runs ``hybrid_head`` from the largest-degree vertex as the
peel does, and stands at the level the WCC job pulls: 8.46 M of 8.87 M
vertices unvisited. There, and with the candidates thinned by hand to
1/2, 1/4 and 1/8 of n (the others marked visited at level 0, so the
frontier is the same), it calls ``hybrid_bu_startL`` at the ``c_cap``
the host loop would size, under XLA's byte gather and, where
``vmem_gather.gather_impl`` takes it, under the table in VMEM: the same
``dist``, ``nu`` and untested list from each (checked on the device),
then the median of 5 calls of each, dispatch to the count on the host.
At the full level one call under each gather is traced and its device
operations are listed by their own time (``benchmark/trace_reduce.py``'s
reduction), which names what the program spends its time on. Also: the
image's one build, and the opener's one compaction beside a sort alone.
Prints one JSON line a share and writes everything to
``chiprun_out/bu_dense_probe.json``.

Until PR 39's review this probe also ran the opener the n-wide one
replaced (a list of the candidates: compaction, column gather, byte
gather, scatter, compaction) at the same points; it lost every one
(946.8, 547.0, 330.1, 198.3 ms against 223.4, 220.0, 223.5, 203.2 under
XLA's gather and 115.7, 115.6, 115.7, 95.3 under the table: PERF.md 6,
PR 39) and went with it.

``--cpu --scale 12`` rehearses off the chip (counts, never times).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def traced_ops(fn, top: int = 14) -> dict:
    """One call of ``fn`` under the profiler: the device's busy seconds
    and its operations by own time, each with the times it ran."""
    import jax

    import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix="bu-dense-probe-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        fn()
        jax.profiler.stop_trace()
        planes = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ops: dict = {}
    calls: dict = {}
    busy = 0.0
    for plane, lines in planes:
        lines = dict(lines)
        if plane.startswith("/device:") and trace_reduce.OPS_LINE in lines:
            events = lines[trace_reduce.OPS_LINE]
            busy += sum(e - s for s, e in trace_reduce.union(
                (s, e) for s, e, _n in events)) / 1e6
            for name, t in trace_reduce.self_times(events).items():
                ops[name] = ops.get(name, 0.0) + t / 1e6
            for _s, _e, name in events:
                calls[name] = calls.get(name, 0) + 1
    return {"busy_ms": round(busy, 3),
            "ops_ms": [[n, round(t, 3), calls[n]] for n, t in sorted(
                ops.items(), key=lambda kv: -kv[1])[:top]]}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000003901)
    ap.add_argument("--scale", type=int, default=None,
                    help="another Kronecker scale than the cell's 24")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import files
    import loadgen
    from titan_tpu.models import bfs_hybrid as H
    from titan_tpu.models.bfs import INF, _next_pow2
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.ops.vmem_gather import gather_impl
    from titan_tpu.utils.jitcache import dev_scalar, enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    _bench, _cell, config, _mix = files.cell_files("g500-24.wcc-c2")
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    t0 = time.time()
    n, src, dst, _perm = loadgen.make_graph(config, args.seed)
    snap = snap_mod.from_arrays(n, src, dst)
    del src, dst
    g = H.build_chunked_csr(snap)
    print(f"graph: n={n} q_total={g['q_total']} in {time.time() - t0:.1f} s",
          flush=True)
    dstT, colstart, degc, deg = g["dstT"], g["colstart"], g["degc"], g["deg"]
    lanes = H.SPLIT_LANES
    cap_n = _next_pow2(max(n, 2))

    # -- the peel's head, as frontier_bfs_hybrid runs it
    source = int(np.argmax(snap.out_degree))
    f_cap_h = min(H.HEAD_F_CAP, cap_n)
    p_cap_h = min(H.HEAD_P_CAP, _next_pow2(max(g["q_total"] - 1 + n, 2)))
    dist0, _frontier, st = H._head_loop()(
        dev_scalar(source), dev_scalar(1000), dstT, colstart, degc,
        f_cap=f_cap_h, p_cap=p_cap_h, n_=n)
    f_count, m8_f, m8_unvis, n_unvis, level = (int(x) for x in np.asarray(st))
    print(f"head: level={level} frontier={f_count} n_unvis={n_unvis} "
          f"({n_unvis / n:.3f} of n)", flush=True)

    # -- the image, once
    t0 = time.perf_counter()
    lead = H.leading_lanes(g, lanes)
    lead.block_until_ready()
    lead_first_ms = (time.perf_counter() - t0) * 1e3
    del g[f"_lead{lanes}"]
    t0 = time.perf_counter()
    lead = H.leading_lanes(g, lanes)
    lead.block_until_ready()
    lead_ms = (time.perf_counter() - t0) * 1e3
    bu0a = H._bu_startL()
    lv = dev_scalar(level)

    def call(dist, impl, c_cap):
        return bu0a(dist, lv, lead, deg, degc, c_cap=c_cap, n_=n,
                    lanes=lanes, impl=impl)

    def median_ms(state, impl, c_cap):
        ts = []
        for i in range(6):
            dist = jnp.array(state, copy=True)      # the call donates it
            dist.block_until_ready()
            t0 = time.perf_counter()
            np.asarray(call(dist, impl, c_cap)[3])
            if i:                                   # the first may build
                ts.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(ts)), 3)

    impls = ["xla"] + (["vmem"] if gather_impl(n) == "vmem" else [])
    rng = np.random.default_rng(39)
    unvis_h = np.flatnonzero((np.asarray(dist0)[:n] >= INF)
                             & (np.asarray(degc)[:n] > 0))
    rows = []
    for share in (1.0, 0.5, 0.25, 0.125):
        keep = min(int(share * n), unvis_h.size)
        state_h = np.asarray(dist0).copy()
        if keep < unvis_h.size:
            drop = rng.permutation(unvis_h)[keep:]
            state_h[drop] = 0                       # visited, not frontier
        state = jnp.asarray(state_h)
        c_cap = min(_next_pow2(max(keep, 2)), cap_n)
        want = call(jnp.array(state, copy=True), "xla", c_cap)
        nu = int(np.asarray(want[3])[0])
        row = {"share_of_n": share, "candidates": keep, "c_cap": c_cap,
               "missed": nu}
        for impl in impls:
            got = call(jnp.array(state, copy=True), impl, c_cap)
            row[f"{impl}_same"] = bool(jnp.array_equal(got[0], want[0])) \
                and int(np.asarray(got[3])[0]) == nu \
                and bool(jnp.array_equal(got[2][:nu], want[2][:nu])) \
                and bool(jnp.array_equal(got[4], want[4]))
            row[f"{impl}_ms"] = median_ms(state, impl, c_cap)
        row["device"] = f"{device.platform}:{device.device_kind}"
        print(json.dumps(row), flush=True)
        rows.append(row)
        if share == 1.0:
            full_state, full_cap = state, c_cap

    # -- where the time goes, at the full level
    traces = {}
    for impl in impls:
        dist = jnp.array(full_state, copy=True)
        dist.block_until_ready()
        traces[impl] = traced_ops(
            lambda: np.asarray(call(dist, impl, full_cap)[3]))
        print(json.dumps({impl: traces[impl]}), flush=True)

    # -- what the opener has left: its one compaction (a cumsum, the
    # chip's sort of the scatter's indices and the scatter), beside a
    # sort ALONE doing the same job (the payload is the index itself and
    # the fill lies past every id, so sorted order is compacted order)
    from titan_tpu.ops.compaction import compact_ids

    after = call(jnp.array(full_state, copy=True), impls[-1], full_cap)[0]
    untested = (full_state >= INF) & (degc > 0) & (after >= INF) \
        & (deg > lanes)
    by_scatter = jax.jit(lambda m: compact_ids(m, full_cap, n)[1])
    by_sort = jax.jit(lambda m: jnp.sort(jnp.where(
        m, jnp.arange(m.shape[0], dtype=jnp.int32), n)))
    nu = int(untested.sum())
    assert bool(jnp.array_equal(by_scatter(untested)[:nu],
                                by_sort(untested)[:nu]))
    assert int(by_sort(untested)[nu]) == n

    def ms_of(fn):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(fn(untested)[:1])
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(float(np.median(ts)), 3)

    compaction = {"untested": nu, "by_scatter_ms": ms_of(by_scatter),
                  "by_sort_ms": ms_of(by_sort)}
    print(json.dumps({"compaction": compaction}), flush=True)

    stats = device.memory_stats() or {}
    out = {"n": n, "level": level, "n_unvis": n_unvis, "lanes": lanes,
           "lead_build_first_ms": round(lead_first_ms, 1),
           "lead_build_ms": round(lead_ms, 3),
           "lead_bytes": int(lead.size) * 4, "rows": rows,
           "traces": traces, "compaction": compaction,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "device": f"{device.platform}:{device.device_kind}"}
    print(json.dumps({k: out[k] for k in (
        "lead_build_first_ms", "lead_build_ms", "lead_bytes",
        "peak_bytes_in_use")}), flush=True)
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "bu_dense_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
