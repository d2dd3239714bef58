"""Chip probe (PR 43): the frontier test of the list-wide bottom-up
programs at ``graphalytics-g500-24``'s shapes: ``hybrid_endgame``
(``end``) and ``hybrid_bu_finish0`` (``bu0b``) of a WCC job's peel.

    python experiments/endgame_probe.py [--seed 3000004301]

It builds the cell's own graph (the benchmark's generator and
relabelling), runs ``frontier_bfs_hybrid`` from the largest-degree
vertex as the peel does, and keeps the arguments the run handed to
``bu0b`` and to ``end`` (``dist`` copied: the programs donate it). Then,
for each road that can serve the frontier test here (XLA's byte gather
of ``_fbit_of``, and the frontier as a table in VMEM where
``vmem_gather.gather_impl`` takes it), it calls each program on that
state: the same ``dist`` and counts from each road (checked on the
device), the median of 5 calls dispatch to readback, and one traced
call whose device operations are listed by their own time with the
times each ran: how many bodies ``end`` runs, what each body's gather
costs, what the rest of a level is.

Also the test alone, at the widths the programs run it on: a block of
8 x Q random vertex ids, Q = 2^10 .. 2^20, under each road (one traced
call each, the device's busy time), beside the one pass that makes the
road's image of the frontier (``_pack_bits``; the 0/1 float32 table):
the column count from which the table pays is read off these lines.

Prints one JSON line a measurement and writes everything to
``chiprun_out/endgame_probe.json``. ``--cpu --scale 12`` rehearses off
the chip (counts, never times; the kernel in Pallas's interpreter).
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

KEYS = ("hybrid_bu_finish0", "hybrid_endgame")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000004301)
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
    from bu_dense_probe import traced_ops
    from titan_tpu.models import bfs_hybrid as H
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.ops import vmem_gather as vg
    from titan_tpu.utils import jitcache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    jitcache.enable_compile_cache()
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
    vmem = vg.gather_impl(n) == "vmem" or args.cpu
    if args.cpu:
        # no Mosaic here: Pallas's interpreter runs the kernel, and the
        # rehearsal takes the table's road whatever the backend
        vg.colsum_vmem = functools.partial(vg.colsum_vmem, interpret=True)
        vg.gather_impl = lambda _n: "vmem"
        H.SPLIT_LANE_MIN, H.HEAD_F_CAP = 2, 1
        H.END_C_CAP = H.END_P_CAP = n // 8          # a pulled level first
    impls = ["xla"] + (["vmem"] if vmem else [])

    # -- the peel, once, keeping what it handed to bu0b and to end
    held: dict = {}
    H._bu_finish_chunk0(), H._endgame()             # built and registered

    def keeping(key, fn):
        def call(dist, *rest, **statics):
            if key not in held:
                held[key] = (jnp.array(dist, copy=True), rest, statics)
            return fn(dist, *rest, **statics)
        return call

    raw = {key: jitcache._JITS[key] for key in KEYS}
    for key in KEYS:
        jitcache._JITS[key] = keeping(key, raw[key])
    source = int(np.argmax(snap.out_degree))
    dist_run, levels = H.frontier_bfs_hybrid(g, source, return_device=True)
    dist_run.block_until_ready()
    jitcache._JITS.update(raw)
    print(json.dumps({"levels": int(levels), "kept": {
        key: held[key][2] for key in held}}), flush=True)

    def run(key, impl, dist=None):
        kept, rest, statics = held[key]
        if dist is None:
            dist = jnp.array(kept, copy=True)
        return raw[key](dist, *rest, **dict(statics, impl=impl))

    def readback(key, out):
        # what the host step reads: bu0b's progress, end's level count
        return np.asarray(out[2] if key == "hybrid_bu_finish0" else out[1])

    programs: dict = {}
    for key in KEYS:
        if key not in held:
            print(json.dumps({key: "the run did not call it"}), flush=True)
            continue
        want = run(key, "xla")
        row = {"statics": dict(held[key][2]),
               "read": [int(x) for x in np.atleast_1d(readback(key, want))]}
        for impl in impls:
            got = run(key, impl)
            row[f"{impl}_same"] = bool(jnp.array_equal(got[0], want[0])) \
                and np.array_equal(readback(key, got), readback(key, want))
            ts = []
            for i in range(6):
                dist = jnp.array(held[key][0], copy=True)
                dist.block_until_ready()
                t0 = time.perf_counter()
                readback(key, run(key, impl, dist))
                if i:                               # the first may build
                    ts.append((time.perf_counter() - t0) * 1e3)
            row[f"{impl}_ms"] = round(float(np.median(ts)), 3)
            row[f"{impl}_trace"] = traced_ops(
                lambda: readback(key, run(key, impl)), top=24)
        print(json.dumps({key: row}), flush=True)
        programs[key] = row

    # -- the test alone, by its block's width
    rng = np.random.default_rng(43)
    dist = jnp.array(held["hybrid_endgame"][0] if "hybrid_endgame" in held
                     else dist_run, copy=True)
    level = jnp.int32(int(levels) - 2)

    @jax.jit
    def pack(dist, level):
        return H._pack_bits(dist, level, n)

    @jax.jit
    def table_of(dist, level):
        return vg.as_table((dist == level).astype(jnp.float32))

    @jax.jit
    def by_xla(fbits, parents):
        return H._fbit_of(fbits, parents).any(axis=0)

    @jax.jit
    def by_vmem(table, parents):
        return vg.colsum_vmem(parents.reshape(-1), table, rows=8) > 0

    def busy_ms(fn, *a):
        fn(*a).block_until_ready()                  # built
        return min(traced_ops(lambda: fn(*a).block_until_ready())["busy_ms"]
                   for _ in range(2))

    fbits, table = pack(dist, level), table_of(dist, level)
    images = {"pack_bits_ms": busy_ms(pack, dist, level),
              "table_ms": busy_ms(table_of, dist, level)}
    print(json.dumps({"images": images}), flush=True)
    widths = []
    for shift in ((10, 11) if args.cpu else (10, 11, 12, 13, 14, 16, 18, 20)):
        q = 1 << shift
        parents = jnp.asarray(rng.integers(0, n, (8, q)).astype(np.int32))
        row = {"columns": q, "xla_ms": busy_ms(by_xla, fbits, parents)}
        if vmem:
            row["vmem_ms"] = busy_ms(by_vmem, table, parents)
            row["same"] = bool(jnp.array_equal(by_xla(fbits, parents),
                                               by_vmem(table, parents)))
        print(json.dumps(row), flush=True)
        widths.append(row)

    stats = device.memory_stats() or {}
    out = {"n": n, "levels": int(levels), "programs": programs,
           "images": images, "widths": widths,
           "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
           "device": f"{device.platform}:{device.device_kind}"}
    path = os.path.join(ROOT, "chiprun_out")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "endgame_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
