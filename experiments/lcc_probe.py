"""Chip probe (PR 42; the credits PR 48): an LCC job at
``graphalytics-g500-22-lcc``'s size, by program, and its counts held
against the plain reference.

    python experiments/lcc_probe.py [--seed 3000004201] [--hubs 8192,16384]
                                    [--exact]

It builds the cell's own graph (the benchmark's generator and
relabelling) and for each hub count in ``--hubs`` the LCC image
(``models/lcc.lcc_image``: its host seconds and bytes), then times the
programs of a job (``models/lcc.py``), each call awaited, median of 3:
``lcc_pass`` over a dispatch's columns of the job's own lanes
(``lcc.pass_chunk``: 875,520 at graph500-22, 7.0 M lanes, a
2 KB row gathered a lane at 16,384 hubs; every edge with a hub at an
end stands in ONE lane since PR 48, where both its directions stood in
PageRank's pull image) with the bytes it gathers a second against the
chip's memory roofline, ``lcc_colsum`` over 2^20 low-low edges,
``lcc_tail`` a class; then **the credits** (ISSUE 48, step 4): an
edge's count goes to the vertex at its other end, 88 M counts a job
into 2.4 M vertices, and two forms can do it: XLA's scatter-add
(``t.at[ids].add(counts)``: the lanes' unsorted names, a low-low edge's
two ends, the second rising, with and without ``indices_are_sorted``,
and all of them in one), and ``lax.sort`` of (name, count) with
``ops/segment.seg_scan`` along the runs and one read a vertex (the sort
alone at the lanes' 42 M, the low-low ends' 46 M and all 88 M; the scan;
the read); the finish whole in either form (``lcc_finish`` is the sort's;
the scatter's lives here, ``finish_by_scatter``), which must agree to
the last count; then whole jobs as the batcher runs them (the dispatches
one ahead of the device, ``lcc_finish`` and the readback behind them)
and the device's peak memory. The floor PR 42 measured the tail
against, ``lax.sort`` of 2^27 two-word keys (404.2 ms), is in PERF.md 5.

``--exact`` (once, at the module's own hub count): ``triangle_counts``
of a job held against the reference's int64 counts
(``benchmark/reference/lcc.py``, computed meanwhile on a thread), every
vertex, exactly; the coefficients by the epsilon rule; and the control:
the reference's own coefficients stored in bfloat16 fail the rule (the
count outside, the largest relative error).

Prints one JSON line a finding and writes everything to
``chiprun_out/lcc_probe.json``. ``--cpu --scale 12`` rehearses off the
chip (counts, never times).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3000004201)
    ap.add_argument("--scale", type=int, default=None,
                    help="another Kronecker scale than the cell's 22")
    ap.add_argument("--hubs", default=None,
                    help="hub counts to probe, comma-separated "
                         "(default: the module's)")
    ap.add_argument("--exact", action="store_true",
                    help="hold a job's counts against the reference")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import files
    import loadgen
    from titan_tpu.models import lcc as L
    from titan_tpu.models.pagerank_pull import pull_columns
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.ops.segment import seg_scan
    from titan_tpu.utils.jitcache import dev_scalar, enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    config = files.load_json("configs", "graphalytics-g500-22-lcc.json")
    peaks = files.load_json("peaks.json")["devices"]
    peak_hbm = float(peaks.get(device.device_kind, peaks["TPU v5 lite"])
                     ["hbm_bytes_per_s"])
    if args.scale is not None:
        config = dict(config, scale=args.scale)
    found: list = []

    def say(**finding):
        found.append(finding)
        print(json.dumps(finding), flush=True)

    def timed(fn, repeat: int = 3):
        """(median ms of ``repeat`` awaited calls behind one that
        compiles, the last result)."""
        out = jax.block_until_ready(fn())
        ms = []
        for _ in range(repeat):
            t0 = time.time()
            out = jax.block_until_ready(fn())
            ms.append((time.time() - t0) * 1e3)
        return sorted(ms)[len(ms) // 2], out

    t0 = time.time()
    n, src, dst, _perm = loadgen.make_graph(config, args.seed)
    reference = {}
    if args.exact:
        def refer():
            from reference import csr
            from reference import lcc as ref_lcc
            t = time.time()
            ref = ref_lcc.prepare(n, *csr.structure(n, src, dst), {}, {})
            reference.update(ref=ref, mod=ref_lcc,
                             reference_s=time.time() - t)
        worker = threading.Thread(target=refer)
        worker.start()
    snap = snap_mod.from_arrays(n, src, dst)
    q = pull_columns(snap.indptr_in, n)
    say(graph={"n": n, "q_in": q, "edges": len(src) // 2,
               "seconds": round(time.time() - t0, 1)})

    def probe(hubs: int) -> None:
        if hasattr(snap, "_lcc_csr"):
            del snap._lcc_csr           # one table at a time on the chip
        t0 = time.time()
        im = L.lcc_image(snap, hubs)
        words = im["table"].shape[1]
        columns = im["idx8"].shape[1]
        say(hubs=hubs, image={
            "seconds": round(time.time() - t0, 1), "bytes": im["bytes"],
            "priced": L.image_bytes(n, q, hubs),
            "table_bytes": int(im["table"].nbytes),
            "pass_columns": columns, "hub_edges": im["hub_edges"],
            "seg_max": im["seg_max"], "credit_max": im["credit_max"],
            "low_low_edges": im["ll_edges"], "tail_wedges": im["wedges"],
            "tail_rows": list(im["rows"].shape),
            "tail_blocks": [list(b["nbr"].shape) for b in im["blocks"]]})
        chunk = L.pass_chunk(columns)
        starts = L._starts(columns, chunk)
        trim = starts[-2] + chunk - starts[-1] if len(starts) > 1 else 0

        def hub_pass(c0):
            return L._pass()(
                im["table"], im["idx8"], im["own"], im["hubl"],
                dev_scalar(c0), chunk=chunk, tile=L.PASS_TILE)
        ms, _ = timed(lambda: hub_pass(0))
        gathered = 9 * chunk * words * 4        # 8 lanes and the owner
        say(hubs=hubs, lcc_pass={
            "columns": chunk, "ms": round(ms, 2),
            "gathered_GB_s": round(gathered / ms / 1e6, 1),
            "roofline_pct": round(100 * gathered / (ms / 1e3) / peak_hbm,
                                  1),
            "dispatches": len(starts),
            "whole_pass_ms": round(ms * len(starts), 1)})
        col_starts = range(0, im["ll"].shape[1], im["col_chunk"]) \
            if im["ll_edges"] else ()

        def colsum(e0):
            return L._colsum()(im["table"], im["ll"], dev_scalar(e0),
                               chunk=im["col_chunk"], tile=L.COL_TILE)
        if im["ll_edges"]:
            cc = im["col_chunk"]
            ms, _ = timed(lambda: colsum(0))
            say(hubs=hubs, lcc_colsum={
                "edges": cc, "ms": round(ms, 2),
                "gathered_GB_s": round(2 * cc * words * 4 / ms / 1e6, 1),
                "dispatches": len(col_starts),
                "whole_ms": round(ms * len(col_starts), 1)})
        tail_ms = 0.0
        credits = []
        for blk in im["blocks"]:
            ms, (place, centre) = timed(lambda: L._tail()(
                im["rows"], blk["nbr"], blk["rows"], per=blk["per"]))
            credits += [(blk["mid"], place), (blk["centres"], centre)]
            b, d = blk["nbr"].shape
            tail_ms += ms
            say(hubs=hubs, lcc_tail={
                "block": [b, d], "ms": round(ms, 2),
                "compares_G_s": round(
                    b * d * d * im["rows"].shape[1] / ms / 1e6, 1)})
        say(hubs=hubs, lcc_tail_whole_ms=round(tail_ms, 1))
        probe_credits(hubs, im, [hub_pass(c0) for c0 in starts], trim,
                      [colsum(e0) for e0 in col_starts], tuple(credits))
        jobs = []
        for _ in range(3):
            t0 = time.time()
            counts, coeff = L.lcc(snap, hubs=hubs)
            jobs.append(round(time.time() - t0, 3))
        stats = device.memory_stats() or {}
        say(hubs=hubs, job_s=jobs, triangles=int(
            counts.sum(dtype=np.int64)) // 3,
            memory={k: stats.get(k) for k in ("peak_bytes_in_use",
                                              "bytes_in_use")})

    def probe_credits(hubs, im, passed, trim, summed, tail_credits):
        """ISSUE 48, step 4: the two forms of crediting an edge's count
        to the vertex at its other end, on a job's own names and
        counts."""
        cols = tuple(p[0] for p in passed)
        lanes = tuple(p[1] for p in passed)
        hub_sums = tuple(p[0] for p in summed)
        ll_counts = tuple(p[1] for p in summed)
        lane_counts = jnp.concatenate(
            lanes[:-1] + (lanes[-1][:, trim:],), axis=1).reshape(-1)
        twice = 2 * jnp.concatenate(ll_counts + (jnp.zeros(0, jnp.int32),))
        ll = im["ll"][:, :twice.shape[0]]
        lane_names = im["idx8"].reshape(-1)
        names = jnp.concatenate([lane_names, ll[0], ll[1]])
        counts = jnp.concatenate([lane_counts, twice, twice])
        zero = jnp.zeros(n + 2, jnp.int32)

        add = jax.jit(lambda t, i, c: t.at[i].add(c))
        add_sorted = jax.jit(
            lambda t, i, c: t.at[i].add(c, indices_are_sorted=True))
        for what, fn, i, c in (
                ("lanes", add, lane_names, lane_counts),
                ("low_low_first_end", add, ll[0], twice),
                ("low_low_second_end_rising", add, ll[1], twice),
                ("low_low_second_end_said_sorted", add_sorted, ll[1],
                 twice),
                ("all", add, names, counts)):
            ms, _ = timed(lambda: fn(zero, i, c))
            say(hubs=hubs, credit_scatter={
                "what": what, "updates": int(i.shape[0]),
                "ms": round(ms, 2),
                "ns_an_update": round(ms * 1e6 / max(int(i.shape[0]), 1),
                                      2)})
        sort = jax.jit(lambda k, v: jax.lax.sort(
            (k, v), num_keys=1, is_stable=False))
        for what, k, v in (
                ("lanes", lane_names, lane_counts),
                ("low_low_both_ends", jnp.concatenate([ll[0], ll[1]]),
                 jnp.concatenate([twice, twice])),
                ("all", names, counts)):
            ms, out = timed(lambda: sort(k, v))
            say(hubs=hubs, credit_sort={
                "what": what, "pairs": int(k.shape[0]), "ms": round(ms, 2),
                "ms_a_million": round(ms * 1e6 / max(int(k.shape[0]), 1),
                                      3)})
        named, by_name = out
        scan = jax.jit(lambda k, v: seg_scan(
            v, jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]]),
            "sum", max_len=im["credit_max"]))
        ms, run = timed(lambda: scan(named, by_name))
        say(hubs=hubs, credit_scan={"pairs": int(named.shape[0]),
                                    "max_len": im["credit_max"],
                                    "ms": round(ms, 2)})
        read = jax.jit(lambda r, last: jnp.where(
            last >= 0, r[jnp.maximum(last, 0)], 0))
        ms, _ = timed(lambda: read(run, im["credit_last"]))
        say(hubs=hubs, credit_read={"vertices": n, "ms": round(ms, 2)})

        reads = {k: im[k] for k in L._FINISH_READS}
        statics = {"seg_max": im["seg_max"], "trim": trim}

        @functools.partial(jax.jit, static_argnames=("seg_max", "trim"))
        def finish_by_scatter(cols, lanes, ll_counts, hub_sums, credits,
                              im, seg_max: int, trim: int):
            """``lcc_finish`` with form (i): every count scattered."""
            cols2 = jnp.concatenate(cols[:-1] + (cols[-1][trim:],))
            lanes2 = jnp.concatenate(
                lanes[:-1] + (lanes[-1][:, trim:],), axis=1)
            twice = 2 * jnp.concatenate(
                ll_counts + (jnp.zeros(0, jnp.int32),))
            ll = im["ll"][:, :twice.shape[0]]
            own = seg_scan(cols2, im["first"], "sum", max_len=seg_max)
            t = jnp.zeros(im["deg"].shape[0] + 2, jnp.int32)
            for ids, c in ((im["owners"], own[im["last"]]),
                           (im["idx8"].reshape(-1), lanes2.reshape(-1)),
                           (ll[0], twice), (ll[1], twice)):
                t = t.at[ids].add(c)
            t = t // 2
            hubs = im["hub_ids"].shape[0]
            t = t.at[im["hub_ids"]].add(
                sum((part[:hubs] for part in hub_sums),
                    jnp.zeros(hubs, jnp.int32)))
            for ids, c in credits:
                t = t.at[ids.reshape(-1)].add(c.reshape(-1))
            return t[:im["deg"].shape[0]]

        ms_sort, (by_sort, _coeff) = timed(lambda: L._finish()(
            cols, lanes, ll_counts, hub_sums, tail_credits, reads,
            credit_max=im["credit_max"], **statics))
        ms_scatter, by_scatter = timed(lambda: finish_by_scatter(
            cols, lanes, ll_counts, hub_sums, tail_credits, reads,
            **statics))
        say(hubs=hubs, finish={
            "sort_ms": round(ms_sort, 1),
            "scatter_ms": round(ms_scatter, 1),
            "counts_that_differ": int((np.asarray(by_sort)
                                       != np.asarray(by_scatter)).sum())})

    hub_counts = [int(h) for h in args.hubs.split(",")] if args.hubs \
        else [L.HUBS]
    for hubs in hub_counts:
        try:
            probe(hubs)
        except Exception as e:      # a table too large for the chip
            say(hubs=hubs, error=f"{type(e).__name__}: {e}"[:400])

    if args.exact:
        worker.join()
        ref, mod = reference["ref"], reference["mod"]
        counts, coeff = L.lcc(snap)
        wrong = int((counts.astype(np.int64) != ref.triangles).sum())
        rel = np.abs(coeff.astype(np.float64) - ref.lcc) \
            / np.where(ref.lcc > 0, ref.lcc, 1.0)
        import ml_dtypes
        rounded = ref.lcc.astype(ml_dtypes.bfloat16).astype(np.float64)
        rel16 = np.abs(rounded - ref.lcc) / np.where(ref.lcc > 0,
                                                     ref.lcc, 1.0)
        say(exact={
            "vertices": n, "counts_that_differ": wrong,
            "triangles": int(ref.triangles.sum()) // 3,
            "largest_count": int(ref.triangles.max()),
            "outside_the_rule": mod.outside(coeff, ref.lcc),
            "largest_relative_error": float(rel.max()),
            "reference_s": round(reference["reference_s"], 1),
            "bfloat16_control": {
                "outside_the_rule": mod.outside(rounded, ref.lcc),
                "largest_relative_error": float(rel16.max())}})
        if wrong:
            return 1

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "lcc_probe.json"), "w") as fh:
        json.dump(found, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
