"""Chip probe (PR 42): an LCC job at ``graphalytics-g500-22-lcc``'s size,
by program, and its counts held against the plain reference.

    python experiments/lcc_probe.py [--seed 3000004201] [--hubs 8192,16384]
                                    [--exact]

It builds the cell's own graph (the benchmark's generator and
relabelling) and its pull image, and for each hub count in ``--hubs``
the LCC image (``models/lcc.lcc_image``: its host seconds and bytes),
then times the programs of a job (``models/lcc.py``), each call awaited,
median of 3: ``lcc_pass`` over 2^20 columns (8.4 M lanes, a 2 KB row
gathered a lane at 16,384 hubs) with the bytes it gathers a second
against the chip's memory roofline, ``lcc_colsum`` over 2^20 low-low
edges, ``lcc_tail`` a class; then whole jobs as the batcher runs them
(the dispatches one ahead of the device, ``lcc_finish`` and the
readback behind them) and the device's peak memory. Beside them the floor the tail is measured
against: ``lax.sort`` of 2^27 two-word keys, what a sort-join pays a
wedge before it has joined anything.

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
    from titan_tpu.models.pagerank_pull import pull_image
    from titan_tpu.olap.tpu import snapshot as snap_mod
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
    pim = pull_image(snap)
    q = pim["q_in"]
    idx8 = pim["idx"].reshape(8, q)
    say(graph={"n": n, "q_in": q, "lanes": 8 * q,
               "seg_max": pim["seg_max"],
               "seconds": round(time.time() - t0, 1)})

    def probe(hubs: int) -> None:
        if hasattr(snap, "_lcc_csr"):
            del snap._lcc_csr           # one table at a time on the chip
        t0 = time.time()
        im = L.lcc_image(snap, hubs)
        words = im["table"].shape[1]
        say(hubs=hubs, image={
            "seconds": round(time.time() - t0, 1), "bytes": im["bytes"],
            "priced": L.image_bytes(n, q, hubs),
            "table_bytes": int(im["table"].nbytes),
            "low_low_edges": im["ll_edges"], "tail_wedges": im["wedges"],
            "tail_rows": list(im["rows"].shape),
            "tail_blocks": [list(b["nbr"].shape) for b in im["blocks"]]})
        chunk = min(L.PASS_CHUNK, q)
        ms, _ = timed(lambda: L._pass()(
            im["table"], idx8, im["own"], im["hubl"], dev_scalar(0),
            chunk=chunk, tile=min(L.PASS_TILE, chunk)))
        gathered = 9 * chunk * words * 4        # 8 lanes and the owner
        say(hubs=hubs, lcc_pass={
            "columns": chunk, "ms": round(ms, 2),
            "gathered_GB_s": round(gathered / ms / 1e6, 1),
            "roofline_pct": round(100 * gathered / (ms / 1e3) / peak_hbm,
                                  1),
            "whole_pass_ms": round(ms * q / chunk, 1)})
        if im["ll_edges"]:
            cc = im["col_chunk"]
            ms, _ = timed(lambda: L._colsum()(
                im["table"], im["ll"], dev_scalar(0), chunk=cc,
                tile=L.COL_TILE))
            say(hubs=hubs, lcc_colsum={
                "edges": cc, "ms": round(ms, 2),
                "gathered_GB_s": round(2 * cc * words * 4 / ms / 1e6, 1),
                "whole_ms": round(ms * im["ll"].shape[1] / cc, 1)})
        tail_ms = 0.0
        for blk in im["blocks"]:
            ms, _ = timed(lambda: L._tail()(
                im["rows"], blk["nbr"], blk["rows"], per=blk["per"]))
            b, d = blk["nbr"].shape
            tail_ms += ms
            say(hubs=hubs, lcc_tail={
                "block": [b, d], "ms": round(ms, 2),
                "compares_G_s": round(
                    b * d * d * im["rows"].shape[1] / ms / 1e6, 1)})
        say(hubs=hubs, lcc_tail_whole_ms=round(tail_ms, 1))
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

    hub_counts = [int(h) for h in args.hubs.split(",")] if args.hubs \
        else [L.HUBS]
    for hubs in hub_counts:
        try:
            probe(hubs)
        except Exception as e:      # a table too large for the chip
            say(hubs=hubs, error=f"{type(e).__name__}: {e}"[:400])

    if not args.cpu:
        keys = (jnp.arange(1 << 27, dtype=jnp.int32) * 40503 % 4194301,
                jnp.arange(1 << 27, dtype=jnp.int32) * 28657 % 4194287)
        sort = jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=2,
                                                 is_stable=False))
        ms, _ = timed(lambda: sort(*keys))
        say(sort_join_floor={"keys": 1 << 27, "ms": round(ms, 1),
                             "ms_a_million": round(ms / 134.2, 2)})
        del keys

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
