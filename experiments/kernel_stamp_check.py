"""Chip check (PR 38): the ``kernel`` spans' stamped device time against
the device trace, in one traced run of a benchmark cell.

    python experiments/kernel_stamp_check.py --workload g500-24.wcc-c2 \
        --seed 3000003801 --seconds 45

It runs ``benchmark/run.py``'s own ``run()`` with ``--trace 1`` (the
result line is printed as the benchmark prints it) and notes when the
traced slice began and ended (``run.take_trace`` sets ``window_s`` the
moment before it stops the trace). Then, from the program's journal:

* ``stamped_in_slice``: the ``kernel`` spans' intervals clipped to the
  slice, summed, beside the trace's ``busy_s`` (the union of the device
  plane's ``XLA Ops``) and the sum of ``device_ms`` of the spans whose
  ``ready`` lies in the slice;
* ``resolution``: for every leaf phase that ends in a blocking readback
  (``sync_ms`` among its attributes) the moment its last kernel was
  stamped ready against the moment the phase ended: two readings of one
  moment, the readback's own copy between them;
* ``unstamped``: calls the watcher could not stamp, of all calls.

One JSON line, also written to ``chiprun_out/kernel_stamp_check-<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

SLICE: dict = {}


def _spread(values) -> dict:
    import stats
    return {"n": len(values), "min": round(min(values), 3),
            "p50": round(stats.median(values), 3),
            "p95": round(stats.percentile(values, 95.0), 3),
            "max": round(max(values), 3)} if values else {"n": 0}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args()
    args.trace = 1

    import files
    import run
    import spans

    take = run.take_trace

    def take_trace(start_at, slice_s, trace_dir, out):
        class Out(dict):
            def __setitem__(self, key, value):
                if key == "window_s":
                    SLICE.update(end=time.time(), window_s=value)
                super().__setitem__(key, value)
        proxy = Out()
        take(start_at, slice_s, trace_dir, proxy)
        out.update(proxy)

    run.take_trace = take_trace
    sys.path.insert(0, files.ROOT)
    result = run.run(args)
    print(json.dumps(result), flush=True)

    t1 = SLICE["end"]
    t0 = t1 - SLICE["window_s"]
    got = spans.journal().window(0.0)
    kernels = spans.named(got, "kernel")
    clipped = sum(max(min(s["end"], t1) - max(s["start"], t0), 0.0)
                  for s in kernels)
    by_ready = sum(spans.attr(s, "device_ms") for s in kernels
                   if t0 <= s["end"] < t1) / 1e3
    busy = result["device"]["busy_s"]

    # a phase's last kernel, stamped ready, against the phase's own end
    by_parent: dict = {}
    for s in kernels:
        by_parent.setdefault((s["trace"], s.get("parent")), []).append(s)
    late, by_phase = [], {}
    for p in got:
        if spans.attr(p, "sync_ms") is None or p["start"] < t0 - 30.0:
            continue
        mine = by_parent.get((p["trace"], p["span"]))
        if mine:
            d = (p["end"] - max(s["end"] for s in mine)) * 1e3
            late.append(d)
            by_phase.setdefault(p["name"], []).append(d)
    out = {
        "workload": args.workload, "seed": args.seed,
        "slice_s": round(SLICE["window_s"], 4), "busy_s": busy,
        "stamped_in_slice_s": round(clipped, 4),
        "stamped_over_busy": round(clipped / busy, 4) if busy else None,
        "stamped_by_ready_s": round(by_ready, 4),
        "by_ready_over_busy": round(by_ready / busy, 4) if busy else None,
        "kernel_spans": len(kernels),
        "unstamped": sum(1 for s in kernels
                         if not spans.attr(s, "stamped")),
        "phase_end_minus_ready_ms": _spread(late),
        "by_phase": {n: _spread(v) for n, v in sorted(by_phase.items())},
        "queued_ms": _spread([spans.attr(s, "queued_ms")
                                 for s in kernels]),
        "device_ms": _spread([spans.attr(s, "device_ms")
                                 for s in kernels]),
    }
    print("kernel_stamp_check " + json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"kernel_stamp_check-{args.workload}.json"),
              "w") as f:
        json.dump({"check": out, "result": result}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
