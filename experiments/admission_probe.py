"""Chip check (PR 41): what each admission of a benchmark run cost and
whether it read a degree array.

    python experiments/admission_probe.py --workload g500-24.wcc-c2 \
        --seed 3000004101 --seconds 45 --trace 1

It runs ``benchmark/run.py``'s own ``run()`` (the result line is printed
as the benchmark prints it) and then reads the program's journal: every
``job.admit`` span (jobs) and ``admit`` phase (lane), parted into the
warm-up's and the window's by when ``Session.window`` was entered, with
its wall, the bytes it reserved (``bytes`` / ``nbytes``) and the passes
over a degree array it paid (``sizing_passes``; None on a commit from
before the attribute). Beside them the process-wide counter
``serving.hbm.sizing_passes{image}``.

One JSON line, also written to ``chiprun_out/admission_probe-<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

ADMISSIONS = ("job.admit", "admit")


def _digest(admits) -> dict:
    import spans
    import stats
    ms = [s["duration_ms"] for s in admits]
    return {
        "n": len(admits),
        "ms": {"min": round(min(ms), 3), "p50": round(stats.median(ms), 3),
               "max": round(max(ms), 3)} if ms else None,
        "bytes": sorted({spans.attr(s, "bytes", spans.attr(s, "nbytes"))
                         for s in admits}, key=str),
        "sizing_passes": [spans.attr(s, "sizing_passes") for s in admits][:8]
        + (["..."] if len(admits) > 8 else []),
        "passes_paid": sum(spans.attr(s, "sizing_passes") or 0
                           for s in admits),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import files
    import run
    import spans

    entered: dict = {}
    window = run.Session.window

    def noted(self, *a, **kw):
        entered.setdefault("at", time.time())
        return window(self, *a, **kw)

    run.Session.window = noted
    sys.path.insert(0, files.ROOT)
    result = run.run(args)
    print(json.dumps(result), flush=True)

    from titan_tpu.utils.metrics import MetricManager
    metrics = MetricManager.instance()
    admits = spans.named(spans.journal().window(0.0), *ADMISSIONS)
    out = {
        "workload": args.workload, "seed": args.seed,
        "warm_up": _digest([s for s in admits
                            if s["start"] < entered["at"]]),
        "window": _digest([s for s in admits
                           if s["start"] >= entered["at"]]),
        "counter": {image: metrics.counter_value(
            "serving.hbm.sizing_passes", {"image": image})
            for image in ("out", "in")},
    }
    print("admission_probe " + json.dumps(out), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"admission_probe-{args.workload}.json"),
              "w") as f:
        json.dump({"probe": out, "result": result}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
