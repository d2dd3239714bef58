#!/usr/bin/env python3
"""Builder's tool, not the run command: find an open-loop cell's knee.

    python3 benchmark/sweep.py --workload <cell> --seed <n> [--seconds 25]

One set-up, then one window per rate: rates double from 1 req/s while the
system holds them, then one step halfway back between the last rate that
held and the first that did not. A rate holds when nothing failed, the
backlog did not grow (the last third's median latency is at most 1.5 x the
middle third's) and what was still waiting when sending stopped drained
within three batch executions (3 x the median ``exec_ms``) and a second.
The knee is the highest rate that held; the cell's rate is 0.8 x that,
rounded to 0.5 req/s. The output is pasted into PERF.md.
"""

from __future__ import annotations

import argparse
import json
import sys

import files
import run
import stats

GROWTH = 1.5
DRAIN_EXECS = 3.0
DRAIN_SLACK_S = 1.0
MAX_RATE = 256.0


def one_rate(session, rate: float, seconds: float, seed: int) -> dict:
    record = session.window(seconds, mix={"rate": rate}, seed=seed)
    w = record["window"]
    stop = w["start"] + seconds
    samples = record["samples"]
    in_time = sum(1 for s in samples if s["ok"] and s["done"] <= stop)
    waiting = sum(1 for s in samples if s["done"] > stop)
    lat = stats.latencies_ms(record)
    row = {"rate": rate, "offered": len(samples), "answered_in_time": in_time,
           "waiting_at_stop": waiting,
           "failed": sum(1 for s in samples if not s["ok"]),
           "p50_ms": stats.median(lat), "p95_ms": stats.percentile(lat, 95),
           "fused_k": stats.mean(stats.field(record, "fused_k") or [0]),
           "exec_ms": stats.median(stats.field(record, "exec_ms") or [0]),
           "compiles": record.get("compiles"),
           "drain_s": w["last_done"] - stop,
           "correct": run.verdict(record)}
    third = len(samples) // 3
    mid, last = lat[third:2 * third] or lat, lat[2 * third:] or lat
    row["growth"] = stats.median(last) / stats.median(mid)
    row["held"] = (row["failed"] == 0 and row["growth"] <= GROWTH
                   and row["drain_s"] <= DRAIN_EXECS * row["exec_ms"] / 1e3
                   + DRAIN_SLACK_S)
    run.log("sweep " + json.dumps(row))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, files.ROOT)
    rows = []
    with run.Session(args.workload, args.seed) as session:
        rate, held, broke = 1.0, None, None
        while rate <= MAX_RATE:
            row = one_rate(session, rate, args.seconds,
                           args.seed + len(rows) + 1)
            rows.append(row)
            if not row["held"]:
                broke = rate
                break
            held, rate = rate, rate * 2
        if held is not None and broke is not None:
            row = one_rate(session, (held + broke) / 2, args.seconds,
                           args.seed + len(rows) + 1)
            rows.append(row)
            if row["held"]:
                held = row["rate"]
    cell_rate = round(0.8 * held * 2) / 2 if held else None
    print(json.dumps({"knee": held, "cell_rate": cell_rate, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
