"""Wire (``server.py``): median of client latency minus the time the
program's own response accounts for (lane wait plus exec)."""

import stats


def read(record: dict):
    out = [s["latency_ms"] - s["envelope"]["wait_ms"]
           - s["envelope"]["exec_ms"]
           for s in stats.answered(record)
           if s["envelope"].get("wait_ms") is not None
           and s["envelope"].get("exec_ms") is not None]
    return stats.median(out) if out else None
