"""The arithmetic every metric reader shares: which requests count, and
how a percentile is taken."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def mean(values) -> float:
    values = list(values)
    return sum(values) / len(values)


def answered(record: dict) -> list:
    """Samples whose answer came back from the device path and was held
    against the reference (right or wrong)."""
    return [s for s in record["samples"] if s["ok"]]


def latencies_ms(record: dict) -> list:
    """Client latency of every request sent in the window; one that
    failed, was refused, timed out or fell back counts as having taken
    the request time limit, so it misses any limit a reader sets."""
    miss = float(record["mix"]["request_timeout_s"]) * 1e3
    return [s["latency_ms"] if s["ok"] else max(miss, s["latency_ms"])
            for s in record["samples"]]


def throughput(record: dict) -> float:
    """Checked answers over the time from the window's start to the last
    completion (requests in flight when sending stops are awaited)."""
    w = record["window"]
    return len(answered(record)) / (w["last_done"] - w["start"])


def field(record: dict, name: str) -> list:
    """One field of the program's own response, over the answered
    requests that carry it."""
    return [s["envelope"][name] for s in answered(record)
            if s["envelope"].get(name) is not None]


def tally(compared: dict, mismatch: dict) -> None:
    """Add one answer's mismatch counts to ``compared`` = {comparison:
    [mismatches, compared]}."""
    for name, bad in mismatch.items():
        c = compared.setdefault(name, [0, 0])
        c[0] += bad
        c[1] += 1
