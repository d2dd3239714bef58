"""Kernels: device-busy milliseconds per answered request — the busy
share of the traced slice over the run's throughput. Cohorts finish in
lumps, so completions inside the slice are not counted."""

import stats


def read(record: dict):
    trace = record.get("trace")
    if not trace:
        return None
    return trace["busy_s"] / trace["window_s"] * 1e3 \
        / stats.throughput(record)
