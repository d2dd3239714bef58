"""Scheduler and batcher (``olap/serving``): median of the jobs'
``exec_ms`` (started -> finished: lease, HBM admission, the run, the
result on the host), from the ``GET /jobs/<id>`` envelope."""

import stats


def read(record: dict):
    values = stats.field(record, "exec_ms")
    return stats.median(values) if values else None
