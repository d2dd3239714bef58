"""Scheduler and batcher (``olap/serving``): median of the jobs'
``queue_ms`` (submitted -> started by the scheduler's worker), from the
``GET /jobs/<id>`` envelope of the answered jobs."""

import stats


def read(record: dict):
    values = stats.field(record, "wait_ms")
    return stats.median(values) if values else None
