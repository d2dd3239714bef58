"""Scheduler and batcher (``olap/serving``): median of the WCC jobs'
``queue_ms`` (submitted -> started by the scheduler's one worker), from
the ``GET /jobs/<id>`` envelope: ``job_queue_ms``'s reading, under the
name this cell lists. With two callers and no think time it is what is
left of the job in front when a caller's next one is posted."""

import files


def read(record: dict):
    return files.load_module("layer_metrics", "job_queue_ms").read(record)
