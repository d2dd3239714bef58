"""Scheduler and batcher: milliseconds of a BFS job during which none of
its programs was on the device, median over the window's jobs: the job's
extent (its ``job.lease`` started -> its last span ended) less the union
of its ``kernel`` intervals, ``kernel_spans.py``'s arithmetic over the
jobs that ran a ``bfs.sweep``. It prints that idle time by the leaf phase
that covers it first (``job.admit``; ``bfs.plan`` and ``bfs.sweep``: the
host between a program's end and its readback in hand; ``bfs.result``;
what no phase covers is the links between them). Nothing where the
program writes no such spans."""

import files
import kernel_spans
import stats


def read(record: dict):
    all_jobs = files.load_module("layer_metrics", "bfs_device_ms") \
        .bfs_jobs(record)
    if not all_jobs:
        return None
    for line in kernel_spans.describe_idle(all_jobs):
        print(line, flush=True)
    return stats.median([kernel_spans.idle_ms(j) for j in all_jobs])
