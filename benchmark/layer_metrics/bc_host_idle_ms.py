"""Scheduler and batcher: milliseconds of a BC job during which none of
its programs was on the device, median over the window's jobs: the job's
extent (its ``job.lease`` started -> its last span ended) less the union
of its ``kernel`` intervals, ``kernel_spans.py``'s arithmetic over the
jobs that ran a ``bc.forward``. It prints that idle time by the leaf
phase that covers it first (``job.admit``, ``bc.forward`` and
``bc.backward``: the host between two awaited levels, ``bc.result``;
what no phase covers is the links between them). Nothing where the
program writes no such spans."""

import kernel_spans
import spans
import stats


def read(record: dict):
    all_jobs = [job for job in kernel_spans.read_jobs(record) or ()
                if spans.named(job, "bc.forward")]
    if not all_jobs:
        return None
    for line in kernel_spans.describe_idle(all_jobs):
        print(line, flush=True)
    return stats.median([kernel_spans.idle_ms(j) for j in all_jobs])
