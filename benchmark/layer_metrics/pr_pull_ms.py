"""Kernels (``models/pagerank_pull.py``): milliseconds one
``pagerank_pull`` was on the device, median over every call of the
window's jobs, from the ``kernel`` spans (``kernel_spans.py``): an
iteration's device time, where ``pr_iter_ms`` times its dispatch and
what drains behind it. Nothing where the program writes no such spans."""

import kernel_spans
import spans
import stats


def read(record: dict):
    all_jobs = kernel_spans.read_jobs(record)
    if all_jobs is None:
        return None
    calls = [spans.attr(s, "device_ms") for job in all_jobs
             for s in kernel_spans.kernels(job, "pagerank_pull")
             if spans.attr(s, "stamped", True)]
    return stats.median(calls) if calls else None
