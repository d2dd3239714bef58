"""Kernels: milliseconds a job's programs were on the device, median
over the window's jobs, from the journal: the summed ``device_ms`` of the
job's ``kernel`` spans (``kernel_spans.py``: each stamped by the program
where it dispatched the call; a cohort's spans are its first member's).
It prints the table by ``jit_once`` key first: calls a job, median ms a
call, ms a job, share, calls that could not be stamped. Nothing where the
program writes no such spans."""

import kernel_spans
import stats


def read(record: dict):
    all_jobs = kernel_spans.read_jobs(record)
    if all_jobs is None:
        return None
    for line in kernel_spans.describe_keys(all_jobs):
        print(line, flush=True)
    return stats.median([kernel_spans.device_ms(j) for j in all_jobs])
