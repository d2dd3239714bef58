"""Kernels (``models/bfs_hybrid.py``): milliseconds a BFS job's programs
were on the device, median over the window's jobs, from the journal: the
summed ``device_ms`` of the ``kernel`` spans of a job that ran a
``bfs.sweep`` (``job_device_ms``'s arithmetic, ``kernel_spans.py``). It
prints the table by ``jit_once`` key first (``batched_seed``,
``batched_plan``, ``batched_list``, ``batched_td``, ``batched_bu``,
``batched_ex``): calls a job, median ms a call, ms a job, share. Nothing
where the program journals no such spans under the job (a commit whose
batched run opens no scope)."""

import kernel_spans
import spans
import stats


def bfs_jobs(record: dict) -> list:
    """The window's jobs that ran a level of the batched loop."""
    return [job for job in kernel_spans.read_jobs(record) or ()
            if spans.named(job, "bfs.sweep")]


def read(record: dict):
    all_jobs = bfs_jobs(record)
    if not all_jobs:
        return None
    for line in kernel_spans.describe_keys(all_jobs):
        print(line, flush=True)
    return stats.median([kernel_spans.device_ms(j) for j in all_jobs])
