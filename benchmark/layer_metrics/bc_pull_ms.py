"""Kernels (``models/bc.py``, ``ops/vmem_gather.py``): milliseconds one
level program (``bc_forward_level`` or ``bc_backward_level``: a table
masked to one level pulled over every lane of the in-edge image) was on
the device, median over every call of the window's jobs, from the
``kernel`` spans (``kernel_spans.py``). It prints each program's median,
its calls a job and what served the reads (the spans' ``impl``: ``vmem``
the Pallas kernel with the table in VMEM, ``xla`` XLA's gather). Nothing
where the program writes no such spans."""

import kernel_spans
import spans
import stats

KEYS = ("bc_forward_level", "bc_backward_level")


def calls(record: dict):
    """The window's jobs' stamped calls of the level programs, a list a
    job that dispatched any; None without a journal."""
    all_jobs = kernel_spans.read_jobs(record)
    if all_jobs is None:
        return None
    out = [[s for s in kernel_spans.kernels(job, *KEYS)
            if spans.attr(s, "stamped", True)] for job in all_jobs]
    return [c for c in out if c] or None


def read(record: dict):
    per_job = calls(record)
    if per_job is None:
        return None
    for key in KEYS:
        mine = [[s for s in job if spans.attr(s, "key") == key]
                for job in per_job]
        ms = [spans.attr(s, "device_ms", 0.0) for job in mine for s in job]
        if ms:
            impls = sorted({spans.attr(s, "impl") for job in mine
                            for s in job} - {None})
            print(f"kernel {key}: median {stats.median(ms):.2f}ms a call, "
                  f"{stats.median([len(j) for j in mine]):g} calls a job"
                  + (f", impl {impls}" if impls else ""), flush=True)
    return stats.median([spans.attr(s, "device_ms", 0.0)
                         for job in per_job for s in job])
