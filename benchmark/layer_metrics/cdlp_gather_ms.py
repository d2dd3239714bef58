"""Kernels (``models/cdlp.py``, ``ops/vmem_gather.py``): milliseconds one
``cdlp_gather`` was on the device (every lane of the in-edge image
reading its neighbour's label), median over every call of the window's
jobs, from the ``kernel`` spans (``kernel_spans.py``). It prints what
served the reads (the spans' ``impl``: ``vmem`` the Pallas kernel with the
labels in VMEM, ``xla`` XLA's gather). Nothing where the program writes
no such spans."""

import kernel_spans
import spans
import stats


def call_ms(record: dict, key: str):
    """Median ``device_ms`` of one stamped call of ``key``."""
    all_jobs = kernel_spans.read_jobs(record)
    if all_jobs is None:
        return None
    calls = [s for job in all_jobs for s in kernel_spans.kernels(job, key)
             if spans.attr(s, "stamped", True)]
    if not calls:
        return None
    impls = sorted({spans.attr(s, "impl") for s in calls} - {None})
    print(f"kernel {key}: {len(calls)} calls"
          + (f", impl {impls}" if impls else ""), flush=True)
    return stats.median([spans.attr(s, "device_ms", 0.0) for s in calls])


def read(record: dict):
    return call_ms(record, "cdlp_gather")
