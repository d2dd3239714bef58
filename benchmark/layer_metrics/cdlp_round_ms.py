"""Kernels (``models/cdlp.py``): milliseconds a round of CDLP holds the
device, from the ``kernel`` spans (``kernel_spans.py``): a job's summed
``device_ms`` in the round's programs (``cdlp_gather``, ``cdlp_sort``,
``cdlp_vote``) over its rounds (the job's ``cdlp.round`` phases), median
over the window's jobs. It prints the rounds' table by ``jit_once`` key
first. The ``cdlp.round`` spans themselves time a dispatch: no round
waits for the device. Nothing where the program writes no such spans."""

import kernel_spans
import spans
import stats

ROUND_KEYS = ("cdlp_gather", "cdlp_sort", "cdlp_vote")


def read(record: dict):
    mine = [job for job in kernel_spans.read_jobs(record) or ()
            if spans.named(job, "cdlp.round")
            and kernel_spans.kernels(job, *ROUND_KEYS)]
    if not mine:
        return None
    for line in kernel_spans.describe_keys(mine):
        print(line, flush=True)
    return stats.median([
        kernel_spans.device_ms(job, *ROUND_KEYS)
        / len(spans.named(job, "cdlp.round")) for job in mine])
