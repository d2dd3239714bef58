"""Kernels (``bfs_hybrid._bu_startL``, ``bu0a``): milliseconds of a WCC
job's device time in ``hybrid_bu_startL``, the split-lane opener's first
lanes over every candidate (``c_cap`` 2^24 at graph500-24), median over
the window's jobs, from the ``kernel`` spans (``kernel_spans.py``).
Nothing where the program writes no such spans or no job pulled."""

import kernel_spans


def read(record: dict):
    return kernel_spans.key_ms(record, "hybrid_bu_startL")
