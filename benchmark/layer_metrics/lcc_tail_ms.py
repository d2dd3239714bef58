"""Kernels (``models/lcc.py``): milliseconds of an LCC job on the device
in the programs dispatched under its ``lcc.tail`` phase (``lcc_tail``,
one call a class of the low graph's centres: a row of higher neighbours
gathered a slot, compared with the centre's), summed a job, median over
the window's jobs, from the ``kernel`` spans (``kernel_spans.py``).
Nothing where the program writes no such spans."""

import kernel_spans


def read(record: dict):
    return kernel_spans.key_ms(record, "lcc_tail")
