"""Kernels (``models/lcc.py``): milliseconds of an LCC job on the device
in the programs dispatched under its ``lcc.hub`` phase, the pass over the
lanes (``lcc_pass``: a 2 KB row of the hub bit table gathered a lane,
ANDed with its owner's, popcounted) and the hubs' column sums
(``lcc_colsum``), summed a job, median over the window's jobs, from the
``kernel`` spans (``kernel_spans.py``). It prints each program's share.
Nothing where the program writes no such spans."""

import kernel_spans

KEYS = ("lcc_pass", "lcc_colsum")


def read(record: dict):
    for key in KEYS:
        ms = kernel_spans.key_ms(record, key)
        if ms is not None:
            print(f"kernel {key}: median {ms:.1f}ms a job", flush=True)
    return kernel_spans.key_ms(record, *KEYS)
