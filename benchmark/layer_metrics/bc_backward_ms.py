"""Kernels (``models/bc.py``): milliseconds of a BC job in its
``bc.backward`` phases (a root's stored levels walked from the deepest
to the root's neighbours, each awaited), summed over the job's roots,
median over the window's jobs, from the program's spans
(``bc_forward_ms``'s arithmetic). Nothing where the program writes no
such spans."""

import files


def read(record: dict):
    return files.load_module("layer_metrics", "bc_forward_ms") \
        .phase_ms(record, "bc.backward")
