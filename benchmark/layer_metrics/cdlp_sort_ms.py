"""Kernels (``models/cdlp.py``): milliseconds one ``cdlp_sort`` was on the
device (a round's (owner, label) pairs put in order, every lane of the
in-edge image), median over every call of the window's jobs, from the
``kernel`` spans (``kernel_spans.py``). Nothing where the program writes
no such spans."""

import files


def read(record: dict):
    return files.load_module("layer_metrics", "cdlp_gather_ms") \
        .call_ms(record, "cdlp_sort")
