"""Kernels (``bfs_hybrid._endgame``): milliseconds of a WCC job's device
time in ``hybrid_endgame`` (every trailing level of the peel in one
dispatch), median over the window's jobs, from the ``kernel`` spans
(``kernel_spans.py``). Nothing where the program writes no such spans."""

import kernel_spans


def read(record: dict):
    return kernel_spans.key_ms(record, "hybrid_endgame")
