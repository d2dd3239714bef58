"""Kernels (``bfs_hybrid``): milliseconds of a WCC job's device time in
what follows the wide opener inside a pulled level: ``hybrid_bu_finish0``
(``bu0b``, the remaining lanes for the candidates that missed),
``hybrid_bu_more`` (the chunk rounds) and ``hybrid_ex`` (the exhaustive
sweep), median over the window's jobs, from the ``kernel`` spans
(``kernel_spans.py``). With ``wcc_bu_wide_ms`` it is the device's share
of the ``bfs.level`` spans under ``dir="bu"``. Nothing where the program
writes no such spans or no job pulled."""

import kernel_spans


def read(record: dict):
    return kernel_spans.key_ms(record, "hybrid_bu_finish0",
                               "hybrid_bu_more", "hybrid_ex")
