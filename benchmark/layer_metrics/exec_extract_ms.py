"""Kernels (lane's ``_sweep``): median over the window's lane batches of
the ``extract`` span (level loop returned to the hop-set sizes on the
host; holds the last level's exhaustive sweep)."""

import spans


def read(record: dict):
    return spans.read_phase(record, "extract")
