"""Kernels (``frontier_bfs_batched``): median over the window's lane
batches of the host's own share of a batch: ``bfs.seed`` and
``bfs.exhaust`` whole, and ``bfs.plan``, ``bfs.sweep`` and ``extract``
less the time they spent blocked in a readback (``sync_ms``)."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    return None if got is None else spans.host_ms(got)
