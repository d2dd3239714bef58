"""Kernels (``frontier_bfs_batched``): median over the window's lane
batches of a batch's summed ``bfs.sweep`` spans (a chunk round
dispatched to its progress read back). Prints each level's median."""

import spans


def read(record: dict):
    return spans.read_phase(record, "bfs.sweep", by_level=True)
