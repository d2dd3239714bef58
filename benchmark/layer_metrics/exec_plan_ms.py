"""Kernels (``frontier_bfs_batched``): median over the window's lane
batches of a batch's summed ``bfs.plan`` spans (plan dispatched to its
statistics read back; holds the exhaustive sweep of the level before).
Prints each level's median."""

import spans


def read(record: dict):
    return spans.read_phase(record, "bfs.plan", by_level=True)
