"""Kernels (``frontier_bfs_batched``): percent of the window's BFS
levels (one a batch and level, however many ``bfs.sweep`` spans it made:
a pushed level makes one, a pulled level one a chunk round) that went
top-down, ``dir == "td"``. Nothing where the program keeps no journal,
ran no sweep in the window, or writes no ``dir`` on its sweeps (a
commit from before it chose a direction)."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    levels = {(s["trace"], spans.attr(s, "level")): spans.attr(s, "dir")
              for s in spans.named(got, "bfs.sweep")}
    dirs = list(levels.values())
    if not dirs or None in dirs:
        return None
    return 100.0 * dirs.count("td") / len(dirs)
