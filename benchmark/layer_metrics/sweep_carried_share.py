"""Kernels (``frontier_bfs_batched``): percent of the window's pushed
levels (one a batch and level: the ``bfs.sweep`` spans with ``dir ==
"td"``) whose frontier came as the pair list the program before left,
``list == "carried"``, and not from an n-wide listing of ``dist``
(``"scan"``). Nothing where the program keeps no journal, pushed no
level in the window, or writes no ``list`` on its pushes (a commit from
before a level handed its frontier forward)."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    roads = list({(s["trace"], spans.attr(s, "level")):
                  spans.attr(s, "list")
                  for s in spans.named(got, "bfs.sweep")
                  if spans.attr(s, "dir") == "td"}.values())
    if not roads or None in roads:
        return None
    return 100.0 * roads.count("carried") / len(roads)
