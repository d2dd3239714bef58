"""Kernels (``frontier_bfs_batched``): percent of the chunk columns the
window's pushed levels paid for that held a live chunk: 100 x the sum of
``mass`` (the frontier's chunk columns) over the sum of ``p_cap`` (the
rung the level ran on: a push costs its rung, not its frontier) of the
``bfs.sweep`` spans with ``dir == "td"``, one a batch and level. Nothing
where the program keeps no journal, pushed no level in the window, or
writes no ``p_cap`` and ``mass`` on its pushes (a commit from before it
pushed on a ladder of rungs)."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    pushed = list({(s["trace"], spans.attr(s, "level")):
                   (spans.attr(s, "mass"), spans.attr(s, "p_cap"))
                   for s in spans.named(got, "bfs.sweep")
                   if spans.attr(s, "dir") == "td"}.values())
    if not pushed or any(None in level for level in pushed):
        return None
    return 100.0 * sum(mass for mass, _cap in pushed) \
        / sum(cap for _mass, cap in pushed)
