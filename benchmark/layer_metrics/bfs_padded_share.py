"""Kernels (``models/bfs_hybrid.py``): percent of the lanes the pulled
levels swept that were padding: 100 x (1 - the levels' true candidate
counts over the rungs they took), over every ``bfs.sweep`` with
``dir="bu"`` of the window's jobs: what the pull's cap ladder costs (a
pulled level costs its rung, not its candidates). From the spans'
``c_cap`` and ``candidates``; nothing where the program writes no
``candidates`` (a commit from before the ladder)."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    pulled = [s for s in spans.named(got, "bfs.sweep")
              if spans.attr(s, "dir") == "bu"
              and spans.attr(s, "candidates") is not None]
    lanes = sum(spans.attr(s, "c_cap") for s in pulled)
    if not lanes:
        return None
    return 100.0 * (1.0 - sum(spans.attr(s, "candidates")
                              for s in pulled) / lanes)
