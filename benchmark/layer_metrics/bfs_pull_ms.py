"""Kernels (``models/bfs_hybrid.py``): milliseconds of a BFS job in its
pulled levels (``bfs.sweep`` with ``dir="bu"``: ``bstep``'s chunk rounds
over the level's candidates, each dispatch with its readback, and
``bfs.exhaust``:
the stragglers' sweep, dispatched and not awaited), summed over the job's
levels, median over the window's jobs, from the program's spans
(``bfs_push_ms``'s arithmetic). It prints the rungs of the pull's ladder
the levels took (``c_cap``) with their counts and medians, and the pairs
the stragglers' sweeps took. Nothing where the program journals no such
spans under the job."""

import files
import spans


def read(record: dict):
    push = files.load_module("layer_metrics", "bfs_push_ms")
    pulled = push.levels(record, "bfs.sweep",
                         lambda s: spans.attr(s, "dir") == "bu")
    if pulled is None:
        return None
    push.describe(pulled, "pull", "c_cap")
    swept = push.levels(record, "bfs.exhaust") or {}
    pairs: dict = {}
    for ss in swept.values():
        for s in ss:
            pair = (spans.attr(s, "c_cap"), spans.attr(s, "p_cap"))
            pairs[pair] = pairs.get(pair, 0) + 1
    print(f"exhaust (c_cap, p_cap): {dict(sorted(pairs.items()))}",
          flush=True)
    return push.level_ms({job: ss + swept.get(job, [])
                          for job, ss in pulled.items()})
