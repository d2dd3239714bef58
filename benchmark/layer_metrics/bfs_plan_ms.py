"""Kernels (``models/bfs_hybrid.py``): milliseconds of a BFS job in its
``bfs.plan`` phases (``bplan``: one n-wide pass for the level's counts,
bitmaps and candidate list, and the readback of its counts; a level whose
statistics the push before it handed on holds the host's decision alone),
summed over the job's levels, median over the window's jobs, from the
program's spans (``bfs_push_ms``'s arithmetic). It prints how many of the
levels planned and how many were carried. Nothing where the program
journals no such spans under the job."""

import files
import spans


def read(record: dict):
    push = files.load_module("layer_metrics", "bfs_push_ms")
    by_job = push.levels(record, "bfs.plan")
    if by_job is None:
        return None
    plans = [s for ss in by_job.values() for s in ss]
    carried = sum(1 for s in plans if spans.attr(s, "carried"))
    print(f"plan: {len(plans) - carried} planned and {carried} carried "
          f"levels in {len(by_job)} jobs", flush=True)
    return push.level_ms(by_job)
