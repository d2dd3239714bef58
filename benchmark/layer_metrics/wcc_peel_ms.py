"""Kernels (``bfs_hybrid.frontier_bfs_hybrid`` under ``frontier_wcc``):
milliseconds of a WCC job's peel, median over the window's jobs, from the
journal: a job's first ``bfs.level`` started -> its last ended (every
host step of the single-source direction-optimising BFS is one such span
and ends in its own blocking readback, so the extent holds the device's
time). It prints the steps' medians by level and direction first. A job
counts once its ``wcc.seed`` is there (the peel ran to its end); in a
cohort of several jobs the phases are the first member's. Nothing where
the program writes no such spans."""

import spans
import stats


def jobs(got) -> list:
    """The ``bfs.level`` / ``wcc.*`` spans of every job (trace) whose
    peel ended, a list a job."""
    by_trace: dict = {}
    for s in spans.named(got, "bfs.level", "wcc.seed", "wcc.propagate",
                         "wcc.result"):
        by_trace.setdefault(s["trace"], []).append(s)
    return [ss for ss in by_trace.values()
            if spans.named(ss, "bfs.level") and spans.named(ss, "wcc.seed")]


def peel_ms(job) -> float:
    levels = spans.named(job, "bfs.level")
    return (max(s["end"] for s in levels)
            - min(s["start"] for s in levels)) * 1e3


def describe_steps(all_jobs) -> list:
    """One line a host step of the peel: its level, its direction, the
    median of its time and of its ``sync_ms`` over the jobs."""
    steps: dict = {}
    for job in all_jobs:
        for s in spans.named(job, "bfs.level"):
            key = (spans.attr(s, "level"), spans.attr(s, "dir"))
            steps.setdefault(key, []).append(s)
    out = []
    for (level, direction), ss in sorted(steps.items()):
        caps = " ".join(f"{k}={spans.attr(ss[0], k)}" for k in (
            "levels", "f_cap", "p_cap", "c_cap", "split", "missed", "left",
            "rounds", "exhaust", "rem8") if spans.attr(ss[0], k) is not None)
        out.append(
            f"peel L{level} {direction}: median "
            f"{stats.median([s['duration_ms'] for s in ss]):.1f}ms (sync "
            f"{stats.median([spans.attr(s, 'sync_ms', 0.0) for s in ss]):.1f}"
            f"ms) in {len(ss)} jobs; {caps}")
    return out


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    all_jobs = jobs(got)
    for line in describe_steps(all_jobs):
        print(line, flush=True)
    return stats.median([peel_ms(j) for j in all_jobs]) if all_jobs \
        else None
