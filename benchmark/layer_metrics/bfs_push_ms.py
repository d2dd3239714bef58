"""Kernels (``models/bfs_hybrid.py``): milliseconds of a BFS job in its
pushed levels (``bfs.sweep`` with ``dir="td"``: the listing where the
level had no list in hand, the push, its readback), summed over the job's
levels, median over the window's jobs, from the program's spans. It
prints the rungs the pushes took (``p_cap``) with their counts and
medians, and the roads (``carried`` / ``scan``). ``level_ms`` is the
arithmetic ``bfs_pull_ms`` and ``bfs_plan_ms`` share. Nothing where the
program journals no such spans under the job."""

import spans
import stats


def levels(record: dict, name: str, keep=lambda s: True):
    """{job: its spans called ``name`` that ``keep`` takes}, over the
    jobs that leased their snapshot inside the window and ran a
    ``bfs.sweep`` (a lane batch sweeps too, and leases nothing); None
    without a journal or without such a job."""
    got = spans.in_window(record)
    if got is None:
        return None
    leased = {s["trace"] for s in spans.named(got, "job.lease")}
    by_job: dict = {s["trace"]: [] for s in spans.named(got, "bfs.sweep")
                    if s["trace"] in leased}
    for s in spans.named(got, name):
        if s["trace"] in by_job and s.get("duration_ms") is not None \
                and keep(s):
            by_job[s["trace"]].append(s)
    return by_job or None


def describe(by_job: dict, what: str, cap: str) -> None:
    """One line a rung: how many levels took it, and their median."""
    rungs: dict = {}
    for ss in by_job.values():
        for s in ss:
            rungs.setdefault(spans.attr(s, cap), []).append(
                s["duration_ms"])
    for rung, ms in sorted(rungs.items(), key=lambda kv: (kv[0] is None,
                                                          kv[0])):
        print(f"{what} {cap}={rung}: {len(ms)} levels in {len(by_job)} "
              f"jobs, median {stats.median(ms):.1f}ms", flush=True)


def level_ms(by_job):
    """Median over the jobs of a job's summed time in the spans kept (a
    job without one counts 0)."""
    if by_job is None:
        return None
    return stats.median([sum(s["duration_ms"] for s in ss)
                         for ss in by_job.values()])


def read(record: dict):
    by_job = levels(record, "bfs.sweep",
                    lambda s: spans.attr(s, "dir") == "td")
    if by_job is None:
        return None
    describe(by_job, "push", "p_cap")
    roads: dict = {}
    for ss in by_job.values():
        for s in ss:
            road = spans.attr(s, "list")
            roads[road] = roads.get(road, 0) + 1
    print(f"push roads: {dict(sorted(roads.items(), key=str))}", flush=True)
    return level_ms(by_job)
