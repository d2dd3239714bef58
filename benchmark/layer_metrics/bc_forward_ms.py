"""Kernels (``models/bc.py``): milliseconds of a BC job in its
``bc.forward`` phases (a root's seed and its forward levels, each awaited
for the count it reads back), summed over the job's roots, median over
the window's jobs, from the program's spans. It prints the levels and
the vertices a root reached (the spans' ``levels`` and ``reached``).
Nothing where the program writes no such spans."""

import spans
import stats


def phase_ms(record: dict, name: str):
    """Median over the window's jobs of a job's summed time in the leaf
    phase ``name``; prints the phase's ``levels`` a root."""
    got = spans.in_window(record)
    if got is None:
        return None
    by_job: dict = {}
    for s in spans.named(got, name):
        if s.get("duration_ms") is not None:
            by_job.setdefault(s["trace"], []).append(s)
    if not by_job:
        return None
    roots = [s for ss in by_job.values() for s in ss]
    levels = sorted({spans.attr(s, "levels") for s in roots} - {None})
    reached = sorted({spans.attr(s, "reached") for s in roots} - {None})
    print(f"phase {name}: {len(roots)} roots in {len(by_job)} jobs, "
          f"median {stats.median([s['duration_ms'] for s in roots]):.1f}ms "
          f"a root, levels {levels}"
          + (f", reached {reached[0]}..{reached[-1]}" if reached else ""),
          flush=True)
    return stats.median([sum(s["duration_ms"] for s in ss)
                         for ss in by_job.values()])


def read(record: dict):
    return phase_ms(record, "bc.forward")
