"""Kernels (``bfs_hybrid.frontier_bfs_hybrid``): percent of the peel's
``bfs.level`` time spent in bottom-up levels (``dir="bu"``: the opener,
split by lanes above 2^21 candidates, the chunk rounds, the exhaustive
sweep), median over the window's jobs, from the journal. 0 where a job's
peel never pulled; nothing where the program writes no such spans."""

import files
import spans
import stats


def share(job) -> float:
    levels = spans.named(job, "bfs.level")
    pulled = sum(s["duration_ms"] for s in levels
                 if spans.attr(s, "dir") == "bu")
    return 100.0 * pulled / sum(s["duration_ms"] for s in levels)


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    jobs = files.load_module("layer_metrics", "wcc_peel_ms").jobs(got)
    return stats.median([share(j) for j in jobs]) if jobs else None
