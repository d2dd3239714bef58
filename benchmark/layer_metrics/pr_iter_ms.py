"""Kernels (``frontier.pagerank_dense``): milliseconds an iteration of a
PageRank job, median over the window's jobs, from the journal: a job's
first ``pr.sweep`` started -> its ``pr.result`` ended, over its
iterations (one ``pr.finish`` each). The loop only dispatches; what it
dispatched drains inside ``pr.result``'s blocking readback, so the extent
ends there (the readback's own copy, ``bytes`` at the link's rate, is a
thousandth of it). Nothing where the program writes no such spans."""

import spans
import stats


def per_job(got) -> list:
    """ms an iteration of every job (trace) that has all three spans."""
    by_trace: dict = {}
    for s in spans.named(got, "pr.sweep", "pr.finish", "pr.result"):
        by_trace.setdefault(s["trace"], []).append(s)
    out = []
    for ss in by_trace.values():
        sweeps = spans.named(ss, "pr.sweep")
        result = spans.named(ss, "pr.result")
        its = len(spans.named(ss, "pr.finish"))
        if sweeps and result and its:
            out.append((max(s["end"] for s in result)
                        - min(s["start"] for s in sweeps)) * 1e3 / its)
    return out


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    values = per_job(got)
    return stats.median(values) if values else None
