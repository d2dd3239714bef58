"""Kernels (``frontier._frontier_run`` under ``frontier_wcc``):
milliseconds of a WCC job's label propagation, median over the window's
jobs, from the journal: the job's ``wcc.propagate`` span (every round's
plan, its blocking readback and its pushes' dispatches; what the last
round dispatched drains inside ``wcc.result``). It prints the medians of
its ``rounds``, of its ``sync_ms`` and of ``wcc.result`` beside it.
Nothing where the program writes no such spans."""

import files
import spans
import stats


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    props, results = [], []
    for job in files.load_module("layer_metrics", "wcc_peel_ms").jobs(got):
        props += spans.named(job, "wcc.propagate")
        results += spans.named(job, "wcc.result")
    if not props:
        return None
    print("propagation: median rounds "
          f"{stats.median([spans.attr(s, 'rounds', 0) for s in props])}, "
          f"sync {stats.median([spans.attr(s, 'sync_ms', 0.0) for s in props]):.1f}ms;"
          " wcc.result median "
          f"{stats.median([s['duration_ms'] for s in results] or [0.0]):.1f}ms"
          f" in {len(props)} jobs", flush=True)
    return stats.median([s["duration_ms"] for s in props])
