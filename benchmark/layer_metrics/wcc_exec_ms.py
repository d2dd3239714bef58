"""Scheduler and batcher (``olap/serving``): median of the WCC jobs'
``exec_ms`` (started -> finished: lease, HBM admission, the peel, the
propagation, the labels on the host, the component count), from the
``GET /jobs/<id>`` envelope: ``job_exec_ms``'s reading, under the name
this cell lists. It also prints how many jobs the scheduler's worker took
at a time (the ``fuse`` events' ``k``): two callers' jobs that wait
together run as one cohort with one shared peel; and the medians of the
host's leaf phases of a job outside its kernels, where the program
writes them: ``job.lease``, ``job.admit`` (``snapshot_csr_bytes`` and
the ledger), ``wcc.count`` (the components of the answer)."""

import files
import spans
import stats

HOST_PHASES = ("job.lease", "job.admit", "wcc.count")


def cohort_sizes(got) -> dict:
    """{k: jobs that ran in a cohort of k} from the ``fuse`` events."""
    sizes: dict = {}
    for s in spans.named(got, "fuse"):
        k = spans.attr(s, "k")
        if spans.attr(s, "kind") == "wcc" and k is not None:
            sizes[int(k)] = sizes.get(int(k), 0) + 1
    return sizes


def read(record: dict):
    got = spans.in_window(record)
    if got is not None:
        print("wcc jobs by cohort size (k: jobs): "
              f"{dict(sorted(cohort_sizes(got).items()))}", flush=True)
        for name in HOST_PHASES:
            ms = [s["duration_ms"] for s in spans.named(got, name)
                  if s.get("duration_ms") is not None]
            if ms:
                print(f"host {name}: median {stats.median(ms):.1f}ms "
                      f"in {len(ms)} jobs", flush=True)
    return files.load_module("layer_metrics", "job_exec_ms").read(record)
