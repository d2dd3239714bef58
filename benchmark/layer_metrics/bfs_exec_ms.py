"""Scheduler and batcher (``olap/serving``): median of the BFS jobs'
``exec_ms`` (started -> finished: lease, HBM admission of the forward
image and the parent plane, the level loop, the depths and the parents
on the host), from the ``GET /jobs/<id>`` envelope. It prints first the
medians of the host's leaf phases of a job, where the program writes
them: ``job.lease``, ``job.admit`` (with the bytes admission reserved)
and ``bfs.result`` (the one readback of the depths and the parents)."""

import spans
import stats

HOST_PHASES = ("job.lease", "job.admit", "bfs.result")


def read(record: dict):
    got = spans.in_window(record)
    for name in HOST_PHASES if got is not None else ():
        found = spans.named(got, name)
        ms = [s["duration_ms"] for s in found
              if s.get("duration_ms") is not None]
        if ms:
            reserved = {spans.attr(s, "bytes") for s in found} - {None}
            print(f"host {name}: median {stats.median(ms):.1f}ms in "
                  f"{len(ms)} jobs" + (f", bytes {sorted(reserved)}"
                                       if reserved else ""), flush=True)
    values = stats.field(record, "exec_ms")
    return stats.median(values) if values else None
