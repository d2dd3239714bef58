"""The program's ``kernel`` spans, for the readers that take them.

Since ISSUE 38 the program journals one ``kernel`` span for every call
through its ``jit_once`` shim (``titan_tpu/obs/devprof.py``): the span's
extent is the program's interval on the device (``start`` = the later of
its dispatch and the program before it becoming ready, ``end`` = the
moment its own output became ready, stamped by a watcher thread), its
``attrs`` carry ``key`` (the ``jit_once`` key), ``fn``, the static
arguments, ``device_ms``, ``queued_ms``, ``dispatch_ms`` and ``stamped``
(false where the output was gone before the watcher reached it: such a
call's time falls to the next stamped one). They lie in the trace, and
under the leaf phase, of the job that dispatched them (a cohort's: its
first member's). A program that writes no such span gives every reader
here nothing. The arithmetic is here, tested once on a hand-made list
(``tests/test_kernel_readers.py``).
"""

from __future__ import annotations

import spans
import stats


def jobs(got) -> list:
    """The spans of every job (trace) that leased its snapshot inside
    the list and dispatched at least one kernel: from its ``job.lease``
    on, a list a job. The window's jobs all end inside it (those in
    flight when sending stops are awaited)."""
    by_trace: dict = {}
    for s in got:
        by_trace.setdefault(s["trace"], []).append(s)
    out = []
    for ss in by_trace.values():
        lease = spans.named(ss, "job.lease")
        if lease and spans.named(ss, "kernel"):
            t0 = min(s["start"] for s in lease)
            out.append([s for s in ss if s["start"] >= t0])
    return out


def read_jobs(record: dict):
    """The window's jobs, or None where the program keeps no journal or
    wrote no ``kernel`` span in it."""
    got = spans.in_window(record)
    return (jobs(got) or None) if got is not None else None


def kernels(job, *keys) -> list:
    """A job's ``kernel`` spans, all of them or those of ``keys``."""
    return [s for s in spans.named(job, "kernel")
            if not keys or spans.attr(s, "key") in keys]


def device_ms(job, *keys) -> float:
    """A job's summed ``device_ms``, of every kernel or of ``keys``."""
    return sum(spans.attr(s, "device_ms", 0.0) for s in kernels(job, *keys))


def key_ms(record: dict, *keys):
    """What a ``<kernels>_ms`` reader returns: the median over the
    window's jobs of a job's ``device_ms`` in ``keys`` (None where no
    job dispatched any of them)."""
    all_jobs = read_jobs(record)
    if all_jobs is None:
        return None
    values = [device_ms(j, *keys) for j in all_jobs if kernels(j, *keys)]
    return stats.median(values) if values else None


def union(intervals) -> list:
    """Disjoint ``(lo, hi)`` covering the same points, ascending."""
    out: list = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def extent(job) -> tuple:
    """``job.lease`` started -> the job's last span ended."""
    return (min(s["start"] for s in job), max(s["end"] for s in job))


def idle(job) -> list:
    """The stretches of a job's extent in which none of its kernels was
    on the device, ascending."""
    lo, hi = extent(job)
    out, at = [], lo
    for a, b in union((s["start"], s["end"]) for s in kernels(job)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def idle_ms(job) -> float:
    return sum(b - a for a, b in idle(job)) * 1e3


def phases(job) -> list:
    """The job's leaf phases: the program names them ``<layer>.<step>``
    (``job.admit``, ``bfs.level``, ``pr.result``); containers (``job``,
    ``attempt``, ``run``), events and ``round`` have no dot."""
    return [s for s in job if "." in s["name"] and s["end"] > s["start"]]


def idle_by_phase(job) -> dict:
    """{phase name: ms of the job's idle stretches that the phase
    covers}; what no phase covers is ``(between phases)``."""
    out: dict = {}
    gaps = idle(job)
    covered = 0.0
    for p in phases(job):
        ms = sum(max(min(b, p["end"]) - max(a, p["start"]), 0.0)
                 for a, b in gaps) * 1e3
        if ms > 0.0:
            out[p["name"]] = out.get(p["name"], 0.0) + ms
            covered += ms
    rest = sum(b - a for a, b in gaps) * 1e3 - covered
    if rest > 0.0:
        out["(between phases)"] = rest
    return out


def describe_idle(all_jobs) -> list:
    """One line a phase: the median over the jobs of the idle time it
    covers (0 for a job without it), dearest first."""
    per_job = [idle_by_phase(j) for j in all_jobs]
    names = {n for d in per_job for n in d}
    rows = sorted(((stats.median([d.get(n, 0.0) for d in per_job]), n)
                   for n in names), reverse=True)
    return [f"idle under {n}: median {ms:.1f}ms a job" for ms, n in rows]


def by_key(all_jobs) -> list:
    """The per-key table: ``(key, fn, median calls a job, median ms a
    call, median ms a job, unstamped calls in all)``, dearest first. A
    job without the key counts 0 ms."""
    calls: dict = {}
    for i, job in enumerate(all_jobs):
        for s in kernels(job):
            k = calls.setdefault(spans.attr(s, "key", "?"), {
                "fn": spans.attr(s, "fn", "?"), "ms": [],
                "jobs": [[0, 0.0] for _ in all_jobs], "unstamped": 0})
            if spans.attr(s, "stamped", True):
                k["ms"].append(spans.attr(s, "device_ms", 0.0))
            else:
                k["unstamped"] += 1
            k["jobs"][i][0] += 1
            k["jobs"][i][1] += spans.attr(s, "device_ms", 0.0)
    rows = [(key, k["fn"], stats.median([c for c, _ in k["jobs"]]),
             stats.median(k["ms"]) if k["ms"] else 0.0,
             stats.median([ms for _, ms in k["jobs"]]), k["unstamped"])
            for key, k in calls.items()]
    return sorted(rows, key=lambda r: -r[4])


def describe_keys(all_jobs) -> list:
    rows = by_key(all_jobs)
    whole = sum(r[4] for r in rows) or 1.0
    return [f"kernel {key} ({fn}): {n:g} calls a job, median "
            f"{call:.2f}ms a call, {job:.1f}ms a job, "
            f"{100.0 * job / whole:.1f}%, unstamped {lost}"
            for key, fn, n, call, job, lost in rows]
