"""The program's span journal, for the readers that take it.

``obs/tracing.current()`` is the enabled tracer the program made last —
still readable after the server closed, which is when the readers run —
and ``Tracer.window(t0, t1)`` its finished spans that started in
``[t0, t1)``, as dicts with ``name``, ``start``, ``end``, ``duration_ms``,
``attrs``, ``span``, ``parent`` and ``trace`` (the lane's batch,
``traverse-<n>``). A program that keeps no such journal (a commit from
before it had one, or tracing switched off) gives None here, and each
reader then reports nothing. The arithmetic over span lists is here too,
so that it is tested once on a hand-made list.
"""

from __future__ import annotations

import stats

PHASES_SYNCED = ("bfs.plan", "bfs.sweep", "extract")
PHASES_HOST = ("bfs.seed", "bfs.exhaust")


def journal():
    try:
        from titan_tpu.obs import tracing
    except ImportError:
        return None
    current = getattr(tracing, "current", None)
    tracer = current() if current is not None else None
    return tracer if hasattr(tracer, "window") else None


def in_window(record: dict, lead_s: float = 0.0):
    """Spans that started between the window's start (less ``lead_s``)
    and its last completion; None without a journal."""
    tracer = journal()
    if tracer is None:
        return None
    w = record["window"]
    return tracer.window(w["start"] - lead_s, w["last_done"])


def before_window(record: dict):
    """Spans that started before the window: set-up and warm-up."""
    tracer = journal()
    if tracer is None:
        return None
    return tracer.window(0.0, record["window"]["start"])


def named(spans, *names) -> list:
    return [s for s in spans if s["name"] in names]


def attr(span: dict, key: str, default=None):
    return (span.get("attrs") or {}).get(key, default)


def batches(spans) -> dict:
    """{batch id: its spans} of the lane batches that ran a sweep to its
    end (they have an ``extract``) and whose root is in the list."""
    by_trace: dict = {}
    for s in spans:
        by_trace.setdefault(s["trace"], []).append(s)
    return {t: ss for t, ss in by_trace.items()
            if named(ss, "interactive") and named(ss, "extract")}


def phase_ms(spans, *names):
    """Median over the batches of a batch's summed time in the phases
    ``names``; None where no batch ran."""
    sums = [sum(s["duration_ms"] for s in named(ss, *names))
            for ss in batches(spans).values()]
    return stats.median(sums) if sums else None


def read_phase(record: dict, name: str, by_level: bool = False):
    """What an ``exec_*`` reader returns: ``phase_ms`` of the window's
    spans (None without a journal), printing each level's median first
    where the phase has levels."""
    got = in_window(record)
    if got is None:
        return None
    if by_level:
        for line in describe_levels(got, name):
            print(line, flush=True)
    return phase_ms(got, name)


def read_warm(record: dict, cache: tuple, verb: str):
    """What a ``warm_*_s`` reader returns: seconds in the ``compile``
    spans before the window whose ``cache`` is among ``cache``, printing
    the keys that took most of them."""
    got = before_window(record)
    if got is None:
        return None
    built = compiles(got, cache)
    print(f"warm-up {verb} {len(built)} executables; most time in:",
          flush=True)
    for line in describe_keys(built):
        print(line, flush=True)
    return sum(s["duration_ms"] for s in built) / 1e3


def describe_levels(spans, name: str) -> list:
    """One line a level: the median over the batches of the phase's
    time at that level (what ``phase_ms`` sums, taken apart)."""
    levels: dict = {}
    for ss in batches(spans).values():
        mine: dict = {}
        for s in named(ss, name):
            lv = attr(s, "level")
            mine[lv] = mine.get(lv, 0.0) + s["duration_ms"]
        for lv, ms in mine.items():
            levels.setdefault(lv, []).append(ms)
    return [f"phase {name} L{lv}: median {stats.median(v):.1f}ms "
            f"in {len(v)} batches" for lv, v in sorted(levels.items())]


def describe_keys(spans, top: int = 5) -> list:
    """The ``top`` keys of a list of compile spans by summed time."""
    keys: dict = {}
    for s in spans:
        k = keys.setdefault(attr(s, "key", "?"), [0, 0.0])
        k[0] += 1
        k[1] += s["duration_ms"]
    return [f"  {key}: {n} x, {ms / 1e3:.2f}s" for key, (n, ms) in sorted(
        keys.items(), key=lambda kv: -kv[1][1])[:top]]


def host_ms(spans):
    """Median over the batches of the host's own share: the phases that
    read nothing back, whole, and the others less their ``sync_ms``."""
    sums = [sum(s["duration_ms"] for s in named(ss, *PHASES_HOST))
            + sum(s["duration_ms"] - attr(s, "sync_ms", 0.0)
                  for s in named(ss, *PHASES_SYNCED))
            for ss in batches(spans).values()]
    return stats.median(sums) if sums else None


def busy_share(spans, t0: float, t1: float) -> float:
    """Percent of [t0, t1] covered by the union of the lane's root
    spans."""
    covered, upto = 0.0, t0
    for s in sorted(named(spans, "interactive"),
                    key=lambda s: s["start"]):
        lo, hi = max(s["start"], upto), min(s["end"], t1)
        if hi > lo:
            covered += hi - lo
            upto = hi
    return 100.0 * covered / (t1 - t0)


def compiles(spans, cache=None) -> list:
    """The ``compile`` spans, or those whose ``cache`` is among
    ``cache``."""
    return [s for s in named(spans, "compile")
            if cache is None or attr(s, "cache") in cache]


def describe_compile(span: dict, spans) -> str:
    """``compile <key> <static args> <ms> <hit/miss> in <batch>
    L<level>``: what was built, and the batch and level it stalled."""
    attrs = dict(span.get("attrs") or {})
    key, cache = attrs.pop("key", "?"), attrs.pop("cache", "?")
    static = " ".join(f"{k}={v}" for k, v in sorted(attrs.items())
                      if not k.endswith("_ms") and k != "thread")
    parent = next((p for p in spans if p["span"] == span.get("parent")
                   and p["trace"] == span["trace"]), None)
    where = span["trace"]
    if parent is not None and parent["name"] != "interactive":
        where += f" {parent['name']}"
        level = attr(parent, "level")
        if level is not None:
            where += f" L{level}"
    return (f"compile {key} {static or '-'} {span['duration_ms']:.1f}ms "
            f"{cache} in {where}")
