"""From a profiler trace (``.xplane.pb``) to numbers: device busy time,
the operations that took most of it, and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
one whose name starts with ``/device:``; its operations are the events of
the line named ``XLA Ops`` (control-flow ops there enclose their bodies, so
busy time is the union of intervals and an op's own time leaves out what
its children cover). Busy seconds are averaged over the device planes.
A gap is named for the host-side event (any thread of the host plane) that
overlaps it longest; what the program was doing there needs annotations
inside the program.
"""

from __future__ import annotations

import glob
import os

import re

OPS_LINE = "XLA Ops"
_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])")
TOP_OPS = 10
TOP_GAPS = 5


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(name: str) -> str:
    """An HLO instruction's text (``%fusion.50 = u8[8388608]{...} fusion(
    ...)``) cut to its name and result shape; any other name as it is."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def _events(line) -> list:
    """(start_ns, end_ns, name) of a line's events, by start."""
    out = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
            short_name(e.name)) for e in line.events]
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union(intervals) -> list:
    """Merged (start, end) intervals, by start."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def self_times(events) -> dict:
    """Summed own time per op name: an enclosing op's time minus what the
    ops nested in it cover."""
    total: dict = {}
    stack: list = []            # [end, name, own]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            total[name] = total.get(name, 0.0) + max(own, 0.0)

    for s, e, name in events:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return total


def _overlap_name(gap, host_events) -> str:
    best, best_len = "host:untraced", 0.0
    gs, ge = gap
    for s, e, name in host_events:
        if e <= gs or s >= ge:
            continue
        got = min(e, ge) - max(s, gs)
        if got > best_len:
            best, best_len = "host:" + name, got
    return best


def reduce_planes(planes, window_s: float) -> dict:
    """``planes``: [(plane name, [(line name, events)])] with events as
    ``_events`` gives them. ``window_s``: length of the traced slice."""
    device = [(p, dict(lines)) for p, lines in planes
              if p.startswith("/device:") and OPS_LINE in dict(lines)]
    host_events = [ev for p, lines in planes if p.startswith("/host:")
                   for _ln, evs in lines for ev in evs]
    busy, ops, gaps = [], {}, []
    for _plane, lines in device:
        events = lines[OPS_LINE]
        merged = union((s, e) for s, e, _n in events)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, t in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + t / 1e9
        gaps += [(merged[i][1], merged[i + 1][0])
                 for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    k = max(len(device), 1)
    return {
        "devices": len(device),
        "busy_s": sum(busy) / k,
        "window_s": float(window_s),
        "device_ops": [[n, t / k] for n, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:TOP_OPS]],
        "idle_gaps": [[_overlap_name(g, host_events), (g[1] - g[0]) / 1e9]
                      for g in gaps[:TOP_GAPS]],
    }


def read_planes(xplane_path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    return [(plane.name, [(line.name, _events(line))
                          for line in plane.lines])
            for plane in data.planes]


def reduce_file(xplane_path: str, window_s: float) -> dict:
    return reduce_planes(read_planes(xplane_path), window_s)


def describe(xplane_path: str) -> str:
    """Planes, lines and event counts of a trace: what to look at by hand
    before trusting a number reduced from it."""
    out = []
    for plane, lines in read_planes(xplane_path):
        out.append(plane)
        for name, events in lines:
            span = (events[-1][1] - events[0][0]) / 1e9 if events else 0.0
            names = sorted({e[2] for e in events})[:4]
            out.append(f"  {name}: {len(events)} events over "
                       f"{span:.3f}s e.g. {names}")
    return "\n".join(out)


if __name__ == "__main__":
    import json
    import sys

    print(describe(sys.argv[1]))
    print(json.dumps(reduce_file(
        sys.argv[1], float(sys.argv[2]) if len(sys.argv) > 2 else 1.0),
        indent=1))
