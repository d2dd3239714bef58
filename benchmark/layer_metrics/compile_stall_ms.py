"""Kernels (``obs/devprof.py``'s ``compile`` spans): milliseconds the
program spent building or loading executables inside the window, summed
over every ``compile`` span that started there; 0.0 when there was none.
Prints one line a span: what was built, and the batch and level it
stalled."""

import spans


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    built = spans.compiles(got)
    for s in built:
        print(spans.describe_compile(s, got), flush=True)
    return sum(s["duration_ms"] for s in built)
