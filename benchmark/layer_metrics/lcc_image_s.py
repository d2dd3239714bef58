"""Kernels (``models/lcc.py``): seconds the warm-up's first job took to
make the LCC image (the hubs ranked, the 4.9 GB bit table, the lanes'
flags, the low graph's edges, rows and blocks: on the host, then sent),
from the ``lcc.image`` spans before the window whose ``cache`` is
``miss``, summed (one, unless the table was evicted and made again). It
prints the image's bytes. Nothing where the program writes no such
span."""

import spans


def read(record: dict):
    got = spans.before_window(record)
    built = [s for s in spans.named(got or (), "lcc.image")
             if spans.attr(s, "cache") == "miss"
             and s.get("duration_ms") is not None]
    if not built:
        return None
    print(f"lcc.image: built {len(built)} x, bytes "
          f"{sorted({spans.attr(s, 'bytes') for s in built} - {None})}",
          flush=True)
    return sum(s["duration_ms"] for s in built) / 1e3
