"""Kernels (``models/bc.py``, ``ops/vmem_gather.py``): the roots that
share one pull over the in-edge image, the median ``width`` over the
window's ``bc.forward`` and ``bc.backward`` spans (one a group of a
job's roots and phase: the group's masked tables stand side by side in
one VMEM table and an index is paid once for all of them). It prints the
spans by width. Nothing where the program writes no such spans or they
carry no ``width`` (a program whose roots run one at a time)."""

import spans
import stats


def read(record: dict):
    got = spans.in_window(record)
    if got is None:
        return None
    widths = [spans.attr(s, "width")
              for s in spans.named(got, "bc.forward", "bc.backward")]
    widths = [w for w in widths if w is not None]
    if not widths:
        return None
    print("phases by width: " + ", ".join(
        f"{w}: {widths.count(w)}" for w in sorted(set(widths))), flush=True)
    return stats.median(widths)
