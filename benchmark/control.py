#!/usr/bin/env python3
"""The control of ``correct``: the plain reference put in the program's
place with one stated guarantee broken, which has to come out NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds <n> [<n> ...]

The configurations promise exact answers read at the one pinned snapshot
epoch. The control answers from a stale epoch instead: the same plain
reference, computed over the generated edge list without its newest
``STALE_SHARE`` of edges — what a later PR would serve if it answered from
an image that lags the snapshot. Its answers to the mix's requests go
through the same comparison a run uses; every limit is 0, so any mismatch
fails it. Host only (no JAX); run on the chip's machine at the cell's own
size, and as a test at a small one.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

import files
import loadgen
import stats
from reference import csr

STALE_SHARE = 0.001
REQUESTS = 256


def control_run(config: dict, mix: dict, seed: int,
                stale_share: float = STALE_SHARE) -> dict:
    """Mismatch counts {comparison: [mismatches, compared]} of the stale
    control's answers against the reference, and ``correct``."""
    gen = files.load_module("graphs", config["generator"])
    n, src, dst, perm = gen.generate(config, seed)
    keep = len(src) - (max(int(len(src) * stale_share), 1)
                       if stale_share else 0)
    op = files.load_module("reference", mix["op"])

    def prepare(s, d, pools=None):
        if config["undirected"]:
            s, d = csr.symmetrise(s, d)
        indptr, indices = csr.structure(n, s, d)
        if pools is None:
            pools = loadgen.draw_pools(np.bincount(s, minlength=n),
                                       mix, config, perm)
        return pools, op.prepare(n, indptr, indices, pools, mix)

    pools, reference = prepare(src, dst)
    _pools, stale = prepare(src[:keep], dst[:keep], pools)
    bodies = loadgen.Bodies(mix, pools, seed)
    compared: dict = {}
    for i in range(REQUESTS):
        body = bodies.get(i)
        answer = stale.answer(body)
        stats.tally(compared,
                    reference.check(body, answer.get("result", answer)))
    correct = all(name in compared and compared[name][0] == 0
                  for name in op.COMPARED)
    return {"seed": seed, "compared": compared, "correct": correct}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    _bench, _cell, config, mix = files.cell_files(args.workload)
    failed_to_fail = 0
    for seed in args.seeds:
        out = control_run(config, mix, seed)
        for name, (bad, of) in out["compared"].items():
            print(f"control {args.workload} seed {seed} {name}: "
                  f"mismatches={bad} of {of} compared, limit=0")
        print(f"control {args.workload} seed {seed}: correct="
              f"{out['correct']} (has to be false)", flush=True)
        failed_to_fail += out["correct"]
    return 1 if failed_to_fail else 0


if __name__ == "__main__":
    sys.exit(main())
