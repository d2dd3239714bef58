"""Chip probe (PR 37): what a propagation round's plan costs over a list
and over all n, at ``graphalytics-g500-24``'s size, and what listing the
remainder adds to the seeding: the measurement behind
``frontier.LIST_PLAN_RATIO``.

    python experiments/wcc_listplan_probe.py [--n 8871268]

No graph is built: a plan reads ``val``, ``val_exp`` and ``degc`` alone.
For each list width ``w`` (2^13 .. 2^23) a finished peel is made up in
which exactly ``w`` vertices with an edge are unreached, the program's own
``_wcc_seed_labels`` seeds and lists them at ``r_cap = w`` (a FULL list:
the dearest plan of that width), and ``_list_plan`` at ``w`` is held
against ``_band_plan`` over n on the same state: the same ``stats`` and
the same members, then medians of 5 of each, dispatch to the statistics
on the host. Prints one JSON line a width and writes them all to
``chiprun_out/wcc_listplan_probe.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8_871_268)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse off the chip (counts, never times)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from titan_tpu.models import frontier as F
    from titan_tpu.models.bfs import INF
    from titan_tpu.utils.jitcache import dev_scalar, enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.cpu:
        print(f"no TPU here ({device.platform}): times come from the chip",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    n = args.n
    w_max = 1 << ((n + 1).bit_length() - 1)
    budget = (1 << 23) - 1024
    rng = np.random.default_rng(37)
    degc_h = rng.integers(1, 9, n + 1).astype(np.int32)
    degc_h[n] = 0
    degc = jnp.asarray(degc_h)
    be = dev_scalar(int(F.IINF))
    seed, lplan, bplan = (F._wcc_seed_labels(), F._list_plan("wcc"),
                          F._band_plan("wcc"))

    def median_ms(fn):
        np.asarray(fn())
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(fn())
            ts.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(ts))

    rows = []
    for lg in (13, 16, 18, 20, 21, 22, 23):
        w = 1 << lg
        if w > w_max:
            break
        dist_h = np.zeros(n, np.int32)
        dist_h[rng.choice(n, w, replace=False)] = INF
        dist = jnp.asarray(dist_h)

        def seeded():
            return seed(dist, degc, n_=n, r_cap=w)

        val, val_exp, rlist, count = seeded()
        assert int(count) == w

        def on_list():
            return lplan(val, val_exp, degc, rlist, be, n_=n, w=w,
                         k_max=F.SLICE_K_MAX, budget=budget)

        def on_n():
            return bplan(val, val_exp, degc, be, n_=n, f_cap=w_max,
                         k_max=F.SLICE_K_MAX, budget=budget,
                         quantile_mass=0)

        got, want = on_list(), on_n()
        assert (np.asarray(got[0]) == np.asarray(want[0])).all()
        assert (np.asarray(got[1]) == np.asarray(want[1][:w])).all()
        row = {"n": n, "w": w, "ratio_n_over_w": round(n / w, 2),
               "seed_and_list_ms": median_ms(lambda: seeded()[3]),
               "list_plan_ms": median_ms(lambda: on_list()[0]),
               "n_plan_ms": median_ms(lambda: on_n()[0]),
               "device": f"{device.platform}:{device.device_kind}"}
        print(json.dumps(row), flush=True)
        rows.append(row)

    # what the seeding cost before it listed anything: the labels alone
    @jax.jit
    def labels_only(dist):
        ids = jnp.arange(n, dtype=jnp.int32)
        reached = dist < INF
        lab = jnp.where(reached, jnp.min(jnp.where(reached, ids, F.IINF)),
                        ids)
        return lab, jnp.where(reached, lab, lab + 1)

    base = {"labels_only_ms": median_ms(lambda: labels_only(dist)[1][:1])}
    print(json.dumps(base), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "wcc_listplan_probe.json"), "w") as f:
        json.dump({"rows": rows, **base}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
