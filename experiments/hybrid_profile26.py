"""Per-dispatch timing of the hybrid BFS at bench scale (default 26).

Wraps every jitted kernel in the process cache with a sync-forcing
timer, so each dispatch's wall cost is attributed by kernel name and
cap bucket (a forced 1-element readback is the sync). Usage:

    python experiments/hybrid_profile26.py [scale]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax

    import titan_tpu.models.bfs_hybrid as H
    import titan_tpu.utils.jitcache as jc
    from titan_tpu.olap.tpu import graph500
    from titan_tpu.utils.jitcache import enable_compile_cache

    enable_compile_cache()
    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    t0 = time.time()
    hg = graph500.load_or_build(scale, 16, seed=2, verbose=False)
    g = graph500.to_device(hg)
    jax.block_until_ready(g["dstT"])
    _ = np.asarray(g["colstart"][0])     # force real completion
    print(f"load+upload: {time.time()-t0:.1f}s")

    deg = np.asarray(hg["deg"])
    rng = np.random.default_rng(12345)
    source = int(rng.choice(np.flatnonzero(deg > 0), size=1,
                            replace=False)[0])

    t0 = time.time()
    d, lv = H.frontier_bfs_hybrid(g, source, return_device=True)
    _ = np.asarray(d[0])
    print(f"warm-up run (incl. compiles): {time.time()-t0:.1f}s lv={lv}")
    del d

    for rep in range(2):
        t0 = time.time()
        d, lv = H.frontier_bfs_hybrid(g, source, return_device=True)
        _ = np.asarray(d[0])
        print(f"clean warm run {rep}: {time.time()-t0:.2f}s lv={lv}")
        del d

    times = []
    orig = {}

    def wrap(name, fn):
        def run(*a, **k):
            t0 = time.time()
            out = fn(*a, **k)
            x = out[0] if isinstance(out, tuple) else out
            try:
                _ = np.asarray(x.ravel()[0])
            except Exception:
                jax.block_until_ready(x)
            times.append((name, k.get("c_cap"), k.get("f_cap"),
                          k.get("p_cap"), time.time() - t0))
            return out
        return run

    for name in list(jc._JITS):
        orig[name] = jc._JITS[name]
        jc._JITS[name] = wrap(name, jc._JITS[name])
    d, lv = H.frontier_bfs_hybrid(g, source, return_device=True)
    _ = np.asarray(d[0])
    for name, cc, fc, pc, dt in times:
        print(f"  {name} c={cc} f={fc} p={pc} {dt:.3f}s")
    for name, fn in orig.items():
        jc._JITS[name] = fn
    print("note: per-kernel syncs serialize the pipeline — the clean "
          "warm runs above are the true wall; this breakdown attributes "
          "it (approximately) by dispatch")


main()
