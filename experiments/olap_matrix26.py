"""Measure the full OLAP matrix at bench scale BEFORE bench day
(VERDICT r2 item 4): scale-26 SSSP + WCC seconds, scale-22 PageRank
s/iter. Usage: python experiments/olap_matrix26.py [scale] [lj_scale]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp  # noqa: F401

    from titan_tpu.models.frontier import (frontier_sssp, frontier_wcc,
                                           pagerank_dense)
    from titan_tpu.olap.tpu import graph500

    from titan_tpu.utils.jitcache import enable_compile_cache
    enable_compile_cache()

    scale = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    lj = int(sys.argv[2]) if len(sys.argv) > 2 else 22

    t0 = time.time()
    hg = graph500.load_or_build(scale, 16, seed=2, verbose=False)
    g = graph500.to_device(hg)
    jax.block_until_ready(g["dstT"])
    _ = np.asarray(g["colstart"][0])
    print(f"s{scale} load+upload: {time.time()-t0:.1f}s", flush=True)
    deg = np.asarray(hg["deg"])
    source = int(np.flatnonzero(deg > 0)[0])

    t0 = time.time()
    d, rounds = frontier_sssp(g, source, return_device=True)
    _ = np.asarray(d[0])
    print(f"s{scale} SSSP first (incl. compile): {time.time()-t0:.1f}s "
          f"rounds={rounds}", flush=True)
    for rep in range(2):
        t0 = time.time()
        d, rounds = frontier_sssp(g, source, return_device=True)
        _ = np.asarray(d[0])
        print(f"s{scale} SSSP: {time.time()-t0:.2f}s rounds={rounds}",
              flush=True)

    t0 = time.time()
    lab, rounds = frontier_wcc(g, return_device=True)
    _ = np.asarray(lab[0])
    print(f"s{scale} WCC first (incl. compile): {time.time()-t0:.1f}s "
          f"rounds={rounds}", flush=True)
    for rep in range(2):
        t0 = time.time()
        lab, rounds = frontier_wcc(g, return_device=True)
        _ = np.asarray(lab[0])
        print(f"s{scale} WCC: {time.time()-t0:.2f}s rounds={rounds}",
              flush=True)

    del g
    t0 = time.time()
    hg2 = graph500.load_or_build(lj, 16, seed=2, verbose=False)
    g2 = graph500.to_device(hg2)
    jax.block_until_ready(g2["dstT"])
    r, _ = pagerank_dense(g2, iterations=2, return_device=True)
    _ = np.asarray(r[0])
    print(f"s{lj} PR warm: {time.time()-t0:.1f}s", flush=True)
    t0 = time.time()
    iters = 10
    r, _ = pagerank_dense(g2, iterations=iters, return_device=True)
    _ = np.asarray(r[0])
    sec = (time.time() - t0) / iters
    print(f"s{lj} PageRank: {sec:.3f}s/iter over {hg2['e_dedup']} edges "
          f"(vs-MR-180s: {180/sec:.0f}x)", flush=True)


main()
