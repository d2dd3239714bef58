#!/usr/bin/env python3
"""Chip smoke: store -> CSR -> served jobs on one TPU chip, every answer
checked against a plain numpy/scipy reference.

    python chip_smoke.py [--scale 20] [--seed 2]     # one chip
    python chip_smoke.py --chips 4                   # sharded BFS only

One process; it is the only one that touches JAX and it starts no child
(the native host library is built by ``titan_tpu.native`` on import,
with make/g++, before JAX is touched). The graph is an R-MAT made from
``--seed``; nothing is read from a cache. Any phase that raises, or any
comparison that fails, ends the run with a traceback and a non-zero exit
code. The LAST line of a passing run is

    {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}

with the device as JAX reports it. Without a TPU the run fails in phase
0 and prints no such line. Times printed here are smoke-grade wall
clocks of one run, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

EDGE_FACTOR = 16
N_BFS = 8
N_TRAVERSE = 16
N_TARGETS = 1000
PR_ITERATIONS = 20
DAMPING = 0.85
#: L1 tolerance for a 20-iteration float32 PageRank against the float64
#: reference: ranks sum to <= 1, per-vertex float32 rounding is ~1e-7
#: relative, so 1e-3 absolute on the L1 norm is three orders of slack
#: for summation order and far below any structural error (one missing
#: hub edge moves L1 by more).
PR_L1_TOL = 1e-3
#: relative tolerance for float32 SSSP path sums against float64 Dijkstra
SSSP_RTOL = 1e-4
COLD_LANE_TIMEOUT_S = 900.0


class SmokeFailure(AssertionError):
    """A comparison against the reference failed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# plain references (numpy / scipy over the generated edge arrays only)
# ---------------------------------------------------------------------------

def csr_structure(src, dst, n):
    """(indptr, indices) of the adjacency structure src->dst
    (duplicates merged — structure is all BFS / hops / WCC need)."""
    import scipy.sparse as sp

    m = sp.csr_matrix((np.ones(len(src), np.int8), (src, dst)),
                      shape=(n, n))
    return m.indptr.astype(np.int64), m.indices.astype(np.int64)


def _neighbours(indptr, indices, frontier):
    starts = indptr[frontier]
    counts = indptr[frontier + 1] - starts
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64)
    base = np.repeat(starts - (np.cumsum(counts) - counts), counts)
    return indices[base + np.arange(total, dtype=np.int64)]


def ref_bfs(indptr, indices, source: int) -> np.ndarray:
    """Level-synchronous BFS; dist int32 [n], -1 = unreached."""
    n = len(indptr) - 1
    dist = np.full(n, -1, np.int32)
    dist[source] = 0
    frontier = np.array([source], np.int64)
    level = 0
    while frontier.size:
        level += 1
        nb = _neighbours(indptr, indices, frontier)
        nb = nb[dist[nb] < 0]
        mark = np.zeros(n, bool)
        mark[nb] = True
        frontier = np.flatnonzero(mark)
        dist[frontier] = level
    return dist


def edge_keys(indptr, indices) -> np.ndarray:
    """Every edge u -> v of the structure as the key ``u * n + v``,
    sorted: what ``bfs_tree_faults`` searches."""
    n = len(indptr) - 1
    keys = np.repeat(np.arange(n, dtype=np.int64), np.diff(indptr)) * n \
        + indices
    keys.sort()
    return keys


def bfs_tree_faults(keys, ref: np.ndarray, source: int, parent) -> str:
    """GAP's rule for a BFS tree (``BFSVerifier``) against the serial
    depths ``ref`` (-1 = unreached): the source its own parent, a parent
    exactly where the serial BFS reaches, every other reached vertex's
    parent a neighbour one level nearer. '' where ``parent`` (dense ids,
    -1 = none) keeps it, else what it breaks."""
    n = len(ref)
    parent = np.asarray(parent, np.int64)
    if parent.shape != (n,):
        return f"shape {parent.shape}"
    if parent[source] != source:
        return f"parent[source] = {parent[source]}"
    there = ref >= 0
    if ((parent >= 0) != there).any():
        return f"{int(((parent >= 0) != there).sum())} parents off the tree"
    there[source] = False
    v = np.flatnonzero(there)
    p = parent[v]
    if (p >= n).any() or (ref[np.minimum(p, n - 1)] != ref[v] - 1).any():
        return "a parent that is not one level nearer the source"
    want = p * n + v
    at = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
    if (keys[at] != want).any():
        return f"{int((keys[at] != want).sum())} parents that are no neighbour"
    return ""


def ref_hops_out_count(indptr, indices, start: int, hops: int) -> int:
    """|out^hops(start)| as a SET (the lane's dedup'd count)."""
    cur = np.array([start], np.int64)
    for _ in range(hops):
        cur = np.unique(_neighbours(indptr, indices, cur))
    return int(cur.size)


def ref_pagerank(src, dst, n, iterations=PR_ITERATIONS, damping=DAMPING,
                 reset=None) -> np.ndarray:
    """rank' = (1-d)*reset + d * sum_{u->v} rank[u]/outdeg[u], float64,
    every generated edge counted (multi-edges and self-loops included),
    dangling mass leaking — the semantics models/pagerank.py documents.
    ``reset=None`` is the uniform 1/n teleport; a one-hot ``reset`` is
    personalised PageRank started at the reset vector."""
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    tele = np.full(n, 1.0 / n) if reset is None else reset
    rank = tele.copy()
    for _ in range(iterations):
        agg = np.bincount(dst, weights=rank[src] * inv[src], minlength=n)
        rank = (1.0 - damping) * tele + damping * agg
    return rank


def ref_components(indptr, indices, n):
    """(component count, size of the largest) of the undirected graph."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    m = sp.csr_matrix((np.ones(len(indices), np.int8), indices, indptr),
                      shape=(n, n))
    count, lab = connected_components(m, directed=False)
    return int(count), int(np.bincount(lab).max())


def ref_sssp(host_csr, n, source: int) -> np.ndarray:
    """scipy Dijkstra over the chunked layout's slots: edge weight =
    slot_weights_np(slot), slot = column*8 + lane — the layout names
    the slots, the shortest-path search is scipy's."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import dijkstra

    from titan_tpu.models.frontier import slot_weights_np

    dstT, degc = host_csr["dstT"], host_csr["degc"]
    q_total = dstT.shape[1]
    owner = np.repeat(np.arange(n + 1, dtype=np.int64), degc)  # per column
    owner = np.concatenate([owner, np.full(q_total - len(owner), n)])
    flat_dst = np.ascontiguousarray(dstT.T).reshape(-1)
    slots = np.arange(q_total * 8, dtype=np.int64)
    ok = flat_dst < n                                          # pad = n+1
    w = slot_weights_np(slots[ok]).astype(np.float64)
    u = np.repeat(owner, 8)[ok]
    indptr = np.concatenate([[0], np.cumsum(np.bincount(u, minlength=n))])
    # rows arrive grouped by owner already; parallel edges stay separate
    # entries (Dijkstra relaxes each), an exact-zero weight is lifted to
    # the smallest positive double so scipy keeps the edge
    m = sp.csr_matrix((np.maximum(w, np.finfo(np.float64).tiny),
                       flat_dst[ok], indptr), shape=(n, n))
    return dijkstra(m, directed=True, indices=source)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def require_tpu(dev) -> None:
    """The smoke is a chip run or it is nothing: no CPU fallback."""
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform={dev.platform!r}); "
            "this script only runs on the chip")


def phase_device(chips: int) -> dict:
    import jax

    from titan_tpu import native
    from titan_tpu.olap.serving import hbm
    from titan_tpu.utils.jitcache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    require_tpu(dev)
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, JAX reports {len(devs)}")
    enable_compile_cache()
    stats = dev.memory_stats() or {}
    limit = stats.get("bytes_limit")
    log(f"phase 0 device: jax {jax.__version__} platform={dev.platform} "
        f"kind={dev.device_kind!r} count={len(devs)} bytes_limit={limit} "
        f"compile_cache={jax.config.jax_compilation_cache_dir} "
        f"native={native.available}")
    check(native.available,
          "titan_tpu.native did not build/load: the numpy R-MAT generator "
          "yields a different graph for the same seed")
    if dev.platform == "tpu":
        check(limit is not None, "device reports no memory_stats bytes_limit")
        check(hbm.DEFAULT_BUDGET_BYTES <= limit,
              f"hbm.DEFAULT_BUDGET_BYTES {hbm.DEFAULT_BUDGET_BYTES:.3e} "
              f"exceeds the device's bytes_limit {limit}")
        log(f"phase 0 hbm: DEFAULT_BUDGET_BYTES="
            f"{hbm.DEFAULT_BUDGET_BYTES:.4e} fits bytes_limit={limit}")
    _probe_host_link()
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def _probe_host_link() -> None:
    """Smoke-grade figures for the host<->device link (ROADMAP S2):
    medians of a few readings, printed, never compared."""
    import jax
    import jax.numpy as jnp

    def median_s(fn, reps):
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    bump = jax.jit(lambda x: x + 1)
    x = bump(jnp.int32(0))
    int(x)                                                  # compiled, warm
    sync = median_s(lambda: int(bump(x)), 20)
    put = median_s(lambda: jnp.int32(7).block_until_ready(), 20)
    host = np.zeros(64 << 20, np.uint8)                     # 64 MiB
    h2d = median_s(lambda: jax.device_put(host).block_until_ready(), 5)
    # a device array keeps its host copy after the first readback, so
    # every reading takes a fresh one
    fresh = [jax.device_put(host).block_until_ready() for _ in range(5)]
    d2h = median_s(lambda: np.asarray(fresh.pop()), 5)
    log(f"phase 0 host link: dispatch+scalar readback {sync * 1e3:.3f} ms, "
        f"scalar put {put * 1e3:.3f} ms, H2D {64 / 1024 / h2d:.2f} GiB/s, "
        f"D2H {64 / 1024 / d2h:.2f} GiB/s (64 MiB, medians)")


def generate(scale: int, seed: int):
    from titan_tpu import native

    n = 1 << scale
    src, dst = native.rmat_gen(n * EDGE_FACTOR, scale, seed=seed)
    return n, src, dst


def phase_store(scale: int, seed: int) -> dict:
    import titan_tpu
    from titan_tpu.olap import bulk
    from titan_tpu.olap.tpu import snapshot as snap_mod

    t0 = time.time()
    n, src, dst = generate(scale, seed)
    t_gen = time.time() - t0
    g = titan_tpu.open({"storage.backend": "inmemory"})
    res = bulk.bulk_load_adjacency(g, src, dst, n=n)
    t1 = time.time()
    snap = snap_mod.build(g, directed=False)
    t_scan = time.time() - t1
    vids = res["vertex_ids"]
    check(snap.n == n, f"snapshot has {snap.n} vertices, generated {n}")
    check(int(snap.out_degree.sum()) == 2 * len(src),
          f"symmetrised snapshot holds {int(snap.out_degree.sum())} edges, "
          f"expected {2 * len(src)}")
    check(np.array_equal(snap.vertex_ids, vids),
          "snapshot vertex ids differ from the ids the bulk load assigned")
    log(f"phase 1 store->csr: n={n} input_edges={len(src)} "
        f"sym_edges={int(snap.out_degree.sum())} gen_s={t_gen:.1f} "
        f"ingest_s={res['ingest_s']:.1f} scan_s={t_scan:.1f}")
    return {"graph": g, "snapshot": snap, "n": n, "src": src, "dst": dst,
            "vids": vids}


def phase_engine(ctx: dict) -> None:
    from titan_tpu.models import pagerank

    t0 = time.time()
    res = pagerank.run(ctx["graph"].compute(), iterations=PR_ITERATIONS)
    got = np.asarray(res["rank"], np.float64)
    wall = time.time() - t0
    ref = ref_pagerank(ctx["src"], ctx["dst"], ctx["n"])
    check(got.shape == ref.shape and np.isfinite(got).all(),
          f"engine pagerank: shape {got.shape} / non-finite values")
    l1 = float(np.abs(got - ref).sum())
    check(l1 < PR_L1_TOL, f"engine pagerank L1 {l1:.3e} >= {PR_L1_TOL}")
    log(f"phase 2 engine pagerank: iterations={res.iterations} "
        f"L1={l1:.3e} (< {PR_L1_TOL}) wall_s={wall:.1f}")


class Client:
    """HTTP client side of phase 3/4 (threads of this process)."""

    def __init__(self, host: str, port: int):
        self.base = f"http://{host}:{port}"

    def req(self, path: str, payload=None, timeout: float = 600.0):
        r = urllib.request.Request(
            self.base + path,
            data=json.dumps(payload).encode() if payload is not None
            else None,
            headers={"Content-Type": "application/json"},
            method="POST" if payload is not None else "GET")
        try:
            with urllib.request.urlopen(r, timeout=timeout) as resp:
                return json.loads(resp.read())
        except urllib.error.HTTPError as e:
            raise SmokeFailure(f"{path}: HTTP {e.code}: "
                               f"{e.read().decode(errors='replace')}") from e

    def concurrently(self, path: str, payloads: list) -> list:
        """POST every payload from its own thread; answers in order. An
        exception in any thread is re-raised here."""
        out = [None] * len(payloads)
        errs = []

        def one(i):
            try:
                out[i] = self.req(path, payloads[i])
            except Exception as e:       # re-raised below, never dropped
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(payloads))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(900)
        if errs:
            raise errs[0]
        check(all(o is not None for o in out), f"{path}: a client hung")
        return out

    def wait_done(self, job_id: str, timeout: float = 900.0) -> dict:
        deadline = time.time() + timeout
        while time.time() < deadline:
            body = self.req(f"/jobs/{job_id}")
            if body["status"] not in ("queued", "running", "retrying"):
                check(body["status"] == "done",
                      f"job {job_id} ended {body['status']}: "
                      f"{body.get('error')}")
                return body
            time.sleep(0.05)
        raise SmokeFailure(f"job {job_id} not done after {timeout}s")


def _bfs_cohort(client, sched, payloads) -> list:
    """Submit the BFS cohort over HTTP while a gate job holds the single
    worker, so all of them are queued before it pops: the fusion is
    deterministic. Returns the final job envelopes."""
    from titan_tpu.olap.api import JobSpec

    gate = threading.Event()
    hold = sched.submit(JobSpec(kind="callable",
                                params={"fn": lambda: gate.wait(600)}))
    try:
        ids = [r["job"] for r in client.concurrently("/jobs", payloads)]
    finally:
        gate.set()
    client.wait_done(hold.id)
    return [client.wait_done(j) for j in ids]


def phase_served(ctx: dict, seed: int) -> None:
    from titan_tpu.models.bfs_hybrid import build_chunked_csr
    from titan_tpu.obs import devprof
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.server import GraphServer

    g, snap, n = ctx["graph"], ctx["snapshot"], ctx["n"]
    src, dst, vids = ctx["src"], ctx["dst"], ctx["vids"]
    inf = 1 << 30

    # references first: a wrong reference must not cost chip compiles
    t0 = time.time()
    s2, d2 = np.concatenate([src, dst]), np.concatenate([dst, src])
    sym_ptr, sym_idx = csr_structure(s2, d2, n)
    out_ptr, out_idx = csr_structure(src, dst, n)
    rng = np.random.default_rng(seed)
    sym_deg = np.diff(sym_ptr)
    bfs_src = rng.choice(np.flatnonzero(sym_deg > 0), N_BFS, replace=False)
    trav_src = rng.choice(np.flatnonzero(np.diff(out_ptr) > 0), N_TRAVERSE,
                          replace=False)
    targets = rng.choice(n, min(N_TARGETS, n), replace=False)
    bfs_ref = [ref_bfs(sym_ptr, sym_idx, int(s)) for s in bfs_src]
    # what a served BFS tree is held to (ISSUE 50): the edges, and each
    # job's source beside its serial depths
    tree = (edge_keys(sym_ptr, sym_idx), [int(s) for s in bfs_src])
    trav_ref = [ref_hops_out_count(out_ptr, out_idx, int(s), 2)
                for s in trav_src]
    wcc_ref = ref_components(sym_ptr, sym_idx, n)
    pr_ref = ref_pagerank(s2, d2, n)
    ppr_reset = np.zeros(n)
    ppr_reset[int(bfs_src[0])] = 1.0
    ppr_ref = ref_pagerank(s2, d2, n, reset=ppr_reset)
    del s2, d2
    log(f"phase 3 references: {time.time() - t0:.1f}s host "
        f"(bfs x{N_BFS}, hops x{N_TRAVERSE}, wcc, pagerank, ppr)")

    prof = devprof.DeviceCostProfiler().install()
    sched = JobScheduler(graph=g, autostart=False, profiler=prof)
    srv = GraphServer(g, port=0, scheduler=sched).start()
    try:
        client = Client(srv.host, srv.port)
        tgt_ids = [int(vids[t]) for t in targets]
        bfs_jobs = [{"kind": "bfs", "source": int(vids[s]),
                     "targets": tgt_ids, "parents": True}
                    for s in bfs_src]

        # -- pass 1: 8 BFS queued on the paused scheduler, then wcc,
        #    pagerank and sssp; started together (serve_smoke.sh shape)
        w1 = prof.window()
        ids = [r["job"] for r in client.concurrently("/jobs", bfs_jobs)]
        # (the profiler counts on the process-wide registry, which an
        # earlier run in this process may have counted on: the delta)
        def end_tests():
            return {impl: prof.metrics.counter_value(
                "device.bfs.frontier_test", {"prog": "end", "impl": impl})
                for impl in ("vmem", "xla")}
        before = end_tests()
        others = {k: client.req("/jobs", body)["job"] for k, body in (
            ("wcc", {"kind": "wcc"}),
            ("pagerank", {"kind": "pagerank",
                          "iterations": PR_ITERATIONS}),
            ("sssp", {"kind": "sssp", "source": int(vids[bfs_src[0]])}))}
        sched.start()
        finals = [client.wait_done(j) for j in ids]
        pass1 = w1.close()
        _check_bfs(finals, sched, bfs_ref, targets, tgt_ids, inf, tree)
        log(f"phase 3 bfs: {N_BFS} jobs fused batch_k={finals[0]['batch_k']}"
            f", reached/levels/{len(tgt_ids)} target distances/full dist "
            f"exact, all {n} parents of each a valid BFS tree; reached={[f['result']['reached'] for f in finals]} "
            f"levels={[f['result']['levels'] for f in finals]}")

        body = client.wait_done(others["wcc"])
        lab = sched.get(others["wcc"]).result["labels"]
        got = (body["result"]["components"], int(np.unique(
            lab, return_counts=True)[1].max()))
        check(got == wcc_ref, f"wcc (components, largest) {got} != "
                              f"reference {wcc_ref}")
        tests = {impl: k - before[impl]
                 for impl, k in end_tests().items()}
        check(sum(tests.values()) > 0, "the wcc peel ran no endgame")
        log(f"phase 3 wcc: components={got[0]} largest={got[1]} exact, "
            f"exec_ms={body.get('exec_ms')}, end's frontier test impl="
            + "+".join(f"{impl} x{k}" for impl, k in tests.items() if k))

        body = client.wait_done(others["pagerank"])
        rank = np.asarray(sched.get(others["pagerank"]).result["rank"],
                          np.float64)
        l1 = float(np.abs(rank - pr_ref).sum())
        top_got = set(np.argsort(-rank)[:10].tolist())
        top_ref = set(np.argsort(-pr_ref)[:10].tolist())
        check(np.isfinite(rank).all() and l1 < PR_L1_TOL,
              f"served pagerank L1 {l1:.3e} >= {PR_L1_TOL}")
        check(top_got == top_ref,
              f"served pagerank top-10 {sorted(top_got)} != reference "
              f"{sorted(top_ref)}")
        log(f"phase 3 pagerank: top-10 ids equal, L1={l1:.3e} "
            f"(< {PR_L1_TOL}), exec_ms={body.get('exec_ms')}")

        body = client.wait_done(others["sssp"])
        sdist = np.asarray(sched.get(others["sssp"]).result["dist"],
                           np.float64)
        t0 = time.time()
        sref = ref_sssp(build_chunked_csr(snap)["_host"], n,
                        int(bfs_src[0]))
        fin = np.isfinite(sref)
        check(np.array_equal(fin, sdist < 1e38),
              "sssp: reached set differs from scipy Dijkstra")
        check(body["result"]["reached"] == int(fin.sum()),
              f"sssp reached {body['result']['reached']} != {int(fin.sum())}")
        err = float(np.max(np.abs(sdist[fin] - sref[fin])
                           / np.maximum(sref[fin], 1.0)))
        check(err < SSSP_RTOL, f"sssp max rel err {err:.3e} >= {SSSP_RTOL}")
        log(f"phase 3 sssp: reached={int(fin.sum())} max_rel_err={err:.2e} "
            f"(< {SSSP_RTOL}) vs scipy Dijkstra ({time.time() - t0:.1f}s "
            f"host), exec_ms={body.get('exec_ms')}")

        # -- interactive lane: 16 concurrent 2-hop out counts, one ppr
        # timeout_s: the lane's 30 s default is a warm-lane figure; the
        # first query of a cold lane scans the store into the directed
        # snapshot and compiles its kernels
        answers = client.concurrently("/traverse", [
            {"start": [int(vids[s])], "dir": "out", "hops": 2,
             "terminal": "count", "timeout_s": COLD_LANE_TIMEOUT_S}
            for s in trav_src])
        for a, want, s in zip(answers, trav_ref, trav_src):
            check(a["fallback"] is False,
                  f"/traverse from {s} fell back: {a.get('why')}")
            check(a["result"] == want,
                  f"/traverse 2-hop out count from {s}: {a['result']} "
                  f"!= reference {want}")
        log(f"phase 3 traverse: {N_TRAVERSE} 2-hop out counts exact, no "
            f"fallback, fused_k={sorted({a.get('fused_k') for a in answers})}"
            f" counts={[a['result'] for a in answers]}")

        a = client.req("/traverse", {"kind": "ppr",
                                     "source": int(vids[bfs_src[0]]),
                                     "iterations": PR_ITERATIONS,
                                     "top_k": 10,
                                     "timeout_s": COLD_LANE_TIMEOUT_S})
        check(a["fallback"] is False, f"ppr fell back: {a.get('why')}")
        want = ppr_ref.copy()
        want[int(bfs_src[0])] = -1.0              # never recommend self
        order = np.argsort(-want)[:10]
        got_ids = [int(v) for v, _ in a["result"]]
        got_rank = np.array([r for _, r in a["result"]])
        check(set(got_ids) == {int(vids[i]) for i in order},
              f"ppr top-10 ids {got_ids} != reference "
              f"{[int(vids[i]) for i in order]}")
        ref_of = {int(vids[i]): want[i] for i in order}
        perr = max(abs(r - ref_of[v]) / ref_of[v]
                   for v, r in zip(got_ids, got_rank))
        check(perr < 1e-3, f"ppr rank rel err {perr:.3e} >= 1e-3")
        log(f"phase 3 ppr: top-10 ids equal, max_rel_err={perr:.2e} "
            "(< 1e-3)")

        # -- phase 4: the same cohort again; no new compile allowed
        w2 = prof.window()
        finals2 = _bfs_cohort(client, sched, bfs_jobs)
        pass2 = w2.close()
        _check_bfs(finals2, sched, bfs_ref, targets, tgt_ids, inf, tree)
        log(f"phase 4 second pass: pass1 compiles={pass1['compiles']} "
            f"wall_s={pass1['wall_s']:.2f} (cohort + wcc/pagerank/sssp "
            f"queued behind it); pass2 compiles={pass2['compiles']} "
            f"wall_s={pass2['wall_s']:.2f} batch_k="
            f"{finals2[0]['batch_k']}")
        check(pass2["compiles"] == 0,
              f"second BFS pass compiled {pass2['compiles']} kernels: "
              f"{prof.compile_log()[-pass2['compiles']:]}")
        # -- a lone BFS job from a drawn source (ISSUE 49): K = 1, so
        #    the job builds every program a source can meet ahead of its
        #    run (bfs.build); a second one, of another source, builds
        #    nothing. Depths, targets, reached and levels held against the
        #    reference as the cohort's are
        lone = []
        for i, (job, ref) in enumerate(zip(bfs_jobs[:2], bfs_ref[:2])):
            w = prof.window()
            body = client.wait_done(client.req("/jobs", job)["job"])
            lone.append((body, w.close()))
            _check_bfs([body], sched, [ref], targets, tgt_ids, inf,
                       (tree[0], tree[1][i:]), batch_k=1)
        check(lone[1][1]["compiles"] == 0,
              f"the second lone BFS job compiled "
              f"{lone[1][1]['compiles']} kernels: "
              f"{prof.compile_log()[-lone[1][1]['compiles']:]}")
        log(f"phase 4 lone bfs: 2 jobs of drawn sources, batch_k=1, all "
            f"{n} depths exact and parents valid; lone1 compiles="
            f"{lone[0][1]['compiles']} "
            f"exec_ms={lone[0][0].get('exec_ms')} (the build-ahead), "
            f"lone2 compiles={lone[1][1]['compiles']} "
            f"exec_ms={lone[1][0].get('exec_ms')}")
        tot = prof.stats()
        log(f"phase 4 device cost: calls={tot['calls']} compiles="
            f"{tot['compiles']} compile_s={tot['compile_s']:.1f} "
            f"h2d_bytes={tot['h2d_bytes']} d2h_bytes={tot['d2h_bytes']}")
        top = sorted(prof.kernel_stats().items(),
                     key=lambda kv: -kv[1]["calls"])[:12]
        log("phase 4 kernels by calls: " + ", ".join(
            f"{k}:{v['calls']}c/{v['compiles']}x" for k, v in top))
    finally:
        srv.stop()
        sched.close()
        prof.uninstall()


def _check_bfs(finals, sched, bfs_ref, targets, tgt_ids, inf, tree,
               batch_k: int = N_BFS) -> None:
    keys, sources = tree
    for body, ref, source in zip(finals, bfs_ref, sources):
        res = body["result"]
        check(body["batch_k"] == batch_k,
              f"bfs job {body['job']} ran in a batch of "
              f"{body['batch_k']}, not {batch_k}")
        check(res["reached"] == int((ref >= 0).sum()),
              f"bfs reached {res['reached']} != {int((ref >= 0).sum())}")
        # the served level count includes the source's own level 0
        check(res["levels"] == int(ref.max()) + 1,
              f"bfs levels {res['levels']} != eccentricity+1 "
              f"{int(ref.max()) + 1}")
        want = {str(v): (int(ref[t]) if ref[t] >= 0 else None)
                for v, t in zip(tgt_ids, targets)}
        check(res["targets"] == want, "bfs target distances differ")
        dist = np.asarray(sched.get(body["job"]).result["dist"])
        full = np.where(dist < inf, dist, -1)
        check(np.array_equal(full, ref), "bfs full distance array differs")
        faults = bfs_tree_faults(
            keys, ref, source, sched.get(body["job"]).result["parent"])
        check(not faults, f"bfs job {body['job']}: parent array: {faults}")


def phase_sharded(scale: int, seed: int) -> None:
    """--chips 4: sharded BFS against the single-chip BFS, and nothing
    else. The snapshot comes straight from the generated arrays — the
    store path is the one-chip run's phase 1."""
    import jax

    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
    from titan_tpu.models.bfs_hybrid_sharded import \
        frontier_bfs_hybrid_sharded
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.parallel.mesh import vertex_mesh

    n, src, dst = generate(scale, seed)
    snap = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    deg = snap.out_degree
    sources = np.random.default_rng(seed).choice(
        np.flatnonzero(deg > 0), 2, replace=False)
    mesh = vertex_mesh(4)
    log(f"sharded: n={n} sym_edges={int(deg.sum())} mesh="
        f"{[d.id for d in mesh.devices.flat]} sources={sources.tolist()}")
    for i, s in enumerate(sources):
        t0 = time.time()
        d4, lv4 = frontier_bfs_hybrid_sharded(snap, int(s), mesh)
        t4 = time.time() - t0
        if i == 0:          # the first call placed the shards
            used = [(d.memory_stats() or {}).get("bytes_in_use")
                    for d in jax.devices()[:4]]
            log(f"sharded: bytes_in_use per device after placement = {used}")
            if all(u is not None for u in used):
                check(used[0] <= 2 * (sum(used) / len(used)),
                      f"device 0 holds {used[0]} bytes, more than twice "
                      f"the mean {sum(used) / len(used):.0f}")
            else:
                check(jax.devices()[0].platform != "tpu",
                      "a TPU device reports no bytes_in_use")
        t0 = time.time()
        d1, lv1 = frontier_bfs_hybrid(snap, int(s))
        t1 = time.time() - t0
        check(np.array_equal(np.asarray(d4), np.asarray(d1)),
              f"sharded BFS from {s} differs from the single-chip BFS")
        check(int(lv4) == int(lv1), f"levels differ: {lv4} vs {lv1}")
        log(f"sharded: source {s} bit-equal, levels={int(lv1)} reached="
            f"{int((np.asarray(d1) < (1 << 30)).sum())} "
            f"sharded_s={t4:.2f} single_s={t1:.2f} (first call compiles)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    t_start = time.time()
    log(f"chip_smoke: R-MAT scale {args.scale} edge factor {EDGE_FACTOR} "
        f"seed {args.seed}; scale cut to 20 by default (the published "
        "Graph500 scale is 26: the store path costs about a minute at 20 "
        "and about nine at 22)")
    device = phase_device(args.chips)
    t0 = time.time()
    if args.chips == 4:
        phase_sharded(args.scale, args.seed)
    else:
        ctx = phase_store(args.scale, args.seed)
        try:
            phase_engine(ctx)
            phase_served(ctx, args.seed)
        finally:
            ctx["graph"].close()
    log(f"chip_smoke: set_up_s={t0 - t_start:.1f} phases_s="
        f"{time.time() - t0:.1f} total_s={time.time() - t_start:.1f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
