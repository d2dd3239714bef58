#!/usr/bin/env python
"""Benchmark driver: prints ONE cumulative JSON line after EVERY stage.

The harness parses the LAST stdout line, so a timeout costs only the
stages not yet reached — never the ones already measured (round-2
post-mortem: a single final print + a 27-minute compile stall recorded
nothing). A wall-clock budget (``BENCH_BUDGET_S``, default 1100 s = the
driver's OBSERVED external window; r4's internal 2400 s budget was
killed at ~1200 s) skips stages that no longer fit, noting them in
``detail.skipped``.

Stage order (the two BASELINE HARD targets first — the headline
literally first so no slow day can starve it — then measure rows, then
droppable evidence stages):
  1. bfs scale-26    — the headline (BASELINE.md row 1: >=1B on v5e-8,
                       125M/chip share); never budget-skipped
  2. pagerank s22    — LiveJournal-class s/iteration (>=50x-vs-MR row)
  3. gods_2hop       — GraphOfTheGods 2-hop Gremlin count, inmemory OLTP
  4. ldbc_is3_4hop   — LDBC-SNB-style 4-hop friends expansion p50, sqlite
  5. sssp/wcc        — Graph500 scale-26 SSSP + WCC seconds
  6. store_ingest    — bulk-load s22 through the edgestore, scan back to
                       a snapshot, BFS must match the generated graph
  7. bfs_heavy       — Twitter-2010-parity (1.5B-edge) single-chip BFS
  8. bfs23_sharded / bfs23 — warm-scale + sharded-overhead evidence

TEPS follows the official Graph500 definition: input edge tuples (incl.
duplicates/self-loops) with both endpoints in the traversed component /
BFS wall time; harmonic mean over sampled sources.

On CPU (no accelerator) small scales keep CI fast.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# r4 set 2400s and was killed externally at ~1200s (rc=124, losing the
# pagerank evidence stage) — stages must be planned against the real
# limit so the skip logic, not the kill, decides what is dropped
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1100"))
# the stage that owns the report metric line; ordered first and never
# budget-skipped
HEADLINE_STAGE = "bfs26"
_T_START = time.time()

# per-stage wall-clock estimates: a COMPUTE floor plus an UPLOAD
# component (GB of host→device upload), so admission can be re-priced
# against the run's MEASURED H2D rate instead of a guessed total.
# ``fixed`` covers compiles + compute; upload cost = gb / measured rate.
_EST = {
    #             fixed_s  upload_gb
    "gods_2hop": (20,      0.0),
    "ldbc":      (90,      0.0),
    "bfs23":     (60,      1.2),
    "bfs23_sharded": (180, 2.4),   # shard replica + plain copy
    "bfs26":     (420,     9.0),
    "ssspwcc":   (300,     0.0),   # shares the resident s26 upload
    "pagerank":  (60,      0.6),
    "store_ingest": (550,  0.6),   # s22 ingest+scan is host-bound;
                                   # scale fallback below re-prices
    "bfs_heavy": (120,     11.6),  # 2 reps ~10s each + compiles
    "live_refresh": (90,   0.3),   # host-array merges + one s20 upload
    "serving":   (90,      0.1),   # small-graph batched BFS + retry
    "tenancy":   (60,      0.1),   # shares serving's kernel shapes
    "interactive": (90,    0.1),   # hops-mode fuse sweep + batched PPR
    "bfs_pallas": (150,    1.2),   # both-mode compiles + warm reps
    "segment_pallas": (60, 0.1),   # synthetic [E] array, two kernels
    "distributed_scan": (30, 0.0),  # host-only: 2 HTTP workers, tiny
                                    # graph, no device work at all
    "fleet": (45, 0.0),             # host-only: router + 2 in-process
                                    # replicas, CPU frontier kernels
}
# nominal fast-day H2D rate (GB/s): bfs26's 9GB uploaded in 16.35s
# (BENCH_r05); the headline stage's measured upload re-prices this
_H2D_NOMINAL_GBPS = 0.55
_h2d_gbps = _H2D_NOMINAL_GBPS
# nothing new starts inside this reserve before the external kill
# (the driver window is observed, not contractual — leave margin for
# the final emits)
_HARD_RESERVE_S = 60.0


def _est(name: str, on_accel: bool = True) -> float:
    fixed, gb = _EST.get(name, (60, 0.0))
    if not on_accel:
        return fixed
    return fixed + gb / max(_h2d_gbps, 1e-3)


def _observe_h2d(gb: float, seconds: float) -> None:
    """Re-price H2D uploads from a measured one (headline stage)."""
    global _h2d_gbps
    if gb > 0.5 and seconds > 0:
        _h2d_gbps = max(min(gb / seconds, 2.0), 0.005)


def _left() -> float:
    return BUDGET_S - (time.time() - _T_START)


class Report:
    """Cumulative result: emit() prints the full JSON line every time.

    ``headline()`` is a ONE-SHOT latch: the first call owns the
    metric/value/vs_baseline line for the rest of the run and every
    later call is ignored (VERDICT r5 weak #1: gods_2hop overwrote the
    scale-26 BFS TEPS headline, so the driver's record reported a 0.137
    ms OLTP latency as the round's metric while the real 156.8M-TEPS
    number sat buried in detail — the headline stage runs first
    precisely so it latches first)."""

    def __init__(self) -> None:
        self.metric = "bench_incomplete"
        self.value = 0.0
        self.unit = ""
        self.vs_baseline = 0.0
        self.detail: dict = {"skipped": [], "budget_s": BUDGET_S}
        self._latched = False

    def headline(self, metric: str, value: float, unit: str,
                 vs_baseline: float) -> None:
        if self._latched:
            return
        self.metric, self.value = metric, value
        self.unit, self.vs_baseline = unit, vs_baseline
        self._latched = True

    def emit(self) -> None:
        self.detail["elapsed_s"] = round(time.time() - _T_START, 1)
        print(json.dumps({
            "metric": self.metric, "value": self.value, "unit": self.unit,
            "vs_baseline": self.vs_baseline, "detail": self.detail,
        }), flush=True)

    def skip(self, stage: str, why: str) -> None:
        self.detail["skipped"].append({"stage": stage, "why": why})
        self.emit()


# device-graph cache shared across stages: the H2D upload of the scale-26
# arrays (9GB) is the largest transfer of a run —
# never upload the same graph twice. ALL bench graphs stay resident
# (s22 0.56GB + s23 1.12GB + s26 9.03GB = 10.7GB of 16GB HBM, leaving
# ~3GB for kernel state/temporaries); largest-first eviction only under
# pressure. The budget/eviction logic is the serving layer's HBM library
# (olap/serving/hbm.py) — the same accounting the job scheduler admits
# against, no longer a script-local.
from titan_tpu.olap.serving.hbm import DeviceGraphCache  # noqa: E402

_DEV_GRAPHS = DeviceGraphCache(budget_bytes=12.0e9)


def _load_device_graph(scale: int, edge_factor: int = 16, seed: int = 2):
    import jax

    from titan_tpu.olap.tpu import graph500

    def upload(hg):
        g = graph500.to_device(hg)
        jax.block_until_ready(g["dstT"])
        return g

    hg, g, gen_s, upload_s = _DEV_GRAPHS.get_or_load(
        (scale, edge_factor, seed),
        lambda: graph500.load_or_build(scale, edge_factor, seed=seed,
                                       verbose=False),
        upload)
    if upload_s > 0:
        from titan_tpu.olap.serving.hbm import graph_bytes
        _observe_h2d(graph_bytes(hg) / 1e9, upload_s)
    return hg, g, gen_s, upload_s


def bfs_teps(scale: int, edge_factor: int = 16, seed: int = 2,
             reps: int = 3, sources: int = 1) -> dict:
    import jax

    from titan_tpu.models.bfs import INF
    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
    from titan_tpu.olap.tpu import graph500

    # multi-chip: shard the edge arrays over a vertex mesh (sparse
    # found-list exchange; models/bfs_hybrid_sharded); single chip: the
    # plain hybrid kernel on the uploaded (stage-shared) graph
    ndev = jax.device_count()
    if ndev > 1:
        t0 = time.time()
        hg = graph500.load_or_build(scale, edge_factor, seed=seed,
                                    verbose=False)
        gen_s = time.time() - t0
        from titan_tpu.models.bfs_hybrid_sharded import \
            frontier_bfs_hybrid_sharded
        from titan_tpu.parallel.mesh import vertex_mesh
        mesh = vertex_mesh(ndev)

        def run_bfs(source):
            return frontier_bfs_hybrid_sharded(hg, source, mesh,
                                               return_device=True)
        upload_s = 0.0          # sharded path uploads inside the first run
    else:
        hg, g, gen_s, upload_s = _load_device_graph(scale, edge_factor,
                                                    seed)

        def run_bfs(source):
            return frontier_bfs_hybrid(g, source, return_device=True)

    deg = np.asarray(hg["deg"])
    # Graph500 rule: sample DISTINCT sources with degree > 0
    rng = np.random.default_rng(12345)
    nonzero = np.flatnonzero(deg > 0)
    srcs = [int(s) for s in
            rng.choice(nonzero, size=min(sources, len(nonzero)),
                       replace=False)]

    # warm-up / compile
    t0 = time.time()
    dist, levels = run_bfs(srcs[0])
    jax.block_until_ready(dist)
    first_s = time.time() - t0

    # single-dispatch fused variant (device-side mode/bucket switch —
    # kills the per-level host↔device readback floor). "auto":
    # only when a previous successful fused run at THIS scale left a
    # marker (the persistent compile cache is then warm for it) — a
    # cold fused compile costs many minutes, and
    # checking for mere cache entries would be fooled by the plain
    # hybrid's own warmup compiles.
    # default OFF: the fused variant's multi-minute cold compile is
    # not worth paying on every cold bench run — opt in when the
    # per-level readback floor is what is being measured
    fused_mode = os.environ.get("TITAN_TPU_FUSED_BFS", "0")
    marker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".bench_cache", f"fused_warm_s{scale}.flag")
    run_fused = ndev == 1 and (
        fused_mode == "1"
        or (fused_mode == "auto" and os.path.exists(marker)))
    fused_fn = None
    fused_first_s = None
    fused_err = None
    if run_fused:
        from titan_tpu.models.bfs_hybrid_fused import \
            frontier_bfs_hybrid_fused

        def fused_fn(source):
            return frontier_bfs_hybrid_fused(g, source,
                                             return_device=True)
        t0 = time.time()
        try:
            dist_f, _ = fused_fn(srcs[0])
            jax.block_until_ready(dist_f)
            fused_first_s = time.time() - t0
        except Exception as e:       # e.g. OOM at this scale: skip
            fused_fn = None
            fused_err = f"{type(e).__name__}: {e}"
        if fused_fn is not None:
            # marker write OUTSIDE the run try-block (a marker failure
            # must not discard a good run) but fenced on its own: a
            # read-only FS must not abort the whole BFS stage either
            try:
                os.makedirs(os.path.dirname(marker), exist_ok=True)
                with open(marker, "w") as fh:
                    fh.write("ok\n")
            except OSError:
                pass                 # marker is an optimization only

    deg_dev = graph500.device_degrees(np.asarray(hg["deg_orig"]))
    per_source = []
    for source in srcs:
        times = []
        for _ in range(reps):
            t0 = time.time()
            dist, levels = run_bfs(source)
            jax.block_until_ready(dist)
            times.append(time.time() - t0)
        t_bfs = min(times)
        if fused_fn is not None:
            tf = []
            for _ in range(reps):
                t0 = time.time()
                dist_f, levels_f = fused_fn(source)
                jax.block_until_ready(dist_f)
                tf.append(time.time() - t0)
            if min(tf) < t_bfs:     # report the better variant
                t_bfs, dist, levels = min(tf), dist_f, levels_f
        m2, nreach = graph500.reachable_edge_sum(
            dist, np.asarray(hg["deg_orig"]), int(INF), deg_dev=deg_dev)
        per_source.append({"teps": (m2 // 2) / t_bfs, "t_bfs": t_bfs,
                           "levels": int(levels), "reach": nreach,
                           "m_traversed": m2 // 2, "source": source})
    # Graph500 reports the HARMONIC mean TEPS over the search keys; the
    # detail fields all come from one run (the fastest source) so they
    # stay mutually consistent
    rep = dict(max(per_source, key=lambda r: r["teps"]))
    rep["teps"] = len(per_source) / sum(1.0 / r["teps"]
                                        for r in per_source)
    rep.update({"gen_s": gen_s, "upload_s": upload_s, "first_s": first_s,
                "n": hg["n"], "e_sym_pre_dedup": hg["e_sym"],
                "e_dedup": hg["e_dedup"], "num_sources": len(per_source),
                "n_devices": ndev,
                "fused_variant_ran": fused_fn is not None,
                "fused_error": fused_err,
                "fused_first_s": round(fused_first_s, 2)
                if fused_first_s is not None else None,
                "per_source_teps": [round(r["teps"], 1)
                                    for r in per_source]})
    return rep


def _bfs_stage(rep: Report, scale: int, tag: str) -> None:
    # Graph500 proper uses 64 search keys; default 1 keeps the stage
    # inside the budget (each source ~12s at scale 26) — raise via env
    r = bfs_teps(scale,
                 sources=int(os.environ.get("BENCH_BFS_SOURCES", "1")))
    rep.detail[f"bfs_s{scale}"] = {
        "teps": round(r["teps"], 1),
        "n_devices": r["n_devices"],
        "num_sources": r["num_sources"],
        "n_vertices": r["n"],
        "m_input_sym_edges": r["e_sym_pre_dedup"],
        "m_dedup_edges": r["e_dedup"],
        "bfs_levels": r["levels"],
        "reachable_vertices": r["reach"],
        "m_traversed": r["m_traversed"],
        "bfs_seconds": round(r["t_bfs"], 4),
        "first_run_seconds": round(r["first_s"], 2),
        "graph_build_seconds": round(r["gen_s"], 2),
        "upload_seconds": round(r["upload_s"], 2),
    }
    if tag == "headline":
        # only the headline scale owns the report's metric line — the
        # warm-scale stage runs AFTER it and must not overwrite it.
        # vs_baseline stays the RAW ratio against the 1B v5e-8 target;
        # the per-chip share (target/8 — only one chip exists in this
        # environment) is recorded alongside for honest comparison
        if r["n_devices"] == 1:
            rep.detail[f"bfs_s{scale}"]["per_chip_share_of_1e9_target"] = \
                round(r["teps"] / (1e9 / 8), 3)
        rep.headline(f"graph500_scale{scale}_bfs_teps",
                     round(r["teps"], 1), "TEPS",
                     round(r["teps"] / 1e9, 4))
    rep.emit()


def bfs_sharded_overhead(rep: Report, scale: int) -> None:
    """VERDICT r3 #2: the sharded BFS path run on a ONE-device mesh vs
    the plain single-chip hybrid — evidence the sharding machinery
    (shard_map + exchange dispatches) costs little when the mesh is
    trivial, so multi-chip TEPS projections can multiply from the
    single-chip number."""
    import jax

    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid
    from titan_tpu.models.bfs_hybrid_sharded import \
        frontier_bfs_hybrid_sharded
    from titan_tpu.parallel.mesh import vertex_mesh

    hg, g, _, _ = _load_device_graph(scale)
    deg = np.asarray(hg["deg"])
    source = int(np.flatnonzero(deg > 0)[0])
    mesh = vertex_mesh(1)

    def t_of(fn, reps=3):
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            d, _lv = fn()
            _ = int(np.asarray(d[0]))     # force completion (scalar D2H)
            best = min(best, time.time() - t0)
        return best

    # first sharded call uploads the shard replica + compiles; untimed
    d, _ = frontier_bfs_hybrid_sharded(hg, source, mesh,
                                       return_device=True)
    _ = int(np.asarray(d[0]))
    t_sh = t_of(lambda: frontier_bfs_hybrid_sharded(
        hg, source, mesh, return_device=True), reps=1)
    d, _ = frontier_bfs_hybrid(g, source, return_device=True)
    _ = int(np.asarray(d[0]))
    t_1c = t_of(lambda: frontier_bfs_hybrid(g, source,
                                            return_device=True))
    from titan_tpu.models.bfs_hybrid_sharded import LAST_PROFILE
    disp = [p["dispatches"] for p in LAST_PROFILE]
    rep.detail[f"bfs_s{scale}_sharded_1dev"] = {
        "sharded_seconds": round(t_sh, 3),
        "plain_seconds": round(t_1c, 3),
        "overhead_pct": round(100.0 * (t_sh / t_1c - 1.0), 1),
        # ROADMAP #1 checklist line (ISSUE 13): the 1-device-mesh
        # overhead ratio the 8-chip TEPS projection divides by
        "sharding_overhead_ratio": round(t_sh / t_1c, 3),
        # fused-level dispatch budget (ISSUE 13): 1 dispatch per level
        # + rare exchange-cap retries; ≤2 is the contract
        "dispatches_per_level_max": max(disp) if disp else None,
        "dispatches_per_level_mean": round(sum(disp) / len(disp), 3)
        if disp else None,
        "levels": len(disp),
        "note": (
            "sharded levels are FUSED (ISSUE 13): one shx_td/shx_bu "
            "dispatch per level per cap bucket — opener + chunk "
            "rounds + exhaust + sparse exchange in one kernel (the "
            "r4 host-driven bu0/bu_more/exhaust chain measured 2.0x "
            "here; the r4-morning fused full-width kernel 52x). "
            "Exchange volume is O(frontier) (dryrun COMM_PROFILE).")}
    # free the shard replica before the scale-26 upload
    hg.pop("_shards", None)
    rep.emit()


def sssp_wcc(rep: Report, scale: int) -> None:
    """BASELINE row 6: Graph500 scale-N SSSP + WCC wall seconds."""
    import jax

    from titan_tpu.models.frontier import frontier_sssp, frontier_wcc

    hg, g, _, _ = _load_device_graph(scale)
    deg = np.asarray(hg["deg"])
    source = int(np.flatnonzero(deg > 0)[0])

    # NO warm-up pass: at bench scale one SSSP run costs ~400s (measured
    # 2026-07-30: 25 sliced rounds) — executables come from the
    # persistent XLA cache, so a single timed run is representative
    trace: list = []
    g["_trace_rounds"] = trace       # per-round (band, nf, m8, t, plan_s)
    # isolation drains make plan_s exact at ONE extra host round trip
    # per round — sssp_seconds therefore includes ~rounds x RT of
    # measurement overhead; the count is disclosed below so the <100s
    # comparison can bound it (r5's 121-130s band was untraced)
    g["_trace_plan_drain"] = True
    t0 = time.time()
    d, rounds = frontier_sssp(g, source, return_device=True)
    jax.block_until_ready(d)
    _ = float(np.asarray(d[0]))      # force completion (scalar D2H)
    rep.detail["sssp_seconds"] = round(time.time() - t0, 3)
    rep.detail["sssp_rounds"] = rounds
    rep.detail["sssp_scale"] = scale
    # per-round PLAN cost (the band extraction + segment-bounds kernel +
    # its one host sync, isolated by a pre-plan drain in _frontier_run):
    # the r5 floor was ~1.1s/round of n-wide nonzero + cap-wide gather;
    # the compaction-library plan must hold this ≥2x lower (ISSUE r6) —
    # recorded here so every bench run keeps the evidence
    plan_costs = [r[4] for r in trace if len(r) > 4]
    if plan_costs:
        rep.detail["sssp_plan_s_per_round_mean"] = round(
            float(np.mean(plan_costs)), 4)
        rep.detail["sssp_plan_s_per_round_p50"] = round(
            float(np.median(plan_costs)), 4)
        rep.detail["sssp_plan_s_per_round_max"] = round(
            float(np.max(plan_costs)), 4)
        rep.detail["sssp_plan_s_total"] = round(
            float(np.sum(plan_costs)), 3)
        rep.detail["sssp_plan_isolation_drains"] = len(plan_costs)
    del g["_trace_rounds"]           # WCC below must not pay the drains
    del g["_trace_plan_drain"]
    rep.emit()

    t0 = time.time()
    lab, rounds = frontier_wcc(g, return_device=True)
    jax.block_until_ready(lab)
    _ = float(np.asarray(lab[0]))
    rep.detail["wcc_seconds"] = round(time.time() - t0, 3)
    rep.detail["wcc_rounds"] = rounds
    rep.emit()


def pagerank_stage(rep: Report, lj_scale: int) -> None:
    """BASELINE row 2: LiveJournal-class PageRank s/iteration — the
    >=50x-vs-MapReduce comparison point (reference harness: titan-test
    TitanGraphIterativeBenchmark; Hadoop PageRank on LiveJournal-class
    graphs runs minutes per iteration through HDFS barriers)."""
    import jax

    from titan_tpu.models.frontier import pagerank_dense

    hg, g, _, _ = _load_device_graph(lj_scale)
    r, _ = pagerank_dense(g, iterations=2, return_device=True)  # warm
    _ = float(np.asarray(r[0]))  # force completion (scalar D2H)
    t0 = time.time()
    iters = 10
    r, _ = pagerank_dense(g, iterations=iters, return_device=True)
    _ = float(np.asarray(r[0]))
    sec = (time.time() - t0) / iters
    rep.detail["pagerank_lj_sec_per_iter"] = round(sec, 3)
    rep.detail["pagerank_lj_edges"] = hg["e_dedup"]
    # conservative MR baseline: 180 s/iteration at LiveJournal scale
    rep.detail["pagerank_vs_mapreduce_x"] = round(180.0 / sec, 1)
    rep.detail["pagerank_mr_note"] = (
        "published Hadoop PageRank iterations on LiveJournal-class "
        "graphs run 3-10 MINUTES each on multi-node clusters (every "
        "iteration rewrites the edge list through HDFS map+shuffle+"
        "reduce); 180s is the conservative end. The reference's own "
        "iterative harness (titan-test TitanGraphIterativeBenchmark) "
        "is an OLTP loop over the storage backend — slower still. One "
        "v5e chip replaces a small Hadoop cluster for iterative graph "
        "analytics at >=50x per-iteration wall-clock.")
    rep.emit()


def live_refresh_stage(rep: Report, scale: int) -> None:
    """ISSUE r9 evidence stage (VERDICT r5 missing-evidence complaint):
    the live plane's value claim is that freshness costs a small
    overlay delta-apply instead of a full snapshot rebuild + device
    re-upload. Measure on a synthetic symmetric graph at ``scale``:
    p50/p95 delta-apply latency (append + tombstone + frozen device
    view — the per-commit-batch serving cost), compaction cost (fold
    overlay into a republished CSR), and the full-rebuild baseline the
    overlay avoids. Host+delta-H2D work only, so the numbers are
    CPU-meaningful today; a chip run re-captures them with the real
    host→device link in the loop."""
    import jax

    from titan_tpu.models.bfs_hybrid import frontier_bfs_batched
    from titan_tpu.olap.live.compactor import EpochCompactor
    from titan_tpu.olap.live.overlay import DeltaOverlay
    from titan_tpu.olap.tpu import snapshot as snap_mod

    rng = np.random.default_rng(42)
    n = 1 << scale
    m = n * 8
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)

    def build():
        return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                    np.concatenate([dst, src]))

    t0 = time.time()
    base = build()
    rebuild_s = time.time() - t0
    # upload baseline: the chunked CSR the rebuild path would re-ship
    t0 = time.time()
    d0, _, _ = frontier_bfs_batched(base, [0], return_device=True)
    jax.block_until_ready(d0)
    upload_and_first_run_s = time.time() - t0

    overlay = DeltaOverlay(base)
    batch_lat: list = []
    batch_edges = 256
    for b in range(32):
        a_s = rng.integers(0, n, batch_edges).astype(np.int32)
        a_d = rng.integers(0, n, batch_edges).astype(np.int32)
        rm = rng.choice(m, 32, replace=False)
        t0 = time.time()
        overlay.append_edges(np.concatenate([a_s, a_d]),
                             np.concatenate([a_d, a_s]),
                             np.zeros(2 * batch_edges, np.int32))
        for i in rm:
            overlay.remove_edge(int(src[i]), int(dst[i]), None)
            overlay.remove_edge(int(dst[i]), int(src[i]), None)
        view = overlay.view()          # includes the delta H2D
        batch_lat.append(time.time() - t0)
    lat = np.asarray(sorted(batch_lat))
    t0 = time.time()
    merged = EpochCompactor().merge(base, overlay)
    compact_s = time.time() - t0

    # ---- ISSUE 9: per-epoch H2D bytes (delta pages vs the full
    # re-upload the host path forces) + device-merge vs host-merge
    # compact cost, as first-class metric lines. One epoch at the
    # DEFAULT policy: feed delta batches until should_compact fires,
    # fold on device, count every byte through an isolated registry.
    from titan_tpu.olap.serving.hbm import snapshot_csr_bytes
    from titan_tpu.utils.metrics import MetricManager

    mm = MetricManager()
    comp = EpochCompactor()
    ov2 = DeltaOverlay(base, metrics=mm)
    epoch_batches = 0
    while not comp.should_compact(ov2):
        a_s = rng.integers(0, n, batch_edges).astype(np.int32)
        a_d = rng.integers(0, n, batch_edges).astype(np.int32)
        ov2.append_edges(np.concatenate([a_s, a_d]),
                         np.concatenate([a_d, a_s]),
                         np.zeros(2 * batch_edges, np.int32))
        for i in rng.choice(m, 8, replace=False):
            ov2.remove_edge(int(src[i]), int(dst[i]), None)
            ov2.remove_edge(int(dst[i]), int(src[i]), None)
        ov2.view()
        epoch_batches += 1
    delta_bytes = mm.counter_value("serving.live.upload_bytes")
    t0 = time.time()
    host_oracle = comp.merge(base, ov2)
    compact_host_s = time.time() - t0
    comp.compact(base, ov2, metrics=mm)   # warm the merge kernels
    t0 = time.time()
    merged_dev, merge_mode = comp.compact(base, ov2, metrics=mm)
    compact_device_s = time.time() - t0
    full_bytes = snapshot_csr_bytes(merged_dev)
    assert merged_dev.num_edges == host_oracle.num_edges

    rep.detail["live_refresh"] = {
        "scale": scale, "edges_sym": 2 * m,
        "delta_batches": len(batch_lat),
        "edges_per_batch": 2 * batch_edges,
        "tombstones_per_batch": 64,
        "apply_p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 3),
        "apply_p95_ms": round(
            float(lat[int(len(lat) * 0.95)]) * 1e3, 3),
        "overlay_capacity": overlay.cap,
        "overlay_device_bytes": view.cap * 8 + overlay.q_total,
        "compact_s": round(compact_s, 3),
        "full_rebuild_s": round(rebuild_s, 3),
        "rebuild_upload_first_run_s": round(upload_and_first_run_s, 3),
        # the headline ratio: per-delta freshness vs the rebuild the
        # overlay avoids (compaction amortizes over every batch since
        # the last epoch)
        "rebuild_over_apply_p50_x": round(
            rebuild_s / max(float(lat[len(lat) // 2]), 1e-9), 1),
        "merged_edges": merged.num_edges,
        # ISSUE 9 epoch-boundary lines: device-resident compaction
        # means the per-epoch H2D cost is the delta pages the overlay
        # shipped incrementally, not the merged CSR image the host
        # path re-uploads — the ratio is the tentpole win, byte-
        # counted so it is CPU-verifiable without a chip
        "merge_mode": merge_mode,
        "epoch_delta_batches": epoch_batches,
        "h2d_delta_bytes_per_epoch": int(delta_bytes),
        "h2d_full_snapshot_bytes": int(full_bytes),
        "h2d_full_over_delta_x": round(
            full_bytes / max(delta_bytes, 1), 1),
        "compact_host_s": round(compact_host_s, 4),
        "compact_device_s": round(compact_device_s, 4),
    }
    rep.emit()


def serving_stage(rep: Report, scale: int) -> None:
    """ISSUE r10 evidence stage (ROADMAP item 5b/5d): the serving and
    recovery planes as FIRST-CLASS metric lines in the driver artifact —
    ``serving.batch.occupancy`` + job latency at K=8 vs K=1, recovery
    replay cost (checkpointed retry: rounds replayed + checkpoint
    commit latency), and the trace digest showing where a fused job's
    time went. Runs the real JobScheduler/Batcher/recovery stack on a
    synthetic graph (CPU-meaningful; a chip run re-captures with the
    device in the loop)."""
    import tempfile

    from titan_tpu.obs.tracing import trace_summary
    from titan_tpu.olap.api import JobSpec
    from titan_tpu.olap.recovery import FaultPlan
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.utils.metrics import MetricManager

    rng = np.random.default_rng(42)
    n = 1 << scale
    m = n * 8
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    snap = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    metrics = MetricManager()        # isolated: bench-only lines
    with tempfile.TemporaryDirectory() as ckdir:
        sched = JobScheduler(snapshot=snap, metrics=metrics,
                             autostart=False, checkpoint_dir=ckdir)
        try:
            # K=8 fused batch (paused scheduler pins the composition)
            sources = rng.integers(0, n, 8)
            t0 = time.time()
            batch = [sched.submit(JobSpec(
                kind="bfs", params={"source_dense": int(s)}))
                for s in sources]
            sched.start()
            for j in batch:
                j.wait(120)
            k8_s = time.time() - t0
            # K=1 reference on the warm kernel
            t0 = time.time()
            j1 = sched.submit(JobSpec(kind="bfs",
                                      params={"source_dense": 0}))
            j1.wait(120)
            k1_s = time.time() - t0
            # recovery replay cost: crash at round 2 with per-round
            # checkpoints → the retry resumes instead of restarting
            jr = sched.submit(JobSpec(
                kind="bfs",
                params={"source_dense": int(sources[0]),
                        "faults": FaultPlan(crash_at_round=2)},
                max_retries=1, checkpoint_every=1))
            jr.wait(120)
            occ = metrics.histogram("serving.batch.occupancy").to_dict()
            lat = metrics.histogram("serving.job.latency_ms").to_dict()
            rep.detail["serving"] = {
                "scale": scale, "edges_sym": 2 * m,
                "batch_occupancy": occ,
                "job_latency_ms": lat,
                "queue_ms": metrics.histogram(
                    "serving.job.queue_ms").to_dict(),
                "k8_batch_wall_s": round(k8_s, 3),
                "k1_wall_s": round(k1_s, 3),
                # amortization evidence: wall clock per job in the
                # fused batch vs the single run
                "k8_per_job_over_k1_x": round(
                    (k8_s / 8) / max(k1_s, 1e-9), 3),
                "recovery": {
                    "status": jr.state.value,
                    "attempts": jr.attempt,
                    "rounds_replayed": metrics.counter_value(
                        "serving.recovery.rounds_replayed"),
                    "resumes": metrics.counter_value(
                        "serving.recovery.resumes"),
                    "retries": metrics.counter_value(
                        "serving.recovery.retries"),
                    "checkpoints": metrics.counter_value(
                        "serving.recovery.checkpoints"),
                    "checkpoint_ms": metrics.histogram(
                        "serving.recovery.checkpoint_ms").to_dict(),
                },
                "trace_k8_job": trace_summary(sched.tracer,
                                              batch[0].id),
                "trace_retried_job": trace_summary(sched.tracer, jr.id),
            }
        finally:
            sched.close()
    rep.emit()


def tenancy_stage(rep: Report, scale: int) -> None:
    """ISSUE 8 evidence stage (ROADMAP item 3 observable-first): the
    per-tenant SLO plane as first-class metric lines — two synthetic
    tenants share one scheduler, and the artifact records each
    tenant's p95 latency (from the {tenant}-labeled histogram
    children), its device-seconds / HBM-byte-seconds attribution, and
    the exactness check that labeled children sum to the unlabeled
    aggregate. Feeds the next hardware window: a chip run re-captures
    the same lines with the device in the loop."""
    from titan_tpu.olap.api import JobSpec
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.utils.metrics import MetricManager, nearest_rank

    rng = np.random.default_rng(42)
    n = 1 << scale
    m = n * 8
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    snap = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    metrics = MetricManager()        # isolated: bench-only lines
    # autotune defaults to SHADOW — the stage leaves it there and
    # drives one explicit post-load tick so the evidence bundle gets a
    # real journaled decision from real signals (the tick interval is
    # parked high so the worker loop doesn't consume the occupancy
    # delta first)
    sched = JobScheduler(snapshot=snap, metrics=metrics,
                         autostart=False, autotune_tick_s=3600.0)
    try:
        # interleaved submits: alpha floods 12 jobs, beta sends 4 —
        # fused batches mix tenants, which is exactly what the per-K
        # attribution split has to untangle
        sources = rng.integers(0, n, 16)
        jobs = [sched.submit(JobSpec(
            kind="bfs", params={"source_dense": int(s)},
            tenant="alpha" if i % 4 else "beta"))
            for i, s in enumerate(sources)]
        sched.start()
        for j in jobs:
            j.wait(120)
        # wait() fires at the state transition inside the batch; the
        # worker finalizes counters/attribution just after — poll so
        # the roll-up exactness line never reads a mid-finalize state
        deadline = time.time() + 10
        while time.time() < deadline and metrics.counter_value(
                "serving.jobs.completed") < len(jobs):
            time.sleep(0.01)
        rows = sched.tenant_stats()["tenants"]
        per_tenant = {}
        for t in ("alpha", "beta"):
            pooled: list = []
            for _lbls, child in metrics.children(
                    "serving.job.latency_ms", {"tenant": t}):
                pooled.extend(child.values())
            r = rows[t]
            per_tenant[t] = {
                "jobs": r["submitted"],
                "p50_latency_ms": round(
                    nearest_rank(pooled, 0.5), 3) if pooled else None,
                "p95_latency_ms": round(
                    nearest_rank(pooled, 0.95), 3) if pooled else None,
                "queue_ms": round(r["queue_ms"], 3),
                "device_seconds": round(r["device_seconds"], 6),
                "hbm_byte_seconds": round(r["hbm_byte_seconds"], 1),
            }
        labeled_sum = sum(
            c.count for _lbls, c in metrics.children(
                "serving.jobs.completed"))
        # ISSUE 14: one shadow-mode controller tick over the stage's
        # real signals — the decision count + an example journal entry
        # feed the --evidence roadmap5 `controller_decisions` line
        controller = None
        if sched.controller is not None:
            sched.controller.tick(force=True)
            journal = sched.controller.journal()
            controller = {
                "mode": sched.controller.mode,
                "decisions": len(journal),
                "example": journal[-1] if journal else None}
        rep.detail["tenancy"] = {
            "controller": controller,
            "scale": scale, "edges_sym": 2 * m,
            "tenants": per_tenant,
            # roll-up exactness: the labeled children account for every
            # completed job the unlabeled aggregate saw
            "completed_total": metrics.counter_value(
                "serving.jobs.completed"),
            "completed_labeled_sum": labeled_sum,
            "device_seconds_total": round(sum(
                r["device_seconds"] for r in rows.values()), 6),
        }
    finally:
        sched.close()
    rep.emit()


def interactive_stage(rep: Report, scale: int) -> None:
    """ISSUE 11 evidence stage (ROADMAP #3): the interactive lane's
    fuse economics as first-class metric lines — per-query p50/p95 of
    2-hop point queries fused K=16 vs run sequentially (K=1), the fuse
    occupancy histogram, and batched personalized-PageRank throughput
    (one vmapped [S, n] dispatch) vs S sequential personalized runs.
    CPU-meaningful; a chip run re-captures with the device in the
    loop."""
    import threading

    from titan_tpu.models.frontier import pagerank_dense
    from titan_tpu.models.pagerank import pagerank_personalized_batched
    from titan_tpu.olap.serving.interactive import plan_from_wire
    from titan_tpu.olap.serving.scheduler import JobScheduler
    from titan_tpu.olap.tpu import snapshot as snap_mod
    from titan_tpu.utils.metrics import MetricManager, nearest_rank

    rng = np.random.default_rng(42)
    n = 1 << scale
    m = n * 8
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    snap = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    K = 16

    def q(vid):
        return plan_from_wire({"start": [int(vid)], "dir": "both",
                               "hops": 2, "terminal": "count"})

    sources = rng.integers(0, n, K)
    metrics = MetricManager()            # isolated: bench-only lines
    # fused lane: a window long enough that a thread burst always
    # lands in ONE batch; solo lane: near-zero window, every query its
    # own dispatch (the K=1 reference)
    fused = JobScheduler(snapshot=snap, metrics=metrics,
                         autostart=False, interactive_window_s=0.05,
                         interactive_max_fuse=K)
    solo = JobScheduler(snapshot=snap, metrics=MetricManager(),
                        autostart=False, interactive_window_s=1e-4)
    try:
        lane_f, lane_s = fused.interactive(), solo.interactive()
        # warm both XLA shape buckets (K=16 padded, K=1)
        lane_s.submit(q(sources[0]))
        warm = [threading.Thread(
            target=lambda v=v: lane_f.submit(q(v))) for v in sources]
        for t in warm:
            t.start()
        for t in warm:
            t.join(60)
        fused_ms: list = []
        exec_ms: list = []

        def go(vid):
            t0 = time.time()
            res = lane_f.submit(q(vid))
            fused_ms.append((time.time() - t0) * 1e3)
            exec_ms.append(res["exec_ms"])

        reps = 3
        for _ in range(reps):
            threads = [threading.Thread(target=go, args=(v,))
                       for v in sources]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        seq_ms: list = []
        for _ in range(reps):
            for vid in sources:
                t0 = time.time()
                lane_s.submit(q(vid))
                seq_ms.append((time.time() - t0) * 1e3)
        occ = metrics.histogram(
            "serving.interactive.fuse_k").to_dict()
        # batched PPR throughput vs sequential personalized oracle
        S, iters = 8, 10
        ppr_src = [int(v) for v in sources[:S]]
        ppr_dense = [snap.dense_of(v) for v in ppr_src]
        pagerank_personalized_batched(snap, ppr_dense,
                                      iterations=iters)  # warm
        t0 = time.time()
        pagerank_personalized_batched(snap, ppr_dense,
                                      iterations=iters)
        batched_s = time.time() - t0
        reset0 = np.zeros(snap.n, np.float32)
        reset0[ppr_dense[0]] = 1.0
        pagerank_dense(snap, iterations=iters, reset=reset0)  # warm
        t0 = time.time()
        for sd in ppr_dense:
            reset = np.zeros(snap.n, np.float32)
            reset[sd] = 1.0
            pagerank_dense(snap, iterations=iters, reset=reset)
        seq_s = time.time() - t0
        rep.detail["interactive"] = {
            "scale": scale, "edges_sym": 2 * m, "k": K,
            "point_query_fused_p50_ms": round(
                nearest_rank(fused_ms, 0.5), 3),
            "point_query_fused_p95_ms": round(
                nearest_rank(fused_ms, 0.95), 3),
            "point_query_seq_p50_ms": round(
                nearest_rank(seq_ms, 0.5), 3),
            "point_query_seq_p95_ms": round(
                nearest_rank(seq_ms, 0.95), 3),
            "fused_exec_ms_per_batch": round(
                nearest_rank(exec_ms, 0.5), 3),
            "fuse_occupancy": occ,
            # device-economics headline: K queries' worth of answers
            # per fused device dispatch vs K separate dispatches
            "fused_device_ms_per_query": round(
                nearest_rank(exec_ms, 0.5) / K, 4),
            "ppr_users": S, "ppr_iterations": iters,
            "ppr_batched_wall_s": round(batched_s, 3),
            "ppr_seq_wall_s": round(seq_s, 3),
            "ppr_batched_users_per_s": round(
                S / max(batched_s, 1e-9), 1),
            "ppr_speedup_x": round(seq_s / max(batched_s, 1e-9), 2),
        }
    finally:
        fused.close()
        solo.close()
    rep.emit()


def bfs_heavy_stage(rep: Report) -> None:
    """BASELINE row 5: Twitter-2010-class (1.5B-edge) single-chip BFS.
    The dataset itself is unreachable in-image (zero egress), so the
    stage substitutes an R-MAT at directed-edge-count parity: scale 25 /
    edge-factor 44 = 1.476B generated edges vs Twitter-2010's 1.468B
    (R-MAT s25 has 33.5M vertices vs Twitter's 41.6M). The one-time
    graph build (~15 min C++) must already be on disk
    (scripts/build_heavy_graph.py); the stage skips rather than blowing
    the budget on it."""
    from titan_tpu.olap.tpu import graph500

    tag = "g500_s25_ef44_seed2"
    if not os.path.exists(os.path.join(graph500.DEFAULT_CACHE,
                                       tag + ".json")):
        rep.skip("bfs_heavy", "graph cache absent (one-time ~15min "
                 "build: python scripts/build_heavy_graph.py)")
        return
    # reps fallback: when the measured H2D rate prices the full stage
    # out of the remaining budget, one rep still lands a driver-captured
    # number (the upload dominates — a second rep adds ~10s)
    reps = 2
    if _left() < _est("bfs_heavy") + 30:
        reps = 1
        rep.detail["bfs_heavy_reps_fallback"] = {
            "reps": 1, "why": f"{_left():.0f}s left, est "
                              f"{_est('bfs_heavy'):.0f}s at "
                              f"{_h2d_gbps:.3f}GB/s"}
    r = bfs_teps(25, edge_factor=44, reps=reps)
    rep.detail["bfs_heavy_single_chip"] = {
        "substitution": "RMAT s25 ef44 at Twitter-2010 directed-edge "
                        "parity (1.476B vs 1.468B input edges)",
        "teps": round(r["teps"], 1),
        "n_vertices": r["n"],
        "m_input_directed_edges": r["n"] * 44,
        "m_dedup_edges": r["e_dedup"],
        "bfs_levels": r["levels"],
        "reachable_vertices": r["reach"],
        "m_traversed": r["m_traversed"],
        "bfs_seconds": round(r["t_bfs"], 4),
        "first_run_seconds": round(r["first_s"], 2),
        "upload_seconds": round(r["upload_s"], 2),
    }
    rep.emit()


def store_ingest_stage(rep: Report, scale: int,
                       smoke: bool = False) -> None:
    """VERDICT r4 #4 / the north-star contract: OLAP over a CSR snapshot
    OF THE EDGE STORE at benchmark scale. Generates an R-MAT edge list,
    bulk-loads it through the storage plane (KCVS mutations via the
    batch-loading path, reference: GraphDatabaseConfiguration
    STORAGE_BATCH), scans the edgestore back into a snapshot
    (native scan), builds the chunked CSR, and runs the SAME BFS —
    checking the result against the generated-graph BFS.

    SCALE FALLBACK (ISSUE r7): the stage is host-bound and scales
    ~linearly with edges, so when the remaining budget can't cover the
    requested scale it steps down (s22 → s21 → s20) instead of being
    skipped outright — a smaller driver-captured number beats a third
    round of no number at all. The chosen scale is recorded."""
    import jax

    fixed, _gb = _EST["store_ingest"]
    if smoke:                    # CPU/CI scales cost ~1/10th (main())
        fixed = fixed / 10
    full_scale = scale
    candidates = [s for s in range(scale, scale - 3, -1) if s >= 10] \
        or [scale]
    chosen = None
    for s in candidates:
        # est halves per scale step down (edge count halves; the
        # +60s covers the fixed BFS/compile tail that doesn't shrink)
        if _left() > fixed / (2 ** (full_scale - s)) + 60:
            chosen = s
            break
    if chosen is None:
        rep.skip("store_ingest",
                 f"budget: {_left():.0f}s left cannot fit even the "
                 f"s{candidates[-1]} fallback")
        return
    scale = chosen
    if scale != full_scale:
        rep.detail["store_ingest_scale_fallback"] = {
            "requested": full_scale, "ran": scale,
            "why": f"{_left():.0f}s left"}

    from titan_tpu.models.bfs import INF
    from titan_tpu.models.bfs_hybrid import (build_chunked_csr,
                                             frontier_bfs_hybrid)
    from titan_tpu.olap import bulk

    t0 = time.time()
    res = bulk.ingest_rmat_store(scale, edge_factor=16, seed=2)
    g, snap = res["graph"], res["snapshot"]
    try:
        t1 = time.time()
        csr = build_chunked_csr(snap)
        jax.block_until_ready(csr["dstT"])
        csr_s = time.time() - t1

        # BFS on the store-derived snapshot, same source rule as the
        # generated-graph stage. Source picked from the GENERATED graph's
        # degrees (the store path keeps self-loops the generated CSR
        # drops, so its nonzero-degree set can differ — the pick must
        # match the reference stage's exactly); dense index spaces are
        # identical because bulk ids were assigned in dense order.
        hg, gref, _, _ = _load_device_graph(scale)   # shared/resident
        # the dist check only holds if the reference cache and the
        # ingest used the SAME R-MAT generator (native vs numpy edge
        # sets differ for one seed; a native-built cache read on a
        # native-less host would falsely indict the bulk-load path)
        from titan_tpu import native as _native
        gen_here = "native" if _native.available else "numpy"
        gen_ref = hg.get("generator", gen_here)
        deg = np.asarray(hg["deg"])
        rng = np.random.default_rng(12345)
        source = int(rng.choice(np.flatnonzero(deg > 0), size=1,
                                replace=False)[0])
        t2 = time.time()
        dist, levels = frontier_bfs_hybrid(csr, source,
                                           return_device=True)
        jax.block_until_ready(dist)
        bfs_s = time.time() - t2

        # equivalence vs the generated-graph CSR: reachable count and
        # level histogram must match exactly (duplicate edges in the
        # store path don't change BFS distances)
        dist_ref, levels_ref = frontier_bfs_hybrid(gref, source,
                                                   return_device=True)
        match = (bulk.dist_match(dist, dist_ref, int(INF))
                 if gen_ref == gen_here else
                 f"not comparable: reference cache built by "
                 f"{gen_ref} generator, ingest used {gen_here}")
        rep.detail[f"store_ingest_s{scale}"] = {
            "n_vertices": res["n"], "m_edges_ingested": res["m"],
            "ingest_seconds": round(res["ingest_s"], 1),
            "scan_snapshot_seconds": round(res["scan_s"], 1),
            "csr_build_upload_seconds": round(csr_s, 1),
            "bfs_seconds": round(bfs_s, 3),
            "bfs_levels": levels, "bfs_levels_ref": levels_ref,
            "dist_matches_generated": match,
            "total_seconds": round(time.time() - t0, 1),
        }
        rep.emit()
    finally:
        g.close()


def ldbc_is3_4hop(rep: Report, tmp_dir: str | None = None,
                  n_persons: int = 10_000, avg_degree: int = 36) -> None:
    """BASELINE row 4: LDBC-SNB-style interactive short-read latency on
    the embedded persistent store (BerkeleyJE role = sqlite here) — p50
    of a 4-hop friends expansion from sampled persons over an SF1-scale
    synthetic social graph (10k persons, ~180k knows edges), built once
    and cached on disk."""
    import shutil

    import titan_tpu

    base = tmp_dir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".bench_cache",
        f"ldbc_{n_persons}")
    # a sentinel marks a COMPLETE build: open() itself creates the dir,
    # so dir-existence would treat an interrupted build as a valid cache
    sentinel = os.path.join(base, ".complete")
    fresh = not os.path.exists(sentinel)
    if fresh and os.path.exists(base):
        shutil.rmtree(base, ignore_errors=True)
    g = titan_tpu.open({"storage.backend": "sqlite",
                        "storage.directory": base})
    try:
        t_build0 = time.time()
        if fresh:
            rng = np.random.default_rng(7)
            tx = g.new_transaction()
            people = [tx.add_vertex("person", name=f"p{i}")
                      for i in range(n_persons)]
            m = n_persons * avg_degree // 2
            for a, b in zip(rng.integers(0, n_persons, m),
                            rng.integers(0, n_persons, m)):
                if a != b:
                    people[int(a)].add_edge("knows", people[int(b)])
            tx.commit()
            with open(sentinel, "w") as f:
                f.write("ok")
        build_s = time.time() - t_build0
        rng = np.random.default_rng(99)
        tx = g.new_transaction()
        ids = [v.id for i, v in zip(range(200), tx.vertices())]
        tx.rollback()
        srcs = [ids[int(i)] for i in rng.integers(0, len(ids), 12)]
        # LDBC interactive measures a steady-state window after a
        # warm-up period: run a handful of untimed 4-hop operations
        # from vertices OUTSIDE the timed set (so no timed sample is a
        # hot repeat) to fill the adjacency cache, exactly like the
        # driver's warm-up phase. The cold first-touch latency is
        # reported separately (VERDICT r3 weak #3: the old single
        # warm-up left the first timed queries paying first-touch
        # parse costs — p95 was 8x p50 from cache fill, not from any
        # engine cliff; rep-2 latencies were uniform 31-100ms).
        warm = [i for i in ids if i not in set(srcs)][:8]
        t0 = time.time()
        g.traversal().V(warm[0]).out("knows").out("knows") \
            .out("knows").out("knows").count().next()
        cold_ms = (time.time() - t0) * 1e3
        for w in warm[1:]:
            g.traversal().V(w).out("knows").out("knows") \
                .out("knows").out("knows").count().next()
        lat = []
        counts = []
        for vid in srcs:
            t0 = time.time()
            c = g.traversal().V(vid).out("knows").out("knows") \
                .out("knows").out("knows").count().next()
            lat.append(time.time() - t0)
            counts.append(c)
        lat.sort()
        rep.detail.update({
            "ldbc_is3_4hop_p50_ms": round(lat[len(lat) // 2] * 1e3, 2),
            "ldbc_is3_4hop_p95_ms": round(lat[-1] * 1e3, 2),
            "ldbc_cold_first_ms": round(cold_ms, 2),
            "ldbc_warmup_ops": len(warm),
            "ldbc_persons": n_persons,
            "ldbc_build_s": round(build_s, 1),
            "ldbc_4hop_median_reach": int(sorted(counts)[len(counts)//2])})
        rep.emit()
    finally:
        g.close()
        if tmp_dir is not None:
            shutil.rmtree(base, ignore_errors=True)


def gods_2hop(rep: Report) -> None:
    """BASELINE config #1: GraphOfTheGods 2-hop Gremlin count on inmemory
    (OLTP traversal latency, p50 of 20 runs)."""
    import titan_tpu
    from titan_tpu import example

    g = titan_tpu.open("inmemory")
    example.load(g)
    two = lambda: g.traversal().V().out().out().count().next()  # noqa: E731
    count = two()
    lat = []
    for _ in range(20):
        t = time.time()
        two()
        lat.append(time.time() - t)
    g.close()
    # detail ONLY — the report's metric line belongs to the headline BFS
    # stage (VERDICT r5 weak #1: the old rep.headline call here
    # overwrote the scale-26 TEPS record in the driver artifact)
    rep.detail["gods_2hop_p50_ms"] = round(sorted(lat)[len(lat) // 2] * 1e3,
                                           3)
    rep.detail["gods_2hop_count"] = int(count)
    rep.emit()


def bfs_pallas_stage(rep: Report, scale: int) -> None:
    """ISSUE 16 evidence stage: the fused Pallas bottom-up frontier
    kernel (``TITAN_TPU_FRONTIER_KERNEL=pallas``, ops/pallas_frontier)
    vs the XLA bu chain on the warm-scale graph — warm best-of-3 per
    mode from one source, results asserted bit-equal. Chip-only:
    interpreter mode times an XLA emulation of the kernel, not the
    chip (CPU parity is tier-1's job — tests/test_pallas_frontier.py),
    so on CPU this stage is a recorded skip, never a fake number."""
    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid

    hg, g, _, _ = _load_device_graph(scale)
    deg = np.asarray(hg["deg"])
    source = int(np.flatnonzero(deg > 0)[0])
    saved = os.environ.get("TITAN_TPU_FRONTIER_KERNEL")

    def timed(mode):
        os.environ["TITAN_TPU_FRONTIER_KERNEL"] = mode
        d, lv = frontier_bfs_hybrid(g, source, return_device=True)
        _ = int(np.asarray(d[0]))     # warm: compiles + first run
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            d, lv = frontier_bfs_hybrid(g, source, return_device=True)
            _ = int(np.asarray(d[0]))  # force completion (scalar D2H)
            best = min(best, time.time() - t0)
        return best, np.asarray(d), lv

    try:
        t_x, d_x, lv_x = timed("xla")
        t_p, d_p, lv_p = timed("pallas")
    finally:
        if saved is None:
            os.environ.pop("TITAN_TPU_FRONTIER_KERNEL", None)
        else:
            os.environ["TITAN_TPU_FRONTIER_KERNEL"] = saved
    if lv_x != lv_p or not np.array_equal(d_x, d_p):
        raise AssertionError(
            f"pallas bu result != xla result (levels {lv_p} vs {lv_x})")
    rep.detail["bfs_pallas"] = {
        "scale": scale, "source": source, "levels": lv_p,
        "xla_seconds": round(t_x, 4),
        "pallas_seconds": round(t_p, 4),
        "pallas_bu_speedup_x": round(t_x / max(t_p, 1e-9), 3),
        "results_bit_equal": True,
    }
    rep.emit()


def segment_pallas_stage(rep: Report) -> None:
    """ISSUE 16 satellite: the one-pass Pallas segmented combine
    (``TITAN_TPU_SEGMENT_KERNEL=pallas``, ops/pallas_segment) vs the
    XLA Hillis-Steele scan on a synthetic dst-sorted edge axis — the
    SpMV primitive's kernel verdict as a first-class evidence line.
    Chip-only for the same reason as bfs_pallas (interpreter mode is
    an emulation; CPU parity lives in tests/test_pallas_segment.py)."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.ops.pallas_segment import pallas_sorted_segment_combine
    from titan_tpu.ops.segment import (segment_metadata,
                                       sorted_segment_combine)

    e, n = 1 << 24, 1 << 20
    rng = np.random.default_rng(5)
    seg_ids = np.sort(rng.integers(0, n, e)).astype(np.int32)
    indptr = np.concatenate(
        [[0], np.cumsum(np.bincount(seg_ids, minlength=n))])
    last_idx, seg_has = segment_metadata(indptr)
    vals = jnp.asarray(rng.random(e, dtype=np.float32))
    ids_d = jnp.asarray(seg_ids)
    li, sh = jnp.asarray(last_idx), jnp.asarray(seg_has)
    scan_jit = jax.jit(sorted_segment_combine,
                       static_argnames=("combine",))

    def timed(fn):
        out = fn()
        _ = float(np.asarray(out[0]))     # warm + force D2H
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            out = fn()
            _ = float(np.asarray(out[0]))
            best = min(best, time.time() - t0)
        return best, out

    t_x, o_x = timed(lambda: scan_jit(vals, ids_d, li, sh, combine="sum"))
    t_p, o_p = timed(lambda: pallas_sorted_segment_combine(
        vals, ids_d, li, sh, "sum"))
    if not np.allclose(np.asarray(o_x), np.asarray(o_p), rtol=1e-5):
        raise AssertionError("pallas segment combine != xla scan")
    rep.detail["segment_pallas"] = {
        "edges": e, "segments": n, "combine": "sum",
        "xla_scan_seconds": round(t_x, 4),
        "pallas_seconds": round(t_p, 4),
        "segment_pallas_speedup_x": round(t_x / max(t_p, 1e-9), 3),
    }
    rep.emit()


def distributed_scan_stage(rep: Report) -> None:
    """ISSUE 18 (ROADMAP #2/#5): cross-process observability evidence.
    A small scan fanned out to two HTTP scan workers over remote-cluster
    storage, with trace propagation ON — records the ONE stitched trace
    (worker split/execute/serialize spans spliced under the
    coordinator's split spans by Tracer.ingest) as span counts + ingest
    drop accounting. Host-only HTTP + dict stores: CPU-runnable."""
    import titan_tpu
    from titan_tpu.obs.tracing import Tracer
    from titan_tpu.olap.distributed import ScanJobSpec
    from titan_tpu.olap.jobs import VertexCountJob
    from titan_tpu.olap.scan_worker import (RemoteScanRunner,
                                            ScanWorkerServer)
    from titan_tpu.storage.inmemory import InMemoryStoreManager
    from titan_tpu.storage.remote import KCVSServer
    from titan_tpu.utils.metrics import MetricManager

    n = 64
    storage = [KCVSServer(InMemoryStoreManager()).start()
               for _ in range(2)]
    workers = [ScanWorkerServer().start() for _ in range(2)]
    try:
        cfg = {"storage.backend": "remote-cluster",
               "storage.hostname":
                   [f"127.0.0.1:{s.port}" for s in storage],
               "storage.cluster.replication-factor": 2}
        g = titan_tpu.open(cfg)
        tx = g.new_transaction()
        for i in range(n):
            tx.add_vertex("person", name=f"b{i}")
        tx.commit()
        g.close()

        m = MetricManager()
        tracer = Tracer()
        t0 = time.time()
        runner = RemoteScanRunner(
            [f"127.0.0.1:{w.port}" for w in workers], cfg,
            metrics=m, tracer=tracer, trace_id="bench-scan")
        got = runner.run(ScanJobSpec(
            "titan_tpu.olap.jobs:make_vertex_count_job"))
        wall = time.time() - t0
        if got.get(VertexCountJob.VERTICES) != n:
            raise AssertionError(
                f"distributed scan counted "
                f"{got.get(VertexCountJob.VERTICES)} != {n}")

        tree = tracer.tree("bench-scan")
        if tree is None:
            raise AssertionError("no stitched trace for bench-scan")
        spans, instances, stack = 0, set(), list(tree["spans"])
        while stack:
            node = stack.pop()
            spans += 1
            attrs = node.get("attrs") or {}
            if attrs.get("remote"):
                instances.add(attrs["instance"])
            stack.extend(node["children"])
        rep.detail["distributed_scan"] = {
            "workers": len(workers),
            "coordinator_splits": len(tree["spans"]),
            "stitched_spans": spans,
            "remote_instances": len(instances),
            "ingest_spans": int(m.counter_value("obs.ingest.spans")),
            "ingest_dropped":
                int(m.counter_value("obs.ingest.dropped")),
            "scan_wall_s": round(wall, 3),
        }
    finally:
        for node in workers + storage:
            node.stop()
    rep.emit()


def fleet_stage(rep: Report) -> None:
    """ISSUE 19 (ROADMAP #2/#5): replica-fleet routing evidence. A
    FleetRouter over two in-process replicas (full GraphServer +
    JobScheduler each) on shared remote-cluster storage, driven by a
    mixed BFS/SSSP/WCC stream — records per-replica occupancy and
    routing-decision counts — then one deterministic failover (a
    never-starting victim scheduler, so the kill always lands mid-
    flight) for the redispatch-latency line. Small CPU frontier
    kernels + host HTTP: runs on CPU and chip days alike."""
    import tempfile

    import titan_tpu
    from titan_tpu.olap.fleet.replica import build
    from titan_tpu.olap.fleet.router import FleetRouter
    from titan_tpu.storage.inmemory import InMemoryStoreManager
    from titan_tpu.storage.remote import KCVSServer
    from titan_tpu.utils.httpnode import json_call, text_get
    from titan_tpu.utils.metrics import MetricManager

    n, m_edges = 192, 900
    storage = KCVSServer(InMemoryStoreManager()).start()
    cfg = {"storage.backend": "remote-cluster",
           "storage.hostname": [f"127.0.0.1:{storage.port}"]}
    g = titan_tpu.open(cfg)
    tx = g.new_transaction()
    vs = [tx.add_vertex("node", name=f"v{i}") for i in range(n)]
    rng = np.random.default_rng(42)
    for _ in range(m_edges):
        a, b = rng.integers(0, n, 2)
        tx.add_edge(vs[int(a)], "link", vs[int(b)])
    tx.commit()
    ids = [v.id for v in vs]
    g.close()
    ck = tempfile.mkdtemp(prefix="bench-fleet-")

    def drive(router, jids, deadline_s=120.0):
        t_end = time.time() + deadline_s
        terminal = ("done", "failed", "timeout", "cancelled",
                    "expired")
        while True:
            router.pump()
            states = [json.loads(text_get(
                router.url, f"/jobs/{j}"))["state"] for j in jids]
            if all(s in terminal for s in states):
                return states
            if time.time() > t_end:
                raise AssertionError(f"fleet stream stalled: {states}")
            time.sleep(0.05)

    # phase 1 — mixed stream routing over two live replicas
    reps = [build({"graph": cfg, "checkpoint_dir": ck})
            for _ in range(2)]
    for _g, _s, srv in reps:
        srv.start()
    mm = MetricManager()
    router = FleetRouter(metrics=mm, autotune="shadow",
                         autopump=False)
    insts = []
    for i, (_g, _s, srv) in enumerate(reps):
        inst = f"replica-{i}"
        router.add_replica(f"http://{srv.host}:{srv.port}",
                           instance=inst)
        insts.append(inst)
    router.start()
    try:
        stream = ([{"kind": "bfs", "source": ids[k]}
                   for k in (0, 3, 7, 11)]
                  + [{"kind": "sssp", "source": ids[k]}
                     for k in (1, 5, 9, 13)]
                  + [{"kind": "wcc"} for _ in range(4)])
        t0 = time.time()
        jids = [json_call(router.url, "/jobs", body)["job"]
                for body in stream]
        states = drive(router, jids)
        stream_wall = time.time() - t0
        if states.count("done") != len(stream):
            raise AssertionError(f"mixed stream not all done: {states}")
        routed = {inst: int(mm.counter_value(
            "serving.fleet.routed", labels={"instance": inst}))
            for inst in insts}
        decisions = int(mm.counter_value("serving.fleet.routed"))
    finally:
        router.stop()
        for _g, _s, srv in reps:
            _s.close()
            srv.stop()
        for _g, _s, _srv in reps:
            _g.close()

    # phase 2 — one deterministic failover for the latency line
    gv, sv, srvv = build({"graph": cfg, "checkpoint_dir": ck,
                          "scheduler": {"autostart": False}})
    gs, ss, srvs = build({"graph": cfg, "checkpoint_dir": ck})
    srvv.start(); srvs.start()
    m2 = MetricManager()
    router = FleetRouter(metrics=m2, autotune="off", autopump=False)
    router.add_replica(f"http://{srvv.host}:{srvv.port}",
                       instance="a-victim")
    router.add_replica(f"http://{srvs.host}:{srvs.port}",
                       instance="b-survivor")
    router.start()
    try:
        jid = json_call(router.url, "/jobs",
                        {"kind": "bfs", "source": ids[0]})["job"]
        router.pump()
        srvv.stop()
        drive(router, [jid])
        w = json.loads(text_get(router.url, f"/jobs/{jid}"))
        if w["state"] != "done" or w["attempts"] != 2:
            raise AssertionError(f"failover did not redispatch: {w}")
        hs = m2.histogram_stats(
            "serving.fleet.redispatch_latency_ms") or {}
    finally:
        router.stop()
        sv.close(); ss.close()
        srvs.stop()
        gv.close(); gs.close()
        storage.stop()

    lo, hi = min(routed.values()), max(routed.values())
    rep.detail["fleet"] = {
        "replicas": 2,
        "stream_jobs": len(stream),
        "stream_mix": {"bfs": 4, "sssp": 4, "wcc": 4},
        "stream_wall_s": round(stream_wall, 3),
        "routing_decisions": decisions,
        "per_replica_routed": routed,
        "occupancy_spread": round((hi - lo) / max(hi, 1), 4),
        "redispatches":
            int(m2.counter_value("serving.fleet.redispatches")),
        "redispatch_latency_ms": round(hs.get("mean", 0.0), 3),
    }
    rep.emit()


class Evidence:
    """``--evidence <path>`` (ISSUE 10, ROADMAP #5): wrap every stage
    in the device-cost profiler and write ONE machine-readable bundle
    beside the stdout report, so a chip day produces a complete
    artifact with zero bespoke scripting.

    The bundle carries the full cumulative detail (skip reasons
    included), a per-stage status + device-cost window (compiles,
    compile/exec wall, H2D/D2H bytes — the numbers that explain a
    slow stage), the process compile log and per-kernel stats, and a
    ``roadmap5`` checklist section where each line ROADMAP #5 demands
    — sharded BFS, batch occupancy + K=8 vs K=1 latency, live_refresh
    delta-vs-rebuild, recovery replay — is either a value or a
    recorded skip reason, never silently absent."""

    FORMAT = "titan-tpu-evidence-v1"

    def __init__(self, path: str, rep: Report):
        from titan_tpu.obs.devprof import DeviceCostProfiler
        from titan_tpu.utils.metrics import MetricManager

        self.path = path
        self.rep = rep
        # isolated registry: the bundle's device.* lines are this
        # run's, not the process history's
        self.metrics = MetricManager()
        self.profiler = DeviceCostProfiler(metrics=self.metrics)
        self.profiler.install()
        self.stages: dict = {}

    def record(self, name: str, status: str, window_delta=None) -> None:
        entry: dict = {"status": status}
        if window_delta is not None:
            entry["device_cost"] = window_delta
        self.stages[name] = entry

    def _lint_clean(self) -> dict:
        """ISSUE 15: chip-day bundles record that the static invariants
        (op-scan ban, host-sync, lock-discipline, metric/clock
        discipline — docs/static-analysis.md) held for the exact tree
        that produced the numbers — a value, or a recorded skip."""
        try:
            repo = os.path.dirname(os.path.abspath(__file__))
            if repo not in sys.path:
                sys.path.insert(0, repo)
            from tools.graftlint.engine import Linter
            res = Linter(root=repo).run(["titan_tpu", "bench.py"])
            return {"present": True, "value": {
                "clean": not res.unsuppressed,
                "unsuppressed": len(res.unsuppressed),
                "suppressed": len(res.findings) - len(res.unsuppressed),
                "files": len(res.files),
                "wall_s": round(res.wall_s, 3)}}
        except Exception as e:          # missing tools/ checkout etc.
            return {"present": False, "stage": "lint",
                    "skip_reason": f"graftlint unavailable: {e!r}"}

    def _checklist(self) -> dict:
        det = self.rep.detail

        def present(value) -> dict:
            return {"present": True, "value": value}

        def absent(stage: str) -> dict:
            why = next((s["why"] for s in det.get("skipped", ())
                        if s["stage"] == stage), "stage did not run")
            return {"present": False, "stage": stage,
                    "skip_reason": why}

        sharded = next((v for k, v in det.items()
                        if k.endswith("_sharded_1dev")), None)
        serving = det.get("serving")
        interactive = det.get("interactive")
        tenancy = det.get("tenancy")
        bfs_pal = det.get("bfs_pallas")
        seg_pal = det.get("segment_pallas")
        return {
            # ISSUE 15: the invariants held for this tree (graftlint)
            "lint_clean": self._lint_clean(),
            # ISSUE 14 (ROADMAP #4): the autotune decision plane — a
            # shadow-mode run of the tenancy stage must produce a
            # journaled, replayable decision; count + one example
            # entry, or the stage's recorded skip reason
            "controller_decisions": (
                present(tenancy["controller"])
                if tenancy is not None
                and tenancy.get("controller") is not None
                else absent("tenancy")),
            "sharded_bfs": (present(sharded) if sharded is not None
                            else absent("bfs23_sharded")),
            # ISSUE 13 (ROADMAP #1): the 1-device sharding-overhead
            # ratio and the fused-level dispatch budget — each a value
            # on any shape the stage ran (CPU proxy included), a
            # recorded skip reason otherwise
            "sharding_overhead_ratio": (
                present(sharded.get("sharding_overhead_ratio"))
                if sharded is not None else absent("bfs23_sharded")),
            "sharded_bfs_dispatches_per_level": (
                present({k: sharded[k] for k in
                         ("dispatches_per_level_max",
                          "dispatches_per_level_mean", "levels")})
                if sharded is not None
                and sharded.get("dispatches_per_level_max") is not None
                else absent("bfs23_sharded")),
            "serving_batch_occupancy_k8_vs_k1": (
                present({k: serving[k] for k in
                         ("batch_occupancy", "job_latency_ms",
                          "k8_batch_wall_s", "k1_wall_s",
                          "k8_per_job_over_k1_x")})
                if serving is not None else absent("serving")),
            "live_refresh_delta_vs_rebuild": (
                present(det["live_refresh"])
                if "live_refresh" in det else absent("live_refresh")),
            "recovery_replay": (present(serving["recovery"])
                                if serving is not None
                                else absent("serving")),
            # ISSUE 11: the interactive lane's fuse economics — point
            # queries K=16 vs sequential + batched-PPR throughput
            "interactive_point_queries": (
                present({k: interactive[k] for k in
                         ("point_query_fused_p50_ms",
                          "point_query_fused_p95_ms",
                          "point_query_seq_p50_ms",
                          "point_query_seq_p95_ms",
                          "fuse_occupancy",
                          "ppr_batched_users_per_s",
                          "ppr_speedup_x")})
                if interactive is not None else absent("interactive")),
            # ISSUE 16: the Pallas kernels' on-chip verdicts — a value
            # on the TPU backend, a recorded skip on CPU (interpreter-
            # mode parity is tier-1's job; wall-clock is the chip's)
            "pallas_bu_speedup": (
                present({k: bfs_pal[k] for k in
                         ("xla_seconds", "pallas_seconds",
                          "pallas_bu_speedup_x", "results_bit_equal")})
                if bfs_pal is not None else absent("bfs_pallas")),
            "segment_kernel_pallas_speedup": (
                present(seg_pal) if seg_pal is not None
                else absent("segment_pallas")),
            # ISSUE 18 (ROADMAP #2): the cross-process trace — stitched
            # span count across 2 worker processes + ingest drop
            # accounting, or the stage's recorded skip reason
            "distributed_scan_trace": (
                present(det["distributed_scan"])
                if det.get("distributed_scan") is not None
                else absent("distributed_scan")),
            # ISSUE 19 (ROADMAP #2): the replica fleet's routing plane —
            # per-replica occupancy + decision counts under a mixed
            # stream and the failover redispatch latency, or the
            # stage's recorded skip reason
            "fleet_routing": (
                present(det["fleet"])
                if det.get("fleet") is not None
                else absent("fleet")),
        }

    def write(self) -> None:
        self.profiler.uninstall()
        rep = self.rep
        bundle = {
            "format": self.FORMAT,
            "generated_at": time.time(),
            "headline": {"metric": rep.metric, "value": rep.value,
                         "unit": rep.unit,
                         "vs_baseline": rep.vs_baseline},
            "roadmap5": self._checklist(),
            "stages": self.stages,
            "compile_log": self.profiler.compile_log(),
            "device_totals": self.profiler.stats(),
            "kernels": self.profiler.kernel_stats(),
            "detail": rep.detail,
        }
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(bundle, f, indent=1, default=str)
        os.replace(tmp, self.path)   # a torn write never becomes an
        #                              artifact (cf. obs/flightrec)


def _parse_args(argv: list) -> tuple:
    """(evidence_path, positional) — bench predates argparse and the
    driver invokes it positionally; keep that contract."""
    evidence = None
    rest = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--evidence":
            if i + 1 >= len(argv):
                sys.exit("bench.py: --evidence requires a path")
            evidence = argv[i + 1]
            i += 2
        elif a.startswith("--evidence="):
            evidence = a.split("=", 1)[1]
            i += 1
        else:
            rest.append(a)
            i += 1
    return evidence, rest


def main() -> None:
    import jax

    # persist compiled executables across bench processes; the one
    # place the cache directory is decided
    from titan_tpu.utils.jitcache import enable_compile_cache
    enable_compile_cache()

    evidence_path, argv = _parse_args(sys.argv[1:])
    platform = jax.devices()[0].platform
    on_accel = platform not in ("cpu",)
    headline_scale = (int(argv[0]) if argv
                      else (26 if on_accel else 16))
    warm_scale = min(23, headline_scale)
    lj_scale = 22 if on_accel else min(headline_scale, 14)

    rep = Report()
    rep.detail["platform"] = platform
    rep.detail["n_devices"] = jax.device_count()
    ev = Evidence(evidence_path, rep) if evidence_path else None

    # stage order = the two BASELINE HARD targets FIRST and in full
    # possession of the budget (the headline BFS literally first —
    # nothing may run before it when the budget is tight), then the cheap
    # OLTP measures, then the "measure" rows (sssp/wcc share the
    # resident scale-26 upload; store-ingest + heavy are r5 evidence
    # stages), then the warm-scale/sharded evidence stages that are
    # first to drop under pressure. The s22 pagerank graph (0.56GB)
    # fits HBM alongside the s26 graph, so pagerank never evicts.
    stages = [
        (HEADLINE_STAGE, lambda: _bfs_stage(rep, headline_scale,
                                            "headline")),
        ("pagerank", lambda: pagerank_stage(rep, lj_scale)),
        ("gods_2hop", lambda: gods_2hop(rep)),
        ("ldbc", (lambda: ldbc_is3_4hop(rep)) if on_accel else
         (lambda: ldbc_is3_4hop(rep, n_persons=1000, avg_degree=10))),
        # store_ingest AHEAD of ssspwcc (VERDICT r5 #2: it is the
        # north-star store->CSR contract and has gone uncaptured for two
        # rounds; SSSP/WCC are "measure" rows and share the resident
        # s26 upload either way)
        ("store_ingest", lambda: store_ingest_stage(
            rep, 22 if on_accel else min(headline_scale, 14),
            smoke=not on_accel)),
        ("ssspwcc", lambda: sssp_wcc(rep, headline_scale)),
        ("bfs_heavy", lambda: bfs_heavy_stage(rep)),
        # live-plane freshness evidence (ISSUE r9): delta-apply p50/p95
        # vs full rebuild; droppable under budget pressure like the
        # other evidence stages
        ("live_refresh", lambda: live_refresh_stage(
            rep, 20 if on_accel else min(headline_scale, 14))),
        # serving/recovery evidence (ISSUE r10): batch occupancy +
        # latency K=8 vs K=1, recovery replay cost, trace digest —
        # first-class metric lines next to live_refresh's
        ("serving", lambda: serving_stage(
            rep, 16 if on_accel else min(headline_scale, 12))),
        # per-tenant SLO plane evidence (ISSUE 8): per-tenant p95 +
        # device-seconds / HBM-byte-seconds attribution, labeled-sum
        # exactness — same scale as serving so the kernels stay warm
        ("tenancy", lambda: tenancy_stage(
            rep, 16 if on_accel else min(headline_scale, 12))),
        # interactive lane evidence (ISSUE 11): 2-hop point queries
        # fused K=16 vs sequential + batched-PPR throughput — the
        # fuse-economics lines ROADMAP #3 asked for
        ("interactive", lambda: interactive_stage(
            rep, 14 if on_accel else min(headline_scale, 12))),
        # cross-process observability evidence (ISSUE 18): stitched
        # distributed-scan trace + ingest accounting — host-only HTTP
        # against dict stores, so it runs on CPU and chip days alike
        ("distributed_scan", lambda: distributed_scan_stage(rep)),
        # replica-fleet routing evidence (ISSUE 19): per-replica
        # occupancy + routing decisions under a mixed BFS/SSSP/WCC
        # stream, and the failover redispatch-latency line — host HTTP
        # + small CPU kernels, runs on CPU and chip days alike
        ("fleet", lambda: fleet_stage(rep)),
        # Pallas kernel verdicts (ISSUE 16): the fused bottom-up
        # frontier kernel and the one-pass segment scan vs their XLA
        # paths — chip-only (interpreter mode times an XLA emulation)
        ("bfs_pallas", lambda: bfs_pallas_stage(rep, warm_scale)),
        ("segment_pallas", lambda: segment_pallas_stage(rep)),
        # the sharded-overhead stage also times the plain hybrid at the
        # warm scale, so it outranks the standalone warm stage when the
        # budget is tight
        ("bfs23_sharded", lambda: bfs_sharded_overhead(rep, warm_scale)),
        ("bfs23", lambda: _bfs_stage(rep, warm_scale, "warm")),
    ]
    # environment-filtered stages get RECORDED skip reasons, not
    # silent removal — the evidence checklist (ROADMAP #5) must show a
    # value or a reason for every line
    if not on_accel:
        cpu_skips = {
            "bfs_heavy":
                "no accelerator: Twitter-parity graph needs a chip",
            "bfs_pallas":
                "no accelerator: interpreter mode times an XLA "
                "emulation of the kernel, not the chip; interpreter-"
                "mode bit-equality is pinned in tier-1 "
                "(tests/test_pallas_frontier.py)",
            "segment_pallas":
                "no accelerator: the pallas segment combine engages "
                "only on the TPU backend; interpreter-mode parity is "
                "pinned in tier-1 (tests/test_pallas_segment.py)",
        }
        stages = [s for s in stages if s[0] not in cpu_skips]
        for st, why in cpu_skips.items():
            rep.detail["skipped"].append({"stage": st, "why": why})
    elif platform == "tpu":
        from titan_tpu.ops.pallas_frontier import TPU_REFUSAL
        stages = [s for s in stages if s[0] != "bfs_pallas"]
        rep.detail["skipped"].append(
            {"stage": "bfs_pallas",
             "why": "the chip's compiler refuses frontier_round "
                    f"(ROADMAP S5 ports it): {TPU_REFUSAL}"})
    if warm_scale == headline_scale:      # CPU/CI path: one BFS scale
        # the plain warm BFS duplicates the headline at this scale and
        # drops; the SHARDED overhead stage stays — it reuses the
        # resident headline graph, and its sharding_overhead_ratio /
        # dispatches-per-level lines are ROADMAP-#1 checklist values
        # the evidence bundle must carry ON CPU too (ISSUE 13: skip
        # reasons are allowed only for chip-scale shapes)
        stages = [s for s in stages if s[0] != "bfs23"]
        rep.detail["skipped"].append(
            {"stage": "bfs23",
             "why": f"warm scale == headline scale "
                    f"(s{headline_scale}): single-BFS-scale run"})

    failed = []
    for name, fn in stages:
        # estimates re-price against the MEASURED H2D rate (the
        # headline stage's own upload observes it — VERDICT r5 weak #2:
        # flat fast-day numbers admitted bfs_heavy into the driver kill)
        est = _est(name, on_accel)
        # stages with IN-STAGE fallbacks are admitted at their cheapest
        # fallback cost — pricing them at full cost here would make the
        # fallback paths unreachable (the stage itself then right-sizes
        # scale/reps against _left())
        if name == "store_ingest":
            est = est / 4 + 60      # two scale steps down (~halves/step)
        elif name == "bfs_heavy":
            est = max(est - 60, est / 2)   # reps 2 -> 1
        if not on_accel and headline_scale < 20:
            # CI/smoke scales: the table's estimates assume bench-scale
            # graphs; a small-scale CPU run costs ~1/10th. On an
            # accelerator the guard must NOT shrink — several stages pin
            # their own scale regardless of the headline (store_ingest
            # s22, pagerank s22, bfs_heavy s25) and admitting them on a
            # tenth of their true cost would blow the driver clock
            est = max(est // 10, 20)
        # the HEADLINE stage is never budget-skipped: a report without
        # the headline metric is worthless however honest the skip note
        # (it runs first, so this only matters for sub-estimate smoke
        # budgets). Everything else also respects a hard reserve before
        # the observed external window — nothing new starts that could
        # ride into the driver kill (rc=124 three rounds running).
        if name != HEADLINE_STAGE and _left() < est + _HARD_RESERVE_S:
            rep.skip(name, f"budget: {_left():.0f}s left < est "
                           f"{est:.0f}s + {_HARD_RESERVE_S:.0f}s reserve "
                           f"(h2d {_h2d_gbps:.3f}GB/s)")
            if ev is not None:
                ev.record(name, "skipped")
            continue
        # each stage runs inside its own profiler window so the bundle
        # attributes compiles / device wall / transfer bytes per stage
        w = ev.profiler.window() if ev is not None else None
        try:
            fn()
            if ev is not None:
                ev.record(name, "ok", w.close())
        except Exception as e:            # a broken stage must not eat
            failed.append(name)
            rep.skip(name, f"error: {type(e).__name__}: {e}")
            if ev is not None:
                ev.record(name, f"error: {type(e).__name__}", w.close())

    rep.emit()
    if ev is not None:
        ev.write()
        rep.detail["evidence"] = ev.path
        rep.emit()
    if failed:
        # the report above still carries every stage that ran; the exit
        # code says that some did not
        sys.exit(f"bench.py: stage(s) raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
