"""The bottom-up (pull) rounds, forced at toy scale, against the plain
reference.

One benchmark cell pulls a level, through the single-source chain's
split-lane opener and one chunk round (`g500-24.wcc-c2`, PR 36); the
exhaust and every batched seam are "unverified by any cell" (PERF.md
7), so the five seams of the bottom-up chunk round are held here, on
the CPU: the single-source chain (opener, split-lane opener, chunk rounds,
exhaust), the batched round, the batched round under a tombstone
overlay, the batched round under per-level slot masks (hops mode), and
the sharded level with its dispatch budget. Each is driven down the
pull by the direction rule's own constants (conftest's
``force_bottom_up``) and compared with
``models.bfs.frontier_bfs`` (per source; on the rebuilt edge list under
an overlay) or, where the seam masks slots level by level, with hop
sets worked out by hand from the chunked layout.
"""

import numpy as np
import pytest

import titan_tpu.models.bfs_hybrid as H
from titan_tpu.models.bfs import frontier_bfs
from titan_tpu.obs import devprof
from titan_tpu.obs.tracing import Tracer, scope
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager

N, M = 192, 900
SEEDS = [0, 1, 2]
#: the cohort the seams were first pinned at, then the lane's common
#: case and a size between
BATCHES = [(seed, 8) for seed in SEEDS] \
    + [(seed, K) for K in (1, 4) for seed in SEEDS[:2]]


def edges(seed, n=N, m=M):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, n, m).astype(np.int32),
            rng.integers(0, n, m).astype(np.int32))


def sym_snap(src, dst, n=N):
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


def sources(seed, K):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.choice(N, K, replace=False)]


def pulled(run):
    """``run()``'s result, after checking that every level it swept
    went bottom-up."""
    tracer = Tracer()
    root = tracer.start("t", "interactive")
    with scope(tracer, "t", root):
        out = run()
    tracer.end(root)
    dirs = [s.attrs["dir"] for s in tracer.spans("t")
            if s.name == "bfs.sweep"]
    assert dirs and set(dirs) == {"bu"}, dirs
    return out


def reference_rows(snap, srcs):
    return np.stack([frontier_bfs(snap, s)[0] for s in srcs])


@pytest.mark.parametrize("seed", SEEDS)
def test_plain_bottom_up_matches_reference(seed, force_bottom_up):
    snap = sym_snap(*edges(seed))
    src = int(np.flatnonzero(snap.out_degree > 0)[0])
    with devprof.DeviceCostProfiler(metrics=MetricManager()) as prof:
        dist, _levels = H.frontier_bfs_hybrid(snap, src)
    ran = prof.kernel_stats()
    assert {"hybrid_bu_startL", "hybrid_bu_finish0"} <= set(ran), sorted(ran)
    assert np.array_equal(dist, frontier_bfs(snap, src)[0])


@pytest.mark.parametrize("n", [5, 32766, 32767, 70000])
def test_the_frontier_bitmap_in_planes_reads_back_every_vertex(n):
    """``_pack_bits`` + ``_fbit_of`` (the single-source family's bitmap,
    in planes since PR 36) against the mask itself, at every id up to the
    pad vertex n+1: a plane's last byte, a width of exactly one tile
    (n + 2 = 8 x FBITS_ALIGN) and the first id past it."""
    import jax.numpy as jnp

    rng = np.random.default_rng(n)
    dist = np.where(rng.random(n + 2) < 0.3, 7, 3).astype(np.int32)
    dist[n:] = 0                        # the pad vertices are never in
    fbits = H._pack_bits(jnp.asarray(dist), jnp.int32(7), n)
    assert fbits.shape == (H._fbits_width(n),) and fbits.dtype == jnp.uint8
    ids = jnp.arange(n + 2, dtype=jnp.int32)[None, :]
    got = np.asarray(H._fbit_of(fbits, ids)).reshape(-1)
    assert np.array_equal(got, dist == 7)


@pytest.mark.parametrize("seed,K", BATCHES)
def test_batched_bottom_up_matches_reference(seed, K, force_bottom_up):
    snap = sym_snap(*edges(seed))
    srcs = sources(seed, K)
    dist, _levels, completed = pulled(
        lambda: H.frontier_bfs_batched(snap, srcs))
    assert completed.all()
    assert np.array_equal(dist, reference_rows(snap, srcs))


@pytest.mark.parametrize("seed,K", BATCHES)
def test_batched_bottom_up_under_tombstones(seed, K, force_bottom_up):
    """Tombstoned base slots stop counting as parents and the overlay's
    added edges are pushed beside the pull: the answer is the plain
    reference's on the edge list rebuilt by hand."""
    from titan_tpu.olap.live.overlay import DeltaOverlay

    src, dst = edges(seed)
    snap = sym_snap(src, dst)
    rng = np.random.default_rng(seed + 100)
    ov = DeltaOverlay(snap, min_cap=256)
    a_s = rng.integers(0, N, 60).astype(np.int32)
    a_d = rng.integers(0, N, 60).astype(np.int32)
    ov.append_edges(np.concatenate([a_s, a_d]), np.concatenate([a_d, a_s]),
                    np.zeros(120, np.int32))
    gone = rng.choice(M, 40, replace=False)
    for i in gone:
        assert ov.remove_edge(int(src[i]), int(dst[i]), None)
        assert ov.remove_edge(int(dst[i]), int(src[i]), None)
    keep = np.ones(M, bool)
    keep[gone] = False
    rebuilt = sym_snap(np.concatenate([src[keep], a_s]),
                       np.concatenate([dst[keep], a_d]))
    srcs = sources(seed, K)
    dist, _levels, completed = pulled(
        lambda: H.frontier_bfs_batched(snap, srcs, overlay=ov.view()))
    assert completed.all()
    assert np.array_equal(dist, reference_rows(rebuilt, srcs))


def hop_stamps_by_hand(g, mask_bytes, srcs, hops: int):
    """Hops mode's state worked out from the layout with sets: a vertex
    joins level ``l + 1``'s frontier when one of its OWN slots holds a
    member of level ``l``'s and the level's bitmap (byte = chunk
    column, bit = lane) leaves that slot standing; ``stamp[k, v]`` is
    the last level that held ``v`` (start level 1, 0 = never)."""
    n = g["n"]
    colstart, degc = np.asarray(g["colstart"]), np.asarray(g["degc"])
    dstT = np.asarray(g["dstT"])
    stamp = np.zeros((len(srcs), n), np.int32)
    for k, s in enumerate(srcs):
        frontier = {s}
        stamp[k, s] = 1
        for level in range(1, hops + 1):
            lm = mask_bytes[level - 1]
            nxt = set()
            for v in range(n):
                for col in range(colstart[v], colstart[v] + degc[v]):
                    for lane in range(8):
                        if lm is not None and (lm[col] >> lane) & 1:
                            continue
                        if int(dstT[lane, col]) in frontier:
                            nxt.add(v)
            frontier = nxt
            stamp[k, list(nxt)] = level + 1
    return stamp


@pytest.mark.parametrize("seed,K", BATCHES)
def test_batched_bottom_up_under_level_masks(seed, K, force_bottom_up):
    """A hop's label mask rides the round's slot bitmap: levels 2 and 3
    each see a random half of the slots."""
    import jax.numpy as jnp

    snap = sym_snap(*edges(seed))
    g = H.build_chunked_csr(snap)
    rng = np.random.default_rng(seed)
    lm = rng.integers(0, 256, g["q_total"]).astype(np.uint8)
    lm[-1] = 0                          # the all-pad sink column
    srcs = sources(seed, K)
    dist, _levels, _completed = pulled(lambda: H.frontier_bfs_batched(
        g, srcs, mode="hops", start_level=1, max_levels=4,
        level_masks=[None, jnp.asarray(lm), jnp.asarray(lm)]))
    assert np.array_equal(dist,
                          hop_stamps_by_hand(g, [None, lm, lm], srcs, 3))


def test_sharded_bottom_up_and_its_dispatch_budget(monkeypatch):
    """``shx_bu`` on the 8-device CPU mesh, every level after the first
    pulled: the plain reference's distances, and at most two dispatches
    a level (one and the found_cap retry)."""
    import titan_tpu.models.bfs_hybrid_sharded as S
    from titan_tpu.parallel.mesh import vertex_mesh

    monkeypatch.setattr(S, "ALPHA", float(1 << 30))
    snap = sym_snap(*edges(SEEDS[0], n=600, m=3000), n=600)
    src = int(np.flatnonzero(snap.out_degree > 0)[0])
    dist, _levels = S.frontier_bfs_hybrid_sharded(snap, src, vertex_mesh(8))
    modes = [p["mode"] for p in S.LAST_PROFILE]
    assert modes[0] == "td" and set(modes[1:]) == {"bu"}, modes
    assert max(p["dispatches"] for p in S.LAST_PROFILE) <= 2
    assert np.array_equal(np.asarray(dist), frontier_bfs(snap, src)[0])
