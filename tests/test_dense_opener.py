"""The split-lane opener (``hybrid_bu_startL``) tests every candidate's
first lanes on the vertex set, n wide over the leading-lane image, and
returns what a plain loop over the vertices returns: the same ``dist``,
the untested in ascending order, the same level-end statistics,
whatever the lane width, the share of the vertices that are candidates
and the graph (vertices without an edge, vertices with no more edges
than lanes, a hub, an n that is a multiple of nothing), and whatever
serves its frontier test (XLA's byte gather, or the frontier as a table
in VMEM under the Pallas gather). The image is built once a graph.
All on the CPU: counts and equality, never a time.
"""

import functools

import numpy as np
import pytest

import titan_tpu.models.bfs_hybrid as H
from titan_tpu.models.bfs import INF, _next_pow2
from titan_tpu.models.frontier import frontier_wcc
from titan_tpu.obs import devprof
from titan_tpu.obs.tracing import Tracer, scope
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import vmem_gather as vg
from titan_tpu.utils import jitcache
from titan_tpu.utils.metrics import MetricManager

LANES = [1, 2, 4]
#: the programs that test parents against the frontier, by their key
#: (``bfs_hybrid._frontier_test`` serves every one)
FRONTIER_TEST_KEYS = ("hybrid_bu_start", "hybrid_bu_startL",
                      "hybrid_bu_finish0", "hybrid_bu_more", "hybrid_ex",
                      "hybrid_endgame")


def _sym(n, src, dst):
    src, dst = np.asarray(src, np.int32), np.asarray(dst, np.int32)
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


def isolated(seed=0):
    """n = 193, a third of the vertices without any edge."""
    rng = np.random.default_rng(seed)
    n = 193
    live = rng.permutation(n)[:128]
    return _sym(n, rng.choice(live, 700), rng.choice(live, 700))


def thin(seed=1):
    """n = 211: a ring (degree 2) with a few chords and pendant
    vertices, so most vertices have ``deg <= lanes`` at every width."""
    rng = np.random.default_rng(seed)
    n = 211
    ring = np.arange(150)
    src = np.concatenate([ring, rng.integers(0, 150, 12),
                          np.arange(150, 200)])
    dst = np.concatenate([(ring + 1) % 150, rng.integers(0, 150, 12),
                          rng.integers(0, 150, 50)])
    return _sym(n, src, dst)


def hub(seed=2):
    """n = 1,531 (past one block of the image): a hub joined to two
    thirds of the vertices, a random graph beneath it, a few vertices
    without an edge."""
    rng = np.random.default_rng(seed)
    n = 1531
    spokes = rng.permutation(np.arange(1, 1500))[:1000]
    src = np.concatenate([np.zeros(1000, np.int64),
                          rng.integers(1, 1500, 4000)])
    dst = np.concatenate([spokes, rng.integers(1, 1500, 4000)])
    return _sym(n, src, dst)


def spoked(seed=3):
    """n = 251: a source joined to 40 vertices of a sparse random graph
    (mean degree 3), so the level behind the source is pulled while
    most vertices are still candidates, and the one behind it while few
    are: every one of them opens on the vertex set all the same."""
    rng = np.random.default_rng(seed)
    n = 251
    src = np.concatenate([np.zeros(40, np.int64),
                          rng.integers(1, n, 380)])
    dst = np.concatenate([rng.permutation(np.arange(1, n))[:40],
                          rng.integers(1, n, 380)])
    return _sym(n, src, dst)


GRAPHS = {"isolated": isolated, "thin": thin, "hub": hub}


@pytest.fixture(params=sorted(GRAPHS))
def snap(request):
    return GRAPHS[request.param]()


def bfs_numpy(snap, source):
    n = snap.n
    dst, indptr = snap.out_csr()
    dist = np.full(n, INF, np.int32)
    dist[source] = 0
    frontier, level = [source], 0
    while frontier:
        nxt = []
        for v in frontier:
            for u in dst[indptr[v]:indptr[v + 1]]:
                if dist[u] == INF:
                    dist[u] = level + 1
                    nxt.append(int(u))
        frontier, level = nxt, level + 1
    return dist


def wcc_numpy(snap):
    n = snap.n
    dst, indptr = snap.out_csr()
    label = np.arange(n, dtype=np.int32)
    seen = np.zeros(n, bool)
    for s in range(n):
        if seen[s]:
            continue
        seen[s] = True
        stack = [s]
        while stack:
            v = stack.pop()
            label[v] = s
            for u in dst[indptr[v]:indptr[v + 1]]:
                if not seen[u]:
                    seen[u] = True
                    stack.append(int(u))
    return label


def source_of(snap):
    return int(np.argmax(snap.out_degree))


def opener(g, dist_host, level, lanes, impl="xla", c_cap=None):
    """One call of ``hybrid_bu_startL`` on the state ``dist_host``
    (levels up to ``level`` decided), its frontier test served by
    ``impl``."""
    import jax.numpy as jnp

    n = g["n"]
    if c_cap is None:
        c_cap = _next_pow2(max(n, 2))
    dist = jnp.asarray(np.concatenate([dist_host, [INF]]).astype(np.int32))
    dist, fbits, cand, prog, st = H._bu_startL()(
        dist, jnp.int32(level), H.leading_lanes(g, lanes), g["deg"],
        g["degc"], c_cap=c_cap, n_=n, lanes=lanes, impl=impl)
    nu = int(np.asarray(prog)[0])
    cand = np.asarray(cand)
    assert cand.shape == (c_cap,) and np.all(cand[nu:] == n)
    bits = np.asarray(H._fbit_of(fbits, jnp.arange(n + 2, dtype=jnp.int32)))
    return np.asarray(dist), bits, nu, cand[:nu], np.asarray(st)


def opener_numpy(snap, g, state, level, lanes):
    """The same call as a loop over the vertices: a candidate
    (unvisited, with an edge) whose first ``lanes`` neighbours hold one
    of the frontier is found; one that misses and has more neighbours
    is handed on, ids ascending."""
    n = snap.n
    dst, indptr = snap.out_csr()
    dist = np.concatenate([state, [INF]]).astype(np.int32)
    untested = []
    for v in range(n):
        deg = int(indptr[v + 1] - indptr[v])
        if state[v] < INF or deg == 0:
            continue
        first = dst[indptr[v]:indptr[v] + min(lanes, deg)]
        if np.any(state[first] == level):
            dist[v] = level + 1
        elif deg > lanes:
            untested.append(v)
    bits = np.concatenate([state == level, [False, False]])
    st = np.zeros(4, np.int32)
    if not untested:
        unvis = dist[:n] >= INF
        degc = np.asarray(g["degc"])[:n]
        st[:] = [int((dist[:n] == level + 1).sum()),
                 int(degc[dist[:n] == level + 1].sum()),
                 int(degc[unvis].sum()), int((unvis & (degc > 0)).sum())]
    return dist, bits, len(untested), np.asarray(untested, np.int32), st


def same(got, want):
    for a, b in zip(got, want):
        assert np.array_equal(a, b)


@pytest.fixture
def kernel_in_the_interpreter(monkeypatch):
    """The CPU has no Mosaic: Pallas's interpreter runs the gather. A
    fresh build of the program, before and behind, so that the
    interpreter's kernel is not kept under the key for other tests."""
    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    for key in FRONTIER_TEST_KEYS:
        jitcache._JITS.pop(key, None)
    yield
    for key in FRONTIER_TEST_KEYS:
        jitcache._JITS.pop(key, None)


# -- the image ---------------------------------------------------------------

@pytest.mark.parametrize("lanes", LANES)
def test_the_leading_lane_image_is_each_vertex_first_lanes(snap, lanes):
    g = H.build_chunked_csr(snap)
    n = g["n"]
    host = g["_host"]
    deg = np.asarray(g["deg"])
    width = vg.padded_columns(n + 1)
    want = np.full((lanes, width), n + 1, np.int32)
    for k in range(lanes):
        has = deg > k
        want[k, :n + 1][has] = host["dstT"][k, host["colstart"][has]]
    lead = np.asarray(H.leading_lanes(g, lanes))
    assert lead.dtype == np.int32 and lead.shape == (lanes * width,)
    assert np.array_equal(lead.reshape(lanes, width), want)
    # a vertex's first lanes are its first neighbours, in id order
    dst, indptr = snap.out_csr()
    for v in np.flatnonzero(deg[:n] > 0)[:40]:
        k = min(lanes, int(deg[v]))
        assert np.array_equal(want[:k, v], dst[indptr[v]:indptr[v] + k])


# -- one call ----------------------------------------------------------------

@pytest.mark.parametrize("level", [0, 1, 2])
@pytest.mark.parametrize("lanes", LANES)
def test_the_opener_returns_what_a_loop_over_the_vertices_does(
        snap, lanes, level):
    g = H.build_chunked_csr(snap)
    ref = bfs_numpy(snap, source_of(snap))
    state = np.where(ref <= level, ref, INF).astype(np.int32)
    got = opener(g, state, level, lanes)
    same(got, opener_numpy(snap, g, state, level, lanes))
    # and that is the level's own: nothing found that the reference
    # does not put on level + 1
    dist = got[0][:g["n"]]
    found = np.flatnonzero((dist == level + 1) & (state >= INF))
    assert np.all(ref[found] == level + 1)


@pytest.mark.parametrize("share", [1, 2, 4, 8])
@pytest.mark.parametrize("lanes", LANES)
def test_the_opener_at_a_share_of_the_vertices(lanes, share):
    """The candidates thinned by hand to 1/``share`` of those a level
    leaves (the others marked visited at a level no test reads), the
    untested list sized from their count as the host loop sizes it:
    narrower than the vertex set the test still runs over."""
    snap = hub()
    g = H.build_chunked_csr(snap)
    n = g["n"]
    ref = bfs_numpy(snap, source_of(snap))
    state = np.where(ref <= 1, ref, INF).astype(np.int32)
    rng = np.random.default_rng(share)
    open_ = np.flatnonzero(state >= INF)
    state[rng.permutation(open_)[len(open_) // share:]] = 0
    deg = np.asarray(g["deg"])[:n]
    n_unvis = int(((state >= INF) & (deg > 0)).sum())
    c_cap = _next_pow2(max(n_unvis, 2))
    assert share == 1 or c_cap < n
    got = opener(g, state, 1, lanes, c_cap=c_cap)
    same(got, opener_numpy(snap, g, state, 1, lanes))


# -- whole runs --------------------------------------------------------------

def traced(run):
    """``run()`` under a profiler and a scope of their own: its result,
    the journal's spans, the registry, the profiler's kernel table."""
    tracer = Tracer()
    mm = MetricManager()
    root = tracer.start("t", "run")
    with devprof.DeviceCostProfiler(metrics=mm) as prof, \
            scope(tracer, "t", root):
        out = run()
        assert devprof.drain(10.0)
        stats = prof.kernel_stats()
    tracer.end(root)
    return out, tracer.spans("t"), mm, stats


def _run_with_openers(run):
    out, spans, mm, stats = traced(run)
    openers = [s.attrs["opener"] for s in spans
               if s.name == "bfs.level" and s.attrs.get("dir") == "bu"]
    impls = [s.attrs.get("impl") for s in spans if s.name == "kernel"
             and s.attrs["key"] == "hybrid_bu_startL"]
    counted = {impl: mm.counter("device.bfs.opener",
                                labels={"impl": impl}).count
               for impl in ("dense", "plain")}
    return out, openers, impls, counted, stats


@pytest.mark.parametrize("impl", ["xla", "vmem"])
@pytest.mark.parametrize("lanes", LANES)
def test_bfs_and_wcc_agree_with_numpy_under_each_gather(
        snap, lanes, impl, force_bottom_up, kernel_in_the_interpreter,
        monkeypatch):
    monkeypatch.setattr(H, "SPLIT_LANES", lanes)
    monkeypatch.setattr(vg, "gather_impl", lambda _n: impl)
    src = source_of(snap)
    (dist, _levels), openers, impls, counted, _ = _run_with_openers(
        lambda: H.frontier_bfs_hybrid(snap, src))
    assert np.array_equal(dist, bfs_numpy(snap, src))
    assert openers and set(openers) == {"dense"}
    assert impls == [impl] * len(openers)
    assert counted == {"dense": len(openers), "plain": 0}
    (labels, _rounds), openers, _, _, _ = _run_with_openers(
        lambda: frontier_wcc(snap))
    assert np.array_equal(labels, wcc_numpy(snap))
    assert openers and set(openers) == {"dense"}


def test_every_split_level_opens_on_the_vertex_set(force_bottom_up):
    """What opens a pulled level is read from its candidates' count
    alone (``SPLIT_LANE_MIN``, 2 under the test thresholds): the first
    pulled level of a toy graph, over about every vertex, and the later
    ones, over the minority it left, open the same way."""
    snap = spoked()
    src = source_of(snap)
    assert src == 0
    (dist, _), openers, impls, counted, _ = _run_with_openers(
        lambda: H.frontier_bfs_hybrid(snap, src))
    assert np.array_equal(dist, bfs_numpy(snap, src))
    assert len(openers) > 1 and set(openers) == {"dense"}, openers
    assert impls == ["xla"] * len(openers)             # tier 1: the CPU
    assert counted == {"dense": len(openers), "plain": 0}


def test_below_the_split_threshold_the_plain_opener_runs(monkeypatch):
    for name, value in (("END_C_CAP", 0), ("END_P_CAP", 0),
                        ("HEAD_F_CAP", 1)):
        monkeypatch.setattr(H, name, value)
    snap = isolated()
    src = source_of(snap)
    (dist, _), openers, impls, counted, stats = _run_with_openers(
        lambda: H.frontier_bfs_hybrid(snap, src))
    assert np.array_equal(dist, bfs_numpy(snap, src))
    assert openers and set(openers) == {"plain"} and impls == []
    assert counted == {"dense": 0, "plain": len(openers)}
    assert "hybrid_lead" not in stats and "hybrid_bu_start" in stats


def test_the_image_is_built_once_a_graph(force_bottom_up):
    snap = spoked()
    src = source_of(snap)
    _, openers, _, _, stats = _run_with_openers(
        lambda: H.frontier_bfs_hybrid(snap, src))
    assert "dense" in openers
    assert stats["hybrid_lead"]["calls"] == 1
    g = H.build_chunked_csr(snap)
    held = g[f"_lead{H.SPLIT_LANES}"]
    # the second run, and a WCC behind it, read the image the first built
    _, openers, _, _, stats = _run_with_openers(
        lambda: (H.frontier_bfs_hybrid(snap, src), frontier_wcc(snap)))
    assert openers.count("dense") >= 2
    assert "hybrid_lead" not in stats
    assert g[f"_lead{H.SPLIT_LANES}"] is held
    # another lane width is another image
    H.leading_lanes(g, 4 if H.SPLIT_LANES != 4 else 1)
    assert len([k for k in g if k.startswith("_lead")]) == 2


# -- the frontier as a table in VMEM (Pallas's interpreter) ------------------

@pytest.mark.parametrize("lanes", LANES)
def test_the_vmem_gather_reads_what_the_bitmap_reads(lanes):
    import jax.numpy as jnp

    snap = hub()
    g = H.build_chunked_csr(snap)
    n = g["n"]
    ref = bfs_numpy(snap, source_of(snap))
    lead = H.leading_lanes(g, lanes)
    for level in (0, 1):
        dist = jnp.asarray(np.concatenate([ref, [INF]]).astype(np.int32))
        fbits = H._pack_bits(dist, jnp.int32(level), n)
        want = np.asarray(H._fbit_of(fbits, lead)).reshape(lanes, -1)
        table = vg.as_table((dist == level).astype(jnp.float32))
        got = np.asarray(vg.colsum_vmem(lead, table, interpret=True,
                                        rows=lanes))
        assert np.array_equal(got, want.sum(axis=0).astype(np.float32))
        assert want.any()


@pytest.mark.parametrize("rows", [0, 3, 6])
def test_the_vmem_gather_takes_a_power_of_two_of_rows(rows):
    """Its pairwise sum halves the rows: another count is refused at
    entry, not by an index out of range inside the trace."""
    import jax.numpy as jnp

    table = vg.as_table(jnp.zeros((200,), jnp.float32))
    with pytest.raises(AssertionError):
        vg.colsum_vmem(jnp.zeros((max(rows, 1) * vg.BLOCK,), jnp.int32),
                       table, interpret=True, rows=rows)


@pytest.mark.parametrize("lanes", [1, 2])
def test_the_opener_under_the_vmem_gather_is_the_same_call(
        lanes, kernel_in_the_interpreter):
    """The whole program with ``impl="vmem"`` (the kernel in Pallas's
    interpreter) against the loop over the vertices."""
    snap = hub()
    g = H.build_chunked_csr(snap)
    ref = bfs_numpy(snap, source_of(snap))
    state = np.where(ref <= 1, ref, INF).astype(np.int32)
    got = opener(g, state, 1, lanes, impl="vmem")
    same(got, opener_numpy(snap, g, state, 1, lanes))
