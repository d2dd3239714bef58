"""LCC (ISSUE 42): LDBC Graphalytics' local clustering coefficient as a
served job, on the CPU. The program (``models/lcc.py``: the hub bit
table's pass over the job's own lanes, every edge with a hub at an end
once (ISSUE 48), the hubs' column sums with the low-low edges' counts
beside them, the compare tail) against the benchmark's plain reference
(``benchmark/reference/lcc.py``: wedges listed in numpy, nothing of
``titan_tpu`` and no table in it) AND against ``set`` intersections a
vertex at a time, so that the reference is itself checked:
``triangle_counts`` exactly, ``lcc`` by the epsilon rule. Shapes worked
by hand (a triangle, K5, a star, a path, two cliques joined by an edge,
vertices of degree 0 and 1), Kronecker graphs with few enough hubs that
every class of triangle (three hubs, two, one, none) holds some, hubs
>= n, hubs that leave no low-low edge, chunks that do not divide the
image; then the served path: ``POST /jobs`` -> result plane, spans and
counters, timeout and cancel between dispatches, what is refused,
admission of the table and the working set, eviction and rebuild.
"""

import functools
import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.models import lcc as L
from titan_tpu.models import pagerank_pull as pp
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving.hbm import (snapshot_csr_bytes,
                                        snapshot_lcc_bytes,
                                        snapshot_lcc_work_bytes)
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name: str):
    spec = importlib.util.spec_from_file_location(
        f"_bench_reference_{name}",
        os.path.join(ROOT, "benchmark", "reference", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def reference():
    return _reference("lcc")


def by_reference(reference, n, src, dst):
    indptr, indices = _reference("csr").structure(n, src, dst)
    counts, deg = reference.triangles(indptr, indices)
    return counts, reference.coefficients(counts, deg)


def by_sets(n, src, dst):
    """The specification read literally, a vertex at a time."""
    nbrs = [set() for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        if u != v:
            nbrs[u].add(v)
            nbrs[v].add(u)
    counts = np.zeros(n, np.int64)
    coeff = np.zeros(n)
    for v in range(n):
        pairs = sum(len(nbrs[v] & nbrs[u]) for u in nbrs[v])  # ordered
        counts[v] = pairs // 2
        d = len(nbrs[v])
        if d >= 2:
            coeff[v] = pairs / (d * (d - 1))
    return counts, coeff


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


def clique(ids):
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


def kronecker(scale: int, seed: int, edge_factor: int = 16):
    """R-MAT (A .57, B .19, C .19) made simple, as the data set is."""
    rng = np.random.default_rng(seed)
    n, m = 1 << scale, edge_factor << scale
    s, d = np.zeros(m, np.int64), np.zeros(m, np.int64)
    for bit in range(scale):
        quad = np.searchsorted([0.57, 0.76, 0.95], rng.random(m),
                               side="right")
        s |= (quad >> 1) << bit
        d |= (quad & 1) << bit
    lo, hi = np.minimum(s, d), np.maximum(s, d)
    key = np.unique((lo * n + hi)[lo != hi])
    return both_ways(n, list(zip((key // n).tolist(),
                                 (key % n).tolist())))


# name -> (graph, the triangles through each vertex worked by hand)
BY_HAND = {
    "a_triangle": (both_ways(3, clique([0, 1, 2])), [1, 1, 1]),
    "k5": (both_ways(5, clique(range(5))), [6] * 5),
    # all 0: no two leaves are joined
    "a_star": (both_ways(7, [(3, v) for v in (0, 1, 2, 4, 5)]),
               [0] * 7),
    "a_path": (both_ways(5, [(0, 1), (1, 2), (2, 3), (3, 4)]), [0] * 5),
    # two 4-cliques joined by 3 - 4: the bridge closes no triangle
    "two_cliques": (both_ways(8, clique([0, 1, 2, 3])
                              + clique([4, 5, 6, 7]) + [(3, 4)]),
                    [3] * 8),
    # 5 has no edge, 4 hangs off the triangle by one
    "degrees_zero_and_one": (both_ways(6, clique([0, 1, 2]) + [(2, 4)]),
                             [1, 1, 1, 0, 0, 0]),
}


@pytest.mark.parametrize("hubs", [1, 2, 4, 64])
@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_by_hand(reference, name, hubs):
    (n, src, dst), want = BY_HAND[name]
    counts, coeff = L.lcc(snap_mod.from_arrays(n, src, dst), hubs=hubs)
    assert counts.dtype == np.int32 and coeff.dtype == np.float32
    assert counts.tolist() == want
    ref_counts, ref_coeff = by_reference(reference, n, src, dst)
    assert ref_counts.tolist() == want
    set_counts, set_coeff = by_sets(n, src, dst)
    assert set_counts.tolist() == want
    assert np.allclose(ref_coeff, set_coeff, rtol=1e-12, atol=0)
    assert reference.outside(coeff, ref_coeff) == 0
    if name == "k5":
        assert coeff.tolist() == [1.0] * 5


GRAPHS = {"kron10": kronecker(10, 7), "kron11": kronecker(11, 3),
          "kron9_thin": kronecker(9, 5, edge_factor=4)}


def classes(n, src, dst, hub_ids):
    """Triangles by how many hubs they hold, 0 to 3."""
    hub = np.zeros(n, bool)
    hub[hub_ids] = True
    nbrs = [set() for _ in range(n)]
    for u, v in zip(src.tolist(), dst.tolist()):
        nbrs[u].add(v)
    held = [0, 0, 0, 0]
    for u in range(n):
        for v in nbrs[u]:
            if v > u:
                for w in nbrs[u] & nbrs[v]:
                    if w > v:
                        held[int(hub[u]) + int(hub[v]) + int(hub[w])] += 1
    return held


@pytest.mark.parametrize("hubs", [8, 64, 256])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_program_reference_and_sets_agree(reference, name, hubs):
    n, src, dst = GRAPHS[name]
    snap = snap_mod.from_arrays(n, src, dst)
    counts, coeff = L.lcc(snap, hubs=hubs)
    set_counts, set_coeff = by_sets(n, src, dst)
    ref_counts, ref_coeff = by_reference(reference, n, src, dst)
    assert (ref_counts == set_counts).all()
    assert np.allclose(ref_coeff, set_coeff, rtol=1e-12, atol=0)
    assert (counts == set_counts).all()
    assert reference.outside(coeff, ref_coeff) == 0
    im = snap._lcc_csr
    if name != "kron9_thin" and hubs < 256:
        # every class of triangle holds some: each part of the road (the
        # pass's hub and low lanes, the column sums, the tail) carries
        # counts that no other part could make up
        held = classes(n, src, dst, np.asarray(im["hub_ids"]))
        assert min(held) > 0, held
        assert im["ll_edges"] > 0 and im["wedges"] > 0
        assert len(im["blocks"]) >= 2       # more than one class of d+


def image_edges(im, n):
    """(owner, neighbour) of every lane of the pass's image that holds
    an edge, in the image's order."""
    idx8, own = np.asarray(im["idx8"]), np.asarray(im["own"])
    held = idx8 != n + 1
    return np.broadcast_to(own, idx8.shape)[held], idx8[held]


@pytest.mark.parametrize("hubs", [8, 64, 256])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_the_image_holds_every_hub_edge_once_at_its_hub(name, hubs):
    """ISSUE 48: the pass's lanes are the edges with a hub at an end,
    each ONCE, in the columns of the hub that owns it (of two hubs the
    higher in (degree, id) order); the low-low edges stand in ``ll``
    and nowhere else; between them every edge of the graph, once."""
    n, src, dst = GRAPHS[name]
    snap = snap_mod.from_arrays(n, src, dst)
    im = L.lcc_image(snap, hubs)
    deg = np.bincount(dst, minlength=n)
    hub = np.zeros(n + 2, bool)
    hub[np.asarray(im["hub_ids"])] = True
    ow, nb = image_edges(im, n)
    assert hub[ow].all()
    both = hub[nb]
    # of two hubs the owner is the higher in (degree, id)
    assert ((deg[ow[both]] > deg[nb[both]])
            | ((deg[ow[both]] == deg[nb[both]])
               & (ow[both] > nb[both]))).all()
    lo, hi = np.minimum(ow, nb).astype(np.int64), np.maximum(ow, nb)
    lanes = lo * n + hi
    assert len(np.unique(lanes)) == len(lanes) == im["hub_edges"]
    ll = np.asarray(im["ll"])[:, :im["ll_edges"]].astype(np.int64)
    assert not hub[ll].any() and (ll[0] < ll[1]).all()
    low_low = ll[0] * n + ll[1]
    assert len(np.unique(low_low)) == len(low_low)
    once = src < dst
    want = src[once].astype(np.int64) * n + dst[once]
    assert np.array_equal(np.sort(np.concatenate([lanes, low_low])),
                          np.sort(want))
    with_hub = hub[src[once]] | hub[dst[once]]
    assert np.array_equal(np.sort(lanes), np.sort(want[with_hub]))
    # an owner's columns are adjacent, ``first`` at each one's start,
    # ``last`` at its end; the pads (columns and lanes) read row n + 1
    own = np.asarray(im["own"])
    held = int((own != n + 1).sum())
    assert (own[held:] == n + 1).all() and (np.diff(own[:held]) >= 0).all()
    first = np.asarray(im["first"])
    assert np.array_equal(
        np.flatnonzero(first[:held]),
        np.flatnonzero(np.diff(own[:held], prepend=-1)))
    assert first[held:].all()
    assert np.array_equal(own[np.asarray(im["last"])],
                          np.asarray(im["owners"]))
    assert im["idx8"].shape[1] % L.PASS_TILE == 0
    assert np.array_equal(np.asarray(im["hubl"]),
                          hub[np.asarray(im["idx8"])])


def test_the_images_shapes_follow_from_the_degrees_alone():
    """A relabelling (the benchmark's seed) moves which of two hubs of
    one degree owns their edge; it moves no shape of the image, and with
    it no program's: a hub's columns keep room for every such edge."""
    n, src, dst = GRAPHS["kron10"]
    shapes = set()
    for seed in range(4):
        perm = np.random.default_rng(seed).permutation(n).astype(np.int32)
        snap = snap_mod.from_arrays(n, perm[src], perm[dst])
        p = L.plan(snap, 64)
        lanes = p["lanes"]
        deg = p["deg"]
        hub = p["is_hub"]
        ties = int((hub[snap.src] & hub[snap.dst]
                    & (deg[snap.src] == deg[snap.dst])).sum()) // 2
        assert ties > 0             # the rule has something to decide
        shapes.add((lanes["idx8"].shape, len(lanes["owners"]),
                    lanes["seg_max"], lanes["edges"], p["credit_max"],
                    p["ll"].shape, p["rows"].shape,
                    tuple(b["nbr"].shape for b in p["blocks"])))
    assert len(shapes) == 1, shapes


# hubs 0, 1, 2 (degrees 6, 5, 5: a triangle), the others low; by hand
BY_EDGE = {
    "n": 7,
    # (x, y): c(x, y), the hubs adjacent to both
    "hub_hub": {(0, 1): 1, (0, 2): 1, (2, 1): 1},    # owner first
    "hub_low": {(0, 3): 1, (1, 3): 1, (0, 4): 2, (1, 4): 2, (2, 4): 2,
                (0, 5): 2, (1, 5): 2, (2, 5): 2, (0, 6): 1, (2, 6): 1},
    "low_low": {(3, 4): 2},
    "triangles": [8, 7, 6, 3, 5, 3, 1],
}


def test_the_case_table_by_edge_by_hand():
    """ISSUE 48's table on seven vertices: a low-low edge gives 2 c to
    both ends, a hub-low edge 2 c to the hub and c to the other, a
    hub-hub edge c to both; each class's share carried separately."""
    import jax.numpy as jnp

    e = BY_EDGE
    pairs = [p for k in ("hub_hub", "hub_low", "low_low") for p in e[k]]
    n, src, dst = both_ways(e["n"], pairs)
    snap = snap_mod.from_arrays(n, src, dst)
    counts, _ = L.lcc(snap, hubs=3)
    assert counts.tolist() == by_sets(n, src, dst)[0].tolist() \
        == e["triangles"]
    im = snap._lcc_csr
    assert sorted(np.asarray(im["hub_ids"]).tolist()) == [0, 1, 2]
    columns = im["idx8"].shape[1]
    cols, lanes = L._pass()(im["table"], im["idx8"], im["own"], im["hubl"],
                            jnp.int32(0), chunk=columns, tile=L.PASS_TILE)
    ow, nb = image_edges(im, n)
    c = np.asarray(lanes)[np.asarray(im["idx8"]) != n + 1]
    made = dict(zip(zip(ow.tolist(), nb.tolist()), c.tolist()))
    # every edge with a hub once, at its owner: 2 owns (2, 1), the tie
    assert made == {**e["hub_hub"], **e["hub_low"]}
    own_sum = {}
    for col, x in zip(np.asarray(cols).tolist(),
                      np.asarray(im["own"]).tolist()):
        own_sum[x] = own_sum.get(x, 0) + col
    assert own_sum.pop(n + 1) == 0
    want = dict.fromkeys((0, 1, 2), 0)
    for (x, _y), cc in e["hub_hub"].items():
        want[x] += cc                               # c to the owner
    for (x, _y), cc in e["hub_low"].items():
        want[x] += 2 * cc                           # 2 c to the hub
    assert own_sum == want == {0: 14, 1: 10, 2: 11}
    sums, ll_counts = L._colsum()(im["table"], im["ll"], jnp.int32(0),
                                  chunk=im["col_chunk"], tile=L.COL_TILE)
    assert np.asarray(im["ll"])[:, 0].tolist() == [3, 4]
    assert np.asarray(ll_counts)[:2].tolist() == [2, 0]
    # the low-low edge (3, 4) stands inside N(0) and N(1): Q
    by_hub = dict(zip(np.asarray(im["hub_ids"]).tolist(),
                      np.asarray(sums)[:3].tolist()))
    assert by_hub == {0: 1, 1: 1, 2: 0}
    # 2 A by the table, from the three classes' shares
    twice_a = [0] * n
    for (x, y), cc in e["hub_hub"].items():
        twice_a[x] += cc
        twice_a[y] += cc
    for (x, y), cc in e["hub_low"].items():
        twice_a[x] += 2 * cc
        twice_a[y] += cc
    for (x, y), cc in e["low_low"].items():
        twice_a[x] += 2 * cc
        twice_a[y] += 2 * cc
    assert twice_a == [14, 12, 12, 6, 10, 6, 2]
    assert [a // 2 + by_hub.get(v, 0) for v, a in enumerate(twice_a)] \
        == e["triangles"]                           # no low triangle


def test_each_part_carries_its_class(reference):
    """The parts one at a time, each against the triangles of its class
    counted by sets: an edge's c is the triangles on it whose third
    vertex is a hub, so the pass's hub-hub lanes sum to 3 x the
    triangles of three hubs, its hub-low lanes to 2 x those of two, the
    low-low edges' counts (and the hubs' column sums) to those of one;
    the tail's centres carry the rest."""
    import jax.numpy as jnp

    n, src, dst = GRAPHS["kron10"]
    snap = snap_mod.from_arrays(n, src, dst)
    L.lcc(snap, hubs=64)
    im = snap._lcc_csr
    held = classes(n, src, dst, np.asarray(im["hub_ids"]))
    columns = im["idx8"].shape[1]
    cols, lanes = L._pass()(im["table"], im["idx8"], im["own"], im["hubl"],
                            jnp.int32(0), chunk=columns, tile=1024)
    lanes, to_hub = np.asarray(lanes), np.asarray(im["hubl"])
    assert int(lanes[to_hub].sum()) == 3 * held[3]
    assert int(lanes[~to_hub].sum()) == 2 * held[2]
    # the owners' share: c at a hub neighbour, 2 c at a low one
    assert int(cols.sum()) == 3 * held[3] + 2 * 2 * held[2]
    sums, ll_counts = L._colsum()(im["table"], im["ll"], jnp.int32(0),
                                  chunk=im["col_chunk"], tile=L.COL_TILE)
    assert im["ll"].shape[1] == im["col_chunk"]
    assert int(sums.sum()) == held[1]       # one hub, once at the hub
    assert int(ll_counts.sum()) == held[1]  # and once at its low-low edge
    centres = 0
    for blk in im["blocks"]:
        place, centre = L._tail()(im["rows"], blk["nbr"], blk["rows"],
                                  per=blk["per"])
        assert int(place.sum()) == 2 * int(centre.sum())
        centres += int(centre.sum())
    assert centres == held[0]               # no hub, once at its lowest


@pytest.mark.parametrize("row", [4, 8])
def test_higher_neighbours_that_fill_several_rows(monkeypatch, row):
    """A vertex with more higher neighbours than a row of the tail's
    table holds stands in several rows, and a centre compares against
    each: every piece's hits credit the same middle."""
    monkeypatch.setattr(L, "TAIL_ROW", row)
    n, src, dst = GRAPHS["kron10"]
    snap = snap_mod.from_arrays(n, src, dst)
    counts, _ = L.lcc(snap, hubs=8)
    im = snap._lcc_csr
    assert im["rows"].shape[1] == row
    held = (np.asarray(im["rows"]) >= 0).sum(1)
    assert (held == row).sum() > 10         # full pieces, so split rows
    assert any(int((np.asarray(b["mid"]) >= 0).sum())
               > int((np.asarray(b["nbr"]) >= 0).sum())
               for b in im["blocks"])       # more middles than neighbours
    assert (counts == by_sets(n, src, dst)[0]).all()


def test_every_vertex_a_hub_and_no_low_low_edge(reference):
    n, src, dst = GRAPHS["kron10"]
    want = by_sets(n, src, dst)[0]
    snap = snap_mod.from_arrays(n, src, dst)
    counts, _ = L.lcc(snap, hubs=1 << 20)           # hubs >= n
    assert snap._lcc_csr["hubs"] == n and snap._lcc_csr["ll_edges"] == 0
    assert (counts == want).all()
    # K(3, 5) with its three joined: the five low vertices share no edge
    pairs = clique([0, 1, 2]) + [(h, v) for h in (0, 1, 2)
                                 for v in range(3, 8)]
    n, src, dst = both_ways(8, pairs)
    snap = snap_mod.from_arrays(n, src, dst)
    counts, coeff = L.lcc(snap, hubs=3)
    assert snap._lcc_csr["ll_edges"] == 0 and not snap._lcc_csr["blocks"]
    assert counts.tolist() == by_sets(n, src, dst)[0].tolist() \
        == [1 + 2 * 5] * 3 + [3] * 5
    assert coeff[3:].tolist() == [1.0] * 5


def test_chunks_that_do_not_divide_the_image(monkeypatch):
    """The last dispatch of the pass and of the table's build is moved
    back to end with the image: its neighbour's columns are neither
    added to the table twice nor summed or credited twice."""
    n, src, dst = GRAPHS["kron11"]
    monkeypatch.setattr(L, "PASS_TILE", 128)
    monkeypatch.setattr(L, "PASS_CHUNK", 1024)
    monkeypatch.setattr(L, "COL_CHUNK", 2048)
    snap = snap_mod.from_arrays(n, src, dst)
    counts, _ = L.lcc(snap, hubs=64)
    columns = snap._lcc_csr["idx8"].shape[1]
    assert columns > 1024 and columns % 1024 == 896, columns
    assert snap._lcc_csr["ll"].shape[1] > 2 * 2048
    assert (counts == by_sets(n, src, dst)[0]).all()


def test_counts_pass_two_to_the_24_exactly():
    """A hub of graph500-22 stands in more triangles than float32
    counts: the finish's sums and the column sums stay int32."""
    import jax.numpy as jnp

    big = (1 << 24) + 2
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    pad = 3                                         # n + 1
    idx8 = np.full((8, 4), pad)
    idx8[5, 3] = 1                                  # one lane names 1
    lanes = np.zeros((8, 3), np.int32)
    lanes[5, 2] = 3                                 # column 3, trimmed by 2
    counts, coeff = L._finish()(
        (i32([big, big, 2]), i32([7, 7, 4])),       # two of it its neighbour's
        (i32(np.zeros((8, 3))), i32(lanes)),
        (i32([1, 0]),), (i32([big]),), (),
        {"first": jnp.asarray([True, False, False, True]),
         "owners": i32([0, 1]), "last": i32([2, 3]), "idx8": i32(idx8),
         # a low-low edge's count goes twice to either end
         "ll": i32([[1, pad, pad], [1, pad, pad]]),
         "credit_last": i32([-1, 2]), "hub_ids": i32([1]),
         "deg": i32([3, 1 << 14])},
        seg_max=3, credit_max=3, trim=2)
    # (2^25 + 6) / 2 = 2^24 + 3, odd and above 2^24: no float32 holds it
    assert counts.tolist() == [(1 << 24) + 3, (3 + 2 + 2 + 4) // 2 + big]
    assert int(np.float32(int(counts[0]))) != int(counts[0])
    assert coeff.dtype == np.float32 and coeff[0] > 0
    words = jnp.full((256, 3), 0x80000001, jnp.uint32)
    sums = np.asarray(L.bit_column_sums(words))
    assert sums.shape == (3, 32) and sums.dtype == np.int32
    assert sums[:, [0, 31]].tolist() == [[256, 256]] * 3
    assert sums[:, 1:31].sum() == 0


def test_what_is_refused():
    class Overlay:
        empty = False
    n, src, dst = BY_HAND["k5"][0]
    with pytest.raises(RuntimeError, match="compact the overlay"):
        L.lcc(snap_mod.from_arrays(n, src, dst), overlay=Overlay())
    with pytest.raises(ValueError, match="self-loop"):
        L.lcc(snap_mod.from_arrays(n, np.append(src, 2),
                                   np.append(dst, 2)))
    with pytest.raises(ValueError, match="edge twice"):
        L.lcc(snap_mod.from_arrays(n, np.append(src, [0, 1]),
                                   np.append(dst, [1, 0])))
    with pytest.raises(ValueError, match="not an undirected"):
        L.lcc(snap_mod.from_arrays(3, np.array([0, 1]), np.array([1, 2])))


def test_a_veto_stops_between_two_dispatches(monkeypatch):
    from titan_tpu.models.frontier import RoundInterrupted

    n, src, dst = GRAPHS["kron10"]
    monkeypatch.setattr(L, "PASS_CHUNK", 1024)
    seen = []

    def veto(i):
        seen.append(i)
        return i < 3

    with pytest.raises(RoundInterrupted):
        L.lcc(snap_mod.from_arrays(n, src, dst), on_round=veto, hubs=64)
    assert seen == [1, 2, 3]


# -- the served path ---------------------------------------------------------

class Served:
    def __init__(self, n, src, dst, **sched):
        self.metrics = MetricManager()
        self.snap = snap_mod.from_arrays(n, src, dst)
        self.sched = JobScheduler(snapshot=self.snap,
                                  metrics=self.metrics, **sched)
        self.http = GraphServer(None, port=0, scheduler=self.sched).start()
        self.base = f"http://{self.http.host}:{self.http.port}"

    def get(self, path):
        with urllib.request.urlopen(self.base + path, timeout=30) as r:
            return r.headers, r.read()

    def post(self, body):
        req = urllib.request.Request(
            self.base + "/jobs", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read())["job"]

    def job(self, body):
        job_id = self.post(body)
        deadline = time.time() + 120
        while time.time() < deadline:
            env = json.loads(self.get(f"/jobs/{job_id}")[1])
            if env["status"] not in ("queued", "running", "retrying"):
                return env
            time.sleep(0.02)
        raise AssertionError(f"job {job_id} did not finish")

    def array(self, job_id, name):
        headers, raw = self.get(f"/jobs/{job_id}/result/{name}")
        shape = tuple(int(d) for d in headers["X-Shape"].split(",") if d)
        return np.frombuffer(raw, np.dtype(headers["X-Dtype"])) \
            .reshape(shape)

    def close(self):
        self.http.stop()
        self.sched.close()


@pytest.fixture
def few_hubs(monkeypatch):
    """The served path takes no hub count: the module's one value, here
    small enough that every part of the road has work."""
    monkeypatch.setattr(L, "HUBS", 64)


@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_a_served_job_equals_the_reference(reference, few_hubs, name):
    n, src, dst = GRAPHS[name]
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "lcc", "timeout_s": 60})
        assert env["status"] == "done", env
        coeff = served.array(env["job"], "lcc")
        counts = served.array(env["job"], "triangle_counts")
        held = served.sched.get(env["job"]).result
        assert coeff.tobytes() == held["lcc"].tobytes()
    finally:
        served.close()
    ref_counts, ref_coeff = by_reference(reference, n, src, dst)
    assert env["result"] == {"triangles": int(ref_counts.sum()) // 3}
    assert isinstance(held["triangles"], int)
    assert env["arrays"] == {
        "lcc": {"dtype": "float32", "shape": [n]},
        "triangle_counts": {"dtype": "int32", "shape": [n]}}
    assert (counts == ref_counts).all()
    assert reference.outside(coeff, ref_coeff) == 0
    # the rule sees one coefficient, a reference 0 wants an exact 0, and
    # an answer of another length is all out
    one = coeff.copy()
    at = int(np.flatnonzero(ref_coeff > 0)[0])
    one[at] *= 1.001
    assert reference.outside(one, ref_coeff) == 1
    one = coeff.copy()
    one[int(np.flatnonzero(ref_coeff == 0)[0])] = 1e-9
    assert reference.outside(one, ref_coeff) == 1
    assert reference.outside(coeff[:-1], ref_coeff) == n


def test_one_altered_count_reads_one_mismatch(reference, few_hubs,
                                              monkeypatch):
    """One triangle too many at one vertex, where the job's answer is
    made: the reference's check, as the load generator applies it to the
    served array, reads 1."""
    n, src, dst = GRAPHS["kron10"]
    real = L._finish

    def altered():
        finish = real()

        def one_more(cols, lanes, ll_counts, hub_sums, *rest, **kw):
            assert len(hub_sums) == 1           # the first hub's sum
            return finish(cols, lanes, ll_counts,
                          (hub_sums[0].at[0].add(1),), *rest, **kw)
        return one_more
    monkeypatch.setattr(L, "_finish", altered)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "lcc"})
        assert env["status"] == "done", env
        coeff = served.array(env["job"], "lcc")
        counts = served.array(env["job"], "triangle_counts")
        at = int(np.asarray(served.snap._lcc_csr["hub_ids"])[0])
    finally:
        served.close()
    indptr, indices = _reference("csr").structure(n, src, dst)
    ref = reference.prepare(n, indptr, indices, {}, {})
    assert ref.check({"kind": "lcc"}, coeff) == {"lcc": 1}
    assert (counts != ref.triangles).sum() == 1
    assert counts[at] == ref.triangles[at] + 1
    assert env["result"]["triangles"] == int(ref.triangles.sum() + 1) // 3


def test_a_directed_snapshot_and_an_unknown_kind_are_refused():
    n, src, dst = BY_HAND["k5"][0]
    served = Served(n, src, dst)
    try:
        with pytest.raises(ValueError, match="directed form"):
            served.sched.submit(JobSpec(kind="lcc", directed=True))
        with pytest.raises(ValueError, match="lcc"):
            served.sched.submit(JobSpec(kind="triangles"))
        assert served.metrics.counter(
            "serving.jobs.rejected",
            labels={"kind": "lcc", "tenant": "default"}).count == 1
        env = served.job({"kind": "lcc"})
        assert env["status"] == "done" and \
            env["result"]["triangles"] == 10
    finally:
        served.close()


def test_the_jobs_spans_and_counters(few_hubs):
    n, src, dst = GRAPHS["kron10"]
    served = Served(n, src, dst)
    try:
        first = served.job({"kind": "lcc"})
        env = served.job({"kind": "lcc"})
        assert first["status"] == env["status"] == "done", env
        from titan_tpu.obs import devprof
        devprof.drain()
        spans = list(served.sched.tracer.spans(env["job"]))
        cold = list(served.sched.tracer.spans(first["job"]))
        m = served.metrics
        text = served.get("/metrics")[1].decode()
        im = served.snap._lcc_csr
    finally:
        served.close()
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (run,) = by_name["run"]
    (image,) = by_name["lcc.image"]
    (hub,) = by_name["lcc.hub"]
    (tail,) = by_name["lcc.tail"]
    (result,) = by_name["lcc.result"]
    (count,) = by_name["lcc.count"]
    leaves = [image, hub, tail, result, count]
    assert all(s.parent_id == run.span_id for s in leaves)
    ordered = sorted(leaves, key=lambda s: s.t_start)
    assert [s.name for s in ordered] == [
        "lcc.image", "lcc.hub", "lcc.tail", "lcc.result", "lcc.count"]
    assert all(a.t_end <= b.t_start for a, b in zip(ordered, ordered[1:]))
    edges = len(src) // 2                   # undirected, each ANDed once
    assert im["hub_edges"] + im["ll_edges"] == edges
    assert image.attrs["cache"] == "hit" and image.attrs["hubs"] == 64
    assert image.attrs["bytes"] == im["bytes"]
    (built,) = [s for s in cold if s.name == "lcc.image"]
    assert built.attrs["cache"] == "miss" \
        and built.attrs["bytes"] == im["bytes"]
    assert hub.attrs["level"] == 1 and hub.attrs["hubs"] == 64
    assert hub.attrs["edges"] == edges and hub.attrs["tiles"] == 2
    assert tail.attrs["wedges"] == im["wedges"] > 0
    assert tail.attrs["edges"] == im["ll_edges"] == im["tail_edges"]
    assert tail.attrs["tiles"] == len(im["blocks"])
    assert result.attrs["bytes"] == 8 * n and result.attrs["sync_ms"] >= 0
    assert len(by_name["job.lease"]) == len(by_name["job.admit"]) == 1
    # every program a kernel span under the phase that dispatched it
    under = {hub.span_id: [], tail.span_id: [], result.span_id: []}
    for s in by_name["kernel"]:
        under[s.parent_id].append(s.attrs["key"])
    assert sorted(under[hub.span_id]) == ["lcc_colsum", "lcc_pass"]
    assert under[tail.span_id] == ["lcc_tail"] * len(im["blocks"])
    assert under[result.span_id] == ["lcc_finish"]
    # the image is made on the host and sent, once: the table a slab
    # at a time, the lanes' hub flags read on the device
    assert sorted({s.attrs["key"] for s in cold if s.name == "kernel"}) \
        == ["lcc_colsum", "lcc_finish", "lcc_flags", "lcc_pass",
            "lcc_place", "lcc_tail"]
    assert m.counter("device.lcc.edges",
                     labels={"part": "hub"}).count == 2 * edges
    assert m.counter("device.lcc.edges",
                     labels={"part": "tail"}).count == 2 * im["ll_edges"]
    assert m.counter("device.lcc.wedges",
                     labels={"part": "tail"}).count == 2 * im["wedges"]
    assert m.counter_value("device.lcc.levels") == 2
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "lcc.result"}).count == 2 * 8 * n
    assert m.counter("device.xfer.h2d_bytes",
                     labels={"site": "lcc.image"}).count \
        == im["bytes"] - im["idx8"].size
    for key in ("lcc_pass", "lcc_colsum", "lcc_tail", "lcc_finish"):
        assert m.counter("device.exec.unstamped",
                         labels={"kernel": key}).count == 0
    assert "device_lcc_edges" in text.replace(".", "_")


def test_timeout_and_cancel_between_two_dispatches(few_hubs, monkeypatch):
    n, src, dst = GRAPHS["kron10"]
    monkeypatch.setattr(L, "PASS_CHUNK", 1024)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics)
    try:
        late = sched.submit(JobSpec(kind="lcc", timeout_s=0.0))
        assert late.wait(120) and late.state.value == "timeout", \
            (late.state, late.error)
        assert late.last_round == 1     # behind its first dispatch
        real = L.lcc

        def cancelling(snap, **kw):
            on_round = kw["on_round"]

            def hook(i):
                if i == 2:
                    sched.cancel(job.id)
                return on_round(i)
            return real(snap, **dict(kw, on_round=hook))
        monkeypatch.setattr(L, "lcc", cancelling)
        job = sched.submit(JobSpec(kind="lcc"))
        assert job.wait(120)
        assert job.state.value == "cancelled", (job.state, job.error)
        assert job.last_round == 2 and job.result is None
    finally:
        sched.close()


def test_a_job_that_asks_for_a_checkpoint_keeps_none(few_hubs, tmp_path):
    """No checkpoint: ``checkpoint_every`` is taken and ignored, and a
    crashed job's retry starts over, the image still resident."""
    from titan_tpu.olap.recovery import FaultPlan

    n, src, dst = GRAPHS["kron10"]
    want = by_sets(n, src, dst)[0]
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics, checkpoint_dir=str(tmp_path))
    try:
        crashed = sched.submit(JobSpec(
            kind="lcc", max_retries=1, checkpoint_every=1,
            params={"faults": FaultPlan(crash_at_round=2)}))
        assert crashed.wait(120)
        spans = list(sched.tracer.spans(crashed.id))
    finally:
        sched.close()
    assert crashed.state.value == "done", crashed.error
    assert crashed.attempt == 2
    assert (crashed.result["triangle_counts"] == want).all()
    assert metrics.counter_value("serving.recovery.resumes") == 0
    assert crashed.rounds_replayed == 2     # all the first attempt ran
    assert metrics.counter_value("serving.recovery.rounds_replayed") == 2
    assert [s.attrs["cache"] for s in spans if s.name == "lcc.image"] \
        == ["miss", "hit"]
    assert not any(f.endswith(".npz") or "ckpt" in f
                   for _d, _s, files in os.walk(tmp_path) for f in files)


def test_admission_reserves_the_table_and_lets_the_working_set_go(
        few_hubs):
    n, src, dst = GRAPHS["kron10"]
    snap = snap_mod.from_arrays(n, src, dst)
    q_in = pp.pull_columns(snap.indptr_in, n)
    images = snapshot_csr_bytes(snap)       # the job reads no pull image
    table = snapshot_lcc_bytes(snap)
    work = snapshot_lcc_work_bytes(snap)
    assert table == L.image_bytes(n, q_in, 64) > L.table_bytes(n, 64) \
        == (n + 2) * 2 * 4
    assert work == L.work_bytes(n, q_in, 64)
    served = Served(n, src, dst)
    try:
        first = served.job({"kind": "lcc"})
        env = served.job({"kind": "lcc"})
        assert first["status"] == env["status"] == "done", env
        admits = [[s for s in served.sched.tracer.spans(e["job"])
                   if s.name == "job.admit"][0] for e in (first, env)]
        ledger = served.sched.ledger
        assert served.snap._lcc_csr["bytes"] <= table
    finally:
        served.close()
    assert [a.attrs["bytes"] for a in admits] == [images + table + work] * 2
    # the first admission prices the snapshot, the second reads it
    assert [a.attrs["sizing_passes"] for a in admits] == [2, 0]
    assert ledger.resident_bytes() == images + table    # the work left
    assert ledger.pinned_bytes() == 0
    # a budget that holds the images and the table but not the working
    # set beside them refuses the job and leaves nothing pinned, where
    # a PageRank job over the same images is admitted
    served = Served(n, src, dst,
                    hbm_budget_bytes=images + table + work - 1)
    try:
        env = served.job({"kind": "lcc"})
        assert env["status"] == "failed" and "admission" in env["error"]
        assert served.sched.ledger.pinned_bytes() == 0
        ok = served.job({"kind": "pagerank", "iterations": 2})
        assert ok["status"] == "done", ok
    finally:
        served.close()


def test_the_table_is_evicted_under_pressure_and_rebuilt(few_hubs):
    """A second tenant's image beside it: pinned, the job is refused at
    admission and not run into the device's memory; let go, the table
    (resident, unpinned, the largest entry) is what makes room, and the
    next job builds it again."""
    n, src, dst = GRAPHS["kron10"]
    snap = snap_mod.from_arrays(n, src, dst)
    need = snapshot_csr_bytes(snap) + snapshot_lcc_bytes(snap) \
        + snapshot_lcc_work_bytes(snap)
    served = Served(n, src, dst, hbm_budget_bytes=need + 1000)
    try:
        ledger = served.sched.ledger
        ledger.reserve("another-tenant", 2000)          # pinned
        env = served.job({"kind": "lcc"})
        assert env["status"] == "failed" and "admission" in env["error"]
        ledger.release("another-tenant")
        env = served.job({"kind": "lcc"})
        assert env["status"] == "done", env
        assert hasattr(served.snap, "_lcc_csr")
        # the other tenant comes back wanting more than the working
        # set's room: the table makes it
        ledger.reserve("another-tenant",
                       snapshot_lcc_work_bytes(snap) + 2000)
        assert not hasattr(served.snap, "_lcc_csr")     # evicted
        ledger.release("another-tenant")
        again = served.job({"kind": "lcc"})
        assert again["status"] == "done", again
        (image,) = [s for s in served.sched.tracer.spans(again["job"])
                    if s.name == "lcc.image"]
        assert image.attrs["cache"] == "miss"           # and rebuilt
        counts = served.array(again["job"], "triangle_counts")
    finally:
        served.close()
    assert (counts == by_sets(n, src, dst)[0]).all()


def test_a_refreshed_snapshot_drops_the_table():
    n, src, dst = BY_HAND["two_cliques"][0]
    snap = snap_mod.from_arrays(n, src, dst)
    L.lcc(snap, hubs=2)
    assert hasattr(snap, "_lcc_csr")
    snap._invalidate_layout_caches()
    assert not hasattr(snap, "_lcc_csr") and not hasattr(snap, "_pull_csr")
