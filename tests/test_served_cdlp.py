"""CDLP (ISSUE 40; the row image: ISSUE 44): LDBC Graphalytics'
community detection by label propagation as a served job, on the CPU.
The program (``models/cdlp.py`` over a lane image of its own, packed in
rows that hold whole vertices and sorted a row at a time; the vote of
``ops/segment.py``) against the
benchmark's plain reference (``benchmark/reference/cdlp.py``: the
specification's equations in numpy, nothing of ``titan_tpu`` in it) AND
against a ``collections.Counter`` count a vertex at a time, so that the
reference is itself checked: every label, exactly. Shapes worked by hand
(a tie, a vertex of degree 1, a star, two cliques joined by an edge, a
vertex whose every neighbour carries another label, a pair that flips
every round), seeded random graphs with a hub that takes the wide class,
0, 1 and 10 rounds; the row image's invariants and the sort's two forms
under forced widths; the Pallas gather in Pallas's interpreter; then the
served
path: ``POST /jobs`` -> result plane, spans and counters, timeout and
cancel at a round's boundary, resume from a checkpoint bit-equal,
admission of the rounds' working set.
"""

import collections
import functools
import importlib.util
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.models import cdlp as C
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving.hbm import (snapshot_cdlp_bytes,
                                        snapshot_cdlp_image_bytes,
                                        snapshot_csr_bytes,
                                        snapshot_pull_bytes)
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import segment
from titan_tpu.ops import vmem_gather as vg
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
    return _reference("cdlp")


def structure(n, src, dst):
    return _reference("csr").structure(n, src, dst)


def by_counter(n, src, dst, iterations):
    """The specification read literally, a vertex at a time."""
    nbrs = [[] for _ in range(n)]
    for u, v in set(zip(src.tolist(), dst.tolist())):
        nbrs[v].append(u)
    labels = list(range(n))
    for _ in range(iterations):
        new = list(labels)
        for v in range(n):
            if nbrs[v]:
                count = collections.Counter(labels[u] for u in nbrs[v])
                most = max(count.values())
                new[v] = min(l for l, c in count.items() if c == most)
        labels = new
    return np.asarray(labels, np.int32)


def both_ways(n, pairs):
    a = np.array([p[0] for p in pairs], np.int32)
    b = np.array([p[1] for p in pairs], np.int32)
    return n, np.concatenate([a, b]), np.concatenate([b, a])


def clique(ids):
    return [(a, b) for i, a in enumerate(ids) for b in ids[i + 1:]]


# name -> (graph, {rounds: labels worked by hand})
BY_HAND = {
    # 0's neighbours 1, 2, 3 each carry their own label once: the
    # smallest wins; 1, 2, 3 have degree 1 and take 0's
    "a_tie_and_degree_one": (both_ways(4, [(0, 1), (0, 2), (0, 3)]),
                             {1: [1, 0, 0, 0], 2: [0, 1, 1, 1]}),
    # a pair flips every round: synchronous semantics show
    "a_pair_flips": (both_ways(2, [(0, 1)]),
                     {0: [0, 1], 1: [1, 0], 2: [0, 1], 3: [1, 0]}),
    # a star of six around 3, and vertex 6 with no edge keeps its label
    "a_star": (both_ways(7, [(3, v) for v in (0, 1, 2, 4, 5)]),
               {1: [3, 3, 3, 0, 3, 3, 6], 2: [0, 0, 0, 3, 0, 0, 6]}),
    # two 4-cliques joined by 3 - 4: each settles on its smallest id
    "two_cliques": (both_ways(8, clique([0, 1, 2, 3]) + clique([4, 5, 6, 7])
                              + [(3, 4)]),
                    {1: [1, 0, 0, 0, 3, 4, 4, 4],
                     2: [0, 0, 0, 0, 4, 4, 4, 4],
                     10: [0, 0, 0, 0, 4, 4, 4, 4]}),
    # after round 1 of this path 0-1-2-3-4, vertex 2's neighbours carry
    # 0 and 2, neither its own label 1
    "every_neighbour_another_label": (
        both_ways(5, [(0, 1), (1, 2), (2, 3), (3, 4)]),
        {1: [1, 0, 1, 2, 3], 2: [0, 1, 0, 1, 2]}),
}


@pytest.mark.parametrize("name,rounds", [
    (name, rounds) for name, (_g, want) in BY_HAND.items()
    for rounds in want])
def test_by_hand(reference, name, rounds):
    (n, src, dst), want = BY_HAND[name]
    assert by_counter(n, src, dst, rounds).tolist() == want[rounds]
    assert reference.propagate(*structure(n, src, dst),
                               rounds).tolist() == want[rounds]
    got, its = C.cdlp(snap_mod.from_arrays(n, src, dst), rounds)
    assert got.dtype == np.int32 and its == rounds
    assert got.tolist() == want[rounds]


def random_graph(seed: int, n: int, m: int, hub: int = 0):
    """A simple undirected graph, hubs near 0, some vertices without an
    edge; ``hub`` more edges tie vertex 1 to that many others, so that
    its columns straddle a block of the image."""
    rng = np.random.default_rng(seed)
    a = (rng.random(m) ** 2 * n).astype(np.int64)
    b = rng.integers(0, n, m)
    if hub:
        a = np.concatenate([a, np.ones(hub, np.int64)])
        b = np.concatenate([b, rng.permutation(n)[:hub]])
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    key = np.unique(lo[lo != hi] * n + hi[lo != hi])
    lo, hi = (key // n).astype(np.int32), (key % n).astype(np.int32)
    return n, np.concatenate([lo, hi]), np.concatenate([hi, lo])


GRAPHS = {"sparse": (41, 400, 700, 0), "dense": (42, 300, 6000, 0),
          "hub": (43, 9000, 12000, 8800)}


@pytest.fixture(scope="module", params=sorted(GRAPHS))
def graph(request):
    seed, n, m, hub = GRAPHS[request.param]
    n, src, dst = random_graph(seed, n, m, hub)
    return request.param, n, src, dst, snap_mod.from_arrays(n, src, dst)


@pytest.mark.parametrize("rounds", [0, 1, 2, 10])
def test_program_reference_and_counter_agree(reference, graph, rounds):
    name, n, src, dst, snap = graph
    want = reference.propagate(*structure(n, src, dst), rounds)
    if name != "hub" or rounds <= 2:        # a vertex at a time is slow
        assert np.array_equal(by_counter(n, src, dst, rounds), want)
    got, its = C.cdlp(snap, rounds)
    assert its == rounds
    assert reference.mislabelled(got, want) == 0
    if rounds == 1:                 # every count is 1: the smallest id
        indptr, indices = structure(n, src, dst)
        has = np.diff(indptr) > 0
        assert np.array_equal(
            got[has], np.minimum.reduceat(indices, indptr[:-1][has]))
        assert np.array_equal(got[~has], np.flatnonzero(~has))


def test_the_hub_takes_the_wide_class_and_pad_lanes_do_not_vote(graph):
    name, n, _src, _dst, snap = graph
    im = C.cdlp_image(snap)
    (rows, small), (wrows, wide) = im["classes"]
    assert small == C.SMALL_MAX and im["keys"] == 1
    assert im["lanes"] == 8 * (rows * small + wrows * wide)
    assert (rows * 8 * small) % vg.BLOCK == 0 == (wrows * 8 * wide) % vg.BLOCK
    idx = np.asarray(im["idx"])
    pads = int((idx == n + 1).sum())
    assert pads > 0 and pads == idx.size - len(snap.src)
    assert im["pad_share"] == pytest.approx(pads / idx.size)
    if name == "hub":
        # one vertex outgrows a small row: a wide row of its own class,
        # the next power of two above its columns, several blocks long
        assert wrows == 1 and wide == 2048 > small
        assert 8 * wide >= 3 * vg.BLOCK
    else:
        assert wrows == 0 and wide == small
    # the pad's label is its own id, above every vertex's: it sorts
    # behind a vertex's labels and its run does not count
    lanes = np.asarray(C._gather()(np.arange(n, dtype=np.int32),
                                   im["idx"], impl="xla", n_=n))
    assert (lanes[idx == n + 1] == n + 1).all()
    assert (lanes[idx <= n] < n).all()


# -- the row image -------------------------------------------------------------

def ring(k: int):
    """k vertices of degree 2: one column each."""
    a = np.arange(k, dtype=np.int32)
    return both_ways(k, list(zip(a.tolist(), np.roll(a, -1).tolist())))


#: name -> (graph, SMALL_MAX, KEY_BITS), None: as the module has it. A
#: forced small row makes rows many and the wide class populous on a
#: graph a test can count; a forced key width makes the bits run out
FORMS = {
    # the hub alone in the wide class, a thousand vertices a small row
    "as_it_is": (lambda: random_graph(43, 9000, 12000, 8800), None, None),
    # rows of 16 columns: most of them full, a wide class of 2,048
    # columns with every vertex above 16 columns in it: 10 bits of owner
    # in the small rows would be 4, in the wide 7
    "rows_of_16": (lambda: random_graph(52, 3000, 40000, 2500), 16, None),
    # a small row exactly full of one-column vertices: no owner number
    # is free for a pad of its own, at either width
    "full_of_units": (lambda: ring(2 * 1024), None, None),
    "full_of_units_16": (lambda: ring(64), 16, None),
    # 2^23 vertices: 24 bits of label leave 8 of owner, a small row of
    # 256 columns; the hub's 2,048 columns are 8 small rows
    "small_row_256": (lambda: random_graph(53, 1 << 23, 30000, 9000),
                      None, None),
    # the bits do not fit: a key of 14 bits holds the label alone, the
    # pair (owner, label) is sorted, rows of SMALL_MAX
    "two_keys": (lambda: random_graph(43, 9000, 12000, 8800), None, 14),
    "two_keys_rows_of_16": (lambda: random_graph(52, 3000, 40000, 2500),
                            16, 14),
}


@pytest.fixture(params=sorted(FORMS))
def form(request, monkeypatch):
    make, small_max, key_bits = FORMS[request.param]
    if small_max is not None:
        monkeypatch.setattr(C, "SMALL_MAX", small_max)
    if key_bits is not None:
        monkeypatch.setattr(C, "KEY_BITS", key_bits)
    n, src, dst = make()
    return request.param, n, src, dst, snap_mod.from_arrays(n, src, dst)


def row_of(im, lane):
    """(row counted over both classes, its first lane) of a lane."""
    (rows, small), (_wrows, wide) = im["classes"]
    edge = rows * 8 * small
    row = np.where(lane < edge, lane // (8 * small),
                   rows + (lane - edge) // (8 * wide))
    return row, np.where(lane < edge, lane // (8 * small) * (8 * small),
                         edge + (lane - edge) // (8 * wide) * (8 * wide))


def lanes_by_vertex(im):
    """(the vertices in lane order, each one's first lane): a vertex's
    lanes end at its ``last`` and begin behind the vertex before it, or
    where its row begins."""
    last, has = np.asarray(im["last_lane"]), np.asarray(im["has"])
    mine = np.flatnonzero(has)
    mine = mine[np.argsort(last[mine])]
    behind = np.concatenate([[0], last[mine][:-1] + 1])
    return mine, np.maximum(behind, row_of(im, last[mine])[1])


def test_the_row_images_invariants(form):
    name, n, _src, _dst, snap = form
    im = C.cdlp_image(snap)
    (rows, small), (wrows, wide) = im["classes"]
    assert im["keys"] == (2 if name.startswith("two_keys") else 1)
    assert small == (256 if name == "small_row_256"
                     else FORMS[name][1] or 1024)
    assert wrows > 0 or name.startswith("full_of_units")
    idx, own = np.asarray(im["idx"]), np.asarray(im["key_hi"])
    last, has = np.asarray(im["last_lane"]), np.asarray(im["has"])
    assert idx.shape == own.shape == (im["lanes"],) and own.dtype == np.uint32
    assert np.array_equal(has, np.diff(snap.indptr_in) > 0)
    mine, first = lanes_by_vertex(im)
    ends = last[mine] + 1
    assert (first < ends).all() and (first[1:] >= ends[:-1]).all()
    # a vertex of every lane, -1 in the rows that hold none
    vertex = np.full(im["lanes"], -1)
    spans = ends - first
    inside = np.repeat(first - np.cumsum(spans) + spans, spans) \
        + np.arange(spans.sum())
    vertex[inside] = np.repeat(mine, spans)
    # every in-edge slot in exactly one lane, under its own vertex; the
    # other lanes are pads
    real = idx != n + 1
    assert (vertex[real] >= 0).all()
    got = np.stack([vertex[real], idx[real]])
    want = np.stack([snap.dst, snap.src]).astype(got.dtype)
    assert np.array_equal(got[:, np.lexsort(got[::-1])],
                          want[:, np.lexsort(want[::-1])])
    # every row holds whole vertices and nothing of any other: a vertex's
    # lanes are whole columns of one row, a row is its vertices' lanes
    assert np.array_equal(row_of(im, first)[0], row_of(im, last[mine])[0])
    assert (first % 8 == 0).all() and (ends % 8 == 0).all()
    starts_row = row_of(im, first)[1] == first
    ends_row = np.append(starts_row[1:], True)
    assert (first[~starts_row] == ends[:-1][~starts_row[1:]]).all()
    assert (row_of(im, ends[ends_row] - 1)[1]
            + 8 * np.where(first[ends_row] < rows * 8 * small, small, wide)
            == ends[ends_row]).all()
    # the owner inside the row: one number a vertex, counted up along
    # its row from 0, under the row's columns, inside the key's bits
    shift = im["label_bits"] if im["keys"] == 1 else 0
    assert im["label_bits"] == (n + 1).bit_length()
    local = own >> shift
    assert (own == local << shift).all()
    assert np.array_equal(local[inside], np.repeat(local[first], spans))
    assert (local[first][starts_row] == 0).all()
    assert (np.diff(local[first])[~starts_row[1:]] == 1).all()
    width = np.where(first < rows * 8 * small, small, wide)
    assert (local[first] < width).all()
    if im["keys"] == 1:
        assert int(local.max()) < 1 << (C.KEY_BITS - im["label_bits"])
        assert wide // small <= 1 << (C.KEY_BITS - im["label_bits"])
    if name.startswith("full_of_units"):
        # full rows, a vertex a column: the last owner number is taken
        assert len(mine) % small == 0 and len(mine) >= 2 * small
        assert int(local.max()) == small - 1
        assert (spans == 8).all()
    # the sort only groups: behind it a vertex's labels stand sorted in
    # the lanes that were its own, pads last, so ``last`` is its last
    labels = np.random.default_rng(9).permutation(n).astype(np.int32)
    gathered = C._gather()(labels, im["idx"], impl="xla", n_=n)
    owner, by_label = (np.asarray(a) for a in C._sort()(
        im["key_hi"], gathered, **C.sort_statics(im)))
    assert owner.dtype == by_label.dtype == np.int32
    assert np.array_equal(owner[inside], np.repeat(owner[first], spans))
    assert len(np.unique(owner[first])) == len(first)
    nth = np.full(im["lanes"], -1)
    nth[inside] = np.repeat(np.arange(len(mine)), spans)
    seen = np.stack([nth[real], labels[idx[real]]])
    seen = seen[:, np.lexsort(seen[::-1])]
    voting = by_label != n + 1
    assert np.array_equal(np.stack([nth[voting], by_label[voting]]), seen)


@pytest.mark.parametrize("rounds", [1, 3])
def test_labels_equal_the_reference_under_every_form(reference, form,
                                                     rounds):
    _name, n, src, dst, snap = form
    want = reference.propagate(*structure(n, src, dst), rounds)
    got, its = C.cdlp(snap, rounds)
    assert its == rounds
    assert reference.mislabelled(got, want) == 0


def test_a_directed_graph_votes_over_its_in_edges(reference):
    """1 -> 0, 2 -> 0, 2 -> 3: vertex 0 hears 1 and 2, vertex 3 hears 2,
    nobody hears 0 or 3; 1 and 2 have no in-edge and keep their labels."""
    src = np.array([1, 2, 2], np.int32)
    dst = np.array([0, 0, 3], np.int32)
    got, _ = C.cdlp(snap_mod.from_arrays(4, src, dst), 3)
    assert got.tolist() == [1, 1, 2, 2]
    assert by_counter(4, src, dst, 3).tolist() == [1, 1, 2, 2]


# -- the vote itself ---------------------------------------------------------

def test_mode_vote_on_sorted_pairs():
    """Owners 0, 1, 3 (2 has no pair): the answer stands at an owner's
    last element; 7 is the pad and does not vote."""
    owner = np.array([0, 0, 0, 0, 0, 1, 1, 1, 3, 3], np.int32)
    label = np.array([2, 2, 4, 4, 7, 5, 7, 7, 7, 7], np.int32)
    best = np.asarray(segment.mode_vote(owner, label, pad=7))
    assert best[4] == 2         # 2 and 4 twice each: the smaller
    assert best[7] == 5         # two pads do not outvote one label
    assert best[9] == 7         # nothing voted


def test_seg_first_max_keeps_the_earlier_of_equals():
    score = np.array([1, 3, 3, 2, 5, 5, 0], np.int32)
    payload = np.arange(10, 17, dtype=np.int32)
    flags = np.array([1, 0, 0, 0, 1, 0, 0], bool)
    s, p = segment.seg_first_max(score, payload, flags)
    assert np.asarray(s).tolist() == [1, 3, 3, 3, 5, 5, 5]
    assert np.asarray(p).tolist() == [10, 11, 11, 11, 14, 14, 14]
    # ``max_len`` stops the passes at the longest segment
    s2, p2 = segment.seg_first_max(score, payload, flags, max_len=4)
    assert np.array_equal(s2, s) and np.array_equal(p2, p)


# -- the gather --------------------------------------------------------------

def test_the_kernel_gathers_the_same_lanes(graph, monkeypatch):
    """``impl="vmem"``: the Pallas kernel a lane at a time, the labels
    as float32 (exact below 2^24), in Pallas's interpreter here."""
    _name, n, _src, _dst, snap = graph
    monkeypatch.setattr(vg, "colsum_vmem", functools.partial(
        vg.colsum_vmem, interpret=True))
    im = C.cdlp_image(snap)
    labels = np.random.default_rng(5).permutation(n).astype(np.int32)
    lanes = {impl: np.asarray(C._gather()(labels, im["idx"], impl=impl,
                                          n_=n))
             for impl in ("xla", "vmem")}
    assert lanes["vmem"].dtype == np.int32
    assert np.array_equal(lanes["vmem"], lanes["xla"])


def test_what_chooses_the_gather_and_the_table_keeps_labels_exact(
        monkeypatch):
    import inspect

    import jax

    seen = []
    monkeypatch.setattr(vg, "gather_impl",
                        lambda n: seen.append(n) or "xla")
    n, src, dst = random_graph(44, 200, 500)
    C.cdlp(snap_mod.from_arrays(n, src, dst), 1)
    assert seen == [n]              # asked once a job, with n alone
    monkeypatch.undo()
    # every label a table in VMEM can hold is an integer float32 keeps
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    edge = vg.VMEM_TABLE_MAX // 4 - 2
    assert vg.gather_impl(edge) == "vmem" and edge + 1 <= 1 << 24
    assert vg.gather_impl(edge + 1) == "xla"
    src_text = inspect.getsource(C)
    assert "os.environ" not in src_text and "getenv" not in src_text
    assert list(inspect.signature(C.cdlp).parameters) == [
        "snap", "iterations", "on_round", "checkpoint", "resume",
        "overlay"]


# -- rounds, veto, resume ----------------------------------------------------

def test_a_veto_stops_at_a_rounds_boundary(graph):
    from titan_tpu.models.frontier import RoundInterrupted

    _name, _n, _src, _dst, snap = graph
    calls = []

    def veto(it):
        calls.append(it)
        return it < 2
    with pytest.raises(RoundInterrupted) as ei:
        C.cdlp(snap, 10, on_round=veto)
    assert ei.value.rounds == 2 and calls == [0, 1, 2]


@pytest.mark.parametrize("at", [1, 4, 9])
def test_resume_is_bit_equal(graph, at):
    _name, _n, _src, _dst, snap = graph
    kept = {}

    def checkpoint(it, state):
        kept[it] = np.asarray(state["labels"])

    straight, _ = C.cdlp(snap, 10, checkpoint=checkpoint)
    assert sorted(kept) == list(range(1, 11))
    assert kept[10].tobytes() == straight.tobytes()
    resumed, its = C.cdlp(snap, 10, resume={"labels": kept[at], "it": at})
    assert its == 10
    assert resumed.tobytes() == straight.tobytes()
    # a checkpoint at a round's boundary IS that many rounds' answer
    assert kept[at].tobytes() == C.cdlp(snap, at)[0].tobytes()


def test_a_live_overlay_is_refused():
    class Overlay:
        empty = False
    n, src, dst = random_graph(45, 100, 300)
    with pytest.raises(RuntimeError, match="compact the overlay"):
        C.cdlp(snap_mod.from_arrays(n, src, dst), 1, overlay=Overlay())


# -- the served path ---------------------------------------------------------

class Served:
    def __init__(self, n, src, dst, **sched):
        self.metrics = MetricManager()
        self.sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
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

    def labels(self, job_id):
        headers, raw = self.get(f"/jobs/{job_id}/result/labels")
        shape = tuple(int(d) for d in headers["X-Shape"].split(",") if d)
        return np.frombuffer(raw, np.dtype(headers["X-Dtype"])) \
            .reshape(shape)

    def close(self):
        self.http.stop()
        self.sched.close()


@pytest.mark.parametrize("seed,rounds", [(3000000019, 10), (12, 1), (7, 0)])
def test_a_served_job_equals_the_reference(reference, seed, rounds):
    n, src, dst = random_graph(seed, 1500, 5000, hub=1200)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": rounds,
                          "timeout_s": 60})
        assert env["status"] == "done", env
        got = served.labels(env["job"])
        held = served.sched.get(env["job"]).result["labels"]
        assert got.tobytes() == held.tobytes()
    finally:
        served.close()
    want = reference.propagate(*structure(n, src, dst), rounds)
    assert env["result"] == {"iterations": rounds,
                             "communities": len(np.unique(want))}
    assert env["arrays"] == {"labels": {"dtype": "int32", "shape": [n]}}
    assert reference.mislabelled(got, want) == 0
    # the comparison sees one label, and an answer of another length
    one = got.copy()
    one[3] = (one[3] + 1) % n
    assert reference.mislabelled(one, want) == 1
    assert reference.mislabelled(got[:-1], want) == n


def test_the_default_is_ten_rounds_and_an_unknown_kind_is_refused():
    n, src, dst = random_graph(46, 300, 900)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp"})
        assert env["status"] == "done" and \
            env["result"]["iterations"] == 10
        with pytest.raises(ValueError, match="cdlp"):
            served.sched.submit(JobSpec(kind="triangles"))
    finally:
        served.close()


def test_the_jobs_spans_and_counters():
    n, src, dst = random_graph(47, 600, 2000)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": 4})
        assert env["status"] == "done", env
        from titan_tpu.obs import devprof
        devprof.drain()
        spans = list(served.sched.tracer.spans(env["job"]))
        m = served.metrics
        text = served.get("/metrics")[1].decode()
    finally:
        served.close()
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    (run,) = by_name["run"]
    rounds = by_name["cdlp.round"]
    (result,) = by_name["cdlp.result"]
    (count,) = by_name["cdlp.count"]
    assert [s.attrs["it"] for s in rounds] == [1, 2, 3, 4]
    assert {s.attrs["impl"] for s in rounds} == {"xla"}
    leaves = rounds + [result, count]
    assert all(s.parent_id == run.span_id for s in leaves)
    ordered = sorted(leaves, key=lambda s: s.t_start)
    assert [s.name for s in ordered] == \
        ["cdlp.round"] * 4 + ["cdlp.result", "cdlp.count"]
    assert all(a.t_end <= b.t_start for a, b in zip(ordered, ordered[1:]))
    assert result.attrs["bytes"] == 4 * n and result.attrs["sync_ms"] >= 0
    assert len(by_name["job.lease"]) == len(by_name["job.admit"]) == 1
    # a round's three programs, each a kernel span under its round
    kernels = by_name["kernel"]
    round_ids = {s.span_id for s in rounds}
    keys = [s.attrs["key"] for s in sorted(kernels,
                                           key=lambda s: s.t_start)]
    assert sorted(keys) == sorted(
        ["cdlp_gather", "cdlp_sort", "cdlp_vote"] * 4)
    assert all(s.parent_id in round_ids for s in kernels)
    assert {s.attrs.get("impl") for s in kernels
            if s.attrs["key"] == "cdlp_gather"} == {"xla"}
    # the sort's span says what it sorted: one small row of 1,024
    # columns here, no wide one, one word a lane, and how much is pad
    im = C.cdlp_image(snap_mod.from_arrays(n, src, dst))
    assert im["classes"] == ((1, 1024), (0, 1024)) and im["lanes"] == 8192
    sorts = [s.attrs for s in kernels if s.attrs["key"] == "cdlp_sort"]
    assert {(a["rows"], a["width"], a["keys"], a["label_bits"],
             a["pad_share"]) for a in sorts} \
        == {("1+0", "1024+1024", 1, 10, f"{1 - len(src) / 8192:.4f}")}
    assert m.counter_value("device.cdlp.rounds") == 4
    assert m.counter("device.cdlp.lanes",
                     labels={"impl": "xla"}).count == 4 * 8192
    assert m.counter("device.cdlp.lanes",
                     labels={"impl": "vmem"}).count == 0
    by_form = {(c, k): m.counter("device.cdlp.sort_lanes", labels={
        "class": c, "keys": k}).count
        for c in ("small", "wide") for k in ("1", "2")}
    assert by_form == {("small", "1"): 4 * 8192, ("wide", "1"): 0,
                       ("small", "2"): 0, ("wide", "2"): 0}
    assert m.counter("device.xfer.h2d_bytes",
                     labels={"site": "cdlp.image"}).count \
        == C.image_bytes(n, 8192) == 8192 * 8 + 5 * n
    assert m.counter("device.xfer.d2h_bytes",
                     labels={"site": "cdlp.result"}).count == 4 * n
    for key in ("cdlp_gather", "cdlp_sort", "cdlp_vote"):
        assert m.counter("device.exec.unstamped",
                         labels={"kernel": key}).count == 0
    assert "device_cdlp_rounds" in text.replace(".", "_")


def test_timeout_and_cancel_at_a_rounds_boundary():
    n, src, dst = random_graph(48, 400, 1200)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics)
    try:
        late = sched.submit(JobSpec(kind="cdlp", timeout_s=0.0,
                                    params={"iterations": 5}))
        assert late.wait(120) and late.state.value == "timeout", \
            (late.state, late.error)
        assert late.last_round == 0         # before its first round
        # a cancel that arrives while the job runs takes effect before
        # the next round: the job's own hook asks for it after round 2
        real = C.cdlp

        def cancelling(snap, **kw):
            on_round = kw["on_round"]

            def hook(it):
                if it == 2:
                    sched.cancel(job.id)
                return on_round(it)
            return real(snap, **dict(kw, on_round=hook))
        C.cdlp = cancelling
        try:
            job = sched.submit(JobSpec(kind="cdlp",
                                       params={"iterations": 8}))
            assert job.wait(120)
        finally:
            C.cdlp = real
        assert job.state.value == "cancelled", (job.state, job.error)
        assert job.last_round == 2 and job.result is None
        assert metrics.counter_value("device.cdlp.rounds") == 2
    finally:
        sched.close()


def test_a_crashed_job_resumes_from_its_checkpoint_bit_equal(tmp_path):
    from titan_tpu.olap.recovery import FaultPlan

    n, src, dst = random_graph(49, 500, 1500)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap_mod.from_arrays(n, src, dst),
                         metrics=metrics, checkpoint_dir=str(tmp_path))
    try:
        straight = sched.submit(JobSpec(kind="cdlp",
                                        params={"iterations": 7}))
        crashed = sched.submit(JobSpec(
            kind="cdlp", max_retries=1, checkpoint_every=1,
            params={"iterations": 7,
                    "faults": FaultPlan(crash_at_round=3)}))
        assert straight.wait(120) and crashed.wait(120)
    finally:
        sched.close()
    assert straight.state.value == crashed.state.value == "done", \
        (straight.error, crashed.error)
    assert crashed.attempt == 2
    assert crashed.result["labels"].tobytes() == \
        straight.result["labels"].tobytes()
    assert crashed.result["iterations"] == 7
    # rounds 1-3 before the crash, 4-7 behind the checkpoint, 7 straight
    assert metrics.counter_value("device.cdlp.rounds") == 3 + 4 + 7
    assert metrics.counter_value("serving.recovery.resumes") == 1


def test_admission_reserves_the_working_set_and_lets_it_go():
    n, src, dst = random_graph(50, 500, 1500)
    snap = snap_mod.from_arrays(n, src, dst)
    # the forward image and the job's own: it reads no pull image
    images = snapshot_csr_bytes(snap) + snapshot_cdlp_image_bytes(snap)
    work = snapshot_cdlp_bytes(snap)
    lanes = C.image_lanes(snap)
    assert lanes == C.cdlp_image(snap)["lanes"] == 8192
    assert snapshot_cdlp_image_bytes(snap) == C.image_bytes(n, lanes) \
        == 2 * 4 * lanes + 5 * n
    assert work == C.work_bytes(n, lanes) > 7 * (lanes * 4)
    served = Served(n, src, dst)
    try:
        env = served.job({"kind": "cdlp", "iterations": 2})
        assert env["status"] == "done", env
        (admit,) = [s for s in served.sched.tracer.spans(env["job"])
                    if s.name == "job.admit"]
        ledger = served.sched.ledger
    finally:
        served.close()
    assert admit.attrs["bytes"] == images + work
    assert ledger.resident_bytes() == images    # the working set left
    assert ledger.pinned_bytes() == 0
    # a budget that holds both images but not the rounds' working set
    # beside them refuses the job and leaves nothing pinned, where a
    # PageRank job, whose two images are smaller, is admitted
    assert snapshot_pull_bytes(snap) < snapshot_cdlp_image_bytes(snap)
    served = Served(n, src, dst, hbm_budget_bytes=images + work - 1)
    try:
        env = served.job({"kind": "cdlp", "iterations": 2})
        assert env["status"] == "failed"
        assert "admission" in env["error"]
        assert served.sched.ledger.pinned_bytes() == 0
        ok = served.job({"kind": "pagerank", "iterations": 2})
        assert ok["status"] == "done", ok
    finally:
        served.close()


def test_a_second_tenants_image_leaves_no_room():
    """A pinned image of another tenant beside it: the job is refused at
    admission, not run into the device's memory."""
    n, src, dst = random_graph(51, 500, 1500)
    snap = snap_mod.from_arrays(n, src, dst)
    need = snapshot_csr_bytes(snap) + snapshot_cdlp_image_bytes(snap) \
        + snapshot_cdlp_bytes(snap)
    served = Served(n, src, dst, hbm_budget_bytes=need + 1000)
    try:
        served.sched.ledger.reserve("another-tenant", 2000)    # pinned
        env = served.job({"kind": "cdlp", "iterations": 1})
        assert env["status"] == "failed" and "admission" in env["error"]
        served.sched.ledger.release("another-tenant")
        env = served.job({"kind": "cdlp", "iterations": 1})
        assert env["status"] == "done", env
    finally:
        served.close()
