"""Admission prices a snapshot's images from integers kept on the
snapshot (ISSUE 41, ``olap/serving/hbm.py``): the column counts of the
forward and the reversed chunked layout and (ISSUE 44) the lanes of
CDLP's row image, one pass over a degree array each, once a snapshot. They equal the formulas computed afresh and the
counts the built images carry; every byte function returns what it
returned when it read the degree arrays a call; whatever changes the
arrays drops the kept counts with the layouts; and a scheduler's jobs
and the lane's batches pay a pass on a snapshot's first admission and
none after (``serving.hbm.sizing_passes``, ``sizing_passes`` on the
``job.admit`` span and the lane's ``admit`` phase).
"""

import numpy as np
import pytest

import titan_tpu
from titan_tpu.models import cdlp as C
from titan_tpu.models import pagerank_pull as pp
from titan_tpu.models.bfs_hybrid import build_chunked_csr
from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving import hbm
from titan_tpu.olap.serving.interactive import PPRPlan, plan_from_wire
from titan_tpu.olap.serving.interactive.compile import reversed_chunked_csr
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager

PASSES = hbm.SIZING_PASSES
IMAGES = ("out", "in", "cdlp")


def passes(metrics) -> tuple:
    """``(out, in, cdlp)`` passes counted on ``metrics`` so far."""
    return tuple(metrics.counter_value(PASSES, {"image": image})
                 for image in IMAGES)


def columns_afresh(deg) -> int:
    """The formula admission computed a call: sum(ceil(deg/8)) + 1."""
    return int((-(-np.asarray(deg).astype("int64") // 8)).sum()) + 1


def lanes_afresh(deg, n: int) -> int:
    """Lanes of CDLP's row image, packed a vertex at a time: whole
    vertices by decreasing column count, next-fit for those of two
    columns and more, the one-column vertices into what is free and then
    into rows of their own; a class a whole number of 1,024-lane blocks
    (the toys' keys have bits to spare: one word a lane)."""
    cols = sorted((int(-(-d // 8)) for d in deg if d), reverse=True)
    bits = 32 - (n + 1).bit_length()
    small = min(1024, 1 << bits)
    wide = max(small, 1 << max(cols[0] - 1, 0).bit_length()) if cols \
        else small
    assert wide // small <= 1 << bits
    lanes = 0
    for width, mine, least in (
            (small, [c for c in cols if c <= small], 1),
            (wide, [c for c in cols if c > small], 0)):
        free = []
        for c in mine:
            if c > 1:
                if not free or free[-1] < c:
                    free.append(width)
                free[-1] -= c
        left = max(mine.count(1) - sum(free), 0)
        rows = max(len(free) + -(-left // width), least)
        whole = max(1, 1024 // (8 * width))
        lanes += -(-rows // whole) * whole * 8 * width
    return lanes


def bytes_afresh(snap, num_devices: int = 8) -> dict:
    """Every byte function as it was written before the counts were
    kept: each from a pass over its degree array."""
    n = snap.n
    q_out = columns_afresh(snap.out_degree)
    q_rev = columns_afresh(np.diff(snap.indptr_in))
    q_pull = pp.pull_columns(snap.indptr_in, n)
    lanes = lanes_afresh(np.diff(snap.indptr_in), n)
    csr = q_out * 8 * 4 + 3 * 4 * (n + 1)
    vert = 3 * 4 * (n + 1)
    return {
        "csr": csr,
        "rev": q_rev * 8 * 4 + 3 * 4 * (n + 1),
        "pull": pp.pull_image_bytes(n, q_pull),
        "cdlp_image": lanes * 8 + 5 * n,
        "cdlp": C.work_bytes(n, lanes),
        "meshed": int(vert + -(-(csr - vert) // num_devices)),
    }


def bytes_kept(snap, num_devices: int = 8) -> dict:
    return {
        "csr": hbm.snapshot_csr_bytes(snap),
        "rev": hbm.snapshot_rev_csr_bytes(snap),
        "pull": hbm.snapshot_pull_bytes(snap),
        "cdlp_image": hbm.snapshot_cdlp_image_bytes(snap),
        "cdlp": hbm.snapshot_cdlp_bytes(snap),
        "meshed": hbm.meshed_snapshot_csr_bytes(snap, num_devices),
    }


def toy(seed: int, directed: bool, n: int = 300, m: int = 900):
    """A random graph whose out- and in-degrees lie on both sides of a
    multiple of 8: vertices 0..15 get exactly 0..15 out-edges to, and
    16..31 exactly 0..15 in-edges from, the upper half; the top tenth of
    the vertices stays isolated."""
    rng = np.random.default_rng(seed)
    live = n - n // 10
    src = [rng.integers(32, live, m)]
    dst = [rng.integers(32, live, m)]
    for k in range(16):
        far = rng.choice(np.arange(live // 2, live), k, replace=False)
        src += [np.full(k, k), far]
        dst += [far, np.full(k, 16 + k)]
    src = np.concatenate(src).astype(np.int32)
    dst = np.concatenate(dst).astype(np.int32)
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    return n, src, dst


# ---------------------------------------------- (a) the counts and the bytes

@pytest.mark.parametrize("built_first", [False, True],
                         ids=["priced-first", "built-first"])
@pytest.mark.parametrize("directed", [True, False],
                         ids=["directed", "undirected"])
@pytest.mark.parametrize("seed", [41, 3000000019])
def test_kept_counts_equal_the_formulas_and_the_images(seed, directed,
                                                       built_first):
    n, src, dst = toy(seed, directed)
    snap = snap_mod.from_arrays(n, src, dst)
    deg_in = np.diff(snap.indptr_in)
    assert (snap.out_degree == 0).any() and (deg_in == 0).any()
    for deg in (snap.out_degree, deg_in):
        assert {7, 8, 9} <= set(deg.tolist())
    want = bytes_afresh(snap)
    metrics = MetricManager()
    if built_first:
        # an image already built is asked for its own count: no pass
        g, rev = build_chunked_csr(snap), reversed_chunked_csr(snap)
        rows = C.cdlp_image(snap)
        assert hbm.price(snap, IMAGES, metrics) == 0
        assert passes(metrics) == (0, 0, 0)
    else:
        # admission prices BEFORE any build: a pass an image, once
        assert hbm.price(snap, IMAGES, metrics) == 3
        assert not hasattr(snap, "_hybrid_csr")
        assert not hasattr(snap, "_cdlp_csr")
        assert passes(metrics) == (1, 1, 1)
        g, rev = build_chunked_csr(snap), reversed_chunked_csr(snap)
        rows = C.cdlp_image(snap)
    assert snap._q_out == columns_afresh(snap.out_degree) == g["q_total"]
    assert snap._q_in == columns_afresh(deg_in) == rev["q_total"]
    assert snap._cdlp_lanes == lanes_afresh(deg_in, n) == rows["lanes"] \
        == rows["idx"].shape[0] == rows["key_hi"].shape[0]
    # the plan the pricing made is the build's, and goes with it
    assert not hasattr(snap, "_cdlp_plan")
    assert pp.pull_image(snap)["q_in"] \
        == pp.pull_columns(snap.indptr_in, n) \
        == hbm._pull_columns(snap)
    assert bytes_kept(snap) == want
    # priced: no later call reads a degree array
    assert hbm.price(snap, IMAGES, metrics) == 0
    assert passes(metrics) == ((0, 0, 0) if built_first else (1, 1, 1))


def test_a_pass_with_no_registry_counts_on_the_process_wide_one():
    n, src, dst = toy(7, True)
    snap = snap_mod.from_arrays(n, src, dst)
    before = passes(MetricManager.instance())
    assert hbm.snapshot_csr_bytes(snap) == bytes_afresh(snap)["csr"]
    assert hbm.snapshot_csr_bytes(snap) == bytes_afresh(snap)["csr"]
    after = passes(MetricManager.instance())
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)


def test_threads_that_price_one_snapshot_at_once_agree():
    """The kept count is written unlocked: every thread that finds none
    runs its own pass, counts it and keeps the same integer."""
    import sys
    import threading

    n, src, dst = toy(11, False, n=20000, m=200000)
    want = bytes_afresh(snap_mod.from_arrays(n, src, dst))
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            snap = snap_mod.from_arrays(n, src, dst)
            metrics = MetricManager()
            barrier = threading.Barrier(16)
            got = []

            def go():
                barrier.wait(30)
                paid = hbm.price(snap, IMAGES, metrics)
                got.append((paid, bytes_kept(snap)))

            threads = [threading.Thread(target=go) for _ in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            assert [b for _paid, b in got] == [want] * 16
            paid = sum(p for p, _b in got)
            assert 3 <= paid <= 48 and sum(passes(metrics)) == paid
            assert hbm.price(snap, IMAGES, metrics) == 0
    finally:
        sys.setswitchinterval(interval)


# ----------------------------------------------------- (b) what re-prices

@pytest.fixture
def graph():
    g = titan_tpu.open("inmemory")
    tx = g.new_transaction()
    vs = [tx.add_vertex("node", name=f"v{i:02d}") for i in range(24)]
    for a in range(20):
        vs[a].add_edge("link", vs[(a + 1) % 20])
    for b in range(1, 8):                   # v00: 8 out-edges, on the edge
        vs[0].add_edge("link", vs[b + 8])   # of a column
    tx.commit()
    yield g
    g.close()


def _named(tx):
    return sorted(tx.vertices(), key=lambda v: v.value("name"))


def _add_edges(g):
    """Edge-only adds: ``apply_changes``' in-place road
    (``np.add.at(self.out_degree, ...)``); v00's ninth out-edge opens a
    new column."""
    tx = g.new_transaction()
    vs = _named(tx)
    vs[0].add_edge("link", vs[21])
    vs[5].add_edge("link", vs[22])
    tx.commit()


def _add_and_remove_vertices(g):
    """The vertex set changes: the rebuild road."""
    tx = g.new_transaction()
    vs = _named(tx)
    w = tx.add_vertex("node", name="v98")
    tx.add_vertex("node", name="v99")
    for k in range(9):
        vs[k].add_edge("link", w)
    vs[3].remove()
    tx.commit()


@pytest.mark.parametrize("directed", [True, False],
                         ids=["directed", "undirected"])
@pytest.mark.parametrize("road", ["edge-adds", "vertex-add-remove",
                                  "rebuild-in-place"])
def test_a_changed_snapshot_is_priced_again(graph, road, directed):
    snap = snap_mod.build(graph, directed=directed)
    metrics = MetricManager()
    assert hbm.price(snap, IMAGES, metrics) == 3
    before = bytes_kept(snap)
    assert before == bytes_afresh(snap)
    if road == "edge-adds":
        _add_edges(graph)
        out_degree = snap.out_degree
        stats = snap.refresh()
        assert snap.out_degree is out_degree            # added to in place
        assert stats["added_vertices"] == stats["removed_vertices"] == 0
    elif road == "vertex-add-remove":
        _add_and_remove_vertices(graph)
        stats = snap.refresh()
        assert stats["added_vertices"] == 2
        assert stats["removed_vertices"] == 1
    else:
        _add_edges(graph)
        snap.rebuild_in_place()
    assert not any(hasattr(snap, kept) for kept in
                   ("_q_out", "_q_in", "_cdlp_lanes", "_cdlp_plan"))
    fresh = snap_mod.build(graph, directed=directed)
    assert hbm.price(snap, IMAGES, metrics) == 3
    assert passes(metrics) == (2, 2, 2)
    assert bytes_kept(snap) == bytes_afresh(fresh) == bytes_kept(fresh)
    assert bytes_kept(snap) != before
    assert hbm.price(snap, IMAGES, metrics) == 0


def test_apply_changes_with_nothing_to_apply_keeps_the_price(graph):
    """A property mutation changes no degree: the counts stay."""
    snap = snap_mod.build(graph)
    metrics = MetricManager()
    hbm.price(snap, IMAGES, metrics)
    tx = graph.new_transaction()
    _named(tx)[2].property("name", "v02")
    tx.commit()
    snap.refresh()
    assert hbm.price(snap, IMAGES, metrics) == 0
    assert bytes_kept(snap) == bytes_afresh(snap)


# --------------------------------------------- (c) through the job scheduler

def _admit(sched, job) -> dict:
    (span,) = [s for s in sched.tracer.spans(job.id)
               if s.name == "job.admit"]
    return span.attrs


def _run(sched, kind: str, **params):
    job = sched.submit(JobSpec(kind=kind, params=params))
    assert job.wait(120) and job.state.value == "done", \
        (kind, job.state, job.error)
    return _admit(sched, job)


def test_jobs_on_a_priced_snapshot_pay_no_pass(graph):
    metrics = MetricManager()
    sched = JobScheduler(graph=graph, metrics=metrics)
    try:
        seen = {}
        for kind, params in (("wcc", {}), ("wcc", {}),
                             ("pagerank", {"iterations": 2}),
                             ("cdlp", {"iterations": 2}),
                             ("wcc", {}),
                             ("pagerank", {"iterations": 2}),
                             ("cdlp", {"iterations": 2})):
            attrs = _run(sched, kind, **params)
            # the snapshot's first job of all prices the forward image,
            # its first job that pulls the reversed one, its first cdlp
            # job the rows of its own image; nobody else
            first = 0 if kind in seen else 1
            assert attrs["sizing_passes"] == first, (kind, attrs)
            assert attrs["bytes"] == seen.setdefault(kind, attrs["bytes"])
        assert passes(metrics) == (1, 1, 1)
        # a mutation between two jobs: the pool refreshes the snapshot
        # in place, the next job pays exactly what it needs
        _add_edges(graph)
        attrs = _run(sched, "wcc")
        assert attrs["sizing_passes"] == 1 and passes(metrics) == (2, 1, 1)
        assert attrs["bytes"] != seen["wcc"]
        assert _run(sched, "wcc")["sizing_passes"] == 0
        # a cdlp job does not pull: the reversed count is pagerank's to pay
        attrs = _run(sched, "cdlp", iterations=2)
        assert attrs["sizing_passes"] == 1 and passes(metrics) == (2, 1, 2)
        assert _run(sched, "pagerank", iterations=2)["sizing_passes"] == 1
        assert _run(sched, "cdlp", iterations=2)["sizing_passes"] == 0
        assert passes(metrics) == (2, 2, 2)
    finally:
        sched.close()


def test_job_bytes_are_the_sums_of_the_byte_functions():
    n, src, dst = toy(43, False)
    snap = snap_mod.from_arrays(n, src, dst)
    want = bytes_afresh(snap)
    sched = JobScheduler(snapshot=snap, metrics=MetricManager())
    try:
        assert _run(sched, "wcc")["bytes"] == want["csr"]
        assert _run(sched, "pagerank", iterations=2)["bytes"] \
            == want["csr"] + want["pull"]
        # its own image and the rounds' working set, no pull image
        assert _run(sched, "cdlp", iterations=2)["bytes"] \
            == want["csr"] + want["cdlp_image"] + want["cdlp"]
    finally:
        sched.close()


# ----------------------------------------------------- (d) through the lane

def _lane_admit(sched, res) -> dict:
    (span,) = [s for s in sched.tracer.spans(res["batch"])
               if s.name == "admit"]
    return span.attrs


def test_lane_batches_on_a_priced_snapshot_pay_no_pass(graph):
    metrics = MetricManager()
    sched = JobScheduler(graph=graph, metrics=metrics, autostart=False,
                         interactive_window_s=0.002)
    try:
        lane = sched.interactive()
        tx = graph.new_transaction()
        ids = [v.id for v in _named(tx)]
        tx.rollback()

        def traverse(dirname):
            return _lane_admit(sched, lane.submit(plan_from_wire(
                {"start": [ids[0]], "dir": dirname, "hops": 2,
                 "terminal": "count"})))

        # both() leases the symmetrized snapshot, out() and in() the
        # directed one: two snapshots, each priced an image at a time
        first = traverse("both")
        assert first["sizing_passes"] == 1 and passes(metrics) == (1, 0, 0)
        again = traverse("both")
        assert again["sizing_passes"] == 0
        assert again["nbytes"] == first["nbytes"]
        out = traverse("out")
        assert out["sizing_passes"] == 1 and passes(metrics) == (1, 1, 0)
        assert traverse("out") == dict(out, sizing_passes=0)
        into = traverse("in")
        assert into["sizing_passes"] == 1 and passes(metrics) == (2, 1, 0)
        assert traverse("in")["sizing_passes"] == 0
        # ppr reads the symmetrized snapshot's forward image: priced
        res = lane.submit(PPRPlan(source=ids[1], iterations=3, top_k=3))
        assert res["iterations"] == 3
        assert passes(metrics) == (2, 1, 0)
    finally:
        sched.close()


def test_the_lanes_first_ppr_batch_prices_its_snapshot(graph):
    metrics = MetricManager()
    sched = JobScheduler(graph=graph, metrics=metrics, autostart=False,
                         interactive_window_s=0.002)
    try:
        lane = sched.interactive()
        tx = graph.new_transaction()
        ids = [v.id for v in _named(tx)]
        tx.rollback()
        for _ in range(2):
            lane.submit(PPRPlan(source=ids[1], iterations=3, top_k=3))
            assert passes(metrics) == (1, 0, 0)
    finally:
        sched.close()
