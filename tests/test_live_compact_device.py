"""Device-side epoch compaction (ISSUE 9, ops/epoch_merge +
olap/live/compactor device path).

The contract under test: the device-merged next-epoch chunked CSR is
BIT-EQUAL to the host oracle (``EpochCompactor.merge`` + ``from_arrays``
+ ``build_chunked_csr`` — one global stable sort) across adds-only /
tombstones-only / mixed / labeled shapes; the host-durable snapshot
synced from delta pages (``snapshot.merge_delta``) is bit-equal to the
oracle's arrays; epochs double-buffer through the HBM ledger; and every
way the device path cannot run degrades LOUDLY to the host oracle
(fallback reason recorded, ``serving.live.device_merge_fallbacks``
bumped).

No kernel dispatches here beyond the eager merge ops — the suite pins
arrays, not BFS results (array equality is strictly stronger), so it
adds no XLA compile buckets to tier-1.
"""

import numpy as np
import pytest

import titan_tpu
from titan_tpu.models.bfs_hybrid import build_chunked_csr
from titan_tpu.olap.live.compactor import EpochCompactor
from titan_tpu.olap.live.overlay import DeltaOverlay
from titan_tpu.olap.serving.hbm import HBMLedger, snapshot_csr_bytes
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.ops import epoch_merge
from titan_tpu.utils.metrics import MetricManager

#: the repo-shared test shape (see tests/test_serving.py)
N, M, SEED = 192, 900, 42


def _base(seed=SEED, labeled=False, n=N, m=M):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    labs = rng.integers(0, 3, m).astype(np.int32) if labeled else None
    return snap_mod.from_arrays(n, src, dst, labels=labs), src, dst, \
        labs, rng


def _mutate(snap, src, dst, labs, rng, adds, removes, kill_add=False):
    ov = DeltaOverlay(snap, min_cap=64)
    a = None
    if adds:
        a = (rng.integers(0, snap.n, adds).astype(np.int32),
             rng.integers(0, snap.n, adds).astype(np.int32),
             rng.integers(0, 3, adds).astype(np.int32))
        ov.append_edges(*a)
    for i in rng.choice(len(src), removes, replace=False):
        ov.remove_edge(int(src[i]), int(dst[i]),
                       int(labs[i]) if labs is not None else None)
    if kill_add and adds > 4:
        # dead-add path: an appended row later tombstoned in place
        assert ov.remove_edge(int(a[0][2]), int(a[1][2]),
                              int(a[2][2]))
    return ov


def _assert_csr_equal(got, want):
    assert got["q_total"] == want["q_total"]
    for k in ("dstT", "colstart", "degc", "deg"):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.shape == b.shape, k
        assert (a == b).all(), k


@pytest.mark.parametrize("adds,removes,labeled,kill", [
    (120, 40, False, True),    # mixed + dead add
    (120, 40, True, True),     # labeled mixed
    (120, 0, False, False),    # adds only
    (0, 60, True, False),      # tombstones only
    (300, 10, False, False),   # adds dominate (cap growth)
])
@pytest.mark.parametrize("seed", [1, SEED])
def test_device_merge_bit_equal_to_host_oracle(seed, adds, removes,
                                               labeled, kill):
    snap, src, dst, labs, rng = _base(seed, labeled)
    ov = _mutate(snap, src, dst, labs, rng, adds, removes, kill)
    build_chunked_csr(snap)            # base CSR device-resident
    comp = EpochCompactor()
    merged, mode = comp.compact(snap, ov)
    assert mode == "device" and comp.last_mode == "device"
    assert comp.device_merges == 1 and not comp.fallbacks
    oracle = comp.merge(snap, ov)
    # 1) the published device CSR vs a fresh build of the oracle
    _assert_csr_equal(merged._hybrid_csr, build_chunked_csr(oracle))
    # 2) the delta-page host sync vs the oracle's full-sort arrays
    for attr in ("src", "dst", "indptr_in", "out_degree"):
        assert (getattr(merged, attr) == getattr(oracle, attr)).all(), \
            attr
    if labeled:
        assert (merged.labels == oracle.labels).all()
    else:
        assert merged.labels is None
    # 3) the lazy _host mirror (shard-slicing surface) vs the oracle's
    hm = merged._hybrid_csr["_host"]
    for k in ("dstT", "colstart", "degc"):
        assert (np.asarray(hm[k])
                == build_chunked_csr(oracle)["_host"][k]).all(), k


def test_merged_degrees_host_matches_device_layout():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 80, 30)
    deg, degc, colstart, q_new = epoch_merge.merged_degrees_host(
        snap, ov)
    oracle = build_chunked_csr(EpochCompactor().merge(snap, ov))
    assert q_new == oracle["q_total"]
    assert (deg == np.asarray(oracle["deg"])).all()
    assert (degc == np.asarray(oracle["degc"])).all()
    assert (colstart == np.asarray(oracle["colstart"])).all()


def test_carry_over_vertex_values_and_epoch():
    snap, src, dst, labs, rng = _base()
    snap.vertex_values["rank"] = ("vals", "present")
    snap.epoch = 7
    ov = _mutate(snap, src, dst, labs, rng, 20, 0)
    build_chunked_csr(snap)
    merged, mode = EpochCompactor().compact(snap, ov)
    assert mode == "device"
    assert merged.vertex_values == {"rank": ("vals", "present")}
    assert merged.epoch == 7


# -- loud degrades -----------------------------------------------------------

def test_ledger_too_small_degrades_loudly_to_host():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 50, 10)
    build_chunked_csr(snap)
    mm = MetricManager()
    # budget below ONE epoch image: the double-buffer reservation for
    # the next epoch must fail and the merge must still succeed (host)
    ledger = HBMLedger(budget_bytes=16)
    comp = EpochCompactor()
    merged, mode = comp.compact(snap, ov, ledger=ledger, metrics=mm)
    assert mode == "host" and comp.last_mode == "host"
    assert comp.fallbacks == {"ledger-full": 1}
    assert mm.counter_value("serving.live.device_merge_fallbacks") == 1
    # host path charges the full re-upload the next run must pay
    assert mm.counter_value("serving.live.upload_bytes") \
        == snapshot_csr_bytes(merged)
    oracle = comp.merge(snap, ov)
    assert (merged.dst == oracle.dst).all()
    assert not hasattr(merged, "_hybrid_csr")


def test_double_buffer_reserves_next_epoch_beside_current():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 50, 10)
    build_chunked_csr(snap)
    ledger = HBMLedger(budget_bytes=10e6)
    # the current epoch is ledger-resident the way a served image is
    ledger.reserve(id(snap), snapshot_csr_bytes(snap))
    ledger.unpin(id(snap))
    before = ledger.resident_bytes()
    merged, mode = EpochCompactor().compact(snap, ov, ledger=ledger)
    assert mode == "device"
    # both epochs resident (double-buffered) until the old one retires
    assert ledger.resident_bytes() > before
    ledger.release(id(snap))           # pool retire path
    assert ledger.resident_bytes() == snapshot_csr_bytes(merged)
    # admission asks a built image for its own column count (ISSUE 41):
    # the merged image's is the formula over the merged degrees
    assert merged._hybrid_csr["q_total"] \
        == int((-(-merged.out_degree.astype("int64") // 8)).sum()) + 1
    # the new entry is resident-but-evictable: a job's reserve pins it
    ledger.reserve(id(merged), snapshot_csr_bytes(merged))
    assert ledger.pinned_bytes() == snapshot_csr_bytes(merged)


def test_base_not_resident_falls_back():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 30, 0)
    assert getattr(snap, "_hybrid_csr", None) is None
    comp = EpochCompactor()
    merged, mode = comp.compact(snap, ov)
    assert mode == "host"
    assert comp.fallbacks == {"base-not-resident": 1}


def test_empty_base_falls_back():
    empty = snap_mod.from_arrays(
        8, np.zeros(0, np.int32), np.zeros(0, np.int32))
    build_chunked_csr(empty)
    ov = DeltaOverlay(empty, min_cap=64)
    ov.append_edges(np.array([0, 1], np.int32),
                    np.array([1, 2], np.int32),
                    np.zeros(2, np.int32))
    comp = EpochCompactor()
    merged, mode = comp.compact(empty, ov)
    assert mode == "host"
    assert comp.fallbacks == {"empty-base": 1}
    assert merged.num_edges == 2


def test_device_merge_disabled_is_not_a_fallback():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 30, 0)
    build_chunked_csr(snap)
    mm = MetricManager()
    comp = EpochCompactor(device_merge=False)
    _, mode = comp.compact(snap, ov, metrics=mm)
    assert mode == "host" and not comp.fallbacks
    assert mm.counter_value(
        "serving.live.device_merge_fallbacks") == 0


def test_verify_device_mode_charges_download_bytes():
    snap, src, dst, labs, rng = _base()
    ov = _mutate(snap, src, dst, labs, rng, 40, 10)
    build_chunked_csr(snap)
    mm = MetricManager()
    comp = EpochCompactor(verify_device=True)
    merged, mode = comp.compact(snap, ov, metrics=mm)
    assert mode == "device"
    got = mm.counter_value("serving.live.download_bytes")
    assert got == np.asarray(merged._hybrid_csr["dstT"]).nbytes


# -- overlay delta pages -----------------------------------------------------

def test_overlay_uploads_only_delta_pages():
    snap, src, dst, labs, rng = _base()
    mm = MetricManager()
    ov = DeltaOverlay(snap, min_cap=64, metrics=mm)
    k = "serving.live.upload_bytes"
    ov.view()
    # buffer establishment is a device-side fill: ZERO bytes H2D
    assert mm.counter_value(k) == 0
    ov.append_edges(np.array([1, 2, 3], np.int32),
                    np.array([4, 5, 6], np.int32),
                    np.zeros(3, np.int32))
    ov.view()
    # 2 int32 payloads + 1 int32 scatter index per shipped row
    assert mm.counter_value(k) == 12 * 3          # the 3-row tail
    # capacity growth pad-extends on device: only the new rows ship
    ov.append_edges(rng.integers(0, N, 100).astype(np.int32),
                    rng.integers(0, N, 100).astype(np.int32),
                    np.zeros(100, np.int32))
    v = ov.view()
    assert v.cap == 128
    assert mm.counter_value(k) == 12 * 103
    # a tombstone dirties single bitmap bytes (1 payload + 4 index
    # bytes each)
    assert ov.remove_edge(int(src[0]), int(dst[0]), None)
    ov.view()
    assert mm.counter_value(k) <= 12 * 103 + 2 * 5
    # an in-place kill below the watermark re-ships just that row
    before = mm.counter_value(k)
    assert ov.remove_edge(1, 4, None)
    v2 = ov.view()
    assert mm.counter_value(k) == before + 12
    # device mirrors stay exact after the scatter-only path
    assert (np.asarray(v2.src_dev) == ov._h_src).all()
    assert (np.asarray(v2.dst_dev) == ov._h_dst).all()
    assert (np.asarray(v2.tomb_dev) == ov._h_tomb).all()
    # frozen views are immutable: the pre-growth view kept its arrays
    assert v.src_dev.shape[0] == 128


# -- plane integration -------------------------------------------------------

@pytest.fixture
def graph():
    g = titan_tpu.open("inmemory")
    tx = g.new_transaction()
    vs = [tx.add_vertex("node", name=f"v{i:02d}") for i in range(10)]
    for a, b in [(0, 1), (1, 2), (2, 3), (3, 4), (4, 5), (5, 6)]:
        vs[a].add_edge("link", vs[b])
    tx.commit()
    yield g
    g.close()


def _commit_edge(g, i, j):
    tx = g.new_transaction()
    vv = sorted(tx.vertices(), key=lambda v: v.id)
    vv[i].add_edge("link", vv[j])
    tx.commit()


def test_plane_publishes_device_merged_epoch(graph):
    from titan_tpu.olap.live import LiveGraphPlane

    mm = MetricManager()
    plane = LiveGraphPlane(graph, metrics=mm, min_cap=4, max_fill=0.5)
    try:
        snap0, v0, _ = plane.lease_state()
        build_chunked_csr(snap0)       # base image device-resident
        _commit_edge(graph, 6, 7)
        _commit_edge(graph, 7, 8)
        snap1, v1, info = plane.lease_state()
        st = plane.stats()
        assert st["epoch"] == 1 and snap1 is not snap0
        assert st["compactor"]["merge_mode"] == "device"
        assert st["compactor"]["device_merges"] == 1
        assert st["counters"]["device_merge_fallbacks"] == 0
        # the new epoch arrives with its CSR pre-attached — the next
        # run re-uploads NOTHING
        assert getattr(snap1, "_hybrid_csr", None) is not None
        # and it is bit-equal to a from-scratch rebuild of the store
        rebuilt = snap_mod.build(graph, directed=False)
        _assert_csr_equal(snap1._hybrid_csr, build_chunked_csr(rebuilt))
        for attr in ("src", "dst", "indptr_in", "out_degree"):
            assert (getattr(snap1, attr)
                    == getattr(rebuilt, attr)).all(), attr
        # byte accounting: only delta pages went host→device
        up = st["counters"]["upload_bytes"]
        assert 0 < up < snapshot_csr_bytes(rebuilt)
        assert st["compact_device_ms"]["count"] == 1
    finally:
        plane.close()


def test_plane_policy_is_configuration_not_module_constants(graph):
    from titan_tpu.olap.live import LiveGraphPlane

    plane = LiveGraphPlane(graph, metrics=MetricManager(),
                           max_fill=0.25, max_tomb_fraction=0.125,
                           device_merge=False)
    try:
        pol = plane.stats()["compactor"]
        assert pol["max_fill"] == 0.25
        assert pol["max_tomb_fraction"] == 0.125
        assert pol["device_merge"] is False
        assert plane.compactor.max_fill == 0.25
    finally:
        plane.close()


def test_plane_host_mode_when_device_disabled(graph):
    from titan_tpu.olap.live import LiveGraphPlane

    mm = MetricManager()
    plane = LiveGraphPlane(graph, metrics=mm, min_cap=4, max_fill=0.5,
                           device_merge=False)
    try:
        snap0, _, _ = plane.lease_state()
        build_chunked_csr(snap0)
        _commit_edge(graph, 6, 7)
        _commit_edge(graph, 7, 8)
        snap1, _, _ = plane.lease_state()
        st = plane.stats()
        assert st["epoch"] == 1
        assert st["compactor"]["merge_mode"] == "host"
        # the host path leaves no device CSR and charges the full
        # re-upload to the byte counter
        assert getattr(snap1, "_hybrid_csr", None) is None
        assert st["counters"]["upload_bytes"] \
            >= snapshot_csr_bytes(snap1)
    finally:
        plane.close()


# -- incremental out-CSR across merge_delta (ISSUE 11 satellite, the
# ROADMAP #5 residual: the merged epoch's src-order argsort must not be
# re-paid by the next overlay's slot-lookup index) -----------------------

def _fresh_out_csr(merged):
    """From-scratch recompute on an identical uncached snapshot."""
    fresh = snap_mod.GraphSnapshot(
        merged.n, merged.vertex_ids, merged.src, merged.dst,
        merged.indptr_in, merged.out_degree, {}, merged.labels,
        dict(merged.label_names))
    dbs, ip = fresh.out_csr()
    return dbs, ip, fresh._out_csr_order


@pytest.mark.parametrize("seed", [3, SEED])
@pytest.mark.parametrize("adds,removes", [(0, 0), (40, 0), (0, 60),
                                          (50, 80)])
def test_merge_delta_out_csr_incremental_bit_equal(seed, adds,
                                                   removes):
    snap, src, dst, labs, rng = _base(seed=seed, labeled=True)
    snap.out_csr()                      # the overlay init's build
    ov = _mutate(snap, src, dst, labs, rng, adds, removes)
    a_src, a_dst, a_lab = ov.live_adds()
    merged = snap_mod.merge_delta(snap, ~ov.tomb_row_mask, a_src,
                                  a_dst, a_lab)
    assert getattr(merged, "_out_csr", None) is not None, \
        "merge_delta must carry the out-CSR cache incrementally"
    got_dbs, got_ip = merged._out_csr
    ref_dbs, ref_ip, ref_order = _fresh_out_csr(merged)
    assert np.array_equal(got_dbs, ref_dbs)
    assert np.array_equal(got_ip, ref_ip)
    assert np.array_equal(np.asarray(merged._out_csr_order, np.int64),
                          np.asarray(ref_order, np.int64))


def test_overlay_slot_index_reuses_snapshot_order():
    """The next epoch's DeltaOverlay reads the cached permutation (no
    argsort): identity, and removals through it still kill the right
    rows."""
    snap, src, dst, labs, rng = _base(labeled=True)
    ov0 = _mutate(snap, src, dst, labs, rng, 16, 8)
    a_src, a_dst, a_lab = ov0.live_adds()
    merged = snap_mod.merge_delta(snap, ~ov0.tomb_row_mask, a_src,
                                  a_dst, a_lab)
    ov1 = DeltaOverlay(merged, min_cap=64)
    assert ov1._base_order() is merged._out_csr_order
    # a removal resolved through the carried index tombstones a live
    # base row (merge_delta output really is dst-sorted + consistent)
    e = 5
    assert ov1.remove_edge(int(merged.src[e]), int(merged.dst[e]),
                           int(merged.labels[e]))
    assert ov1.tomb_row_mask[e] or ov1.tomb_row_mask.sum() == 1


def test_device_compaction_chain_keeps_out_csr_incremental():
    """EpochCompactor's device path publishes a merged snapshot whose
    out-CSR cache is pre-attached (and correct) — epoch N+1's overlay
    never re-sorts."""
    snap, src, dst, labs, rng = _base()
    build_chunked_csr(snap)
    ov = _mutate(snap, src, dst, labs, rng, 24, 12)
    comp = EpochCompactor()
    merged, mode = comp.compact(snap, ov)
    assert mode == "device"
    assert getattr(merged, "_out_csr", None) is not None
    got_dbs, got_ip = merged._out_csr
    ref_dbs, ref_ip, ref_order = _fresh_out_csr(merged)
    assert np.array_equal(got_dbs, ref_dbs)
    assert np.array_equal(got_ip, ref_ip)
    assert np.array_equal(np.asarray(merged._out_csr_order, np.int64),
                          np.asarray(ref_order, np.int64))
