"""CSR snapshot: bulk-export the edgestore into dense device-ready arrays.

This is the seam the reference fills with ScanJob + StandardScannerExecutor
(reference: titan-core diskstorage/keycolumnvalue/scan/
StandardScannerExecutor.java:85-188 feeding FulgoraGraphComputer) — redesigned
for the TPU: instead of streaming rows through per-vertex Java callbacks, one
ordered scan decodes the adjacency into numpy arrays, vertices are densified
to [0, n) (key order is partition-major, so dense index ranges are exactly
the storage partitions), and edges are sorted by destination for pull-mode
segment reduction on the MXU-adjacent vector units.

The decode hot loop uses the C++ codec when built (native/), else a Python
loop (correct, slower — fine for OLTP-scale graphs; synthetic benchmarks
construct snapshots directly from arrays).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from titan_tpu import native
from titan_tpu.codec import relation_ids as rids
from titan_tpu.core.defs import Direction, RelationCategory
from titan_tpu.storage.api import SliceQuery


@dataclass
class GraphSnapshot:
    """Dense read-only graph image.

    Edges are stored dst-sorted (``dst`` ascending, the pull layout);
    ``indptr_in`` indexes them per destination. ``out_degree`` supports
    degree-normalized programs (PageRank).
    """

    n: int
    vertex_ids: np.ndarray          # [n] int64, original ids, ascending key order
    src: np.ndarray                 # [E] int32 dense indices, dst-sorted
    dst: np.ndarray                 # [E] int32 dense indices, ascending
    indptr_in: np.ndarray           # [n+1] int64
    out_degree: np.ndarray          # [n] int32
    edge_values: dict = field(default_factory=dict)  # name -> [E] array
    labels: Optional[np.ndarray] = None              # [E] int32 label codes
    label_names: dict = field(default_factory=dict)  # code -> label name
    # name -> (values object-array [n], present bool [n]) — dense vertex
    # property columns for the device-compiled traversal subset
    # (attach_vertex_values / olap_compile has()/values() steps)
    vertex_values: dict = field(default_factory=dict)
    # freshness contract (see refresh()): epoch is graph.mutation_epoch at
    # build/refresh time; build() subscribes an in-process change listener
    epoch: int = 0
    _graph: object = None
    _listener_token: int = 0
    _listener: Optional[list] = None
    _build_params: dict = field(default_factory=dict)

    @property
    def num_edges(self) -> int:
        return len(self.src)

    @property
    def stale(self) -> bool:
        """True when commits landed on the source graph after this
        snapshot's epoch (the reference never has this problem — its OLAP
        scans the LIVE store every run, StandardScannerExecutor.java:85-188;
        a build-once device snapshot needs the explicit contract)."""
        g = self._graph
        return g is not None and self.epoch < g.mutation_epoch

    def close(self) -> None:
        """Detach the change listener (stops delta accumulation)."""
        g = self._graph
        if g is not None and self._listener_token:
            g.unsubscribe_changes(self._listener_token)
            self._graph = None
            self._listener = None

    def refresh(self) -> dict:
        """Apply the commits since ``epoch`` to this snapshot IN MEMORY —
        no store re-scan. Pure edge additions take an O(delta + E) merge
        into the dst-sorted arrays; vertex additions/removals or edge
        removals rebuild the CSR from the patched in-memory edge list
        (still host-array work only). Device-layout caches (_out_csr,
        bfs_hybrid's chunked CSR) are invalidated. Returns stats.

        Only commits on THIS graph instance are seen (they are the only
        ones the in-process listener observes); cross-instance writers
        need a rebuild — or wire the durable trigger log into
        ``apply_changes`` via the LogProcessorFramework."""
        g = self._graph
        if g is None:
            raise RuntimeError("snapshot has no source graph "
                               "(built from_arrays or closed)")
        if self.edge_values:
            raise NotImplementedError(
                "refresh() with extracted edge_values: change payloads "
                "don't carry edge properties — rebuild the snapshot")
        q = self._listener
        if getattr(q, "overflowed", False):
            raise RuntimeError(
                "change backlog overflowed (>10k commits since the last "
                "refresh) — delta refresh is unsound; rebuild the "
                "snapshot")
        new_epoch = g.mutation_epoch
        # drain UP TO new_epoch only: a commit that bumped the epoch
        # we read has already queued its payload (push precedes bump,
        # under the commit lock), but a commit racing THIS refresh may
        # queue payloads with epoch > new_epoch — those must stay queued
        # for the next refresh, or its continuity check would find a
        # hole and force a spurious rebuild. Scan-then-slice, not
        # pop(0)-per-payload: against the 10k-commit backlog cap the
        # per-pop list shift made this drain O(backlog^2)
        cut = 0
        while cut < len(q) and (q[cut].get("epoch") is None
                                or q[cut]["epoch"] <= new_epoch):
            cut += 1
        pending = list(q[:cut])
        del q[:cut]
        # continuity: the payloads must cover exactly
        # (self.epoch, new_epoch] — a gap means commits this listener
        # never saw (e.g. they landed during build()'s store scan), and
        # applying around the hole would corrupt the CSR
        epochs = [p.get("epoch") for p in pending]
        covered = [e for e in epochs if e is not None
                   and self.epoch < e <= new_epoch]
        if len(covered) != new_epoch - self.epoch:
            raise RuntimeError(
                f"snapshot delta gap: epochs ({self.epoch}, {new_epoch}] "
                f"but only {len(covered)} payloads — commits landed "
                "concurrently with build()'s scan; rebuild the snapshot")
        stats = self.apply_changes(
            [p for p in pending
             if p.get("epoch") is None or p["epoch"] > self.epoch],
            g.schema, g.idm)
        self.epoch = new_epoch
        return stats

    def rebuild_in_place(self) -> None:
        """Full store re-scan adopted into THIS object: the recovery
        path when delta refresh is unsound (listener overflow, delta
        gap, extracted edge_values). The existing change queue is
        RE-ANCHORED at the rebuilt epoch — cleared, overflow flag
        reset, atomically with the scan's epoch verification — so
        later refresh()es take the delta path again instead of being
        forced into a rebuild forever (ISSUE r9 satellite). Callers
        must guarantee no live device run is reading the arrays (the
        SnapshotPool only takes this path with zero active leases)."""
        g = self._graph
        if g is None:
            raise RuntimeError("snapshot has no source graph "
                               "(built from_arrays or closed)")
        p = self._build_params or {}
        fresh = build(g, labels=p.get("labels"),
                      edge_keys=p.get("edge_keys", ()),
                      directed=p.get("directed", True),
                      _reuse_listener=(self._listener_token,
                                       self._listener))
        self.n = fresh.n
        self.vertex_ids = fresh.vertex_ids
        self.src, self.dst = fresh.src, fresh.dst
        self.indptr_in = fresh.indptr_in
        self.out_degree = fresh.out_degree
        self.edge_values = fresh.edge_values
        self.labels = fresh.labels
        self.label_names = fresh.label_names
        # the vertex set may have changed arbitrarily: every dense
        # column and derived device layout is invalid
        self.vertex_values.clear()
        self._invalidate_layout_caches()
        self.epoch = fresh.epoch
        # fresh shares our listener (reused, not subscribed) — detach it
        # so fresh's GC/close cannot unregister the queue we keep using
        fresh._graph = None
        fresh._listener = None
        fresh._listener_token = 0

    def apply_changes(self, payloads: list, schema, idm) -> dict:
        """Apply change payloads (core/changes.change_payload dicts — from
        the in-process listener or deserialized from the user trigger
        log) to the in-memory CSR."""
        params = self._build_params or {}
        label_ids = params.get("label_ids")
        directed = params.get("directed", True)
        add_src: list = []
        add_dst: list = []
        add_lab: list = []
        removed_edges: list = []
        new_vids: set = set()
        dead_vids: set = set()
        prop_keys: set = set()
        for p in payloads:
            for r in (*p.get("added", ()), *p.get("removed", ())):
                if "in" not in r:          # property mutation
                    prop_keys.add(r.get("type"))
            for vid in p.get("added_vertices", ()):
                new_vids.add(idm.canonical_vertex_id(vid))
            for vid in p.get("removed_vertices", ()):
                dead_vids.add(idm.canonical_vertex_id(vid))
            for r in p.get("added", ()):
                if "in" not in r:
                    continue                      # property, not an edge
                st = schema.get_by_name(r["type"])
                if st is None or (label_ids is not None
                                  and st.id not in label_ids):
                    continue
                add_src.append(idm.canonical_vertex_id(r["out"]))
                add_dst.append(idm.canonical_vertex_id(r["in"]))
                add_lab.append(idm.count(st.id))
                self.label_names.setdefault(idm.count(st.id), st.name)
            for r in p.get("removed", ()):
                if "in" not in r:
                    continue
                st = schema.get_by_name(r["type"])
                if st is None:
                    continue
                removed_edges.append(
                    (idm.canonical_vertex_id(r["out"]),
                     idm.canonical_vertex_id(r["in"]), idm.count(st.id)))
        new_vids -= set(self.vertex_ids.tolist())
        stats = {"added_edges": len(add_src),
                 "removed_edges": len(removed_edges),
                 "added_vertices": len(new_vids),
                 "removed_vertices": len(dead_vids)}
        # property mutations invalidate the dense vertex-property
        # columns even when no edge/vertex changed (a stale column would
        # silently mis-answer compiled has()/values() — pinned by
        # tests/test_olap_compile.py)
        for k in prop_keys:
            self.vertex_values.pop(k, None)
        if not (add_src or removed_edges or new_vids or dead_vids):
            return stats

        self._invalidate_layout_caches()
        need_rebuild = bool(removed_edges or new_vids or dead_vids)
        if need_rebuild:
            # the vertex SET changes: every dense property column's
            # length/alignment is invalidated (edge-only merges keep
            # them — property mutations were already handled above)
            self.vertex_values.clear()
        if not need_rebuild:
            self._merge_edges(np.asarray(add_src, np.int64),
                              np.asarray(add_dst, np.int64),
                              np.asarray(add_lab, np.int32), directed)
            return stats

        # general path: patch the edge list in memory, re-densify, rebuild
        old_ids = self.vertex_ids
        src_ids = old_ids[self.src.astype(np.int64)]
        dst_ids = old_ids[self.dst.astype(np.int64)]
        labs = self.labels if self.labels is not None \
            else np.zeros(len(src_ids), np.int32)
        keep = np.ones(len(src_ids), bool)
        if removed_edges:
            # drop ONE row per removed relation per direction (parallel
            # edges are distinct relations, each contributing one row
            # [+reverse]). Undirected snapshots hold BOTH rows of every
            # relation, so each removal is seeded under both keys —
            # matching one forward AND one reverse row (the old
            # rkey-fallback matched only whichever row scanned first,
            # leaving the mirror row behind and silently
            # de-symmetrizing the CSR)
            from collections import Counter
            want = Counter(removed_edges)
            if not directed:
                want.update((d, s, lb) for s, d, lb in removed_edges)
            for i in range(len(src_ids)):
                key = (int(src_ids[i]), int(dst_ids[i]), int(labs[i]))
                if want.get(key, 0) > 0:
                    want[key] -= 1
                    keep[i] = False
        if dead_vids:
            dead = np.asarray(sorted(dead_vids), np.int64)
            keep &= ~np.isin(src_ids, dead) & ~np.isin(dst_ids, dead)
        src_ids, dst_ids, labs = src_ids[keep], dst_ids[keep], labs[keep]
        if add_src:
            a_s = np.asarray(add_src, np.int64)
            a_d = np.asarray(add_dst, np.int64)
            a_l = np.asarray(add_lab, np.int32)
            if not directed:
                a_s, a_d = (np.concatenate([a_s, a_d]),
                            np.concatenate([a_d, a_s]))
                a_l = np.concatenate([a_l, a_l])
            src_ids = np.concatenate([src_ids, a_s])
            dst_ids = np.concatenate([dst_ids, a_d])
            labs = np.concatenate([labs, a_l])
        ids = np.asarray(sorted((set(old_ids.tolist()) | new_vids)
                                - dead_vids), np.int64)
        si = np.clip(np.searchsorted(ids, src_ids), 0, max(len(ids) - 1, 0))
        di = np.clip(np.searchsorted(ids, dst_ids), 0, max(len(ids) - 1, 0))
        # drop rows whose endpoint is not a live vertex (an added edge
        # can reference a vertex a LATER pending commit removed, or a
        # ghost id): exactly build()'s endpoint validation
        ok = np.ones(len(src_ids), bool)
        if len(ids):
            ok = (ids[si] == src_ids) & (ids[di] == dst_ids)
        si, di, labs = si[ok], di[ok], labs[ok]
        rebuilt = from_arrays(len(ids), si.astype(np.int32),
                              di.astype(np.int32), ids, None, labs,
                              self.label_names)
        self.n = rebuilt.n
        self.vertex_ids = rebuilt.vertex_ids
        self.src, self.dst = rebuilt.src, rebuilt.dst
        self.indptr_in = rebuilt.indptr_in
        self.out_degree = rebuilt.out_degree
        self.labels = rebuilt.labels
        return stats

    def _merge_edges(self, src_ids, dst_ids, labs, directed) -> None:
        """Fast path: merge NEW edges of EXISTING vertices into the
        dst-sorted arrays (one O(E) insert, no re-sort of old rows)."""
        if not directed:
            src_ids, dst_ids = (np.concatenate([src_ids, dst_ids]),
                                np.concatenate([dst_ids, src_ids]))
            labs = np.concatenate([labs, labs])
        si = np.searchsorted(self.vertex_ids, src_ids)
        di = np.searchsorted(self.vertex_ids, dst_ids)
        ok = (si < self.n) & (di < self.n)
        ok &= (self.vertex_ids[np.minimum(si, self.n - 1)] == src_ids) \
            & (self.vertex_ids[np.minimum(di, self.n - 1)] == dst_ids)
        si, di, labs = (si[ok].astype(np.int32), di[ok].astype(np.int32),
                        labs[ok])
        order = np.argsort(di, kind="stable")
        si, di, labs = si[order], di[order], labs[order]
        pos = np.searchsorted(self.dst, di, side="right")
        self.src = np.insert(self.src, pos, si)
        self.dst = np.insert(self.dst, pos, di)
        if self.labels is not None:
            self.labels = np.insert(self.labels, pos, labs)
        counts = np.diff(self.indptr_in)
        np.add.at(counts, di.astype(np.int64), 1)
        self.indptr_in = np.concatenate(
            [np.zeros(1, np.int64), np.cumsum(counts, dtype=np.int64)])
        np.add.at(self.out_degree, si, 1)

    def _invalidate_layout_caches(self) -> None:
        """Drop every derived layout / device-array cache the model
        kernels lazily attach (they rebuild from the refreshed arrays),
        and with them the column counts admission prices those layouts
        by (``_q_out``, ``_q_in``, ``_cdlp_lanes``: olap/serving/hbm keeps
        them here).
        The dense vertex-property columns are NOT cleared here — they
        stay aligned across edge-only merges; apply_changes clears them
        on property mutations (by key) and vertex-set changes (all)."""
        for attr in ("_out_csr", "_out_csr_order", "_hybrid_csr",
                     "_hybrid_csr_rev", "_pull_csr", "_lcc_csr", "_cdlp_csr",
                     "_cdlp_plan", "_q_out", "_q_in", "_cdlp_lanes",
                     "_frontier_shards",
                     "_dev_frontier_sh", "_tiled_shards", "_dev_outdeg",
                     "_dev_frontier"):
            if hasattr(self, attr):
                delattr(self, attr)

    def attach_vertex_values(self, graph, keys) -> None:
        """Build dense vertex property columns through the OLTP tx (one
        batched pass; SINGLE-cardinality keys only) and cache them for
        the device-compiled traversal subset. Keys already attached are
        skipped; unknown keys attach as all-absent columns."""
        from titan_tpu.core.defs import Cardinality

        want = [k for k in keys if k not in self.vertex_values]
        if not want:
            return
        for k in want:
            st = graph.schema.get_by_name(k)
            if st is not None and \
                    graph.schema.cardinality(st.id) is not Cardinality.SINGLE:
                raise ValueError(
                    f"attach_vertex_values: key {k!r} is not "
                    "SINGLE-cardinality; multi-valued columns have no "
                    "dense representation")
        tx = graph.new_transaction(read_only=True)
        try:
            cols = {k: (np.empty(self.n, object), np.zeros(self.n, bool))
                    for k in want}
            # batched: one multi-row property-slice read per id chunk
            # (tx.multi_vertex_properties), not n point reads — the
            # first compiled has()/values() on an OLAP-scale snapshot
            # must not pay minutes of host time
            chunk = 4096
            for c0 in range(0, self.n, chunk):
                ids = [int(v) for v in self.vertex_ids[c0:c0 + chunk]]
                got = tx.multi_vertex_properties(ids, keys=want)
                for j, vid in enumerate(ids):
                    props = got.get(vid)
                    if not props:
                        continue
                    for k, val in props.items():
                        if val is not None:
                            cols[k][0][c0 + j] = val
                            cols[k][1][c0 + j] = True
        finally:
            tx.rollback()
        self.vertex_values.update(cols)

    def dense_of(self, vertex_id: int) -> int:
        i = int(np.searchsorted(self.vertex_ids, vertex_id))
        if i >= self.n or self.vertex_ids[i] != vertex_id:
            raise KeyError(f"vertex {vertex_id} not in snapshot")
        return i

    def out_csr(self) -> tuple[np.ndarray, np.ndarray]:
        """(dst_by_src, indptr_out): edges sorted by SOURCE — the push/
        expansion layout used by frontier-sparse traversal. Computed once
        and cached (the snapshot is immutable). The src-order
        permutation itself is kept as ``_out_csr_order`` (src-order
        position → dst-order row): the live overlay's slot-lookup index
        reads it instead of re-paying the argsort, and ``merge_delta``
        carries both caches across an epoch merge incrementally."""
        cached = getattr(self, "_out_csr", None)
        if cached is None:
            # indptr is just the cumsum of the existing out_degree; the sort
            # takes the native counting-sort path when available (np.add.at
            # at 268M edges costs tens of host seconds)
            indptr_out = np.concatenate(
                [np.zeros(1, np.int64),
                 np.cumsum(self.out_degree, dtype=np.int64)])
            if native.available and self.n > 0 and len(self.src):
                order, _, _ = native.csr_build(self.dst, self.src, self.n)
                dst_by_src = native.gather_i32(self.dst, order)
            else:
                order = np.argsort(self.src, kind="stable")
                dst_by_src = self.dst[order]
            cached = (dst_by_src, indptr_out)
            self._out_csr = cached
            self._out_csr_order = np.asarray(order, np.int64)
        return cached

    def reverse(self) -> "GraphSnapshot":
        """Swap edge direction (push layout / in-degree programs)."""
        return from_arrays(self.n, self.dst, self.src, self.vertex_ids,
                           edge_values=self.edge_values, labels=self.labels,
                           label_names=self.label_names)


def from_arrays(n: int, src, dst, vertex_ids=None, edge_values=None,
                labels=None, label_names=None) -> GraphSnapshot:
    """Build a snapshot from raw (src, dst) dense-index arrays."""
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    if len(src) and (int(src.min()) < 0 or int(src.max()) >= n
                     or int(dst.min()) < 0 or int(dst.max()) >= n):
        raise IndexError(f"edge endpoint out of range [0, {n})")
    if vertex_ids is None:
        vertex_ids = np.arange(n, dtype=np.int64)
    if native.available and n > 0:
        order, indptr, out_degree = native.csr_build(src, dst, n)
        src_s = native.gather_i32(src, order)
        dst_s = native.gather_i32(dst, order)
    else:
        order = np.argsort(dst, kind="stable")
        src_s, dst_s = src[order], dst[order]
        indptr = np.zeros(n + 1, dtype=np.int64)
        np.add.at(indptr, dst_s + 1, 1)
        np.cumsum(indptr, out=indptr)
        out_degree = np.zeros(n, dtype=np.int32)
        np.add.at(out_degree, src, 1)
    ev = {k: np.asarray(v)[order] for k, v in (edge_values or {}).items()}
    lab = np.asarray(labels, dtype=np.int32)[order] if labels is not None else None
    return GraphSnapshot(n, np.asarray(vertex_ids, dtype=np.int64), src_s,
                         dst_s, indptr, out_degree, ev, lab,
                         dict(label_names or {}))


def merge_delta(snap: GraphSnapshot, keep: np.ndarray, add_src,
                add_dst, add_labels=None) -> GraphSnapshot:
    """Incremental dst-sorted merge: drop the rows where ``keep`` is
    False and insert the added edges, WITHOUT re-sorting the surviving
    rows — bit-equal to ``from_arrays(n, concat(src[keep], add_src),
    concat(dst[keep], add_dst), ...)`` (the full stable sort both the
    native and numpy builders run), because the kept rows stay
    dst-ascending and a stable dst-sort puts equal-dst adds AFTER the
    kept rows in append order, which is exactly a ``side='right'``
    searchsorted insert. O(E) memcpy + O(delta log delta), no O(E log
    E) sort — the epoch compactor's host-durable sync
    (olap/live/compactor.py device merge path) runs this every epoch.
    """
    add_src = np.asarray(add_src, np.int32)
    add_dst = np.asarray(add_dst, np.int32)
    if len(add_src) and (int(add_src.min()) < 0
                        or int(add_src.max()) >= snap.n
                        or int(add_dst.min()) < 0
                        or int(add_dst.max()) >= snap.n):
        raise IndexError(f"edge endpoint out of range [0, {snap.n})")
    order = np.argsort(add_dst, kind="stable")
    a_s, a_d = add_src[order], add_dst[order]
    dst_kept = snap.dst[keep]
    pos = np.searchsorted(dst_kept, a_d, side="right")
    src = np.insert(snap.src[keep], pos, a_s)
    dst = np.insert(dst_kept, pos, a_d)
    labels = None
    if snap.labels is not None:
        a_l = np.asarray(add_labels, np.int32)[order] \
            if add_labels is not None \
            else np.zeros(len(a_s), np.int32)
        labels = np.insert(snap.labels[keep], pos, a_l)
    counts = np.diff(snap.indptr_in)
    dead_dst = snap.dst[~keep].astype(np.int64)
    if len(dead_dst):
        np.add.at(counts, dead_dst, -1)
    if len(a_d):
        np.add.at(counts, a_d.astype(np.int64), 1)
    indptr_in = np.concatenate(
        [np.zeros(1, np.int64), np.cumsum(counts, dtype=np.int64)])
    out_degree = snap.out_degree.copy()
    dead_src = snap.src[~keep].astype(np.int64)
    if len(dead_src):
        np.add.at(out_degree, dead_src, -1)
    if len(a_s):
        np.add.at(out_degree, a_s.astype(np.int64), 1)
    merged = GraphSnapshot(snap.n, snap.vertex_ids, src, dst, indptr_in,
                           out_degree, {}, labels,
                           dict(snap.label_names))
    # ROADMAP #5 residual (ISSUE 11 satellite): the merged epoch's
    # out-CSR — and the src-order permutation the next overlay's
    # slot-lookup index is built from — carry over INCREMENTALLY when
    # the base had them cached (the overlay's own construction always
    # does), so the next DeltaOverlay never re-pays the O(E log E)
    # argsort the device merge path already eliminated everywhere else
    if getattr(snap, "_out_csr", None) is not None \
            and getattr(snap, "_out_csr_order", None) is not None:
        _merge_out_csr(snap, merged, keep, add_src, add_dst, pos)
    return merged


def _merge_out_csr(snap: GraphSnapshot, merged: GraphSnapshot,
                   keep: np.ndarray, add_src: np.ndarray,
                   add_dst: np.ndarray, pos_d: np.ndarray) -> None:
    """Incremental src-sorted layout across ``merge_delta``: build the
    merged snapshot's ``_out_csr`` (dst_by_src, indptr_out) and
    ``_out_csr_order`` from the base's cached pair — O(E) gathers +
    O(delta log delta) sorts, bit-equal to a from-scratch
    ``out_csr()`` on the merged arrays (pinned by
    tests/test_live_compact_device.py).

    Correctness: a stable src-sort preserves dst order within each
    source group (the merged array is dst-ascending), kept rows keep
    their relative order under row drops, and equal-(src, dst) adds
    land AFTER kept rows in append order — exactly a ``side='right'``
    insert on the (src, dst) composite key. ``pos_d`` is the dst-order
    insert-position vector ``merge_delta`` already computed (the adds'
    merged-row indices are ``pos_d + arange``)."""
    dst_by_src_old, _ = snap._out_csr
    order_old = snap._out_csr_order
    n = snap.n
    keep_s = keep[order_old]                  # keep mask, src order
    kept_dst_s = dst_by_src_old[keep_s]
    # src values in src order are just each vertex id repeated by its
    # OLD out-degree — no sort needed
    src_sorted_old = np.repeat(np.arange(n, dtype=np.int64),
                               snap.out_degree.astype(np.int64))
    kept_src_s = src_sorted_old[keep_s]
    # adds in (src, dst, append) order: stable dst-sort then stable
    # src-sort composes to exactly that
    o1 = np.argsort(add_dst, kind="stable")
    o = o1[np.argsort(add_src[o1], kind="stable")]
    as_s, ad_s = add_src[o].astype(np.int64), add_dst[o]
    # composite (src, dst) key: kept rows are sorted under it (groups
    # ascend by src, dst ascends within each group)
    key_kept = kept_src_s * np.int64(n + 1) + kept_dst_s
    key_add = as_s * np.int64(n + 1) + ad_s
    pos_s = np.searchsorted(key_kept, key_add, side="right")
    dst_by_src_new = np.insert(kept_dst_s, pos_s, ad_s)
    indptr_out_new = np.concatenate(
        [np.zeros(1, np.int64),
         np.cumsum(merged.out_degree, dtype=np.int64)])
    # merged-array row index per src-order position: kept row j (in
    # kept-dst order) shifts by the adds inserted at/before it;
    # dst-order add k lands at pos_d[k] + k
    kept_rank = np.cumsum(keep, dtype=np.int64) - 1
    j_kept = kept_rank[order_old[keep_s]]
    merged_idx_kept = j_kept + np.searchsorted(pos_d, j_kept,
                                               side="right")
    merged_idx_add_d = pos_d.astype(np.int64) \
        + np.arange(len(pos_d), dtype=np.int64)
    # map each ORIGINAL add row to its dst-order rank, then read its
    # merged index in the src-sorted visit order
    ord_d = np.argsort(add_dst, kind="stable")
    rank_d = np.empty(len(ord_d), np.int64)
    rank_d[ord_d] = np.arange(len(ord_d), dtype=np.int64)
    merged_idx_add_s = merged_idx_add_d[rank_d[o]]
    order_new = np.insert(merged_idx_kept, pos_s, merged_idx_add_s)
    merged._out_csr = (dst_by_src_new, indptr_out_new)
    merged._out_csr_order = order_new


def _scan_python(graph, rows, exists_q, scan_q, label_ids, key_ids):
    """Per-entry decode via the Python codec (fallback; also the path when
    edge property values must be extracted)."""
    idm, schema, codec = graph.idm, graph.schema, graph.codec
    srcs: list[int] = []
    dsts: list[int] = []
    labs: list[int] = []
    ev: dict[str, list] = {name: [] for name in key_ids.values()}
    vertex_id_list: list[int] = []
    for key, entries in rows:
        vid = idm.id_of_key_bytes(key)
        if not idm.is_user_vertex_id(vid):
            continue
        # vertex-cut rows fold into the canonical vertex (reference:
        # VertexProgramScanJob.java:76-92 canonical-representative aggregation)
        vid = idm.canonical_vertex_id(vid)
        has_exist = False
        for e in entries:
            if exists_q.contains(e.column):
                has_exist = True
            elif scan_q.contains(e.column):
                rc = codec.parse(e, schema)
                if rc.direction is not Direction.OUT or not rc.is_edge:
                    continue
                if schema.system.is_system(rc.type_id):
                    continue
                if label_ids is not None and rc.type_id not in label_ids:
                    continue
                srcs.append(vid)
                dsts.append(rc.other_vertex_id)
                labs.append(idm.count(rc.type_id))
                for kid, name in key_ids.items():
                    ev[name].append(rc.properties.get(kid, 0))
        if has_exist:
            vertex_id_list.append(vid)
    return vertex_id_list, srcs, dsts, labs, ev


def _scan_native(graph, rows, exists_q, label_ids):
    """Bulk decode via the C++ codec (native/): Python only concatenates
    column bytes; head classification and other-vertex varint decode run as
    two vectorized native sweeps. Labels whose columns carry sort keys or
    park the other-vertex id in the value (unique directions) fall back to
    per-entry Python parse — rare, and only for those entries."""
    idm = graph.idm

    cols = bytearray()
    offs: list[int] = [0]
    entry_row: list[int] = []
    entry_refs: list = []
    row_vids: list[int] = []
    for key, entries in rows:
        vid = idm.id_of_key_bytes(key)
        if not idm.is_user_vertex_id(vid):
            continue
        ridx = len(row_vids)
        row_vids.append(vid)
        for e in entries:
            cols += e.column
            offs.append(len(cols))
            entry_row.append(ridx)
            entry_refs.append(e)

    if not entry_refs:
        return [], np.empty(0, np.int64), np.empty(0, np.int64), [], {}

    return _native_classify(
        graph, np.frombuffer(cols, dtype=np.uint8),
        np.asarray(offs, dtype=np.int64),
        np.asarray(entry_row, dtype=np.int64),
        np.asarray(row_vids, dtype=np.int64),
        exists_q, label_ids, lambda i: entry_refs[i])


def _scan_native_packed(graph, packed_rows, exists_q, label_ids):
    """_scan_native over a store's packed row scan (scan_rows_packed,
    features.packed_ops): per-ROW joins and C-speed length maps replace
    the per-Entry Python loop — the entry-wise accumulation measured
    ~3us/cell and dominated benchmark-scale snapshot builds."""
    from titan_tpu.storage.api import Entry
    idm = graph.idm

    chunks: list[bytes] = []
    lens: list[int] = []
    counts: list[int] = []
    row_vids: list[int] = []
    row_refs: list = []
    for key, cols_list, vals_list in packed_rows:
        vid = idm.id_of_key_bytes(key)
        if not idm.is_user_vertex_id(vid):
            continue
        row_vids.append(vid)
        chunks.append(b"".join(cols_list))
        lens.extend(map(len, cols_list))
        counts.append(len(cols_list))
        row_refs.append((cols_list, vals_list))

    if not lens:
        return [], np.empty(0, np.int64), np.empty(0, np.int64), [], {}

    col_buf = np.frombuffer(b"".join(chunks), dtype=np.uint8)
    offs = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(np.asarray(lens, np.int64), out=offs[1:])
    counts_a = np.asarray(counts, np.int64)
    entry_row = np.repeat(np.arange(len(counts_a), dtype=np.int64),
                          counts_a)
    row_start = np.zeros(len(counts_a) + 1, np.int64)
    np.cumsum(counts_a, out=row_start[1:])

    def resolve(i: int) -> Entry:
        r = int(entry_row[i])
        li = i - int(row_start[r])
        cols_list, vals_list = row_refs[r]
        return Entry(cols_list[li], vals_list[li])

    return _native_classify(graph, col_buf, offs, entry_row,
                            np.asarray(row_vids, np.int64), exists_q,
                            label_ids, resolve)


def _native_classify(graph, col_buf, offs, entry_row_a, row_vids_raw,
                     exists_q, label_ids, resolve_entry):
    """Shared tail of the native scan paths: classify column heads,
    bulk-decode other-vertex ids, per-entry-parse the rare slow labels
    (sort keys / unique directions) via ``resolve_entry(i)``."""
    from titan_tpu.ids import IDType
    idm, schema, codec = graph.idm, graph.schema, graph.codec

    kind, tcount, dpos = native.parse_heads(col_buf, offs, exists_q.start)
    # vertex-cut rows fold into the canonical vertex (vectorized analog of
    # the scan job's canonical-representative aggregation)
    row_vids_a = idm.canonicalize_np(row_vids_raw)

    exists_rows = np.unique(entry_row_a[kind == native.KIND_EXISTS])
    vertex_id_list = row_vids_a[exists_rows].tolist()

    edge_mask = kind == native.KIND_OUT_EDGE
    keep_counts, fast_counts = [], []
    for c in np.unique(tcount[edge_mask]).tolist():
        tid = idm.schema_id(IDType.USER_EDGE_LABEL, int(c))
        if label_ids is not None and tid not in label_ids:
            continue
        keep_counts.append(c)
        if (not schema.sort_key(tid)
                and not schema.multiplicity(tid).unique(Direction.OUT)):
            fast_counts.append(c)
    keep = edge_mask & np.isin(tcount, keep_counts)
    fast = keep & np.isin(tcount, fast_counts)

    entry_ends = offs[1:]
    others, _ = native.bulk_read_uvar(col_buf, dpos[fast], entry_ends[fast])
    srcs = row_vids_a[entry_row_a[fast]]
    dsts = others
    labs = tcount[fast].astype(np.int64)

    slow_idx = np.flatnonzero(keep & ~fast)
    if len(slow_idx):
        s_src, s_dst, s_lab = [], [], []
        for i in slow_idx.tolist():
            rc = codec.parse(resolve_entry(i), schema)
            s_src.append(row_vids_a[entry_row_a[i]])
            s_dst.append(rc.other_vertex_id)
            s_lab.append(idm.count(rc.type_id))
        srcs = np.concatenate([srcs, np.asarray(s_src, np.int64)])
        dsts = np.concatenate([dsts, np.asarray(s_dst, np.int64)])
        labs = np.concatenate([labs, np.asarray(s_lab, np.int64)])
    return vertex_id_list, srcs, dsts, labs.tolist(), {}


def build(graph, labels: Optional[Sequence[str]] = None,
          edge_keys: Sequence[str] = (),
          directed: bool = True,
          _reuse_listener: Optional[tuple] = None) -> GraphSnapshot:
    """Scan the edgestore and build the snapshot.

    ``labels``: restrict to these edge labels (None = all user labels).
    ``edge_keys``: edge property names to extract into aligned arrays.
    ``directed=False`` adds the reverse of every edge (symmetrize).
    ``_reuse_listener``: a ``(token, ChangeQueue)`` pair to RE-ANCHOR at
    the scan-verified epoch instead of subscribing a fresh queue —
    ``rebuild_in_place()``'s seam: the queue is cleared and its
    overflow flag reset under the same commit-lock window that proves
    the scan saw a committed prefix, so delta refresh resumes soundly
    after an overflow-forced rebuild.
    """
    idm = graph.idm
    schema = graph.schema
    codec = graph.codec
    label_ids = None
    if labels is not None:
        label_ids = {st.id for name in labels
                     if (st := schema.get_by_name(name)) is not None}
    key_ids = {}
    for name in edge_keys:
        st = schema.get_by_name(name)
        if st is not None:
            key_ids[st.id] = name

    lo, hi = rids.category_bounds(RelationCategory.EDGE, Direction.OUT,
                                  include_system=False)
    scan_q = SliceQuery(lo, hi)

    # Epoch discipline: capture epoch0, scan, then — under the commit
    # lock — verify the epoch did not move during the scan and subscribe
    # atomically. A commit that lands mid-scan may or may not be in the
    # scanned rows (the scan has no store-level snapshot isolation), so
    # its delta payload can't be safely applied OR skipped; retry the
    # scan, and fail loud if writers keep racing. Commits push payload +
    # bump epoch atomically with commit_storage (core/graph.py commit),
    # so an unchanged epoch proves the scan saw a committed prefix.
    import contextlib

    def _scan_once():
        btx = graph.backend.begin_transaction()
        try:
            exists_q = codec.query_type(schema.system.vertex_exists,
                                        Direction.OUT, schema)[0]
            store = graph.backend.edge_store.store
            if native.available and not key_ids:
                if getattr(graph.backend.manager.features, "packed_ops",
                           False):
                    return _scan_native_packed(
                        graph, store.scan_rows_packed(btx.store_tx),
                        exists_q, label_ids)
                return _scan_native(graph,
                                    store.get_keys(SliceQuery(),
                                                   btx.store_tx),
                                    exists_q, label_ids)
            return _scan_python(graph,
                                store.get_keys(SliceQuery(), btx.store_tx),
                                exists_q, scan_q, label_ids, key_ids)
        finally:
            btx.commit()

    def _anchor_locked():
        """Under the commit lock with the scan verified: attach the
        listener — a fresh subscription, or the caller's existing queue
        re-anchored (same atomicity guarantee either way)."""
        if _reuse_listener is not None:
            tok, rq = _reuse_listener
            rq.reanchor()
            return tok, rq
        return graph._subscribe_locked()

    token = q = None
    for attempt in range(3):
        # final attempt scans while HOLDING the commit lock: writers are
        # excluded for one scan, so build() terminates under any write
        # load instead of spinning forever on epoch bumps
        hold = graph._commit_lock if attempt == 2 else \
            contextlib.nullcontext()
        with hold:
            epoch0 = graph.mutation_epoch
            vertex_id_list, srcs, dsts, labs, ev = _scan_once()
            if attempt == 2:
                token, q = _anchor_locked()
                break
        with graph._commit_lock:
            if graph.mutation_epoch == epoch0:
                token, q = _anchor_locked()
                break
    assert token is not None

    vertex_ids = np.array(sorted(vertex_id_list), dtype=np.int64)
    n = len(vertex_ids)
    raw_src = np.array(srcs, dtype=np.int64)
    raw_dst = np.array(dsts, dtype=np.int64)
    # drop edges whose endpoint is missing (ghosts)
    si = np.searchsorted(vertex_ids, raw_src)
    di = np.searchsorted(vertex_ids, raw_dst)
    si = np.clip(si, 0, max(n - 1, 0))
    di = np.clip(di, 0, max(n - 1, 0))
    ok = np.ones(len(raw_src), dtype=bool)
    if n:
        ok = (vertex_ids[si] == raw_src) & (vertex_ids[di] == raw_dst)
    src = si[ok].astype(np.int32)
    dst = di[ok].astype(np.int32)
    labs_arr = np.array(labs, dtype=np.int32)[ok]
    evs = {name: np.array(vals)[ok] for name, vals in ev.items()}
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
        labs_arr = np.concatenate([labs_arr, labs_arr])
        evs = {name: np.concatenate([v, v]) for name, v in evs.items()}
    label_names = {}
    for code in np.unique(labs_arr).tolist() if len(labs_arr) else []:
        from titan_tpu.ids import IDType
        st = schema.get_type(idm.schema_id(IDType.USER_EDGE_LABEL, code))
        if st is not None:
            label_names[code] = st.name
    snap = from_arrays(n, src, dst, vertex_ids, evs, labs_arr, label_names)
    # freshness contract: stamp the scan-verified epoch and attach the
    # listener subscribed atomically with the epoch check above, so
    # refresh() can catch this snapshot up without a store re-scan
    snap.epoch = epoch0
    snap._graph = graph
    snap._listener_token, snap._listener = token, q
    snap._build_params = {"label_ids": label_ids, "directed": directed,
                          "labels": (tuple(labels)
                                     if labels is not None else None),
                          "edge_keys": tuple(edge_keys)}
    return snap
