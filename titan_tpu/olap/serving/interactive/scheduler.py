"""InteractiveLane: the low-latency execution lane for point queries.

A dedicated worker + micro-batcher that BYPASSES the heavy OLAP heap
(no priorities, no retry plane, no checkpoints — a point query answers
in milliseconds or answers with its error) while still flowing through
the owning ``JobScheduler``'s shared planes:

* **snapshot pool + HBM ledger** — groups lease epoch-consistent
  ``(snapshot, overlay)`` pairs from the SAME pool the heavy queue
  uses, and the graph image (plus the ``out()``-orientation's reversed
  CSR) is reserved/pinned on the same ledger for the run;
* **tenant quotas** — every request passes ``TenantAccounting.admit``
  under the scheduler's quota table and enforce flag (shadow mode
  counts ``serving.tenant.throttled``, enforced violations are
  ``serving.tenant.rejected`` + ``QuotaExceeded`` → HTTP 429), and the
  fused batch wall is attributed to member tenants split over K;
* **tracing** — one trace per executed batch (trace id
  ``traverse-<seq>``, readable at ``GET /trace?job=traverse-<seq>``):
  the ``interactive`` root, one ``member`` a request, and the leaf
  phases ``admit``, ``bfs.seed`` / ``bfs.plan`` / ``bfs.sweep`` /
  ``bfs.exhaust`` (the level loop's, through ``obs/tracing.scope``),
  ``extract`` and ``reply``, each also a profiler annotation; a
  ``compile`` span wherever an executable was built or loaded
  (docs/observability.md has the table);
* **device-cost profiler** — each batch executes inside a profiler
  window; its compile/exec/transfer deltas land on the batch trace.

Metrics (``serving.interactive.*`` — docs/monitoring.md):
  serving.interactive.requests     admitted lane requests ({tenant})
  serving.interactive.fallbacks    loud interpreter fallbacks
                                   (uncompilable chain or a runtime
                                   FallbackToInterpreter)
  serving.interactive.batches      executed fused device runs
  serving.interactive.fuse_k       histogram: members per executed
                                   batch (occupancy — the fusion
                                   evidence)
  serving.interactive.wait_ms      histogram: fuse-window wait per
                                   request
  serving.interactive.latency_ms   histogram ({tenant}): submit →
                                   reply for compiled requests — the
                                   lane's p95 SLO SLI
                                   (``obs/slo.SLO(metric=...)``)
  serving.interactive.ppr_users    personalized-PageRank source rows
                                   served
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Optional

import numpy as np

from titan_tpu.obs.tracing import phase, scope
from titan_tpu.olap.serving.interactive.collector import (
    DEFAULT_MAX_FUSE, DEFAULT_WINDOW_S, Collector, InteractiveRequest)
from titan_tpu.olap.serving.interactive.compile import (
    DEFAULT_MAX_DEPTH, FallbackToInterpreter, PPRPlan, TraversalPlan,
    reversed_chunked_csr)
from titan_tpu.olap.serving.tenants import (QuotaExceeded,
                                            effective_tenant)

_batch_seq = itertools.count(1)


def _padded(k: int) -> int:
    """A batch's row count: the next power of two (1 stays 1)."""
    return 1 << (k - 1).bit_length()


class InteractiveLane:
    """See module doc. One lane per JobScheduler
    (``JobScheduler.interactive()``); independently constructible for
    tests."""

    def __init__(self, scheduler, *,
                 window_s: float = DEFAULT_WINDOW_S,
                 max_fuse: int = DEFAULT_MAX_FUSE,
                 max_depth: int = DEFAULT_MAX_DEPTH,
                 autostart: bool = True):
        self.sched = scheduler
        self._metrics = scheduler._metrics
        self.max_depth = int(max_depth)
        self.collector = Collector(window_s=window_s, max_fuse=max_fuse)
        self._closed = False
        # shapes (n, q_total, largest padded batch) whose executables
        # this lane has built (_build_shapes)
        self._built: set = set()
        self._worker: Optional[threading.Thread] = None
        if autostart:
            self.start()

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "InteractiveLane":
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(target=self._run,
                                            name="serving-interactive",
                                            daemon=True)
            self._worker.start()
        return self

    def close(self, timeout: float = 5.0) -> None:
        self._closed = True
        self.collector.close()
        if self._worker is not None:
            self._worker.join(timeout)

    @property
    def closed(self) -> bool:
        return self._closed

    def stats(self) -> dict:
        return {"queue_depth": self.collector.depth(),
                "window_s": self.collector.window_s,
                "max_fuse": self.collector.max_fuse,
                "max_depth": self.max_depth}

    # -- submission ----------------------------------------------------------

    def submit(self, plan, tenant: Optional[str] = None,
               timeout_s: float = 30.0) -> dict:
        """Blocking point-query execution. Returns the response
        envelope; raises QuotaExceeded (enforced quota violation),
        FallbackToInterpreter (the LOUD unsupported-at-runtime path —
        the caller reruns on the dsl interpreter), or the member's
        parameter error."""
        if self._closed:
            raise RuntimeError("interactive lane is closed")
        tenant = self._admit(tenant)
        req = InteractiveRequest(plan, tenant)
        state = "failed"
        try:
            if isinstance(plan, TraversalPlan) \
                    and plan.depth > self.max_depth:
                # inside the admitted section: a depth-ceiling
                # fallback is still this tenant's traffic
                self._metrics.counter(
                    "serving.interactive.fallbacks").inc()
                state = "fallback"
                raise FallbackToInterpreter(
                    f"depth {plan.depth} past the lane ceiling "
                    f"{self.max_depth} — an analytics-depth chain "
                    "belongs on the heavy queue or the interpreter")
            self.collector.submit(req)
            if not req.wait(timeout_s):
                raise RuntimeError(
                    f"interactive request timed out after {timeout_s}s")
            if req.error is not None:
                if isinstance(req.error, FallbackToInterpreter):
                    state = "fallback"
                    self._metrics.counter(
                        "serving.interactive.fallbacks").inc()
                raise req.error
            state = "completed"
            self._metrics.histogram(
                "serving.interactive.latency_ms",
                labels={"tenant": tenant}).update(
                (time.time() - req.submitted_at) * 1e3)
            self._metrics.histogram(
                "serving.interactive.wait_ms").update(req.wait_ms)
            return req.result
        finally:
            self.sched.tenants.finished(tenant, state)

    def _admit(self, tenant: Optional[str]) -> str:
        """The lane's quota gate (shared by compiled submits and
        interpreter fallbacks): atomic tenant admission under the
        scheduler's quota table — enforced violations raise
        QuotaExceeded (HTTP 429), shadow-mode ones count throttled.
        Returns the effective tenant; the caller MUST balance with
        ``tenants.finished``."""
        tenant = effective_tenant(tenant)
        sched = self.sched
        # an enforcing autotune controller's tenant shed scales the
        # configured quota HERE too — a shed tenant must not dodge the
        # throttle by switching its flood to point queries
        quota = sched.quotas.get(tenant)
        if sched.controller is not None:
            quota = sched.controller.scaled_quota(tenant, quota)
        why = sched.tenants.admit(tenant, quota, sched.enforce_quotas)
        if why is not None and sched.enforce_quotas:
            self._metrics.counter("serving.tenant.rejected",
                                  labels={"tenant": tenant}).inc()
            raise QuotaExceeded(f"tenant {tenant!r}: {why}")
        if why is not None:
            self._metrics.counter("serving.tenant.throttled",
                                  labels={"tenant": tenant}).inc()
        self._metrics.counter("serving.interactive.requests",
                              labels={"tenant": tenant}).inc()
        return tenant

    def account_fallback(self, tenant: Optional[str] = None):
        """Admission + accounting for a COMPILE-TIME interpreter
        fallback (the server routes chains outside the compilable
        subset to the dsl interpreter): same quota gate as compiled
        submits — a tenant over its enforced quota gets 429 for
        uncompilable traffic too, not a free interpreter ride. Counts
        the fallback and returns a ``done(state)`` callable the caller
        MUST invoke exactly once after the interpreter run."""
        tenant = self._admit(tenant)
        self._metrics.counter("serving.interactive.fallbacks").inc()

        def done(state: str = "fallback") -> None:
            self.sched.tenants.finished(tenant, state)
        return done

    # -- worker --------------------------------------------------------------

    def _run(self) -> None:
        while True:
            grp = self.collector.pop_due()
            if grp is None:
                return
            try:
                self._execute(grp)
            except Exception as e:
                # NOTHING may kill the lane worker: answer every
                # member with the error and keep serving
                for r in grp.members:
                    if not r._done.is_set():
                        r.finish(error=e)

    def _execute(self, grp) -> None:
        sched = self.sched
        members = grp.members
        t_exec0 = time.time()
        for r in members:
            r.wait_ms = (t_exec0 - r.submitted_at) * 1e3
        batch_id = f"traverse-{next(_batch_seq)}"
        trace = None
        if sched.tracer.enabled:
            kind = grp.key[0]
            trace = sched.tracer.start(batch_id, "interactive",
                                       kind=kind, k=len(members))
            sched.tracer.event(batch_id, "fuse", parent=trace,
                               k=len(members),
                               window_ms=round(
                                   max(r.wait_ms for r in members), 3))
        w = sched.profiler.window() if sched.profiler is not None \
            else None
        err = None
        dispatched = False
        try:
            with scope(sched.tracer, batch_id, trace):
                if isinstance(members[0].plan, PPRPlan):
                    dispatched = self._run_ppr(members, batch_id)
                else:
                    with phase("admit") as admit:
                        dispatched = self._run_traverse(
                            members, batch_id, admit)
        except Exception as e:
            err = e
            raise
        finally:
            wall = time.time() - t_exec0
            if err is None and dispatched:
                # executed device runs only: a group that fell back,
                # died, or had no resolvable members is not fusion
                # evidence
                self._metrics.counter(
                    "serving.interactive.batches").inc()
                self._metrics.histogram("serving.interactive.fuse_k") \
                    .update(float(len(members)))
            share = wall / len(members)
            for r in members:
                sched.tenants.device_seconds(r.tenant, share)
            if trace is not None:
                if w is not None:
                    cost = w.close()
                    w = None
                    if cost["calls"]:
                        sched.tracer.event(
                            batch_id, "device_cost", parent=trace,
                            k=len(members),
                            kernel_calls=cost["calls"],
                            compiles=cost["compiles"],
                            h2d_bytes=cost["h2d_bytes"],
                            d2h_bytes=cost["d2h_bytes"])
                now = time.time()
                for r in members:
                    # submitted_at -> finish: starts before the root
                    # by the request's wait for the lane
                    sched.tracer.event(
                        batch_id, "member", parent=trace,
                        t0=r.submitted_at, t1=r.finished_at or now,
                        tenant=r.tenant, wait_ms=round(r.wait_ms, 3))
                sched.tracer.end(trace,
                                 wall_ms=round(wall * 1e3, 3),
                                 **({"error": type(err).__name__}
                                    if err is not None else {}))
            if w is not None:
                w.close()
            if sched.recorder is not None:
                sched.recorder.metric_delta()

    # -- traversal groups ----------------------------------------------------

    def _run_traverse(self, members: list, batch_id: str,
                      admit) -> bool:
        """``admit`` is the open ``admit`` phase: lease, seeds, ledger,
        CSR look-up and label masks; ended just before the sweep."""
        from titan_tpu.core.defs import Direction
        from titan_tpu.models.bfs_hybrid import build_chunked_csr
        from titan_tpu.olap.serving.hbm import (AdmissionError, price,
                                                snapshot_csr_bytes,
                                                snapshot_rev_csr_bytes)

        sched = self.sched
        plan0: TraversalPlan = members[0].plan
        direction = plan0.direction
        labels = list(plan0.labels) if plan0.labels else None
        lease = sched.pool.acquire(labels=labels,
                                   directed=direction
                                   is not Direction.BOTH)
        with lease as snap:
            overlay = lease.overlay
            if overlay is None:
                overlay = getattr(snap, "_live_overlay", None)
            if overlay is not None and overlay.empty:
                overlay = None
            if overlay is not None and direction is not Direction.BOTH:
                # the overlay's slot bitmap and add-COO orientation
                # belong to the symmetrized live base; a directed
                # chain under live writes falls back LOUDLY
                raise FallbackToInterpreter(
                    "directed chain over a live overlay: the overlay "
                    "seam serves the symmetrized (both) orientation")
            if overlay is not None and plan0.hop_labels is not None:
                # per-level label masks ride the tombstone-bitmap seam,
                # and the overlay's add-COO edges carry labels the slot
                # mask cannot filter — mixed-label chains under live
                # writes fall back LOUDLY (frontier_bfs_batched raises
                # on the combination too; this keeps the error a
                # fallback, not a batch failure)
                raise FallbackToInterpreter(
                    "mixed-label chain over a live overlay: compact "
                    "the overlay first")
            epoch_info = lease.epoch_info \
                or {"epoch": getattr(snap, "epoch", 0)}
            # seeds: V(ids) skips unknown vertices, like the
            # interpreter's tx.vertex(i) None-filter
            runnable: list = []
            seeds: list = []
            for r in members:
                ds = []
                for vid in r.plan.start_ids:
                    try:
                        ds.append(snap.dense_of(int(vid)))
                    except (KeyError, TypeError, ValueError):
                        pass
                if ds:
                    runnable.append(r)
                    seeds.append(ds)
                else:
                    r.finish(result=self._empty_result(
                        r.plan, batch_id, len(members), epoch_info))
            if not runnable:
                return False
            # HBM admission FIRST, build second (the heavy queue's
            # order): the layout this run reads is sized host-side —
            # forward graph image for in_/both, the REVERSED layout
            # (the only resident one) for out(), each from the column
            # count kept on the snapshot (one pass over a degree array
            # the first batch of a snapshot, none after) — and reserved
            # BEFORE any device bytes move, so the ledger can evict or
            # refuse while refusal is still free. An AdmissionError
            # fails the group; the finally unpins exactly what was
            # reserved
            if direction is Direction.OUT:
                key = ("interactive-rev", id(snap))
                passes = price(snap, ("in",), self._metrics)
                nbytes = snapshot_rev_csr_bytes(snap)
                handle = (snap, "_hybrid_csr_rev")
            else:
                key = id(snap)
                passes = price(snap, ("out",), self._metrics)
                nbytes = snapshot_csr_bytes(snap)
                handle = snap
            try:
                sched.ledger.reserve(key, nbytes)
            except AdmissionError as e:
                for r in runnable:
                    r.finish(error=e)
                return False
            sched._evictable.setdefault(key, handle)
            g = reversed_chunked_csr(snap) \
                if direction is Direction.OUT \
                else build_chunked_csr(snap)
            if direction is Direction.IN:
                # the forward columns are an in_() chain's parents,
                # not its children: no push (bfs_hybrid._td_cap)
                g = dict(g, directed=True)
            # mixed-label chain (ISSUE 13): per-hop slot bitmaps over
            # the union-label lease — one bitmap per distinct hop label
            # set, threaded through the kernels as per-level masks
            level_masks = None
            if plan0.hop_labels is not None:
                from titan_tpu.olap.serving.interactive.compile import \
                    hop_label_masks
                level_masks = hop_label_masks(snap, plan0, direction)
            # per-tenant HBM accounting, exactly like the heavy
            # queue: the image bytes are HELD against each member's
            # tenant while the run is in flight (the max_hbm_bytes
            # quota view) and converted to byte-seconds after
            share = nbytes / len(runnable)
            for r in runnable:
                sched.tenants.hold_hbm(r.tenant, share)
            admit.set(k_runnable=len(runnable), nbytes=int(nbytes),
                      sizing_passes=passes,
                      epoch=epoch_info.get("epoch")).end()
            self._build_shapes(g)
            t0 = time.time()
            try:
                self._sweep(runnable, seeds, g, overlay, snap,
                            batch_id, len(members), epoch_info,
                            level_masks=level_masks)
            finally:
                wall = time.time() - t0
                for r in runnable:
                    sched.tenants.drop_hbm(r.tenant, share)
                    sched.tenants.hbm_byte_seconds(
                        r.tenant, share * wall)
                sched.ledger.unpin(key)
            return True

    def _build_shapes(self, g) -> None:
        """Before the first answer against a layout of a new shape: run
        a single-start hops batch of every padded size the collector
        can fuse (seed, push, extract), and at that size the plan, the
        listing and every rung of the top-down step. A level's rung
        follows its frontier's mass, its road what the level before
        left it, and a batch's size the arrivals, so no warm-up by
        traffic covers them, and
        a shape met first inside a served window stalls it for the
        build. The executables are keyed by the layout's shape, not by
        the layout: the next epoch's layout of the same shape builds
        nothing. Journaled as one ``build`` span that holds the dummy
        batches' phases and every ``compile`` span. Directed layouts
        pull at every level (caps of data-dependent counts: not a
        finite set) and are left as they were."""
        from titan_tpu.models.bfs_hybrid import warm_batched_td
        from titan_tpu.obs.tracing import current_span

        sizes = [1 << e for e in range(
            _padded(self.collector.max_fuse).bit_length())]
        key = (g["n"], g["q_total"], sizes[-1])
        if g.get("directed") or key in self._built:
            return

        def build():
            for Kp in sizes:
                self._hops(g, [[0]] * Kp, [1] * Kp)
                warm_batched_td(g, Kp, expand=True)

        cur = current_span()
        if cur is None:
            build()
        else:
            tracer, trace_id, root = cur
            with tracer.span(trace_id, "build", parent=root, n=g["n"],
                             q_total=g["q_total"],
                             max_k=sizes[-1]) as span:
                with scope(tracer, trace_id, span):
                    build()
        self._built.add(key)

    def _hops(self, g, seeds, depths_p, overlay=None, level_masks=None):
        """One fused hops run and its hop-set sizes: ``seeds`` (one
        list of dense start ids a member) over a batch padded to
        ``len(depths_p)`` rows. Returns ``(masks [Kp, n] device bool,
        sizes np int32 [Kp])``: row k's hop set at its own depth."""
        from titan_tpu.models.bfs_hybrid import (hop_extract,
                                                 batched_bfs_state)
        from titan_tpu.obs import devprof

        n = g["n"]
        Kp = len(depths_p)
        D = max(depths_p)

        def on_level(level, nf):
            keep = np.asarray([level <= d for d in depths_p])
            return keep if not keep.all() else None

        if all(len(ds) == 1 for ds in seeds):
            # the common point-query shape (one start vertex): seed on
            # DEVICE through the kernel's sources path — no [Kp, n]
            # host init array, no O(n) H2D per query
            srcs = [ds[0] for ds in seeds] + [0] * (Kp - len(seeds))
            dist, _levels, _completed = batched_bfs_state(
                g, srcs, max_levels=D + 1, start_level=1,
                on_level=on_level, overlay=overlay, mode="hops",
                level_masks=level_masks)
        else:
            # multi-start members (V(id1, id2, ...)): rarer — pay the
            # dense init upload
            init = np.zeros((Kp, n), np.int32)
            for k, ds in enumerate(seeds):
                init[k, ds] = 1
            dist, _levels, _completed = batched_bfs_state(
                g, [0] * Kp, max_levels=D + 1, start_level=1,
                init_dist=init, on_level=on_level, overlay=overlay,
                mode="hops", level_masks=level_masks)
        # hop-set extraction stays DEVICE-side: one [Kp] size readback,
        # then a compacted index list per id/values member — never the
        # O(n) dist row (a scale-26 row is a ~270 MB D2H transfer)
        with phase("extract", Kp=Kp) as ph:
            masks, sizes = hop_extract()(
                dist, np.asarray(depths_p, np.int32) + 1, n_=n)
            with ph.sync():
                sizes = np.asarray(sizes)
            devprof.count_d2h("interactive.sizes", int(sizes.nbytes))
        return masks, sizes

    def _sweep(self, runnable, seeds, g, overlay, snap, batch_id,
               fused_k, epoch_info, level_masks=None) -> None:
        from titan_tpu.models.bfs import _next_pow2
        from titan_tpu.obs import devprof
        from titan_tpu.ops.compaction import compact_ids

        n = g["n"]
        depths = [r.plan.depth for r in runnable]
        K = len(runnable)
        # pad the batch to its power-of-two capacity bucket so fuse
        # occupancy never mints a fresh XLA shape; pad rows carry
        # depth 0 — the level-1 keep mask retires them before any sweep
        depths_p = depths + [0] * (_padded(K) - K)
        t0 = time.time()
        masks, sizes = self._hops(g, seeds, depths_p, overlay,
                                  level_masks)
        exec_ms = (time.time() - t0) * 1e3
        with phase("reply") as ph:
            d2h_bytes = 0
            for k, r in enumerate(runnable):
                plan: TraversalPlan = r.plan
                count = int(sizes[k])
                try:
                    if plan.terminal == "count":
                        result = count
                    elif count == 0:
                        result = []
                    else:
                        cap = min(_next_pow2(max(count, 2)),
                                  _next_pow2(max(n, 2)))
                        _c, ids_dev = compact_ids(masks[k], cap, n)
                        hopset = np.asarray(ids_dev)[:count]
                        devprof.count_d2h("interactive.hopset",
                                          int(hopset.nbytes))
                        d2h_bytes += int(hopset.nbytes)
                        result = self._terminal(plan, snap, hopset)
                except FallbackToInterpreter as e:
                    r.finish(error=e)
                    continue
                r.finish(result={"result": result, "batch": batch_id,
                                 "fused_k": fused_k, "hops": plan.depth,
                                 "wait_ms": round(r.wait_ms, 3),
                                 "exec_ms": round(exec_ms, 3),
                                 "epoch": epoch_info})
            ph.set(d2h_bytes=d2h_bytes)

    def _empty_result(self, plan, batch_id, fused_k, epoch_info) -> dict:
        empty = 0 if plan.terminal == "count" else []
        return {"result": empty, "batch": batch_id, "fused_k": fused_k,
                "hops": plan.depth, "wait_ms": 0.0, "exec_ms": 0.0,
                "epoch": epoch_info}

    def _terminal(self, plan: TraversalPlan, snap, hopset):
        if plan.terminal == "count":
            return int(len(hopset))
        if plan.terminal == "id":
            return [int(snap.vertex_ids[i]) for i in hopset]
        key = plan.terminal[1]
        vals, present = self._vertex_column(snap, key)
        return [vals[i] for i in hopset if present[i]]

    def _vertex_column(self, snap, key: str):
        """Dense property column for a values() terminal — attached
        from the pool's graph when safe, FallbackToInterpreter when
        the snapshot can't answer faithfully (unbound snapshot, stale
        epoch, non-SINGLE cardinality — mirrors
        traversal/olap_compile's dataset-consistency guards)."""
        got = snap.vertex_values.get(key)
        if got is not None:
            return got
        graph = self.sched.pool.graph
        if graph is None or getattr(snap, "_graph", None) is None:
            raise FallbackToInterpreter(
                f"snapshot carries no {key!r} column and is not bound "
                "to a graph to build one from")
        if snap.stale:
            raise FallbackToInterpreter(
                f"snapshot went stale before the {key!r} column was "
                "attached")
        try:
            snap.attach_vertex_values(graph, [key])
        except ValueError as e:
            raise FallbackToInterpreter(str(e)) from e
        return snap.vertex_values[key]

    # -- personalized PageRank groups ---------------------------------------

    def _run_ppr(self, members: list, batch_id: str) -> bool:
        from titan_tpu.models.pagerank import (
            pagerank_personalized_batched, top_k_per_user)
        from titan_tpu.olap.serving.hbm import (AdmissionError, price,
                                                snapshot_csr_bytes)

        sched = self.sched
        plan0: PPRPlan = members[0].plan
        labels = list(plan0.labels) if plan0.labels else None
        # dense window sweeps have no overlay seam: compacted=True
        # folds the live overlay first (the heavy queue's documented
        # pagerank/dense fallback)
        lease = sched.pool.acquire(labels=labels,
                                   directed=plan0.directed,
                                   compacted=True)
        with lease as snap:
            epoch_info = lease.epoch_info \
                or {"epoch": getattr(snap, "epoch", 0)}
            runnable, sources = [], []
            for r in members:
                try:
                    sources.append(snap.dense_of(int(r.plan.source)))
                    runnable.append(r)
                except (KeyError, TypeError, ValueError) as e:
                    r.finish(error=ValueError(
                        f"unknown ppr source {r.plan.source!r}: {e}"))
            if not runnable:
                return False
            key = id(snap)
            price(snap, ("out",), self._metrics)
            nbytes = snapshot_csr_bytes(snap)
            try:
                sched.ledger.reserve(key, nbytes)
            except AdmissionError as e:
                for r in runnable:
                    r.finish(error=e)
                return False
            sched._evictable.setdefault(key, snap)
            # per-tenant HBM hold + byte-seconds, like the heavy queue
            share = nbytes / len(runnable)
            for r in runnable:
                sched.tenants.hold_hbm(r.tenant, share)
            try:
                t0 = time.time()
                ranks, iters = pagerank_personalized_batched(
                    snap, sources, iterations=plan0.iterations,
                    damping=plan0.damping, overlay=lease.overlay)
                exec_ms = (time.time() - t0) * 1e3
            finally:
                wall = time.time() - t0
                for r in runnable:
                    sched.tenants.drop_hbm(r.tenant, share)
                    sched.tenants.hbm_byte_seconds(r.tenant,
                                                   share * wall)
                sched.ledger.unpin(key)
            self._metrics.counter("serving.interactive.ppr_users") \
                .inc(len(runnable))
            for s, r in enumerate(runnable):
                plan: PPRPlan = r.plan
                recs = top_k_per_user(
                    ranks[s:s + 1], snap.vertex_ids, k=plan.top_k,
                    exclude=[None if plan.include_source
                             else sources[s]])[0]
                r.finish(result={
                    "result": [[vid, rank] for vid, rank in recs],
                    "batch": batch_id, "fused_k": len(members),
                    "iterations": int(iters),
                    "wait_ms": round(r.wait_ms, 3),
                    "exec_ms": round(exec_ms, 3),
                    "epoch": epoch_info})
            return True
