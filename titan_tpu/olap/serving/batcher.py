"""Multi-source fusion: execute compatible jobs as one batched device run.

The batcher is the execution half of the serving layer: given a group of
admitted jobs leased onto ONE snapshot, it

* fuses BFS jobs into a single ``[K, n]`` multi-source run
  (models/bfs_hybrid.frontier_bfs_batched) — the per-level plan and
  every edge-chunk gather are shared across the K jobs, amortizing the
  per-round plan floor K-fold (PERF_NOTES "K-way plan-amortization
  model"). Cancellation and timeout act through the kernel's per-job
  early-exit mask at level boundaries;
* fuses fresh SSSP / WCC jobs into per-member cohorts
  (models/frontier._frontier_cohort);
* runs everything else singly through ONE body (``run_single``) and the
  kind's row (serving/kinds.py: the kernel call, the parameters'
  defaults and the result keys are the row's ``run``), honoring
  cancel-before-start.

Results are plain dicts; the full distance arrays stay host-side under
keys the wire form omits (Job.to_wire) — callers resolve per-target
distances via ``params['targets']``.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import numpy as np

from titan_tpu.obs.tracing import phase
from titan_tpu.olap.serving.jobs import Job
from titan_tpu.olap.serving.kinds import (KINDS, ParamError, RunContext,
                                          checkpointing, dense_source,
                                          sssp_answer, under, wants_parents,
                                          wcc_answer)


@contextmanager
def job_phase(job, name: str, **attrs):
    """A leaf phase of a job's host work outside its ``run`` (the lease,
    HBM admission, counting an answer): a span under the job's current
    ``attempt`` and a profiler annotation, so a device idle gap between
    two runs carries its name."""
    h = job.trace
    with under(h, h.attempt if h is not None else None):
        with phase(name, **attrs) as ph:
            yield ph


def _epoch_token(snap, overlay):
    """Checkpoint-compatibility token: the snapshot epoch, widened with
    the overlay delta seq when a live overlay is active. Checkpoints
    resume only on an EXACT match (olap/recovery JobRecovery.latest) —
    overlay deltas between attempts would otherwise leak stale
    reachability into the resumed state (tombstones are not monotone),
    so a changed seq forces a clean restart instead."""
    e = getattr(snap, "epoch", None)
    if overlay is not None and not overlay.empty:
        return [e, overlay.seq]
    return e


def _bfs_result(snap, dist_row: np.ndarray, levels: int, inf: int,
                params: dict, parent_row=None) -> dict:
    reached = int((dist_row < inf).sum())
    out = {"levels": int(levels), "reached": reached, "n": int(dist_row.shape[0]),
           "dist": dist_row}
    if parent_row is not None:
        # the BFS tree, dense ids as ``dist`` is indexed: the source its
        # own parent, -1 where the source reaches nobody
        out["parent"] = parent_row
    targets = params.get("targets")
    if targets:
        td = {}
        for t in targets:
            try:
                d = int(dist_row[snap.dense_of(int(t))])
            except Exception:     # unknown vertex / malformed value —
                d = None          # a bad target is None, never a crash
            td[str(t)] = d if d is not None and d < inf else None
        out["targets"] = td
    return out


class Batcher:
    """Stateless executor over leased snapshots (the scheduler owns the
    queue, admission and leases).

    Mesh-aware placement (ISSUE 13): with ``mesh`` set, batched BFS
    cohorts run over the multi-device mesh — the leased snapshot's
    chunked CSR is placed once per snapshot through
    ``parallel/partition.place_batched_csr`` (edge image's chunk
    columns sharded over ``"v"``, per-vertex arrays replicated, the
    ``[K, n]`` dist sharded ``P(None, "v")`` with K replicated) and the
    UNCHANGED batched kernels are GSPMD-partitioned from those
    committed placements, so K-way plan amortization and sharding
    compose. Live-overlay leases run unmeshed (the overlay's COO/
    tombstone buffers belong to the single-device layout) — recorded
    per group as ``meshed`` on the run span."""

    def __init__(self, max_batch: int = 16, mesh=None):
        self.max_batch = max_batch
        self.mesh = mesh

    def would_mesh(self, kind: str, overlay) -> bool:
        """THE meshed-execution predicate — the scheduler's per-device
        HBM admission accounting queries this exact method, so the
        bytes the ledger charges and the layout this batcher actually
        uploads can never disagree (a forked copy relaxing one side
        would over-commit real device HBM past the admission guard)."""
        return (self.mesh is not None
                and int(self.mesh.devices.size) > 1
                and KINDS[kind].meshes
                and (overlay is None or overlay.empty))

    def run_batch(self, jobs: list[Job], snap, overlay=None) -> None:
        """Kind-generic batch entry (the scheduler's one dispatch
        point): BFS groups go through the [K, n] batched kernel,
        SSSP/WCC groups through the frontier cohort driver
        (``_BATCHED``). The scheduler's grouping key always carries
        the kind, so a group is single-kind by construction."""
        road = self._BATCHED.get(jobs[0].spec.kind)
        if road is not None:
            road(self, jobs, snap, overlay=overlay)
        else:
            for job in jobs:
                self.run_single(job, snap, overlay=overlay)

    # -- batched BFS --------------------------------------------------------

    def run_bfs_batch(self, jobs: list[Job], snap, overlay=None) -> None:
        """Execute K BFS jobs as one batched [K, n] device run; each
        job's row is bit-equal to a sequential single-source run. Jobs
        whose source does not resolve fail up front (they never join the
        batch); cancellation/timeout drop individual jobs at level
        boundaries via the kernel's keep mask.

        Recovery plane: a retry attempt with a valid checkpoint resumes
        SOLO (its level counter differs from any fresh batchmate, and
        the batched kernel runs ONE shared level loop); fresh jobs — and
        retries restarting clean — fuse as usual. Checkpoints capture
        each active job's dist row at its cadence; an injected fault
        raising out of a level boundary fails the WHOLE batch (that is
        what a real worker death does), and each member then retries
        under its own policy. A job that asked for ``parents`` saves the
        parent plane beside ``dist`` and resumes from both (a group is
        all of one mind: ``kinds._bfs_knobs``); a checkpoint that lacks
        the plane its job needs is not resumed from."""
        t_fuse0 = time.time()
        fresh: list[Job] = []
        fresh_src: list[int] = []
        resumed: list[tuple[Job, int, object]] = []
        for job in jobs:
            try:
                src = dense_source(snap, job.spec.params)
                # junk max_levels is a param error too — it must fail
                # permanently HERE, not detonate retryably mid-group
                int(job.spec.params.get("max_levels", 1000))
            except (KeyError, ValueError, TypeError) as e:
                # param errors are permanent: retrying cannot fix them
                job.fail(f"{type(e).__name__}: {e}", permanent=True)
                continue
            ck = None
            rec = job.recovery
            # adoption: any retry attempt, OR a FIRST attempt carrying
            # an idempotency key (fleet failover redispatch — the
            # logical job already ran elsewhere and its checkpoints
            # share the key, so attempt 1 here must resume, not
            # restart; a keyed first run with no checkpoint is simply
            # fresh, never counted restarted)
            if rec is not None and (job.attempt > 1
                                    or job.spec.idempotency_key):
                ck = rec.latest(kind="bfs",
                                epoch=_epoch_token(snap, overlay))
                if ck is not None and wants_parents(job.spec.params) \
                        and "parent" not in ck.arrays:
                    ck = None
                if ck is not None:
                    rec.resumed(ck.round)
                elif job.attempt > 1:
                    rec.restarted()
            if ck is not None:
                resumed.append((job, src, ck))
            else:
                fresh.append(job)
                fresh_src.append(src)
        # fuse decision record (obs): K, shared-plan reuse, and why a
        # member ran solo — the amortization evidence per trace
        t_fuse1 = time.time()
        for job in fresh:
            if job.trace is not None:
                job.trace.event("fuse", t0=t_fuse0, t1=t_fuse1,
                                k=len(fresh), shared_plan=len(fresh) > 1)
        for job, _src, ck in resumed:
            if job.trace is not None:
                job.trace.event("fuse", t0=t_fuse0, t1=t_fuse1, k=1,
                                shared_plan=False,
                                solo="resumed from checkpoint "
                                     f"round {ck.round}")
        if fresh:
            self._bfs_group(fresh, fresh_src, snap, None, 0,
                            overlay=overlay)
        for job, src, ck in resumed:
            self._bfs_group(
                [job], [src], snap, np.asarray(ck.arrays["dist"])[None, :],
                ck.round, overlay=overlay,
                init_parent=np.asarray(ck.arrays["parent"])[None, :]
                if wants_parents(job.spec.params) else None)

    def _bfs_group(self, runnable: list[Job], sources: list[int], snap,
                   init_dist, start_level: int, overlay=None,
                   init_parent=None) -> None:
        from titan_tpu.models.bfs import INF
        from titan_tpu.models.bfs_hybrid import (batched_is_warm,
                                                 build_chunked_csr,
                                                 frontier_bfs_batched,
                                                 warm_batched)
        from titan_tpu.obs import devprof

        K = len(runnable)
        for job in runnable:
            job.batch_k = K
        # the group's one answer to "with the BFS tree?": its members
        # agree (the knob is in their batch key)
        parents = wants_parents(runnable[0].spec.params)
        dropped = [None] * K    # terminal state decided at a boundary
        n = snap.n if hasattr(snap, "n") else snap["n"]
        # mesh placement: overlay leases stay single-device (the
        # overlay's device buffers belong to the unsharded layout);
        # everything else runs over the mesh via the placed graph dict
        target = snap
        meshed = self.would_mesh("bfs", overlay)
        if meshed:
            from titan_tpu.parallel.partition import place_batched_csr
            target = place_batched_csr(snap, self.mesh)
        # device-run spans (obs): one "run" per job covering the shared
        # level loop; per-level "round" children carry the job's OWN
        # frontier count — all host timestamps from the level callback
        # the kernel already makes (no extra syncs)
        runs = [job.trace.start("run", k=K, start_level=start_level,
                                **({"overlay_edges": overlay.count,
                                    "overlay_tombs": overlay.tomb_count}
                                   if overlay is not None
                                   and not overlay.empty else {}),
                                **({"meshed": int(self.mesh.devices.size)}
                                   if meshed else {}))
                if job.trace is not None else None
                for job in runnable]
        if K == 1 and not meshed and (overlay is None or overlay.empty):
            # the first lone job on a layout of a new shape builds every
            # program a source can meet (the push's rungs, the pull's
            # ladder: bfs_hybrid.warm_batched), so that no later source
            # builds one inside a served window; the job's own clock
            # starts behind it
            g = build_chunked_csr(snap)
            if not batched_is_warm(g, K, parents=parents):
                with under(runnable[0].trace, runs[0]), \
                        phase("bfs.build", K=K, n=g["n"], parents=parents):
                    warm_batched(g, K, parents=parents)
        started = time.time()
        # anchor AFTER the run spans open so the first round's window
        # nests inside them (children must not start before parents)
        prev_t = [time.time()]

        def on_level(level, nf):
            keep = np.ones(K, bool)
            now = time.time()
            for i, job in enumerate(runnable):
                if job.trace is not None and dropped[i] is None:
                    job.trace.event("round", parent=runs[i],
                                    t0=prev_t[0], t1=now, level=level,
                                    frontier=int(nf[i]))
                if dropped[i] is not None:
                    keep[i] = False
                    continue
                job.last_round = level
                rec = job.recovery
                if rec is not None and rec.faults is not None:
                    # deterministic fault injection (tests): raising
                    # here kills the batch, like a real worker death
                    rec.faults.check(level, job.attempt, snap)
                if job.cancel_requested:
                    dropped[i] = "cancel"
                    keep[i] = False
                elif job.spec.timeout_s is not None and \
                        now - started > job.spec.timeout_s:
                    dropped[i] = "timeout"
                    keep[i] = False
            prev_t[0] = now
            return keep if not keep.all() else None

        token = _epoch_token(snap, overlay)

        def checkpoint(level, state, act):
            planes = dict(zip(("dist", "parent"),
                              state if parents else (state,)))
            for i, job in enumerate(runnable):
                rec = job.recovery
                if rec is not None and act[i] and rec.due(level):
                    rec.save(level,
                             {name: np.asarray(a[i, :n])
                              for name, a in planes.items()},
                             kind="bfs",
                             meta={"epoch": token})

        wants_ckpt = any(j.recovery is not None
                         and j.recovery.store is not None
                         for j in runnable)
        try:
            # the level loop's leaf phases (bfs.seed, bfs.plan,
            # bfs.sweep, bfs.exhaust), the readback's and the programs'
            # kernel spans journal under the FIRST member's `run` span,
            # as a WCC cohort's do: one thread drives the batch, and at
            # K = 1 that is the job
            with under(runnable[0].trace, runs[0]):
                out, levels, completed = frontier_bfs_batched(
                    target, sources, max_levels=int(
                        runnable[0].spec.params.get("max_levels", 1000)),
                    on_level=on_level, return_device=True,
                    init_dist=init_dist, start_level=start_level,
                    checkpoint=checkpoint if wants_ckpt else None,
                    overlay=overlay, parents=parents,
                    init_parent=init_parent)
                # the one readback of the answer: [K, n] depths, and
                # the [K, n] parents where the group asked for them
                planes = out if parents else (out,)
                nbytes = sum(int(a.nbytes) for a in planes)
                with phase("bfs.result", bytes=nbytes,
                           parents=parents) as ph:
                    with ph.sync():
                        planes = [np.asarray(a) for a in planes]
                    devprof.count_d2h("bfs.result", nbytes)
                dist = planes[0]
                parent = planes[1] if parents else None
        except Exception as e:
            for i, job in enumerate(runnable):
                if job.trace is not None:
                    job.trace.end(runs[i], error=f"{type(e).__name__}")
                job.fail(f"{type(e).__name__}: {e}")
            return
        inf = int(INF)
        for i, job in enumerate(runnable):
            if job.trace is not None:
                job.trace.end(runs[i], levels=int(levels[i]))
        for i, job in enumerate(runnable):
            if completed[i]:
                job.complete(_bfs_result(
                    snap, dist[i], levels[i], inf, job.spec.params,
                    parent[i] if parents else None))
            elif dropped[i] == "timeout":
                job.time_out()
            else:
                job.mark_cancelled()

    # -- batched SSSP / WCC cohorts -----------------------------------------

    def run_frontier_batch(self, jobs: list[Job], snap,
                           overlay=None) -> None:
        """Execute a same-kind group of SSSP or WCC jobs as one fused
        cohort (models/frontier.frontier_sssp_batched /
        frontier_wcc_batched): per-member device state under ONE shared
        round loop with a single stacked plan readback per round, each
        member bit-equal to its sequential run. Fresh first attempts
        fuse; retry attempts and idempotency-keyed redispatches run
        SOLO through ``run_single`` (their adoption bookkeeping and —
        when a checkpoint matches — a round counter no fresh batchmate
        shares; the same split the batched BFS makes for resumes)."""
        t_fuse0 = time.time()
        kind = jobs[0].spec.kind
        fresh: list[Job] = []
        fresh_src: list[int] = []
        solo: list[Job] = []
        for job in jobs:
            src = 0
            if kind == "sssp":
                try:
                    src = dense_source(snap, job.spec.params)
                except (KeyError, ValueError, TypeError) as e:
                    job.fail(f"{type(e).__name__}: {e}", permanent=True)
                    continue
            rec = job.recovery
            if rec is not None and (job.attempt > 1
                                    or job.spec.idempotency_key):
                solo.append(job)
            else:
                fresh.append(job)
                fresh_src.append(src)
        t_fuse1 = time.time()
        for job in fresh:
            if job.trace is not None:
                job.trace.event("fuse", t0=t_fuse0, t1=t_fuse1,
                                k=len(fresh), kind=kind,
                                shared_plan=len(fresh) > 1)
        for job in solo:
            if job.trace is not None:
                job.trace.event("fuse", t0=t_fuse0, t1=t_fuse1, k=1,
                                kind=kind, shared_plan=False,
                                solo="retry/redispatch attempt: may "
                                     "resume from a checkpoint")
        if fresh:
            self._frontier_group(fresh, fresh_src, snap,
                                 overlay=overlay)
        for job in solo:
            self.run_single(job, snap, overlay=overlay)

    def _frontier_group(self, runnable: list[Job], sources: list[int],
                        snap, overlay=None) -> None:
        from titan_tpu.models.frontier import (frontier_sssp_batched,
                                               frontier_wcc_batched)

        kind = runnable[0].spec.kind
        K = len(runnable)
        for job in runnable:
            job.batch_k = K
        started = time.time()
        dropped = [None] * K    # terminal state decided at a boundary
        runs = [job.trace.start("run", kind=kind, k=K,
                                **({"overlay_edges": overlay.count,
                                    "overlay_tombs": overlay.tomb_count}
                                   if overlay is not None
                                   and not overlay.empty else {}))
                if job.trace is not None else None
                for job in runnable]
        # per-member round-window anchors, after the run spans open
        prev_t = [time.time()] * K

        def on_round(k, rounds):
            job = runnable[k]
            now = time.time()
            if job.trace is not None:
                job.trace.event("round", parent=runs[k],
                                t0=prev_t[k], t1=now, round=rounds)
                prev_t[k] = now
            job.last_round = rounds
            rec = job.recovery
            if rec is not None and rec.faults is not None:
                # raising here kills the WHOLE cohort — that is what a
                # real worker death does, same as the batched BFS; each
                # member then retries under its own policy
                rec.faults.check(rounds, job.attempt, snap)
            if job.cancel_requested:
                dropped[k] = "cancel"
                return False
            if job.spec.timeout_s is not None and \
                    now - started > job.spec.timeout_s:
                dropped[k] = "timeout"
                return False
            return True

        # what a member saves, a solo retry resumes (run_single): one
        # shape, the row's
        token = _epoch_token(snap, overlay)
        savers = [checkpointing(KINDS[kind], job.recovery, token)[0]
                  for job in runnable]

        def ckpt(k, rounds, state):
            if savers[k] is not None:
                savers[k](rounds, state)

        wants_ckpt = any(save is not None for save in savers)
        params0 = runnable[0].spec.params
        try:
            if kind == "sssp":
                outs, rounds_l, stopped = frontier_sssp_batched(
                    snap, sources,
                    delta=params0.get("delta"),
                    quantile_mass=params0.get("quantile_mass"),
                    max_rounds=int(params0.get("max_rounds", 10_000)),
                    on_round=on_round,
                    checkpoint=ckpt if wants_ckpt else None,
                    overlay=overlay)
            else:
                # the cohort's leaf phases (the shared peel's bfs.level
                # and wcc.seed, wcc.propagate, a wcc.result a member)
                # journal under its FIRST member's `run` span: one
                # thread drives the cohort, and at K = 1 that is the job
                with under(runnable[0].trace, runs[0]):
                    outs, rounds_l, stopped = frontier_wcc_batched(
                        snap, K, on_round=on_round,
                        checkpoint=ckpt if wants_ckpt else None,
                        overlay=overlay)
        except Exception as e:
            for i, job in enumerate(runnable):
                if job.trace is not None:
                    job.trace.end(runs[i], error=f"{type(e).__name__}")
                job.fail(f"{type(e).__name__}: {e}")
            return
        for i, job in enumerate(runnable):
            if job.trace is not None:
                job.trace.end(runs[i], rounds=int(rounds_l[i]))
            if stopped[i] is not None:
                if dropped[i] == "timeout":
                    job.time_out()
                else:
                    job.mark_cancelled()
                continue
            if kind == "sssp":
                answer = sssp_answer(outs[i], rounds_l[i])
            else:       # counted where it was read back: wcc.result
                with job_phase(job, "wcc.count"):
                    answer = wcc_answer(outs[i], rounds_l[i])
            job.complete(answer)

    # -- single execution ---------------------------------------------------

    def run_single(self, job: Job, snap, overlay=None) -> None:
        """One job alone (still async from the caller's view), through
        its kind's row (kinds.KINDS): ONE body for every kind, the few
        lines that are a kind's own in the row's ``run``. Cancellation
        and timeout act at ROUND boundaries through the kernel's
        ``on_round`` veto (models/frontier RoundInterrupted) — the
        single-execution analog of the batched kernel's level mask. The
        same boundaries drive the recovery plane (job.recovery): fault
        injection, checkpoint capture at the job's cadence, and — on a
        retry attempt — resume from the newest valid checkpoint
        (epoch-matched; otherwise clean restart). Param errors fail
        permanently (no retry)."""
        kind = job.spec.kind
        row = KINDS.get(kind)
        if row is None:
            job.fail(f"unknown job kind {kind!r}", permanent=True)
            return
        job.batch_k = 1
        if row.run is None:
            # the batched road owns its own resume bookkeeping (doing
            # it here too would double-count serving.recovery.resumes /
            # rounds_replayed)
            self._BATCHED[kind](self, [job], snap, overlay=overlay)
            return
        params = dict(job.spec.params)
        params.pop("faults", None)       # injector is not a kernel param
        rec = job.recovery
        started = time.time()
        interrupted = {}

        # a host job (no snapshot) has no device run: no `run` span, no
        # checkpoint to adopt
        h = job.trace
        run_span = None
        if h is not None and snap is not None:
            run_span = h.start(
                "run", kind=kind,
                **({"overlay_edges": overlay.count,
                    "overlay_tombs": overlay.tomb_count}
                   if overlay is not None and not overlay.empty
                   else {}))
        # round-window anchor: at/after the run span's start so round
        # children nest inside it
        prev_t = [time.time()]
        # per-round timeline (obs): rounds are stamped from the host
        # callback below; a kind whose row says `round_trace` takes them
        # from _frontier_run's existing mass-accounting trace instead —
        # it already carries frontier size / listed chunk mass / plan
        # cost per round at zero extra syncs (the stats readback happens
        # regardless), so the span timeline gets the band/plan story
        # for free
        trace_rounds = None
        _csr_trace_prev = None
        if h is not None and row.round_trace:
            from titan_tpu.models.bfs_hybrid import build_chunked_csr
            _csr = build_chunked_csr(snap)
            _csr_trace_prev = _csr.get("_trace_rounds")
            trace_rounds = []
            _csr["_trace_rounds"] = trace_rounds

        def on_round(rounds):
            job.last_round = rounds
            if h is not None and trace_rounds is None:
                now = time.time()
                h.event("round", parent=run_span, t0=prev_t[0], t1=now,
                        round=rounds)
                prev_t[0] = now
            if rec is not None and rec.faults is not None:
                rec.faults.check(rounds, job.attempt, snap)
            if job.cancel_requested:
                interrupted["why"] = "cancel"
                return False
            if job.spec.timeout_s is not None and \
                    time.time() - started > job.spec.timeout_s:
                interrupted["why"] = "timeout"
                return False
            return True
        epoch = _epoch_token(snap, overlay)
        ck = None
        # adoption: any retry attempt, OR a first attempt under an
        # idempotency key (fleet failover redispatch: the checkpoint
        # store is shared and keyed, so attempt 1 here resumes the
        # logical job's newest checkpoint instead of restarting; keyed
        # first runs with no checkpoint are fresh, never "restarted")
        if rec is not None and snap is not None \
                and (job.attempt > 1 or job.spec.idempotency_key):
            ck = rec.latest(kind=kind, epoch=epoch)
            if ck is not None:
                rec.resumed(ck.round)
            elif job.attempt > 1:
                rec.restarted()
        checkpoint, resume = checkpointing(row, rec, epoch, ck)

        try:
            job.complete(row.run(RunContext(
                job, snap, overlay, params, on_round, checkpoint, resume,
                run_span)))
        except ParamError as e:
            job.fail(str(e), permanent=True)
        except Exception as e:
            from titan_tpu.models.frontier import RoundInterrupted
            if isinstance(e, RoundInterrupted):
                if interrupted.get("why") == "timeout":
                    job.time_out()
                else:
                    job.mark_cancelled()
            else:
                job.fail(f"{type(e).__name__}: {e}")
        finally:
            if h is not None:
                if trace_rounds is not None:
                    # bridge _frontier_run's per-round tuples
                    # (band, frontier, chunk_mass, t_plan_done, plan_s)
                    # into the span timeline, then detach the hook from
                    # the snapshot's cached CSR
                    t_prev = run_span.t_start if run_span is not None \
                        else started
                    for i, (band, nf, m8, t, plan_s) in \
                            enumerate(trace_rounds):
                        extra = {"band": float(band)} \
                            if 0.0 < float(band) < 1e30 else {}
                        h.event("round", parent=run_span, t0=t_prev,
                                t1=t, round=i, frontier=int(nf),
                                chunk_mass=int(m8),
                                plan_ms=round(plan_s * 1e3, 3), **extra)
                        t_prev = t
                    if _csr_trace_prev is None:
                        _csr.pop("_trace_rounds", None)
                    else:
                        _csr["_trace_rounds"] = _csr_trace_prev
                if run_span is not None:
                    h.end(run_span, rounds=int(job.last_round))

    #: the batched kernels: kind -> the road a group of its jobs takes
    #: (a kind whose row has no ``run`` takes it alone too)
    _BATCHED = {"bfs": run_bfs_batch, "sssp": run_frontier_batch,
                "wcc": run_frontier_batch}
