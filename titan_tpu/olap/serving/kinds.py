"""One row a job kind: what it reads, reserves, checkpoints and runs.

``KINDS`` is the serving layer's one table of job kinds. The scheduler
asks a row what to refuse at ``submit``, how to lease (``compacted``,
``edge_keys``), what to reserve on the HBM ledger (``images``, ``work``)
and which jobs may share a run (``batch_key``, ``meshes``); the batcher
asks it how a run is traced (``round_trace``), what a checkpoint holds
(``checkpoint``) and calls its ``run``. Nothing else in the package
names a kind but the batcher's choice of a BATCHED kernel (the [K, n]
BFS, the SSSP / WCC cohorts). A new kind is its model module and a row
here (docs/serving.md, "Adding a job kind").

Wire form, a kind's parameters and result keys: docs/serving.md.
"""

from __future__ import annotations

from contextlib import nullcontext
from dataclasses import dataclass
from typing import Callable, NamedTuple, Optional

import numpy as np

from titan_tpu.obs import devprof
from titan_tpu.obs.tracing import phase, scope
from titan_tpu.olap.serving import hbm


class Image(NamedTuple):
    """A resident, evictable device image a run reads."""
    #: ledger key ``(key, id(snap))``; None: the forward image, under
    #: ``id(snap)`` alone (its per-device share where the run is meshed)
    key: Optional[str]
    #: the count kept on the snapshot that prices it (hbm.price)
    count: str
    nbytes: Callable                    # bytes(snap)
    #: the snapshot attribute an eviction drops
    attr: str


class Work(NamedTuple):
    """The working set reserved for a run and released behind it."""
    key: str
    nbytes: Callable                    # bytes(snap)
    #: the working set follows from who shares the run: ``nbytes`` is
    #: then bytes(snap, specs, devices), ``specs`` the group's and
    #: ``devices`` the mesh's size where the run places over one (else
    #: 1); 0 reserves nothing
    grouped: bool = False

    def price(self, snap, specs, devices: int) -> int:
        return self.nbytes(snap, specs, devices) if self.grouped \
            else self.nbytes(snap)


#: marks a meta field a resume cannot do without
REQUIRED = object()


@dataclass(frozen=True)
class Checkpoint:
    """The shape of a kind's checkpoint: the one definition its writers
    (a single run, a cohort) and its reader (a single run) share."""
    #: the name the kernel's ``resume=`` gives the round a run restarts at
    round: str
    #: the state's arrays, saved and handed back by name; None: the whole
    #: state (a DenseProgram's is its own), handed back under ``"state"``
    arrays: Optional[tuple] = None
    #: (name, cast on save, default on resume or REQUIRED) a field
    meta: tuple = ()

    def save(self, state) -> tuple:
        """``(arrays, meta)`` of a kernel's ``state``."""
        names = state if self.arrays is None else self.arrays
        return ({k: np.asarray(state[k]) for k in names},
                {k: cast(state[k]) for k, cast, _default in self.meta})

    def resume(self, ck) -> dict:
        """The kernel's ``resume=`` from a stored checkpoint."""
        out = {"state": ck.arrays} if self.arrays is None \
            else {k: ck.arrays[k] for k in self.arrays}
        out[self.round] = ck.round
        for k, _cast, default in self.meta:
            out[k] = ck.meta[k] if default is REQUIRED \
                else ck.meta.get(k, default)
        return out


@dataclass
class RunContext:
    """What ``Batcher.run_single`` hands a row's ``run``."""
    job: object
    snap: object
    overlay: object
    params: dict                        # the spec's, ``faults`` taken out
    on_round: Callable                  # (round) -> keep going?
    checkpoint: Optional[Callable]      # (round, state), where one is kept
    resume: Optional[dict]
    span: object = None                 # the job's ``run`` span

    def under(self):
        """The scope that hangs a kernel's leaf phases from the job's
        ``run`` span."""
        return under(self.job.trace, self.span)


def under(handle, span):
    """The scope in which phases journal as children of ``span`` of the
    job's trace (nothing without a trace)."""
    return nullcontext() if handle is None \
        else scope(handle.tracer, handle.trace_id, span)


@dataclass(frozen=True)
class Kind:
    name: str
    #: (RunContext) -> the result dict; None: the kind has no road of
    #: its own and runs, alone too, as a batch of one (``Batcher._BATCHED``)
    run: Optional[Callable] = None
    #: in the order admission reserves them; none: a host job, no lease
    images: tuple = ()
    work: Optional[Work] = None
    #: the lease folds the live overlay first (the image has no seam)
    compacted: bool = False
    #: (spec) -> the knobs a fused run shares, beside the kind and the
    #: snapshot's; None: the kind runs alone
    batch_key: Optional[Callable] = None
    #: the batched layout places over a device mesh
    meshes: bool = False
    #: (spec) -> why ``submit`` refuses it, or None
    refuse: Callable = lambda spec: None
    #: (spec) -> the edge keys the lease extracts where the spec names none
    edge_keys: Callable = lambda spec: ()
    checkpoint: Optional[Checkpoint] = None
    #: the round timeline comes from ``_frontier_run``'s own per-round
    #: tuples (the ``_trace_rounds`` bridge), not from ``on_round``
    round_trace: bool = False


# -- what several rows share --------------------------------------------------

FORWARD = Image(None, "out", hbm.snapshot_csr_bytes, "_hybrid_csr")
PULL = Image("pagerank-pull", "in", hbm.snapshot_pull_bytes, "_pull_csr")


def dense_source(snap, params: dict) -> int:
    """Resolve a job's source to a dense index: ``source_dense`` wins,
    else ``source`` is an original vertex id mapped through the
    snapshot. Raises ValueError for ANY malformed value (None, lists,
    non-numeric strings) — callers catch it per job; it must never
    escape as a TypeError that could take the worker thread down."""
    try:
        if "source_dense" in params:
            return int(params["source_dense"])
        if "source" in params:
            return snap.dense_of(int(params["source"]))
    except KeyError as e:                 # dense_of: unknown vertex
        raise ValueError(str(e)) from e
    except (TypeError, ValueError) as e:
        raise ValueError(f"bad source value: {e}") from e
    raise ValueError("job params need 'source' (vertex id) or "
                     "'source_dense'")


class ParamError(ValueError):
    """A parameter no retry can fix: the job fails permanently."""


def sssp_answer(dist, rounds) -> dict:
    """An SSSP run's result, a single run's and a cohort member's."""
    from titan_tpu.models.frontier import FINF
    devprof.count_d2h("frontier.result", getattr(dist, "nbytes", 0))
    dist = np.asarray(dist)
    return {"rounds": int(rounds),
            "reached": int((dist < float(FINF)).sum()),
            "dist": dist}


def wcc_answer(labels, rounds) -> dict:
    """A WCC run's result, a single run's and a cohort member's; the
    caller opens the ``wcc.count`` phase where its spans hang. A label
    is its component's smallest vertex id, so a component is counted at
    the one vertex that carries its own id: one pass, where
    ``np.unique`` sorts all n labels inside the job's ``exec_ms``."""
    components = int((labels == np.arange(labels.shape[0],
                                          dtype=labels.dtype)).sum())
    return {"rounds": int(rounds), "components": components,
            "labels": labels}


def _communities(labels: np.ndarray) -> int:
    """Distinct labels of a CDLP answer. A label is a vertex id, so each
    is marked where it points: one pass, no sort."""
    seen = np.zeros(labels.shape[0], bool)
    seen[labels] = True
    return int(seen.sum())


def batch_key(spec) -> Optional[tuple]:
    """Grouping key: jobs with equal keys may fuse into one batch. The
    kind is always in the key (a mixed stream fuses into PER-ALGORITHM
    cohorts, never across kinds), plus every knob the fused run shares
    (the row's ``batch_key``). None: the job runs alone — its kind
    does, or a knob's value is junk (it runs, and fails, alone)."""
    row = KINDS.get(spec.kind)
    if row is None or row.batch_key is None:
        return None
    base = (spec.kind,
            tuple(spec.labels) if spec.labels is not None else None,
            bool(spec.directed))
    try:
        return base + row.batch_key(spec)
    except (TypeError, ValueError):
        return None


# -- the rows' own lines ------------------------------------------------------

def wants_parents(params: dict) -> bool:
    """Whether a ``bfs`` job asked for its BFS tree (``"parents":
    true``: the result array ``parent`` beside ``dist``)."""
    return params.get("parents") is True


def _bfs_knobs(spec) -> tuple:
    # one shared level loop, and one state: a job that wants the tree
    # does not fuse with one that does not (the group would carry the
    # second [K, n] plane, and pay its scatters, for all)
    return (int(spec.params.get("max_levels", 1000)),
            wants_parents(spec.params))


def _bfs_refuses(spec) -> Optional[str]:
    got = spec.params.get("parents", False) \
        if isinstance(spec.params, dict) else False
    if not isinstance(got, bool):
        return (f"bfs: 'parents' must be true or false, got {got!r}: "
                "with true the job answers the array 'parent' (the BFS "
                "tree, GAP's answer) beside 'dist'")
    return None


def _bfs_work(snap, specs, devices: int) -> int:
    # the parent plane of the jobs that asked for it; the depth plane
    # every BFS has carried since before the ledger priced working sets
    # stays unpriced
    if not wants_parents(specs[0].params):
        return 0
    return hbm.bfs_plane_bytes(snap.n, len(specs), devices)


def _sssp_knobs(spec) -> tuple:
    # the cohort runs each member's trajectory under cohort-wide mode
    # knobs, so differing knobs must not fuse
    delta = spec.params.get("delta")
    qm = spec.params.get("quantile_mass")
    return (int(spec.params.get("max_rounds", 10_000)),
            float(delta) if delta is not None else None,
            int(qm) if qm is not None else None)


def _run_sssp(ctx: RunContext) -> dict:
    from titan_tpu.models import frontier
    p = ctx.params
    try:
        src = dense_source(ctx.snap, p)
    except (KeyError, ValueError) as e:
        raise ParamError(f"{type(e).__name__}: {e}") from e
    dist, rounds = frontier.frontier_sssp(
        ctx.snap, src, delta=p.get("delta"),
        quantile_mass=p.get("quantile_mass"),
        max_rounds=int(p.get("max_rounds", 10_000)),
        on_round=ctx.on_round, checkpoint=ctx.checkpoint,
        resume=ctx.resume, overlay=ctx.overlay)
    return sssp_answer(dist, rounds)


def _run_pagerank(ctx: RunContext) -> dict:
    from titan_tpu.models import frontier
    p = ctx.params
    # the sweep's leaf phases (pr.sweep, pr.finish, pr.result) journal
    # under the job's `run` span; the readback is counted where it is
    # made (device.xfer.d2h_bytes{site="pagerank.result"})
    with ctx.under():
        rank, iters = frontier.pagerank_dense(
            ctx.snap, iterations=int(p.get("iterations", 20)),
            damping=float(p.get("damping", 0.85)), tol=p.get("tol"),
            on_round=ctx.on_round, checkpoint=ctx.checkpoint,
            resume=ctx.resume, overlay=ctx.overlay)
    return {"iterations": int(iters), "rank": rank}


def _run_wcc(ctx: RunContext) -> dict:
    from titan_tpu.models import frontier
    # the peel's, the propagation's and the readback's leaf phases
    # (bfs.level, wcc.seed, wcc.propagate, wcc.result) journal under the
    # job's `run` span; the readback is counted where it is made
    # (device.xfer.d2h_bytes{site="wcc.result"})
    with ctx.under():
        labels, rounds = frontier.frontier_wcc(
            ctx.snap, on_round=ctx.on_round, checkpoint=ctx.checkpoint,
            resume=ctx.resume, overlay=ctx.overlay)
        with phase("wcc.count"):
            return wcc_answer(labels, rounds)


def _run_cdlp(ctx: RunContext) -> dict:
    from titan_tpu.models import cdlp
    # the rounds' and the readback's leaf phases (cdlp.round,
    # cdlp.result) journal under the job's `run` span; the readback is
    # counted where it is made (device.xfer.d2h_bytes{site="cdlp.result"})
    with ctx.under():
        labels, iters = cdlp.cdlp(
            ctx.snap, iterations=int(ctx.params.get("iterations", 10)),
            on_round=ctx.on_round, checkpoint=ctx.checkpoint,
            resume=ctx.resume, overlay=ctx.overlay)
        with phase("cdlp.count"):
            communities = _communities(labels)
    return {"iterations": int(iters), "communities": communities,
            "labels": labels}


def _run_lcc(ctx: RunContext) -> dict:
    from titan_tpu.models import lcc
    # no checkpoint: a retried job starts over, the image still
    # resident. The parts' leaf phases (lcc.image, lcc.hub, lcc.tail,
    # lcc.result) journal under the job's `run` span; the readback is
    # counted where it is made (device.xfer.d2h_bytes{site="lcc.result"})
    with ctx.under():
        counts, coeff = lcc.lcc(ctx.snap, on_round=ctx.on_round,
                                overlay=ctx.overlay)
        with phase("lcc.count"):
            # every triangle stands at its three vertices
            triangles = int(counts.sum(dtype=np.int64)) // 3
    return {"triangles": triangles, "lcc": coeff,
            "triangle_counts": counts}


def _lcc_refuses(spec) -> Optional[str]:
    if spec.directed:
        return ("lcc on a directed snapshot: the specification's "
                "directed form (in- and out-neighbours together, a pair "
                "counted in each direction it is an edge) is not "
                "implemented; submit with directed=false")
    return None


def _run_bc(ctx: RunContext) -> dict:
    from titan_tpu.models import bc
    try:
        roots = bc.dense_roots(ctx.snap, ctx.params)
    except ValueError as e:
        raise ParamError(f"{type(e).__name__}: {e}") from e
    # no checkpoint: a retried job starts over. The phases (bc.forward
    # and bc.backward a group of roots, bc.result) journal under the
    # job's `run` span; the readback is counted where it is made
    # (device.xfer.d2h_bytes{site="bc.result"})
    with ctx.under():
        scores, levels, reached = bc.bc(
            ctx.snap, roots, on_round=ctx.on_round, overlay=ctx.overlay)
    return {"levels": levels, "reached": reached, "scores": scores}


def _bc_refuses(spec) -> Optional[str]:
    if spec.directed:
        return ("bc on a directed snapshot: the backward phase walks "
                "the out-edges, whose image a directed snapshot would "
                "need beside the in-edges', is not implemented; submit "
                "with directed=false")
    return None


def _run_dense(ctx: RunContext) -> dict:
    from titan_tpu.olap.tpu.engine import run_single
    program = ctx.params.pop("program")
    rec = ctx.job.recovery
    hook, every = None, 0
    if rec is not None and (ctx.checkpoint is not None
                            or rec.faults is not None):
        # dense programs have no on_round veto; the chunk boundary is
        # the only host hook, so the round is stamped and faults fire
        # here — and a fault plan WITHOUT a store still needs the
        # chunked loop (every=1) to get hooks
        every = rec.every if ctx.checkpoint is not None else 1

        def hook(it, state):
            ctx.on_round(it)
            if ctx.checkpoint is not None:
                ctx.checkpoint(it, state)
    res = run_single(program, ctx.snap, ctx.params, resume=ctx.resume,
                     checkpoint=hook, checkpoint_every=every)
    return {"iterations": res.iterations,
            **{k: np.asarray(v) for k, v in res.items()}}


def _dense_edge_keys(spec) -> tuple:
    # a DenseProgram that reads edge properties needs them extracted
    # into the snapshot — derive from the program
    program = spec.params.get("program")
    if program is not None and hasattr(program, "edge_keys"):
        return tuple(program.edge_keys())
    return ()


def _run_callable(ctx: RunContext) -> dict:
    return {"value": ctx.params["fn"]()}


_FRONTIER_STATE = ("val", "val_exp")

#: the order is the one ``submit``'s refusal of an unknown kind lists
KINDS: dict[str, Kind] = {row.name: row for row in (
    # same-snapshot jobs fuse into ONE [K, n] run, which keeps its own
    # checkpoint bookkeeping (Batcher.run_bfs_batch)
    Kind("bfs", images=(FORWARD,),
         work=Work("bfs-parents", _bfs_work, grouped=True),
         batch_key=_bfs_knobs, meshes=True, refuse=_bfs_refuses),
    Kind("sssp", _run_sssp, images=(FORWARD,), batch_key=_sssp_knobs,
         round_trace=True,
         checkpoint=Checkpoint(
             "rounds", _FRONTIER_STATE,
             (("bucket_end", float, REQUIRED),
              ("quantile_mass", int, REQUIRED)))),
    Kind("pagerank", _run_pagerank, images=(FORWARD, PULL),
         compacted=True, checkpoint=Checkpoint("it", ("rank",))),
    Kind("wcc", _run_wcc, images=(FORWARD,),
         batch_key=lambda spec: (),      # no per-job kernel knobs
         round_trace=True,
         checkpoint=Checkpoint("rounds", _FRONTIER_STATE,
                               (("levels", int, 0),))),
    Kind("cdlp", _run_cdlp,
         images=(FORWARD,
                 Image("cdlp-image", "cdlp", hbm.snapshot_cdlp_image_bytes,
                       "_cdlp_csr")),
         work=Work("cdlp-work", hbm.snapshot_cdlp_bytes),
         compacted=True, checkpoint=Checkpoint("it", ("labels",))),
    Kind("lcc", _run_lcc,
         images=(FORWARD,
                 Image("lcc-image", "in", hbm.snapshot_lcc_bytes,
                       "_lcc_csr")),
         work=Work("lcc-work", hbm.snapshot_lcc_work_bytes),
         compacted=True, refuse=_lcc_refuses),
    Kind("bc", _run_bc, images=(FORWARD, PULL),
         work=Work("bc-work", hbm.snapshot_bc_work_bytes),
         compacted=True, refuse=_bc_refuses),
    Kind("dense", _run_dense, images=(FORWARD,), compacted=True,
         edge_keys=_dense_edge_keys, checkpoint=Checkpoint("iteration")),
    # the host computer's async delegation hook
    Kind("callable", _run_callable),
)}


def image_keys() -> set:
    """Every ledger key name an image of some kind rides a snapshot
    under (what a retired snapshot must leave the ledger of)."""
    return {image.key for row in KINDS.values() for image in row.images
            if image.key is not None}


def checkpointing(row: Kind, rec, epoch, ck=None) -> tuple:
    """``(checkpoint, resume)`` a kernel takes, from the row's
    ``checkpoint``: ``checkpoint(round, state)`` saves at the job's
    cadence where the job keeps a store and the kind a shape (else
    None); ``resume`` is the kernel's form of the stored ``ck`` (else
    None). A cohort that writes a checkpoint and the single run that
    reads it both come here."""
    shape = row.checkpoint
    save = None
    if shape is not None and rec is not None and rec.store is not None:
        def save(round_, state):
            if rec.due(round_):
                arrays, meta = shape.save(state)
                rec.save(round_, arrays, kind=row.name,
                         meta={"epoch": epoch, **meta})
    resume = shape.resume(ck) \
        if shape is not None and ck is not None else None
    return save, resume
