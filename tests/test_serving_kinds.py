"""The seam of serving/kinds.py: a job kind is ONE row, and the
scheduler, the batcher and the server read it.

(a) a job of every snapshot kind reserves exactly its row's ledger keys,
in order, at the bytes the row's functions give, and lets its working
set go behind the run; (b) the lease is asked ``compacted`` as the row
says; (c) for every row with a ``checkpoint``, what the first attempt
saved (a COHORT's ``_frontier_group`` for sssp and wcc, ``run_single``
for the rest) the retry's ``run_single`` resumes, bit-equal to an
uninterrupted run, and the stored arrays and meta carry exactly the
row's names; (d) a NINTH row that this file alone registers is
submitted over ``POST /jobs``, leased, admitted, run and served with no
other edit; (e) the two refusals at ``submit``, word for word.
"""

import json
import time
import urllib.request

import numpy as np
import pytest

from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.recovery import FaultPlan
from titan_tpu.olap.serving import hbm, kinds
from titan_tpu.olap.serving.batcher import Batcher
from titan_tpu.olap.serving.kinds import KINDS
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager

#: the rows as they stood before the table (PR 44; lcc reads no pull
#: image since PR 48): the names a ledger
#: key is made of, in the order admission reserves them (None: the
#: forward image, under ``id(snap)`` alone; the last of cdlp's, lcc's
#: and bc's is the working set), and whether the lease folds the overlay
LEDGER = {
    "bfs": [None],
    "sssp": [None],
    "pagerank": [None, "pagerank-pull"],
    "wcc": [None],
    "cdlp": [None, "cdlp-image", "cdlp-work"],
    "lcc": [None, "lcc-image", "lcc-work"],
    "bc": [None, "pagerank-pull", "bc-work"],
    "dense": [None],
}
WORK = {"cdlp": "cdlp-work", "lcc": "lcc-work", "bc": "bc-work"}
BYTES = {None: hbm.snapshot_csr_bytes,
         "pagerank-pull": hbm.snapshot_pull_bytes,
         "cdlp-image": hbm.snapshot_cdlp_image_bytes,
         "cdlp-work": hbm.snapshot_cdlp_bytes,
         "lcc-image": hbm.snapshot_lcc_bytes,
         "lcc-work": hbm.snapshot_lcc_work_bytes,
         "bc-work": hbm.snapshot_bc_work_bytes}
COMPACTED = {"bfs": False, "sssp": False, "pagerank": True, "wcc": False,
             "cdlp": True, "lcc": True, "bc": True, "dense": True}
ORDER = ["bfs", "sssp", "pagerank", "wcc", "cdlp", "lcc", "bc", "dense",
         "callable"]


def graph(seed: int = 7):
    """One component of 60 vertices that the peel takes, six paths of
    nine (so that label propagation has rounds of its own to checkpoint
    in), a few vertices with no edge, under a seeded relabelling."""
    rng = np.random.default_rng(seed)
    src = list(rng.integers(0, 60, 240)) + list(range(59))
    dst = list(rng.integers(0, 60, 240)) + list(range(1, 60))
    at = 60
    for _ in range(6):
        ids = list(range(at, at + 9))
        src += ids[:-1]
        dst += ids[1:]
        at += 9
    n = at + 6
    perm = rng.permutation(n)
    a = perm[np.asarray(src)].astype(np.int32)
    b = perm[np.asarray(dst)].astype(np.int32)
    # a simple graph (LCC's neighbourhoods are sets): no loop, a pair once
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    pairs = np.unique(np.stack([lo, hi], 1)[lo != hi], axis=0)
    a, b = pairs[:, 0], pairs[:, 1]
    return n, np.concatenate([a, b]), np.concatenate([b, a])


@pytest.fixture(scope="module")
def few_hubs():
    """The served LCC path takes no hub count: the module's one value,
    here small enough for the graph."""
    from titan_tpu.models import lcc
    mp = pytest.MonkeyPatch()
    mp.setattr(lcc, "HUBS", 16)
    yield
    mp.undo()


def _source(snap) -> int:
    return int(np.argmax(snap.out_degree))


def _params(kind: str, snap) -> dict:
    from titan_tpu.models.bfs import BFS
    return {"bfs": {"source_dense": _source(snap)},
            "sssp": {"source_dense": _source(snap)},
            "pagerank": {"iterations": 6},
            "cdlp": {"iterations": 6},
            "bc": {"sources_dense": [_source(snap), 0]},
            "dense": {"program": BFS(max_iterations=100),
                      "source_dense": _source(snap)},
            }.get(kind, {})


# -- (a), (b): admission and the lease ---------------------------------------

@pytest.fixture(scope="module")
def admitted(few_hubs):
    """kind -> what one job of it asked of the pool and of the ledger
    (each kind on a scheduler and a snapshot of its own, so nothing is
    resident before it), run on first use."""
    seen: dict = {}

    def of(kind: str) -> dict:
        if kind in seen:
            return seen[kind]
        n, src, dst = graph()
        snap = snap_mod.from_arrays(n, src, dst)
        sched = JobScheduler(snapshot=snap, metrics=MetricManager())
        calls = {"acquire": [], "reserve": [], "snap": snap}
        acquire, reserve = sched.pool.acquire, sched.ledger.reserve

        def spy_acquire(**kw):
            calls["acquire"].append(kw)
            return acquire(**kw)

        def spy_reserve(key, nbytes):
            calls["reserve"].append((key, nbytes))
            return reserve(key, nbytes)
        sched.pool.acquire = spy_acquire
        sched.ledger.reserve = spy_reserve
        try:
            params = {"fn": lambda: 5} if kind == "callable" \
                else _params(kind, snap)
            job = sched.submit(JobSpec(kind=kind, params=params))
            assert job.wait(120) and job.state.value == "done", job.error
            (admit,) = [s for s in sched.tracer.spans(job.id)
                        if s.name == "job.admit"] or [None]
            calls["admit"] = admit.attrs if admit is not None else None
            calls["resident"] = dict(sched.ledger._bytes)
            calls["pinned"] = sched.ledger.pinned_bytes()
            calls["evictable"] = dict(sched._evictable)
        finally:
            sched.close()
        seen[kind] = calls
        return calls
    return of


@pytest.mark.parametrize("kind", sorted(LEDGER))
def test_a_job_reserves_its_rows_keys_in_order(admitted, kind):
    calls = admitted(kind)
    snap = calls["snap"]
    want = [(id(snap) if name is None else (name, id(snap)),
             BYTES[name](snap)) for name in LEDGER[kind]]
    assert calls["reserve"] == want
    # the row says the same, from its own functions
    # (a working set is priced from the snapshot and the specs that
    # share the run; one that reads 0 reserves nothing: a ``bfs`` job
    # that asked for no parents)
    row = KINDS[kind]
    work = row.work.price(snap, [JobSpec(kind=kind)], 1) \
        if row.work else 0
    assert [image.key for image in row.images] \
        + ([row.work.key] if work else []) == LEDGER[kind]
    assert [image.nbytes(snap) for image in row.images] \
        + ([work] if work else []) == [nbytes for _key, nbytes in want]
    # `job.admit` reads the sum; every count was priced in this job
    assert calls["admit"]["bytes"] == sum(b for _k, b in want)
    assert calls["admit"]["sizing_passes"] \
        == len({image.count for image in row.images})
    # behind the run: the images resident and evictable, nothing
    # pinned, the working set gone
    work = (WORK[kind], id(snap)) if kind in WORK else None
    assert calls["pinned"] == 0
    assert calls["resident"] == {k: b for k, b in want if k != work}
    assert set(calls["evictable"]) == set(calls["resident"])
    for image in row.images:
        handle = calls["evictable"][
            id(snap) if image.key is None else (image.key, id(snap))]
        assert handle is snap if image.key is None \
            else handle == (snap, image.attr)


@pytest.mark.parametrize("kind", ORDER)
def test_the_lease_is_asked_compacted_as_the_row_says(admitted, kind):
    calls = admitted(kind)
    if kind == "callable":          # no image: no lease, no admission
        assert KINDS[kind].images == () and KINDS[kind].work is None
        assert calls["acquire"] == [] and calls["reserve"] == []
        assert calls["admit"] is None
        return
    (asked,) = calls["acquire"]
    assert asked["compacted"] is KINDS[kind].compacted is COMPACTED[kind]
    assert asked["edge_keys"] == () and asked["directed"] is False


@pytest.mark.parametrize("kind", ORDER)
def test_only_a_row_with_a_batch_key_fuses(kind):
    fuses = {"bfs", "sssp", "wcc"}
    key = kinds.batch_key(JobSpec(kind=kind))
    assert (key is not None) == (kind in fuses) \
        == (KINDS[kind].batch_key is not None)
    if key is not None:
        assert key[:3] == (kind, None, False)
        assert kinds.batch_key(JobSpec(kind=kind, directed=True)) != key
    assert KINDS[kind].meshes == (kind == "bfs")
    # a junk knob runs (and fails) alone
    if kind in ("bfs", "sssp"):
        knob = {"bfs": "max_levels", "sssp": "max_rounds"}[kind]
        assert kinds.batch_key(JobSpec(kind=kind,
                                       params={knob: "junk"})) is None


# -- (c): one definition of a checkpoint's shape ------------------------------

#: kind -> (the round the first attempt dies at, the arrays and the meta
#: fields of its checkpoint, the result's arrays)
CHECKPOINTS = {
    "sssp": (3, {"val", "val_exp"}, {"bucket_end", "quantile_mass"},
             ["dist"]),
    "pagerank": (4, {"rank"}, set(), ["rank"]),
    "wcc": (3, {"val", "val_exp"}, {"levels"}, ["labels"]),
    "cdlp": (4, {"labels"}, set(), ["labels"]),
    "dense": (3, {"dist"}, set(), ["dist"]),
}


def test_the_rows_that_checkpoint():
    assert {k for k, row in KINDS.items() if row.checkpoint is not None} \
        == set(CHECKPOINTS)
    assert [KINDS[k].checkpoint.round for k in sorted(CHECKPOINTS)] \
        == ["it", "iteration", "it", "rounds", "rounds"]
    assert {k for k, row in KINDS.items() if row.round_trace} \
        == {"sssp", "wcc"}


@pytest.fixture(scope="module")
def recovered(tmp_path_factory):
    """kind -> an uninterrupted job, a job whose first attempt died at
    a round and whose retry resumed, every checkpoint the latter left
    and who ran which attempt; run on first use."""
    seen: dict = {}

    def of(kind: str) -> dict:
        if kind in seen:
            return seen[kind]
        n, src, dst = graph()
        snap = snap_mod.from_arrays(n, src, dst)
        metrics = MetricManager()
        sched = JobScheduler(
            snapshot=snap, metrics=metrics,
            checkpoint_dir=str(tmp_path_factory.mktemp(kind)))
        roads = []
        mp = pytest.MonkeyPatch()
        for road in ("_frontier_group", "run_single"):
            def spy(self, jobs, *a, _road=road,
                    _real=getattr(Batcher, road), **kw):
                job = jobs[0] if isinstance(jobs, list) else jobs
                roads.append((_road, job.id, job.attempt))
                return _real(self, jobs, *a, **kw)
            mp.setattr(Batcher, road, spy)
        crash = CHECKPOINTS[kind][0]
        try:
            plain = sched.submit(JobSpec(kind=kind,
                                         params=_params(kind, snap)))
            assert plain.wait(120) and plain.state.value == "done", \
                plain.error
            job = sched.submit(JobSpec(
                kind=kind,
                params=dict(_params(kind, snap),
                            faults=FaultPlan(crash_at_round=crash)),
                max_retries=1, checkpoint_every=1, retry_backoff_s=0.01))
            assert job.wait(120) and job.state.value == "done", job.error
            store = sched.ckpt_store
            saved = [store.load(p)
                     for p in store.checkpoints(job.recovery.key)]
            resume = [s for s in sched.tracer.spans(job.id)
                      if s.name == "resume"]
        finally:
            mp.undo()
            sched.close()
        seen[kind] = {"plain": plain, "job": job, "saved": saved,
                      "roads": [(r, a) for r, j, a in roads
                                if j == job.id],
                      "resume": resume, "crash": crash,
                      "resumes": metrics.counter_value(
                          "serving.recovery.resumes")}
        return seen[kind]
    return of


@pytest.mark.parametrize("kind", sorted(CHECKPOINTS))
def test_what_the_first_attempt_saved_run_single_resumes(recovered, kind):
    got = recovered(kind)
    job, plain, crash = got["job"], got["plain"], got["crash"]
    # a fresh sssp or wcc job runs in a cohort, which WRITES; its retry
    # runs solo through run_single, which READS
    first = "_frontier_group" if kind in ("sssp", "wcc") else "run_single"
    assert got["roads"] == [(first, 1), ("run_single", 2)]
    assert job.attempt == 2 and got["resumes"] == 1
    (resume,) = got["resume"]
    # a kernel saves a round before or behind the veto that kills it
    at = resume.attrs["from_round"]
    assert at in (crash - 1, crash)
    assert resume.attrs["rounds_replayed"] == crash - at
    by_attempt = {}
    for ck in got["saved"]:
        by_attempt.setdefault(ck.attempt, []).append(ck.round)
    assert by_attempt[1] == list(range(1, at + 1))  # every round before
    assert min(by_attempt.get(2, [at])) >= at       # resumed, not redone
    # bit for bit the uninterrupted run's answer
    for name in CHECKPOINTS[kind][3]:
        assert job.result[name].dtype == plain.result[name].dtype
        assert job.result[name].tobytes() == plain.result[name].tobytes()
    scalars = {k: v for k, v in plain.result.items()
               if not hasattr(v, "shape")}
    assert scalars and {k: job.result[k] for k in scalars} == scalars


@pytest.mark.parametrize("kind", sorted(CHECKPOINTS))
def test_a_checkpoint_carries_exactly_the_rows_names(recovered, kind):
    _crash, arrays, meta, _result = CHECKPOINTS[kind]
    shape = KINDS[kind].checkpoint
    if shape.arrays is not None:        # dense: the program's own state
        assert set(shape.arrays) == arrays
    assert {name for name, _cast, _default in shape.meta} == meta
    for ck in recovered(kind)["saved"]:
        assert ck.kind == kind
        assert set(ck.arrays) == arrays
        assert set(ck.meta) == {"epoch"} | meta
    # the kernel's resume= is the arrays, the meta and the round under
    # the row's name for it
    ck = recovered(kind)["saved"][0]
    resume = shape.resume(ck)
    assert resume[shape.round] == ck.round
    if shape.arrays is None:
        assert set(resume) == {"state", shape.round}
        assert set(resume["state"]) == arrays
    else:
        assert set(resume) == arrays | meta | {shape.round}


def test_a_checkpoint_from_before_a_meta_field_still_resumes():
    """``levels`` came to WCC's checkpoint after its first: a stored one
    without it resumes at 0; SSSP's two fields have no default."""
    class Stored:
        round = 4
        arrays = {"val": 1, "val_exp": 2}
        meta = {"epoch": 0}
    assert KINDS["wcc"].checkpoint.resume(Stored) == {
        "val": 1, "val_exp": 2, "rounds": 4, "levels": 0}
    with pytest.raises(KeyError):
        KINDS["sssp"].checkpoint.resume(Stored)


# -- (d): a kind is a row ------------------------------------------------------

def _http(base, path, body=None):
    req = urllib.request.Request(
        base + path, method="POST" if body is not None else "GET",
        data=json.dumps(body).encode() if body is not None else None,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.headers, r.read()


def test_a_ninth_row_is_served_with_no_other_edit(monkeypatch):
    """What a new kind costs: its model (here two lines) and a row."""
    def run(ctx):
        assert ctx.resume is None and ctx.checkpoint is None
        assert ctx.on_round(0)                      # no veto asked
        degrees = np.asarray(ctx.snap.out_degree, np.int32) \
            * int(ctx.params.get("iterations", 1))
        return {"edges": int(ctx.snap.out_degree.sum()),
                "degrees": degrees}
    ninth = kinds.Kind(
        "ninth", run,
        images=(kinds.FORWARD,
                kinds.Image("ninth-image", "out", lambda snap: 4 * snap.n,
                            "_ninth_csr")),
        work=kinds.Work("ninth-work", lambda snap: 12345))
    monkeypatch.setitem(KINDS, "ninth", ninth)
    n, src, dst = graph()
    snap = snap_mod.from_arrays(n, src, dst)
    metrics = MetricManager()
    sched = JobScheduler(snapshot=snap, metrics=metrics)
    http = GraphServer(None, port=0, scheduler=sched).start()
    base = f"http://{http.host}:{http.port}"
    try:
        _h, raw = _http(base, "/jobs", {"kind": "ninth", "iterations": 3})
        job_id = json.loads(raw)["job"]
        deadline = time.time() + 60
        while time.time() < deadline:
            env = json.loads(_http(base, f"/jobs/{job_id}")[1])
            if env["status"] not in ("queued", "running"):
                break
            time.sleep(0.01)
        assert env["status"] == "done", env
        assert env["result"] == {"edges": len(src)}
        assert env["arrays"] == {"degrees": {"dtype": "int32",
                                             "shape": [n]}}
        assert env["batch_k"] == 1
        headers, raw = _http(base, f"/jobs/{job_id}/result/degrees")
        assert (np.frombuffer(raw, headers["X-Dtype"])
                == 3 * snap.out_degree).all()
        spans = {}
        for s in sched.tracer.spans(job_id):
            spans.setdefault(s.name, []).append(s)
        (admit,) = spans["job.admit"]
        assert len(spans["job.lease"]) == 1
        assert admit.attrs["bytes"] \
            == hbm.snapshot_csr_bytes(snap) + 4 * n + 12345
        (run_span,) = spans["run"]
        assert run_span.attrs["kind"] == "ninth"
        (round_,) = spans["round"]
        assert round_.parent_id == run_span.span_id
        # its images stay, evictable; its working set left
        assert sched.ledger._bytes == {
            id(snap): hbm.snapshot_csr_bytes(snap),
            ("ninth-image", id(snap)): 4 * n}
        assert sched.ledger.pinned_bytes() == 0
        assert sched._evictable[("ninth-image", id(snap))] \
            == (snap, "_ninth_csr")
        snap._ninth_csr = object()
        sched._evict(("ninth-image", id(snap)))
        assert not hasattr(snap, "_ninth_csr")
        # and a retired snapshot leaves the ledger with them
        sched.ledger.reserve(("ninth-image", id(snap)), 4 * n)
        sched._forget_snapshot(snap)
        assert sched.ledger._bytes == {}
        assert metrics.counter(
            "serving.jobs.completed",
            labels={"kind": "ninth", "tenant": "default"}).count == 1
        # the refusal of an unknown kind lists it, last
        with pytest.raises(ValueError, match=r"dense, callable, ninth\)$"):
            sched.submit(JobSpec(kind="tenth"))
    finally:
        http.stop()
        sched.close()


# -- (e): the refusals, word for word -----------------------------------------

@pytest.fixture
def sched():
    s = JobScheduler(snapshot=snap_mod.from_arrays(
        4, np.array([0, 1], np.int32), np.array([1, 0], np.int32)),
        metrics=MetricManager())
    yield s
    s.close()


def test_the_table_holds_nine_rows_in_order():
    assert list(KINDS) == ORDER
    assert all(row.name == name for name, row in KINDS.items())
    assert {k for k, row in KINDS.items() if row.images} == set(LEDGER)


@pytest.mark.parametrize("kind", ["nope", ["bfs"]])
def test_an_unknown_kind_is_refused_with_the_list(sched, kind):
    """(Off the wire a kind may be any JSON value.)"""
    with pytest.raises(ValueError) as e:
        sched.submit(JobSpec(kind=kind))
    assert str(e.value) == (
        f"unknown job kind {kind!r} (known: bfs, sssp, pagerank, wcc, "
        "cdlp, lcc, bc, dense, callable)")
    assert sched._metrics.counter(
        "serving.jobs.rejected",
        labels={"kind": "unknown", "tenant": "default"}).count == 1


def test_lcc_on_a_directed_snapshot_is_refused(sched):
    with pytest.raises(ValueError) as e:
        sched.submit(JobSpec(kind="lcc", directed=True))
    assert str(e.value) == (
        "lcc on a directed snapshot: the specification's directed form "
        "(in- and out-neighbours together, a pair counted in each "
        "direction it is an edge) is not implemented; submit with "
        "directed=false")
    assert sched._metrics.counter(
        "serving.jobs.rejected",
        labels={"kind": "lcc", "tenant": "default"}).count == 1
    assert sched.jobs() == []
