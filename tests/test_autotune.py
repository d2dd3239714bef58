"""Closed-loop autotune controller (ISSUE 14, ROADMAP #4).

Three contracts pinned here:

* **pinned decision sequences** — synthetic signal trajectories driven
  through an injectable clock produce exactly the decisions the rules
  promise: multiplicative bounded steps, cooldown hysteresis, burn
  gating, shed-victim selection, Young's cadence;
* **the explainable guarantee** — every journaled decision is
  reconstructible from its own entry alone: ``autotune.replay(entry)``
  re-runs the SAME pure rule functions over the journaled signal
  snapshot and must reproduce the decision;
* **shadow is provably inert** — a scheduler with autotune shadowed
  produces byte-identical job results and identical
  pre-``controller.*`` metric snapshots to one with autotune off,
  while enforce mode moves exactly the knobs it journals (batch K,
  tenant quota scale, compaction trigger, checkpoint cadence).
"""

import json
import os
import time

import numpy as np
import pytest

from titan_tpu.olap.api import JobSpec
from titan_tpu.olap.serving import autotune
from titan_tpu.olap.serving.autotune import Controller, replay
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.serving.tenants import QuotaExceeded, TenantQuota
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.utils.metrics import MetricManager


class Clock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def _sig(occ=None, batches=1, burn=0.0, burn_slo=None, protected=(),
         tenants=None, deltas=None, live=None, recovery=None,
         jobs_delta=0):
    """A synthetic signal snapshot in the collector's shape (minus the
    knob snapshot, which tick() stamps in itself)."""
    return {
        "t": 0.0,
        "occupancy": {"recent_mean": occ, "batches": batches},
        "queue_depth": 0,
        "burn": ({burn_slo or "slo": {"300s": burn}} if burn else {}),
        "burn_max": burn, "burn_max_slo": burn_slo,
        "protected_tenants": sorted(protected),
        "tenants": tenants or {},
        "tenant_device_s_delta": deltas or {},
        "jobs_delta": jobs_delta,
        "recovery": recovery or {},
        **({"live": live} if live is not None else {}),
    }


def _controller(clock, feed, **kw):
    kw.setdefault("metrics", MetricManager())
    return Controller(mode=kw.pop("mode", "shadow"), clock=clock,
                      signals=feed, **kw)


def _snap(n=192, m=900, seed=42):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    return snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))


# -- mode resolution ----------------------------------------------------------

def test_mode_resolution():
    assert autotune.resolve_mode(None) == "shadow"
    assert autotune.resolve_mode("") == "shadow"
    assert autotune.resolve_mode("shadow") == "shadow"
    assert autotune.resolve_mode("OFF") == "off"
    assert autotune.resolve_mode("0") == "off"
    assert autotune.resolve_mode("false") == "off"
    assert autotune.resolve_mode("enforce") == "enforce"
    assert autotune.resolve_mode("1") == "enforce"
    with pytest.raises(ValueError):
        autotune.resolve_mode("sideways")


def test_off_mode_means_no_controller():
    snap = _snap()
    s = JobScheduler(snapshot=snap, metrics=MetricManager(),
                     autostart=False, profiling=False, autotune="off")
    try:
        assert s.controller is None
    finally:
        s.close()


def test_unknown_param_rejected():
    with pytest.raises(ValueError, match="unknown autotune params"):
        Controller(metrics=MetricManager(), signals=lambda: _sig(),
                   typo_knob=3)


# -- pinned decision sequences (simulation) -----------------------------------

def test_batch_k_trajectory_pinned():
    clk = Clock()
    feed = {"sig": _sig(occ=8.0)}
    ctl = _controller(clk, lambda: dict(feed["sig"]), k_init=8)

    # t0: occupancy at target, burn 0 → grow 8→16
    e = ctl.tick(force=True)
    assert [(x["rule"], x["old"], x["new"]) for x in e] == \
        [("batch_k.grow", 8, 16)]
    # inside the cooldown the SAME signal decides nothing
    clk.advance(0.5)
    feed["sig"] = _sig(occ=16.0)
    assert ctl.tick(force=True) == []
    # past the cooldown it doubles again, clamping at k_cap
    clk.advance(11.0)
    e = ctl.tick(force=True)
    assert [(x["rule"], x["old"], x["new"]) for x in e] == \
        [("batch_k.grow", 16, 32)]
    clk.advance(11.0)
    feed["sig"] = _sig(occ=32.0)
    assert ctl.tick(force=True) == []          # at the cap: bounded
    # occupancy collapse → halve back
    clk.advance(11.0)
    feed["sig"] = _sig(occ=2.0)
    e = ctl.tick(force=True)
    assert [(x["rule"], x["old"], x["new"]) for x in e] == \
        [("batch_k.shrink", 32, 16)]
    # high burn blocks growth even at full occupancy
    clk.advance(11.0)
    feed["sig"] = _sig(occ=16.0, burn=2.5, burn_slo="p95")
    assert ctl.tick(force=True) == []
    # an idle tick (no executed batch since last) decides nothing
    clk.advance(11.0)
    feed["sig"] = _sig(occ=None, batches=0)
    assert ctl.tick(force=True) == []
    assert ctl.target_k == 16


def test_tenant_shed_and_restore_pinned():
    clk = Clock()
    tenants = {"flood": {"in_flight": 5, "device_seconds": 1.0},
               "quiet": {"in_flight": 1, "device_seconds": 0.1}}
    spike = _sig(occ=None, batches=0, burn=3.0, burn_slo="quiet-p95",
                 protected=("quiet",), tenants=tenants,
                 deltas={"flood": 0.5, "quiet": 0.1})
    calm = _sig(occ=None, batches=0, burn=0.1, protected=("quiet",),
                tenants=tenants)
    feed = {"sig": spike}
    ctl = _controller(clk, lambda: dict(feed["sig"]))

    seq = []
    for _ in range(4):                 # shed halves to the floor, once
        seq += ctl.tick(force=True)    # per cooldown, then stops
        clk.advance(11.0)
    feed["sig"] = calm
    for _ in range(4):                 # restores double back to 1.0
        seq += ctl.tick(force=True)
        clk.advance(11.0)
    got = [(x["rule"], x["knob"], x["old"], x["new"]) for x in seq]
    assert got == [
        ("tenant.shed", "tenant.quota_scale.flood", 1.0, 0.5),
        ("tenant.shed", "tenant.quota_scale.flood", 0.5, 0.25),
        # floor reached: no further shed even under sustained burn
        ("tenant.restore", "tenant.quota_scale.flood", 0.25, 0.5),
        ("tenant.restore", "tenant.quota_scale.flood", 0.5, 1.0),
    ]
    assert ctl.scales == {}            # fully restored
    # the journal carries the triggering burn reading (smoke contract)
    sheds = [x for x in seq if x["rule"] == "tenant.shed"]
    assert all(x["signals"]["burn_max"] >= 2.0 for x in sheds)
    assert all("quiet-p95" in x["why"] for x in sheds)


def test_protected_tenant_is_never_shed():
    clk = Clock()
    sig = _sig(occ=None, batches=0, burn=5.0, burn_slo="quiet-p95",
               protected=("quiet",),
               tenants={"quiet": {"in_flight": 9,
                                  "device_seconds": 3.0}},
               deltas={"quiet": 3.0})
    ctl = _controller(clk, lambda: dict(sig))
    assert ctl.tick(force=True) == []  # the only consumer is protected


def test_compact_trigger_pinned():
    clk = Clock()
    live = {"overlay_rows": 1000, "tombs": 0, "fill": 0.1,
            "tomb_fraction": 0.0, "base_edges": 10_000,
            "merge_us_per_row": 0.05, "fallbacks": 0}
    feed = {"sig": _sig(occ=None, batches=0, live=live, jobs_delta=10)}
    ctl = _controller(clk, lambda: dict(feed["sig"]))
    e = ctl.tick(force=True)
    # defer = 1000 rows * 0.5us * 10 jobs = 5ms >= merge
    # 0.05us * 11000 rows = 0.55ms → compact
    assert [(x["rule"], x["old"], x["new"]) for x in e] == \
        [("live.compact", "deferred", "compact")]
    # idle plane (no job flow) defers forever
    clk.advance(11.0)
    feed["sig"] = _sig(occ=None, batches=0, live=live, jobs_delta=0)
    assert ctl.tick(force=True) == []
    # a tiny overlay never engages the rule
    clk.advance(11.0)
    feed["sig"] = _sig(occ=None, batches=0, jobs_delta=100,
                       live={**live, "overlay_rows": 8, "tombs": 0})
    assert ctl.tick(force=True) == []


def test_checkpoint_cadence_pinned():
    clk = Clock()
    rec = {"retries_delta": 1, "replayed_delta": 50,
           "checkpoint_ms_mean": 20.0, "round_ms_mean": 10.0,
           "retries": 1, "rounds_replayed": 50}
    ctl = _controller(clk, lambda: _sig(occ=None, batches=0,
                                        recovery=rec))
    e = ctl.tick(force=True)
    # Young: sqrt(2 * (20/10) * 50) = sqrt(200) ≈ 14 rounds
    assert [(x["rule"], x["old"], x["new"]) for x in e] == \
        [("recovery.cadence", 0, 14)]
    assert ctl.checkpoint_every == 14
    # no failure news → no cadence churn
    clk.advance(31.0)
    e = ctl.tick(force=True)
    assert e == []


# -- the explainable guarantee ------------------------------------------------

def test_replay_reconstructs_every_decision():
    """Every journal entry re-derives from its OWN signal snapshot:
    the rules are pure, the snapshot carries the knob state, and
    replay() must land on the same old→new."""
    clk = Clock()
    feeds = [
        _sig(occ=8.0),
        _sig(occ=16.0),
        _sig(occ=2.0),
        _sig(occ=None, batches=0, burn=3.0, burn_slo="q",
             protected=("q",),
             tenants={"flood": {"in_flight": 3, "device_seconds": 1.0}},
             deltas={"flood": 0.4}),
        _sig(occ=None, batches=0, burn=0.0, protected=("q",)),
        _sig(occ=None, batches=0, jobs_delta=10,
             live={"overlay_rows": 1000, "tombs": 50, "fill": 0.2,
                   "tomb_fraction": 0.01, "base_edges": 10_000,
                   "merge_us_per_row": None, "fallbacks": 0}),
        _sig(occ=None, batches=0,
             recovery={"retries_delta": 2, "replayed_delta": 36,
                       "checkpoint_ms_mean": 8.0, "round_ms_mean": 4.0}),
    ]
    it = iter(feeds)
    ctl = _controller(clk, lambda: dict(next(it)), k_init=8)
    entries = []
    for _ in feeds:
        entries += ctl.tick(force=True)
        clk.advance(31.0)              # past every cooldown
    assert len(entries) >= 5           # every rule family fired
    rules = {e["rule"] for e in entries}
    assert {"batch_k.grow", "batch_k.shrink", "tenant.shed",
            "tenant.restore", "live.compact",
            "recovery.cadence"} <= rules
    for e in entries:
        got = replay(e)
        assert got is not None, (e["rule"], e["knob"])
        assert got["new"] == e["new"], (e["rule"], got, e)
        assert got["old"] == e["old"], (e["rule"], got, e)
    # and a journal entry survives a JSON round trip intact (the wire /
    # postmortem form replays too)
    wire = json.loads(json.dumps(entries[0]))
    assert replay(wire)["new"] == wire["new"]


def test_journal_bounded_and_drop_counted():
    clk = Clock()
    m = MetricManager()
    flip = {"burn": 3.0}
    tenants = {"a": {"in_flight": 1, "device_seconds": 0.5},
               "b": {"in_flight": 1, "device_seconds": 0.4}}

    def feed():
        flip["burn"] = 3.0 if flip["burn"] < 1 else 0.0
        return _sig(occ=None, batches=0, burn=flip["burn"],
                    burn_slo="s", tenants=tenants,
                    deltas={"a": 0.5, "b": 0.4})

    ctl = _controller(clk, feed, metrics=m, journal_cap=4,
                      shed_cooldown_s=0.0)
    for _ in range(12):                # shed/restore ping-pong
        ctl.tick(force=True)
        clk.advance(1.0)
    j = ctl.journal()
    assert len(j) == 4                 # bounded
    assert m.counter_value("controller.journal.dropped") > 0
    assert ctl.state()["journal_dropped"] > 0
    # seq stays monotone across the drop window
    assert [e["seq"] for e in j] == sorted(e["seq"] for e in j)


# -- shadow mode: provably inert ----------------------------------------------

def _run_jobs(sched, snap, k=8):
    jobs = [sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": int(s)}))
            for s in range(k)]
    sched.start()
    for j in jobs:
        assert j.wait(120), j.state
    deadline = time.time() + 10
    while time.time() < deadline and sched._metrics.counter_value(
            "serving.jobs.completed") < k:
        time.sleep(0.01)
    return jobs


def _metric_shape(m):
    """{name: count} for every non-controller metric — the inertness
    comparison (values carry wall time and can never be identical
    across two real runs; counts and the name SET must be)."""
    return {name: v["count"] for name, v in m.snapshot().items()
            if not name.startswith("controller.")}


def test_shadow_mode_is_byte_identical_to_off():
    # a snapshot each: the first admission of a snapshot prices it
    # (serving.hbm.sizing_passes), whichever scheduler that is
    snap, snap_sh = _snap(), _snap()
    m_off, m_sh = MetricManager(), MetricManager()
    s_off = JobScheduler(snapshot=snap, metrics=m_off, autostart=False,
                         profiling=False, max_batch=8, autotune="off")
    s_sh = JobScheduler(snapshot=snap_sh, metrics=m_sh, autostart=False,
                        profiling=False, max_batch=8,
                        autotune="shadow", autotune_tick_s=3600.0)
    try:
        jobs_off = _run_jobs(s_off, snap)
        jobs_sh = _run_jobs(s_sh, snap_sh)
        # a full-occupancy batch ran: the shadow controller DECIDES...
        entries = s_sh.controller.tick(force=True)
        assert [(e["rule"], e["old"], e["new"]) for e in entries] == \
            [("batch_k.grow", 8, 16)]
        assert entries[0]["mode"] == "shadow"
        assert entries[0]["applied"] is False
        # ...but nothing moves: the knob is untouched,
        assert s_sh.max_batch == 8 and s_sh.batcher.max_batch == 8
        # results are byte-identical,
        for jo, js in zip(jobs_off, jobs_sh):
            assert np.array_equal(jo.result["dist"], js.result["dist"])
            assert jo.result["levels"] == js.result["levels"]
        # and the pre-controller metric registries match exactly —
        # same name set, same counts (shadow observation created
        # NOTHING: every signal read is non-creating)
        assert _metric_shape(m_off) == _metric_shape(m_sh)
        # the controller family exists only on the shadow side
        assert not any(n.startswith("controller.")
                       for n in m_off.snapshot())
        assert m_sh.counter_value("controller.tick.count") >= 1
        # shadow never scales admission either
        s_sh.controller.scales["t"] = 0.25
        q = TenantQuota(max_in_flight=4)
        assert s_sh.controller.scaled_quota("t", q) is q
    finally:
        s_off.close()
        s_sh.close()


# -- enforce mode: the knobs actually move ------------------------------------

def test_scaled_quota_floors_at_one_in_flight():
    """A shed throttles, it never zeroes: int() truncation on a small
    max_in_flight must not turn 'halve the quota' into a total outage
    no restore could be observed through."""
    ctl = Controller(metrics=MetricManager(), mode="enforce",
                     signals=lambda: _sig())
    ctl.scales["t"] = 0.25
    q = ctl.scaled_quota("t", TenantQuota(max_in_flight=2,
                                          max_hbm_bytes=1000.0))
    assert q.max_in_flight == 1        # not int(0.5) == 0
    assert q.max_hbm_bytes == 250.0    # continuous limits scale freely
    assert ctl.scaled_quota("t", TenantQuota(
        max_in_flight=64)).max_in_flight == 16


class _FakeLive:
    """Just enough live-plane surface for the compact-apply seam."""

    def __init__(self):
        self.compacted = []

    def compact_now(self, why="controller"):
        self.compacted.append(why)
        return True

    def stats(self):
        return None

    def close(self):
        pass


def test_enforce_applies_batch_k_and_compact():
    snap = _snap()
    m = MetricManager()
    sched = JobScheduler(snapshot=snap, metrics=m, autostart=False,
                         profiling=False, max_batch=8,
                         autotune="enforce", autotune_tick_s=3600.0)
    fake = _FakeLive()
    sched.live = fake                  # the compact seam under test
    ctl = sched.controller
    clk = Clock()
    ctl.clock = clk
    feed = {"sig": _sig(occ=8.0)}
    ctl._signals_fn = lambda: dict(feed["sig"])
    try:
        e = ctl.tick(force=True)
        assert [(x["rule"], x["new"], x["applied"], x["mode"])
                for x in e] == [("batch_k.grow", 16, True, "enforced")]
        # the knob MOVED — scheduler and batcher both
        assert sched.max_batch == 16 and sched.batcher.max_batch == 16
        assert m.counter_value("controller.decisions.applied",
                               labels={"rule": "batch_k.grow"}) == 1
        # compaction trigger pokes the live plane
        clk.advance(11.0)
        feed["sig"] = _sig(occ=None, batches=0, jobs_delta=10,
                           live={"overlay_rows": 1000, "tombs": 0,
                                 "fill": 0.2, "tomb_fraction": 0.0,
                                 "base_edges": 10_000,
                                 "merge_us_per_row": 0.05,
                                 "fallbacks": 0})
        e = ctl.tick(force=True)
        assert [x["rule"] for x in e] == ["live.compact"]
        assert fake.compacted == ["controller"]
        # the decision timeline lives under the reserved trace id
        spans = sched.tracer.spans("controller")
        assert spans and all(s.name == "decision" for s in spans)
        assert {s.attrs["rule"] for s in spans} == \
            {"batch_k.grow", "live.compact"}
    finally:
        sched.close()


def test_enforce_shed_scales_admission_to_429():
    snap = _snap()
    sched = JobScheduler(snapshot=snap, metrics=MetricManager(),
                         autostart=False, profiling=False,
                         enforce_quotas=True,
                         quotas={"noisy": TenantQuota(max_in_flight=4)},
                         autotune="enforce", autotune_tick_s=3600.0)
    ctl = sched.controller
    try:
        # quota alone admits 4 in flight
        sched.submit(JobSpec(kind="bfs", params={"source_dense": 0},
                             tenant="noisy"))
        sched.submit(JobSpec(kind="bfs", params={"source_dense": 1},
                             tenant="noisy"))
        # a shed decision scales the CONFIGURED quota: 4 * 0.5 = 2
        ctl._signals_fn = lambda: _sig(
            occ=None, batches=0, burn=3.0, burn_slo="quiet-p95",
            protected=("quiet",),
            tenants={"noisy": {"in_flight": 2, "device_seconds": 1.0}},
            deltas={"noisy": 0.9})
        e = ctl.tick(force=True)
        assert [(x["rule"], x["new"]) for x in e] == \
            [("tenant.shed", 0.5)]
        with pytest.raises(QuotaExceeded):
            sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": 2},
                                 tenant="noisy"))
        # the interactive lane checks the SAME scaled quota — a shed
        # tenant cannot dodge the throttle via point queries
        lane = sched.interactive()
        with pytest.raises(QuotaExceeded):
            lane._admit("noisy")
        # unscaled tenants are untouched
        sched.submit(JobSpec(kind="bfs", params={"source_dense": 3},
                             tenant="quiet"))
        # a tenant with NO configured quota is never refused by a scale
        # (the controller scales limits, it does not invent them)
        ctl.scales["default"] = 0.25
        sched.submit(JobSpec(kind="bfs", params={"source_dense": 4}))
    finally:
        sched.close()


def test_enforce_cadence_hint_adopted_by_retryable_jobs(tmp_path):
    snap = _snap()
    sched = JobScheduler(snapshot=snap, metrics=MetricManager(),
                         autostart=False, profiling=False,
                         checkpoint_dir=str(tmp_path),
                         autotune="enforce", autotune_tick_s=3600.0)
    ctl = sched.controller
    try:
        ctl._signals_fn = lambda: _sig(
            occ=None, batches=0,
            recovery={"retries_delta": 1, "replayed_delta": 50,
                      "checkpoint_ms_mean": 20.0,
                      "round_ms_mean": 10.0})
        e = ctl.tick(force=True)
        assert [(x["rule"], x["new"]) for x in e] == \
            [("recovery.cadence", 14)]
        assert ctl.checkpoint_every_hint() == 14
        # a retryable job with NO cadence of its own adopts the hint
        j = sched.submit(JobSpec(kind="bfs",
                                 params={"source_dense": 0},
                                 max_retries=2))
        assert j.recovery is not None and j.recovery.every == 14
        # an explicit per-spec cadence always wins
        j2 = sched.submit(JobSpec(kind="bfs",
                                  params={"source_dense": 1},
                                  max_retries=2, checkpoint_every=3))
        assert j2.recovery.every == 3
        # a non-retryable job is never checkpointed by the hint
        j3 = sched.submit(JobSpec(kind="bfs",
                                  params={"source_dense": 2}))
        assert j3.recovery is None
    finally:
        sched.close()


def test_applied_decisions_stitched_into_job_traces():
    snap = _snap()
    sched = JobScheduler(snapshot=snap, metrics=MetricManager(),
                         autostart=False, profiling=False, max_batch=8,
                         autotune="enforce", autotune_tick_s=3600.0)
    ctl = sched.controller
    try:
        ctl._signals_fn = lambda: _sig(occ=8.0)
        # seed occupancy so the grow rule has a reading, then decide
        sched._metrics.histogram("serving.batch.occupancy").update(8.0)
        e = ctl.tick(force=True)
        assert e and e[0]["applied"]
        jobs = _run_jobs(sched, snap, k=4)
        tree = sched.tracer.tree(jobs[0].id)

        def names(node, acc):
            acc.append(node["name"])
            for c in node["children"]:
                names(c, acc)
            return acc

        got = []
        for root in tree["spans"]:
            names(root, got)
        assert "controller" in got
        spans = sched.tracer.spans(jobs[0].id)
        ctl_spans = [s for s in spans if s.name == "controller"]
        assert ctl_spans[0].attrs["decisions"][0]["rule"] == \
            "batch_k.grow"
    finally:
        sched.close()


# -- HTTP + postmortem surfaces ----------------------------------------------

def test_get_controller_endpoint():
    import urllib.request

    import titan_tpu
    from titan_tpu import example
    from titan_tpu.server import GraphServer

    g = titan_tpu.open("inmemory")
    example.load(g)
    sched = JobScheduler(graph=g, metrics=MetricManager(),
                         autostart=False, profiling=False,
                         autotune_tick_s=3600.0)
    srv = GraphServer(g, port=0, scheduler=sched).start()
    try:
        ctl = sched.controller
        ctl._signals_fn = lambda: _sig(occ=16.0)
        ctl.tick(force=True)
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/controller",
                timeout=30) as resp:
            body = json.loads(resp.read())
        assert body["enabled"] is True
        assert body["mode"] == "shadow"
        # "knobs" is the EFFECTIVE state — shadow moved nothing; the
        # would-be trajectory is reported apart as shadow_knobs
        assert body["knobs"]["batcher.target_k"] == 16
        assert body["shadow_knobs"]["batcher.target_k"] == 32
        decs = body["decisions"]
        assert decs and decs[0]["rule"] == "batch_k.grow"
        # the wire entry replays — GET /controller is enough to audit
        assert replay(decs[0])["new"] == decs[0]["new"]
    finally:
        srv.stop()
        g.close()


def test_postmortem_bundle_carries_controller_state(tmp_path):
    snap = _snap()
    sched = JobScheduler(snapshot=snap, metrics=MetricManager(),
                         autostart=False, profiling=False,
                         flight_dir=str(tmp_path),
                         autotune_tick_s=3600.0)
    try:
        sched.controller._signals_fn = lambda: _sig(occ=16.0)
        sched.controller.tick(force=True)
        j = sched.submit(JobSpec(kind="bfs",
                                 params={"source": "junk"}))
        sched.start()
        assert j.wait(60)
        deadline = time.time() + 10
        while time.time() < deadline and j.dump_path is None:
            time.sleep(0.01)
        assert j.dump_path is not None
        with open(j.dump_path) as f:
            bundle = json.load(f)
        ctl = bundle["state"]["controller"]
        assert ctl["mode"] == "shadow"
        assert ctl["decisions"] and \
            ctl["decisions"][0]["rule"] == "batch_k.grow"
        assert bundle["config"]["autotune"] == "shadow"
    finally:
        sched.close()
