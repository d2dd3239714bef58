"""HTTP job endpoints: the serving layer's wire surface.

POST /jobs, GET /jobs[/<id>], DELETE /jobs/<id> over the gods example
graph, including the in-CI version of scripts/serve_smoke.sh: 8
concurrent BFS jobs submitted through the wire, all fusing into one
batched device run, each completing with its own (distinct) result.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import titan_tpu
from titan_tpu import example
from titan_tpu.olap.serving.scheduler import JobScheduler
from titan_tpu.olap.tpu import snapshot as snap_mod
from titan_tpu.server import GraphServer
from titan_tpu.utils.metrics import MetricManager


def _req(srv, path, payload=None, method="GET"):
    req = urllib.request.Request(
        f"http://{srv.host}:{srv.port}{path}",
        data=json.dumps(payload).encode() if payload is not None else None,
        headers={"Content-Type": "application/json"}, method=method)
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _poll(srv, job_id, timeout=90.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        code, body = _req(srv, f"/jobs/{job_id}")
        assert code == 200
        if body["status"] not in ("queued", "running"):
            return body
        time.sleep(0.05)
    raise AssertionError(f"job {job_id} did not finish")


@pytest.fixture
def served():
    g = titan_tpu.open("inmemory")
    example.load(g)
    srv = GraphServer(g, port=0).start()
    yield g, srv
    srv.stop()
    g.close()


def test_job_submit_poll_result_and_delete_conflict(served):
    g, srv = served
    code, body = _req(srv, "/traversal",
                      {"gremlin": "g.V().has('name','hercules')"
                                  ".next().id"}, method="POST")
    assert code == 200
    vid = body["result"]
    code, body = _req(srv, "/jobs",
                      {"kind": "bfs", "source": vid, "targets": [vid]},
                      method="POST")
    assert code == 202 and body["status"] == "queued"
    final = _poll(srv, body["job"])
    assert final["status"] == "done", final
    # symmetrized gods graph is one connected component of 12
    assert final["result"]["reached"] == 12
    assert final["result"]["targets"][str(vid)] == 0
    assert final["batch_k"] == 1 and final["exec_ms"] > 0
    # cancel after completion -> 409 Conflict
    code, body = _req(srv, f"/jobs/{final['job']}", method="DELETE")
    assert code == 409
    # unknown id -> 404; listing carries stats
    code, _ = _req(srv, "/jobs/nope")
    assert code == 404
    code, body = _req(srv, "/jobs")
    assert code == 200 and body["stats"]["jobs_total"] >= 1


def test_job_bad_kind_rejected(served):
    _, srv = served
    code, body = _req(srv, "/jobs", {"kind": "explode"}, method="POST")
    assert code == 400 and "unknown job kind" in body["error"]


def test_job_numeric_fields_coerced_at_the_wire(served):
    """A string timeout_s (easy for JSON clients to send) must be
    coerced at submit — an uncoerced one would detonate inside the
    fused batch's level callback and fail every batchmate. Garbage
    values are a 400 for the one caller, not a batch failure."""
    _, srv = served
    code, body = _req(srv, "/jobs",
                      {"kind": "bfs", "source_dense": 0,
                       "timeout_s": "30", "max_levels": "5"},
                      method="POST")
    assert code == 202
    final = _poll(srv, body["job"])
    assert final["status"] == "done", final
    code, body = _req(srv, "/jobs",
                      {"kind": "bfs", "source_dense": 0,
                       "timeout_s": "soon"}, method="POST")
    assert code == 400


def test_delete_cancels_queued_job(served):
    g, srv = served
    # paused scheduler: the job stays QUEUED so DELETE hits the
    # queued-cancellation path deterministically
    metrics = MetricManager()
    srv._scheduler = JobScheduler(graph=g, metrics=metrics,
                                  autostart=False)
    code, body = _req(srv, "/jobs", {"kind": "bfs", "source_dense": 0},
                      method="POST")
    assert code == 202
    code, body = _req(srv, f"/jobs/{body['job']}", method="DELETE")
    assert code == 200 and body["status"] == "cancelled"
    assert metrics.counter_value("serving.jobs.cancelled") == 1


def test_eight_concurrent_jobs_fuse_and_return_distinct_results(served):
    """The smoke contract (scripts/serve_smoke.sh runs the same flow
    out-of-process): 8 BFS jobs POSTed concurrently against a paused
    scheduler fuse into ONE batch and each completes with its own
    per-source result, checked against sequential references."""
    from titan_tpu.models.bfs_hybrid import frontier_bfs_hybrid

    g, srv = served
    metrics = MetricManager()
    srv._scheduler = JobScheduler(graph=g, metrics=metrics,
                                  autostart=False)
    code, body = _req(srv, "/traversal",
                      {"gremlin": "sorted(v.id for v in g.V().to_list())"},
                      method="POST")
    assert code == 200
    vids = body["result"][:8]
    results: dict = {}
    errors: list = []

    def submit(vid):
        try:
            code, body = _req(srv, "/jobs",
                              {"kind": "bfs", "source": vid,
                               "targets": [vids[0]]}, method="POST")
            assert code == 202, body
            results[vid] = body["job"]
        except Exception as e:       # pragma: no cover - fail loud
            errors.append(repr(e))

    threads = [threading.Thread(target=submit, args=(v,)) for v in vids]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not errors and len(results) == 8, (errors, results)
    srv._scheduler.start()

    # reference: sequential single-source runs on an equivalent
    # symmetrized snapshot
    snap = snap_mod.build(g, directed=False)
    finals = {vid: _poll(srv, jid) for vid, jid in results.items()}
    for vid, final in finals.items():
        assert final["status"] == "done", final
        assert final["batch_k"] == 8     # ONE fused batch
        ref, _ = frontier_bfs_hybrid(snap, snap.dense_of(vid))
        ref = np.asarray(ref)
        assert final["result"]["reached"] == int((ref < (1 << 30)).sum())
        want = int(ref[snap.dense_of(vids[0])])
        got = final["result"]["targets"][str(vids[0])]
        assert got == (want if want < (1 << 30) else None)
    # distinct sources produced distinct jobs (and distinct distances
    # to the probe target for at least two of them)
    assert len({f["job"] for f in finals.values()}) == 8
    target_dists = [f["result"]["targets"][str(vids[0])]
                    for f in finals.values()]
    assert len(set(target_dists)) > 1
    assert metrics.histogram("serving.batch.occupancy").max == 8


def test_tenant_wire_quota_429_and_tenant_slo_endpoints(served):
    """ISSUE 8 wire surface: ``tenant`` rides the POST /jobs body into
    the envelope; a quota-refused submit is 429 + retryable (never a
    400 caller error); GET /tenants returns the attribution rows +
    quotas; GET /slo reports burn rates (and {"enabled": false}
    without objectives)."""
    from titan_tpu.obs.slo import SLO
    from titan_tpu.olap.serving.tenants import TenantQuota

    g, srv = served
    # default scheduler first: /slo and /tenants answer without setup
    code, body = _req(srv, "/slo")
    assert code == 200 and body == {"enabled": False}
    code, body = _req(srv, "/tenants")
    assert code == 200 and body["enforce_quotas"] is False

    sched = JobScheduler(
        graph=g, autostart=False, enforce_quotas=True,
        quotas={"flood": TenantQuota(max_in_flight=1)},
        slos=[SLO("flood-avail", tenant="flood",
                  success_rate=0.999)])
    srv._scheduler = sched
    code, body = _req(srv, "/traversal",
                      {"gremlin": "g.V().has('name','hercules')"
                                  ".next().id"}, method="POST")
    vid = body["result"]
    code, j1 = _req(srv, "/jobs",
                    {"kind": "bfs", "source": vid,
                     "tenant": "flood"}, method="POST")
    assert code == 202 and j1["tenant"] == "flood"
    # paused worker keeps j1 in flight → the second submit violates
    code, err = _req(srv, "/jobs",
                     {"kind": "bfs", "source": vid,
                      "tenant": "flood"}, method="POST")
    assert code == 429, err
    assert err["type"] == "QuotaExceeded" and err["retryable"] is True
    # other tenants unaffected; absent tenant falls back to default
    code, j2 = _req(srv, "/jobs", {"kind": "bfs", "source": vid},
                    method="POST")
    assert code == 202 and j2["tenant"] == "default"
    sched.start()
    assert _poll(srv, j1["job"])["status"] == "done"
    assert _poll(srv, j2["job"])["status"] == "done"
    code, body = _req(srv, "/tenants")
    assert code == 200 and body["enforce_quotas"] is True
    rows = body["tenants"]
    assert rows["flood"]["rejected"] == 1
    assert rows["flood"]["by_state"] == {"completed": 1}
    assert rows["default"]["device_seconds"] > 0
    assert body["quotas"]["flood"]["max_in_flight"] == 1
    code, body = _req(srv, "/slo")
    assert code == 200 and body["enabled"] is True
    (s,) = body["slos"]
    assert s["slo"] == "flood-avail" and s["tenant"] == "flood"
    assert s["sli"]["ok"] is True
    assert s["windows"]["300s"]["burn_rate"] == 0.0


# -- the result plane: GET /jobs/<id>/result/<name> (ISSUE 33) ---------------

def _raw(srv, path):
    """(status, headers, body bytes) of a GET, errors included."""
    try:
        with urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}{path}", timeout=30) as r:
            return r.status, r.headers, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.headers, e.read()


@pytest.mark.parametrize("kind,name", [("pagerank", "rank"),
                                       ("sssp", "dist"),
                                       ("wcc", "labels")])
def test_result_plane_serves_the_array_bytes(served, kind, name):
    """One path for every kind: the envelope describes the array, the
    plane hands out its little-endian bytes, equal to the array the job
    holds in-process."""
    _, srv = served
    code, body = _req(srv, "/jobs",
                      {"kind": kind, "source_dense": 0, "iterations": 5},
                      method="POST")
    assert code == 202
    final = _poll(srv, body["job"])
    assert final["status"] == "done", final
    held = srv.scheduler().get(final["job"]).result[name]
    assert final["arrays"][name] == {"dtype": held.dtype.name,
                                     "shape": list(held.shape)}
    assert name not in final["result"]          # scalars only, as before
    code, headers, raw = _raw(srv, f"/jobs/{final['job']}/result/{name}")
    assert code == 200
    assert headers["Content-Type"] == "application/octet-stream"
    assert headers["X-Dtype"] == held.dtype.name
    assert headers["X-Shape"] == ",".join(str(d) for d in held.shape)
    assert int(headers["Content-Length"]) == held.nbytes == len(raw)
    got = np.frombuffer(raw, np.dtype(headers["X-Dtype"]).newbyteorder("<"))
    assert got.tobytes() == np.ascontiguousarray(held).tobytes()


def test_result_plane_404_and_409_bodies(served):
    g, srv = served
    # unknown job
    code, _, raw = _raw(srv, "/jobs/nope/result/rank")
    assert code == 404 and json.loads(raw)["type"] == "NotFound"
    # a job that is not DONE (paused scheduler: it stays QUEUED)
    srv._scheduler = JobScheduler(graph=g, metrics=MetricManager(),
                                  autostart=False)
    code, body = _req(srv, "/jobs", {"kind": "pagerank", "iterations": 2},
                      method="POST")
    assert code == 202
    jid = body["job"]
    code, _, raw = _raw(srv, f"/jobs/{jid}/result/rank")
    err = json.loads(raw)
    assert code == 409 and err["type"] == "Conflict"
    assert err["status"] == "queued" and err["retryable"] is True
    # a cancelled job is terminal and never DONE: 409, not retryable
    code, body = _req(srv, "/jobs", {"kind": "pagerank"}, method="POST")
    gone = body["job"]
    assert _req(srv, f"/jobs/{gone}", method="DELETE")[0] == 200
    code, _, raw = _raw(srv, f"/jobs/{gone}/result/rank")
    err = json.loads(raw)
    assert code == 409 and err["status"] == "cancelled"
    assert err["retryable"] is False
    srv._scheduler.start()
    assert _poll(srv, jid)["status"] == "done"
    # a name the result does not hold: a scalar is not an array either
    for name in ("labels", "iterations", ""):
        code, _, raw = _raw(srv, f"/jobs/{jid}/result/{name}")
        assert code == 404 and json.loads(raw)["type"] == "NotFound", name


def test_to_wire_describes_arrays_and_keeps_scalars():
    from titan_tpu.olap.api import JobSpec
    from titan_tpu.olap.serving.jobs import Job

    job = Job(JobSpec(kind="pagerank"))
    assert "arrays" not in job.to_wire()
    job.start()
    job.complete({"iterations": 3, "rank": np.zeros((2, 5), np.float32),
                  "names": ["a"]})
    w = job.to_wire()
    assert w["result"] == {"iterations": 3, "names": ["a"]}
    assert w["arrays"] == {"rank": {"dtype": "float32", "shape": [2, 5]}}
    json.dumps(w)                                # JSON-safe
