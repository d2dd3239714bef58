"""The closed-loop job driver against a stub of the wire: how it paces,
what a sample keeps, what counts as a failed request, and that its
warm-up refuses a server without the result plane."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import files
import loadgen

MIX = dict(files.load_json("traffic", "pr-jobs-c2.json"), poll_s=0.01,
           request_timeout_s=5)
N = 64
RANK = (np.arange(N, dtype=np.float32) + 1) / N


class Stub:
    """``POST /jobs``, ``GET /jobs``, ``GET /jobs/<id>`` and the result
    plane. One job runs at a time for ``job_s`` seconds, in the order
    submitted, as the scheduler's one worker runs them."""

    def __init__(self, job_s=0.1, plane=True, fail=(), seeded=True):
        self.job_s, self.plane, self.fail = job_s, plane, set(fail)
        self.jobs: list = []
        self.lock = threading.Lock()
        if seeded:                 # what a warm-up leaves behind
            self.jobs.append({"job": "job-warm", "kind": "pagerank",
                              "submitted_at": 0.0, "started_at": 0.0,
                              "finished_at": job_s, "body": {}})
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                self._json(202, {"job": stub.submit(body)})

            def do_GET(self):
                if self.path == "/jobs":
                    self._json(200, {"jobs": [stub.wire(j)
                                              for j in stub.jobs]})
                    return
                job_id, _, name = self.path[len("/jobs/"):].partition(
                    "/result/")
                job = next(j for j in stub.jobs if j["job"] == job_id)
                if not name:
                    self._json(200, stub.wire(job))
                elif not stub.plane:
                    self._json(404, {"error": "unknown job",
                                     "type": "NotFound"})
                else:
                    self.send_response(200)
                    self.send_header("X-Dtype", "float32")
                    self.send_header("X-Shape", str(N))
                    self.send_header("Content-Length", str(RANK.nbytes))
                    self.end_headers()
                    self.wfile.write(RANK.tobytes())

        self.http = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self.http.serve_forever,
                         daemon=True).start()
        self.base = "http://127.0.0.1:%d" % self.http.server_address[1]

    def submit(self, body) -> str:
        with self.lock:
            now = time.time()
            free = max([now] + [j["finished_at"] for j in self.jobs])
            job = {"job": f"job-{len(self.jobs)}", "kind": body["kind"],
                   "submitted_at": now, "started_at": free,
                   "finished_at": free + self.job_s, "body": body}
            self.jobs.append(job)
            return job["job"]

    def wire(self, job) -> dict:
        now = time.time()
        out = {"job": job["job"], "kind": job["kind"], "batch_k": 1,
               "finished_at": job["finished_at"]}
        if now < job["started_at"]:
            return dict(out, status="queued")
        out["queue_ms"] = (job["started_at"] - job["submitted_at"]) * 1e3
        if now < job["finished_at"]:
            return dict(out, status="running")
        out["exec_ms"] = (job["finished_at"] - job["started_at"]) * 1e3
        out["device_ms"] = out["exec_ms"]
        if self.jobs.index(job) in self.fail:
            return dict(out, status="failed", error="stub: boom")
        if self.plane:
            out["arrays"] = {"rank": {"dtype": "float32", "shape": [N]}}
        return dict(out, status="done", result={"iterations": 10})

    def compiles(self):
        return 0

    def close(self):
        self.http.shutdown()
        self.http.server_close()


class Reference:
    n, edges = N, 640

    def __init__(self):
        self.seen = []

    def check(self, body, result):
        self.seen.append((body, result))
        return {"rank": int((np.asarray(result) != RANK).sum())}


@pytest.fixture
def driver():
    return files.load_module("drivers", "closed_jobs")


def run(driver, stub, seconds, mix=MIX):
    events, ref = [], Reference()
    try:
        record = driver.run(loadgen.Http(stub.base), mix, {}, 7, seconds,
                            ref, events.append)
    finally:
        stub.close()
    return record, events, ref


def test_two_callers_back_to_back(driver):
    stub = Stub(job_s=0.2)
    record, events, ref = run(driver, stub, seconds=1.0)
    samples = sorted(record["samples"], key=lambda s: s["sent"])
    w = record["window"]
    assert events == [{"event": "window_start", "t": w["start"]}]
    assert record["graph"] == {"n": N, "edge_slots": 640}
    json.dumps(record)                      # what goes up the pipe
    # the server runs one job at a time, 0.2 s each: the jobs sent in a
    # 1 s window are 5 or 6, the last one awaited after it closed
    assert 5 <= len(samples) <= 7
    assert all(s["ok"] and s["mismatch"] == {"rank": 0} for s in samples)
    assert sorted(s["i"] for s in samples) == list(range(len(samples)))
    assert all(s["sent"] - w["start"] < 1.0 for s in samples)
    assert w["last_done"] == max(s["done"] for s in samples) \
        > w["start"] + 1.0
    # the second caller starts half a job (the newest DONE job of the
    # kind in the server's list: the warm-up's, 0.2 s) after the first
    assert samples[0]["sent"] - w["start"] < 0.05
    assert samples[1]["sent"] - w["start"] == pytest.approx(0.1, abs=0.05)
    # what a sample keeps of the job's envelope, and the latency's parts
    for s in samples:
        env = s["envelope"]
        assert set(env) == {"wait_ms", "exec_ms", "fetch_ms"}
        assert env["exec_ms"] == pytest.approx(200.0, abs=1.0)
        assert s["due"] == s["sent"]
        assert s["latency_ms"] == pytest.approx(
            (s["done"] - s["sent"]) * 1e3)
        assert s["latency_ms"] >= env["wait_ms"] + env["exec_ms"] \
            + env["fetch_ms"] - 1.0
    # every body is the mix's, every answer went to the reference whole
    assert len(ref.seen) == len(samples)
    assert all(b == MIX["request"]["body"] and len(r) == N
               for b, r in ref.seen)
    # the device ran back to back: each job started when the one before
    # ended, once both callers were in
    mine = [j for j in stub.jobs if j["job"] != "job-warm"]
    assert all(b["started_at"] == pytest.approx(a["finished_at"], abs=0.06)
               for a, b in zip(mine[1:], mine[2:]))


def test_a_failed_job_is_a_failed_request(driver):
    stub = Stub(job_s=0.05, fail={1})       # the first of the window
    record, _events, ref = run(driver, stub, seconds=0.3,
                               mix=dict(MIX, callers=1))
    samples = record["samples"]
    assert len(samples) >= 3
    assert [s["ok"] for s in samples] == [False] + [True] * (
        len(samples) - 1)
    assert "failed: stub: boom" in samples[0]["why"]
    assert samples[0]["mismatch"] == {} and samples[0]["envelope"] == {}
    assert len(ref.seen) == len(samples) - 1


def test_no_job_of_the_kind_yet_no_stagger(driver):
    stub = Stub(job_s=0.05, seeded=False)
    record, _e, _r = run(driver, stub, seconds=0.2)
    first = sorted(s["sent"] for s in record["samples"])[:2]
    assert first[1] - first[0] < 0.05


def test_warm_runs_a_job_and_wants_the_result_plane(driver):
    lines = []
    stub = Stub(job_s=0.02)
    try:
        driver.warm(stub, MIX, {}, lines.append)
    finally:
        stub.close()
    assert len(lines) == 1 and lines[0].startswith("warm job 1: ")
    assert "compiles=0" in lines[0] and "float32[64]" in lines[0]
    # a program from before the plane: one job, then set-up fails
    stub = Stub(job_s=0.02, plane=False)
    t0 = time.time()
    try:
        with pytest.raises(RuntimeError, match="no result plane"):
            driver.warm(stub, MIX, {}, lines.append)
    finally:
        stub.close()
    assert time.time() - t0 < 2.0 and len(stub.jobs) == 2


def test_warm_gives_up_after_its_rounds(driver):
    stub = Stub(job_s=0.01)
    counts = iter(range(2 * driver.WARM_ROUNDS))    # every job builds one
    stub.compiles = lambda: next(counts)
    lines = []
    try:
        driver.warm(stub, MIX, {}, lines.append)
    finally:
        stub.close()
    assert len(lines) == driver.WARM_ROUNDS == 3


def test_the_mix_asks_for_the_configurations_algorithm():
    """The driver never reads the configuration: every cell of a
    PageRank mix sends the iteration count and the damping its
    configuration states."""
    bench = files.benchmark_json()
    cells = [files.cell_files(w["name"]) for w in bench["workloads"]]
    cells = [(config, mix) for _b, _c, config, mix in cells
             if mix.get("op") == "pagerank"]
    assert cells
    for config, mix in cells:
        body, algorithm = mix["request"]["body"], config["algorithm"]
        assert body["iterations"] == algorithm["num-iterations"]
        assert body["damping"] == algorithm["damping-factor"]


def test_warm_goes_on_until_a_job_compiles_nothing(driver):
    stub = Stub(job_s=0.01)
    counts = iter([0, 2, 2, 2])             # job 1 built two, job 2 none
    stub.compiles = lambda: next(counts)
    lines = []
    try:
        driver.warm(stub, MIX, {}, lines.append)
    finally:
        stub.close()
    assert ["compiles=2" in ln for ln in lines] == [True, False]
