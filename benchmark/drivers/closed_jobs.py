"""Closed-loop analytics jobs over ``POST /jobs`` and the result plane.

``callers`` clients, no think time. Each: ``POST /jobs`` with the mix's
request body, ``GET /jobs/<id>`` every ``poll_s`` until a terminal state,
``GET /jobs/<id>/result/<array>`` for the answer's bytes, all of it handed
to the reference, then the next job at once. Latency runs from the job
sent to the bytes in hand. Caller k starts k / callers of a job's time
after the first (a job's time: the newest DONE job of this kind in ``GET
/jobs``, which the warm-up left), so the callers never run in lockstep
and one's download and check overlap the other's run. A caller sends no
new job once the window's seconds have passed; jobs in flight are awaited
and counted. A job that fails, times out, is cancelled or refused is a
failed request. ``warm`` (parent side) runs whole jobs until one compiles
nothing.
"""

from __future__ import annotations

import threading
import time
import urllib.error
import urllib.request

import numpy as np

import loadgen

WARM_ROUNDS = 3


def fetch(base: str, job_id: str, name: str, timeout_s: float):
    """The array ``name`` of a DONE job over the result plane."""
    try:
        with urllib.request.urlopen(
                f"{base}/jobs/{job_id}/result/{name}",
                timeout=timeout_s) as resp:
            dtype = np.dtype(resp.headers["X-Dtype"]).newbyteorder("<")
            shape = tuple(int(d) for d in
                          resp.headers["X-Shape"].split(",") if d)
            return np.frombuffer(resp.read(), dtype).reshape(shape)
    except urllib.error.HTTPError as e:
        raise loadgen.RequestFailed(
            f"HTTP {e.code}: "
            f"{e.read().decode(errors='replace')[:200]}") from e
    except (urllib.error.URLError, OSError, TypeError, ValueError) as e:
        raise loadgen.RequestFailed(f"{type(e).__name__}: {e}") from e


def await_job(http, mix: dict, body: dict) -> dict:
    """Submit and poll; the envelope of the job once it is DONE."""
    timeout_s = float(mix["request_timeout_s"])
    deadline = time.time() + timeout_s
    job_id = http.call(mix["request"]["path"], body, timeout_s)["job"]
    while True:
        env = http.call(f"/jobs/{job_id}", None, timeout_s)
        if env["status"] not in ("queued", "running", "retrying"):
            break
        if time.time() > deadline:
            raise loadgen.RequestFailed(
                f"job {job_id} still {env['status']} after {timeout_s}s")
        time.sleep(float(mix["poll_s"]))
    if env["status"] != "done":
        raise loadgen.RequestFailed(
            f"job {job_id} {env['status']}: {env.get('error')}")
    return env


def fetch_result(http, mix: dict, env: dict):
    """(the mix's result array of the DONE job ``env``, seconds the
    fetch took)."""
    t0 = time.time()
    array = fetch(http.base, env["job"], mix["result_array"],
                  float(mix["request_timeout_s"]))
    return array, time.time() - t0


def job_seconds(http, kind: str):
    """Run time of the newest DONE job of ``kind`` the server lists."""
    done = [j for j in http.call("/jobs")["jobs"]
            if j["kind"] == kind and j["status"] == "done"
            and j.get("exec_ms") is not None]
    if not done:
        return 0.0
    return max(done, key=lambda j: j["finished_at"])["exec_ms"] / 1e3


# -- child side: the measured window ----------------------------------------

def run(http, mix: dict, pools: dict, seed: int, seconds: float,
        reference, emit) -> dict:
    body = loadgen.render(mix["request"]["body"], {})
    callers = int(mix["callers"])
    stagger_s = job_seconds(http, body["kind"]) / callers
    samples: list = []
    lock = threading.Lock()
    start = time.time()

    def caller(k: int):
        time.sleep(max(start + k * stagger_s - time.time(), 0.0))
        while time.time() - start < seconds:
            sent = time.time()
            env = array = why = None
            fetch_s = 0.0
            try:
                env = await_job(http, mix, body)
                array, fetch_s = fetch_result(http, mix, env)
            except loadgen.RequestFailed as e:
                env, why = None, str(e)
            done = time.time()
            envelope = None
            if env is not None:
                envelope = {"wait_ms": env.get("queue_ms"),
                            "exec_ms": env.get("exec_ms"),
                            "fetch_ms": fetch_s * 1e3}
            with lock:
                samples.append(loadgen.sample(
                    len(samples), sent, sent, done, body,
                    envelope=envelope, result=array, why=why,
                    reference=reference))

    emit({"event": "window_start", "t": start})
    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"samples": samples,
            "graph": {"n": reference.n, "edge_slots": reference.edges},
            "window": {"start": start, "seconds": seconds,
                       "last_done": max((s["done"] for s in samples),
                                        default=start)}}


# -- parent side: warm-up ----------------------------------------------------

def warm(server, mix: dict, pools: dict, log) -> None:
    """Whole jobs, awaited and fetched, at most ``WARM_ROUNDS`` of them,
    until one builds no executable. The first DONE job's envelope has to
    describe the array the window will fetch (``arrays``): a program
    without the result plane fails here, after one job, and not in the
    window."""
    http = loadgen.Http(server.base)
    body = loadgen.render(mix["request"]["body"], {})
    name = mix["result_array"]
    for round_no in range(1, WARM_ROUNDS + 1):
        t0 = time.time()
        before = server.compiles()
        try:
            env = await_job(http, mix, body)
            if name not in (env.get("arrays") or {}):
                raise RuntimeError(
                    f"warm-up: the DONE job's envelope describes no "
                    f"array {name!r} (arrays={env.get('arrays')!r}): "
                    "this program has no result plane")
            array, fetch_s = fetch_result(http, mix, env)
        except loadgen.RequestFailed as e:
            raise RuntimeError(f"warm-up: {e}") from e
        compiled = (server.compiles() or 0) - (before or 0)
        log(f"warm job {round_no}: exec_ms={env.get('exec_ms')} "
            f"fetch_ms={fetch_s * 1e3:.1f} {array.dtype}{list(array.shape)} "
            f"compiles={compiled} {time.time() - t0:.1f}s")
        if not compiled:
            return
