"""Closed-loop analytics jobs whose bodies are drawn per request.

``closed_jobs``'s protocol (``POST /jobs``, ``GET /jobs/<id>`` every
``poll_s``, ``GET /jobs/<id>/result/<array>``, all of it handed to the
reference, the next job at once; caller k starts k / callers of a job's
time after the first) with one difference: the i-th job sent in the
window carries the i-th body of ``loadgen.Bodies``, so a ``{"draw":
pool}`` in the mix's template is the pool entry THIS job drew (each pool
walked whole in a seeded order: every seed sends the same multiset of
keys) and the reference answers for the body it is handed. A mix that
draws nothing renders the constant body ``closed_jobs`` sends. The
samples, their envelopes and the run record have the shape
``closed_jobs.run`` returns, so every reader takes them as they are.
``warm`` (parent side) runs whole jobs of distinct keys until one
compiles nothing: a program whose shapes depended on the key would show
here, in set-up, as a warm-up that never comes clean.
"""

from __future__ import annotations

import threading
import time

import files
import loadgen

closed_jobs = files.load_module("drivers", "closed_jobs")


def envelope_of(env: dict, fetch_s: float) -> dict:
    return {"wait_ms": env.get("queue_ms"), "exec_ms": env.get("exec_ms"),
            "fetch_ms": fetch_s * 1e3}


# -- child side: the measured window ----------------------------------------

def run(http, mix: dict, pools: dict, seed: int, seconds: float,
        reference, emit) -> dict:
    bodies = loadgen.Bodies(mix, pools, seed)
    callers = int(mix["callers"])
    kind = mix["request"]["body"]["kind"]
    stagger_s = closed_jobs.job_seconds(http, kind) / callers
    samples: list = []
    sent_jobs = 0
    lock = threading.Lock()
    start = time.time()

    def caller(k: int):
        nonlocal sent_jobs
        time.sleep(max(start + k * stagger_s - time.time(), 0.0))
        while time.time() - start < seconds:
            with lock:
                i, sent_jobs = sent_jobs, sent_jobs + 1
            body = bodies.get(i)
            sent = time.time()
            envelope = array = why = None
            try:
                env = closed_jobs.await_job(http, mix, body)
                array, fetch_s = closed_jobs.fetch_result(http, mix, env)
                envelope = envelope_of(env, fetch_s)
            except loadgen.RequestFailed as e:
                why = str(e)
            done = time.time()
            # the check runs outside the lock: one caller's validation
            # overlaps the other's job
            s = loadgen.sample(i, sent, sent, done, body,
                               envelope=envelope, result=array, why=why,
                               reference=reference)
            with lock:
                samples.append(s)

    emit({"event": "window_start", "t": start})
    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(callers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    samples.sort(key=lambda s: s["i"])
    return {"samples": samples,
            "graph": {"n": reference.n, "edge_slots": reference.edges},
            "window": {"start": start, "seconds": seconds,
                       "last_done": max((s["done"] for s in samples),
                                        default=start)}}


# -- parent side: warm-up ----------------------------------------------------

def warm(server, mix: dict, pools: dict, log) -> None:
    """Whole jobs, awaited and fetched, each of other keys, until one
    builds no executable: at most as many as the smallest drawn pool
    holds (``closed_jobs.WARM_ROUNDS`` where the mix draws nothing).
    The first DONE job's envelope has to describe the array the window
    will fetch; a program that refuses the kind fails here, on the first
    request."""
    http = loadgen.Http(server.base)
    bodies = loadgen.Bodies(mix, pools, seed=0)
    rounds = min((len(pools[name]) for name in bodies.drawn),
                 default=closed_jobs.WARM_ROUNDS)
    name = mix["result_array"]
    for round_no in range(1, rounds + 1):
        t0 = time.time()
        before = server.compiles()
        try:
            env = closed_jobs.await_job(http, mix, bodies.get(round_no - 1))
            if name not in (env.get("arrays") or {}):
                raise RuntimeError(
                    f"warm-up: the DONE job's envelope describes no "
                    f"array {name!r} (arrays={env.get('arrays')!r}): "
                    "this program has no result plane")
            array, fetch_s = closed_jobs.fetch_result(http, mix, env)
        except loadgen.RequestFailed as e:
            raise RuntimeError(f"warm-up: {e}") from e
        compiled = (server.compiles() or 0) - (before or 0)
        log(f"warm job {round_no}: exec_ms={env.get('exec_ms')} "
            f"fetch_ms={fetch_s * 1e3:.1f} {array.dtype}{list(array.shape)} "
            f"result={env.get('result')} compiles={compiled} "
            f"{time.time() - t0:.1f}s")
        if not compiled:
            return
    raise RuntimeError(
        f"warm-up: {rounds} jobs of distinct keys and every one built an "
        "executable: the program's shapes depend on the key")
