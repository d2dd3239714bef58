"""Open-loop point queries over ``POST /traverse``.

Independent users: arrivals are due at seeded times at the mix's fixed
``rate`` whatever the server does, each request is answered on its own
connection, and latency runs from the time a request was due. Sending
stops when the window closes; every request in flight is awaited and
counted. ``warm`` (parent side, before the window) sends bursts that fill
every padded batch size the lane can form.
"""

from __future__ import annotations

import threading
import time

import loadgen


def _ask(http, path, body, timeout_s):
    res = http.call(path, body, timeout_s)
    if res.get("fallback") is not False:
        raise loadgen.RequestFailed(
            f"interpreter fallback: {res.get('why')}")
    return res


# -- child side: the measured window ----------------------------------------

def run(http, mix: dict, pools: dict, seed: int, seconds: float,
        reference, emit) -> dict:
    path = mix["request"]["path"]
    timeout_s = float(mix["request_timeout_s"])
    due = loadgen.arrival_times(float(mix["rate"]), seconds, seed)
    bodies = loadgen.Bodies(mix, pools, seed)
    samples = [None] * len(due)

    def one(i, t_due):
        body = bodies.get(i)
        sent = time.time()
        res, why = None, None
        try:
            res = _ask(http, path, body, timeout_s)
        except loadgen.RequestFailed as e:
            why = str(e)
        done = time.time()
        samples[i] = loadgen.sample(
            i, t_due, sent, done, body,
            envelope={k: res.get(k) for k in (
                "fused_k", "wait_ms", "exec_ms")} if res else None,
            result=res.get("result") if res else None, why=why,
            reference=reference)

    threads = []
    start = time.time()
    emit({"event": "window_start", "t": start})
    for i, offset in enumerate(due):
        t_due = start + float(offset)
        delay = t_due - time.time()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one, args=(i, t_due), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    return {"samples": samples,
            "window": {"start": start, "seconds": seconds,
                       "last_done": max((s["done"] for s in samples),
                                        default=start)}}


# -- parent side: warm-up ----------------------------------------------------

def warm(server, mix: dict, pools: dict, log) -> None:
    """Bursts of ``warm_bursts`` concurrent queries, each sent while the
    lane is busy with the one before so that it fuses whole; rounds are
    repeated (at most ``warm_rounds`` times) until every burst size has
    run fused to its padded size with no compile."""
    http = loadgen.Http(server.base)
    path = mix["request"]["path"]
    timeout_s = float(mix["warm_timeout_s"])
    bodies = loadgen.Bodies(mix, pools, seed=0)
    lane = server.sched.interactive()
    sent = 0
    clean: set = set()
    errors: list = []

    def burst(size: int, out: list):
        nonlocal sent

        def one(body):
            # a burst can overflow the server's listen backlog; a query
            # that never got in is sent again (warm-up only: in the window
            # it is a failed request)
            for attempt in range(3):
                try:
                    out.append(_ask(http, path, dict(
                        body, timeout_s=timeout_s), timeout_s))
                    return
                except loadgen.ConnectionFailed as e:
                    if attempt == 2:
                        errors.append(e)
                except loadgen.RequestFailed as e:
                    errors.append(e)
                    return

        threads = [threading.Thread(target=one,
                                    args=(bodies.get(sent + k),),
                                    daemon=True) for k in range(size)]
        sent += size
        for t in threads:
            t.start()
        return threads

    wanted = [int(b) for b in mix["warm_bursts"]]
    for round_no in range(1, int(mix["warm_rounds"]) + 1):
        t0 = time.time()
        before = server.compiles()
        answers: list = []
        # a lone query first: the lane is busy with it while the first
        # burst collects
        threads = burst(1, answers)
        for size in wanted:
            time.sleep(0.05)            # the burst before has arrived ...
            deadline = time.time() + timeout_s
            while lane.collector.depth() and time.time() < deadline:
                time.sleep(0.001)       # ... and the lane has taken it up
            threads += burst(size, answers)
        for t in threads:
            t.join(timeout_s)
        compiled = (server.compiles() or 0) - (before or 0)
        if errors or len(answers) != 1 + sum(wanted):
            raise RuntimeError(
                f"warm-up: {1 + sum(wanted) - len(answers)} queries "
                f"failed or fell back: {errors[:1]}")
        fused = sorted({a["fused_k"] for a in answers})
        log(f"warm round {round_no}: fused_k={fused} "
            f"compiles={compiled} {time.time() - t0:.1f}s")
        if not compiled:
            clean.update(fused)
        if set(wanted) <= clean:
            break
