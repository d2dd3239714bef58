#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a traffic
mix; those files name the graph generator, the driver and the reference;
the metrics named there are read by a file each (``files.py``). This
process is the only one that touches JAX: it builds the served graph,
starts the program's scheduler and HTTP server with their defaults, warms
every shape the mix can produce, and then only waits — the load comes from
a child process (``loadgen.py``) that never imports JAX and that also
holds every answer against the plain reference. Without a TPU it exits
non-zero and prints no result line. The last line of stdout is the result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

import files  # noqa: E402
import stats  # noqa: E402

CHILD_TIMEOUT_S = 900.0


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(devices, chips: int) -> None:
    """A measurement is a chip run or it is nothing: no CPU fallback."""
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"benchmark: needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform!r}")


class Child:
    """The load generator process and its line protocol."""

    def __init__(self, job: dict):
        self.proc = subprocess.Popen(
            [sys.executable, files.path("loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=files.ROOT)
        self._events: queue.Queue = queue.Queue()
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        self.send(job)

    def _read(self) -> None:
        for line in self.proc.stdout:
            self._events.put(json.loads(line))
        self._events.put(None)

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def expect(self, event: str, timeout: float = CHILD_TIMEOUT_S) -> dict:
        try:
            got = self._events.get(timeout=timeout)
        except queue.Empty:
            raise RuntimeError(f"load generator: no {event!r} within "
                               f"{timeout}s") from None
        if got is None or got.get("event") != event:
            raise RuntimeError(f"load generator: expected {event!r}, "
                               f"got {got!r}")
        return got

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.send({"cmd": "exit"})
                self.proc.stdin.close()
                self.proc.wait(10)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
        self.proc.wait()


class Served:
    """The system under test: the program's scheduler and HTTP server
    over one pinned snapshot, every option at its default."""

    def __init__(self, snapshot):
        from titan_tpu.olap.serving.scheduler import JobScheduler
        from titan_tpu.server import GraphServer

        self.sched = JobScheduler(snapshot=snapshot)
        self.http = GraphServer(None, port=0, scheduler=self.sched).start()
        self.base = f"http://{self.http.host}:{self.http.port}"

    def compiles(self):
        """Executables the program has built so far (devprof's count; a
        hit in the persistent cache counts), or None with profiling off."""
        prof = self.sched.profiler
        return prof.compiles() if prof is not None else None

    def close(self) -> None:
        self.http.stop()
        self.sched.close()


def take_trace(start_at: float, slice_s: float, trace_dir: str,
               out: dict) -> None:
    """Trace one slice of the window (thread of the chip's process)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # Python frames of the HTTP threads
    options.enable_hlo_proto = False    # would swamp the trace
    time.sleep(max(start_at - time.time(), 0.0))
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.time()
    time.sleep(slice_s)
    out["window_s"] = time.time() - t0
    jax.profiler.stop_trace()


class Session:
    """One process's life with one cell: set-up once, then windows."""

    def __init__(self, workload: str, seed: int):
        self.bench, self.cell, self.config, self.mix = \
            files.cell_files(workload)
        self.driver = files.load_module("drivers", self.mix["driver"])
        self.seed = seed
        self.chips = int(self.cell["chips"])
        self.scratch = self.child = self.served = None
        self.setup: dict = {}

    def __enter__(self) -> "Session":
        self.scratch = tempfile.mkdtemp(prefix="titan-bench-")
        self.child = Child({"config": self.config, "mix": self.mix,
                            "seed": self.seed})
        try:
            self._set_up()
        except BaseException as e:
            self.__exit__(type(e), e, None)
            raise
        return self

    def __exit__(self, *exc) -> None:
        if self.child is not None:
            if exc[0] is not None:      # a failed run does not wait for it
                self.child.proc.kill()
            self.child.close()
        if self.served is not None:
            self.served.close()
        shutil.rmtree(self.scratch, ignore_errors=True)

    def _set_up(self) -> None:
        """Device, graph, snapshot, server, warm-up; then the reference
        (made meanwhile by the child) has to be ready."""
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              files.path(".cache", "jax"))
        import jax
        import numpy as np

        self.devices = jax.devices()
        require_tpu(self.devices, self.chips)
        os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
        from titan_tpu.models.bfs_hybrid import build_chunked_csr
        from titan_tpu.olap.tpu import snapshot as snap_mod
        from titan_tpu.utils.jitcache import enable_compile_cache

        import loadgen

        enable_compile_cache()
        setup = self.setup
        setup["import_s"] = time.time() - T_START
        t0 = time.time()
        n, src, dst, perm = loadgen.make_graph(self.config, self.seed)
        setup["graph_gen_s"] = time.time() - t0
        t0 = time.time()
        snap = snap_mod.from_arrays(n, src, dst)
        degree = np.bincount(src, minlength=n)
        del src, dst
        build_chunked_csr(snap)
        setup["snapshot_s"] = time.time() - t0
        self.pools = loadgen.draw_pools(degree, self.mix, self.config,
                                        perm)
        self.served = Served(snap)
        t0 = time.time()
        self.driver.warm(self.served, self.mix, self.pools, log)
        setup["warmup_s"] = time.time() - t0
        prof = self.served.sched.profiler
        if prof is not None:
            built = prof.stats()
            log(f"warm-up built {built['compiles']} executables, "
                f"compile_s={built['compile_s']:.1f}")
        t0 = time.time()
        setup["reference_s"] = self.child.expect("ready")["reference_s"]
        setup["reference_wait_s"] = time.time() - t0
        setup["before_window_s"] = (time.time() - T_START
                                    - setup["reference_wait_s"])

    def window(self, seconds: float, trace: bool = False,
               mix: dict | None = None, seed: int | None = None) -> dict:
        """One measured window: the child sends, this process waits (and,
        traced, records a slice from the middle). Returns the run record
        the metric readers take. ``mix`` overrides keys of the cell's mix
        for this window (the sweep's rates)."""
        mix = dict(self.mix, **(mix or {}))
        compiles_before = self.served.compiles()
        t_sent = time.time()
        self.child.send({"cmd": "run", "base": self.served.base,
                         "seconds": seconds, "mix": mix,
                         "seed": self.seed if seed is None else seed})
        t_window = self.child.expect("window_start")["t"]
        setup = dict(self.setup)
        setup["total_s"] = setup["before_window_s"] + (t_window - t_sent)
        tracer, traced = None, {}
        if trace:
            slice_s = min(float(mix["trace_slice_s"]), seconds)
            tracer = threading.Thread(target=take_trace, args=(
                t_window + (seconds - slice_s) / 2, slice_s,
                os.path.join(self.scratch, "trace"), traced))
            tracer.start()
        record = self.child.expect("done", CHILD_TIMEOUT_S + seconds)
        if compiles_before is not None:
            record["compiles"] = (self.served.compiles()
                                            - compiles_before)
        if tracer is not None:
            tracer.join()
        record.update(mix=mix, config=self.config, setup=setup, trace=None)

        # after the window: every comparison summed
        compared: dict = {}
        for s in record["samples"]:
            stats.tally(compared, s["mismatch"])
        record["compared"] = compared
        if trace:
            import trace_reduce
            xplane = trace_reduce.find_xplane(
                os.path.join(self.scratch, "trace"))
            record["trace"] = trace_reduce.reduce_file(
                xplane, traced["window_s"])
        return record

    def memory_peak_bytes(self) -> int:
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in self.devices[: self.chips])


def verdict(record: dict) -> bool:
    """``correct``: every comparison the mix's reference makes was made,
    and none found a mismatch (each limit is 0). Prints each number
    compared beside its limit."""
    compared = record["compared"]
    names = files.load_module("reference", record["mix"]["op"]).COMPARED
    for name in names:
        bad, of = compared.get(name, (None, 0))
        log(f"compare {name}: mismatches={bad} of {of} compared, limit=0")
    return bool(record["samples"]) and all(
        name in compared and compared[name][0] == 0 for name in names)


def run(args) -> dict:
    with Session(args.workload, args.seed) as session:
        record = session.window(args.seconds, trace=bool(args.trace))
        peak = session.memory_peak_bytes()
    log("setup " + " ".join(f"{k}={v:.2f}"
                            for k, v in record["setup"].items()))
    samples = record["samples"]
    failed = [s for s in samples if not s["ok"]]
    for s in failed[:5]:
        log(f"failed request {s['i']}: {s['why']}")
    correct = verdict(record)
    kind, key = ("layer_metrics", "per_layer") if args.trace \
        else ("end_to_end", "end_to_end")
    metrics = {}
    for m in session.bench[key]:
        if "workloads" in m and session.cell["name"] not in m["workloads"]:
            continue
        value = files.load_module(kind, m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            log(f"metric {m['name']} = {value} {m['unit']}")
    devices = session.devices
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(samples),
              "failed": len(failed), "metrics": metrics, "device": device}
    if record["trace"]:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        result["breakdown"] = {k: record["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, files.ROOT)          # the program under test
    result = run(args)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
