"""Device-cost profiler: compile / dispatch / transfer telemetry.

The layer that actually decides latency on a TPU — XLA compilations,
per-kernel device time, H2D/D2H traffic — was invisible outside
hand-run benches (ISSUE 10). This module makes it a first-class metric
surface:

* **Interception**: every kernel fetched through
  ``utils/jitcache.jit_once`` (the whole bfs_hybrid / frontier kernel
  library) is shimmed; the shim hands calls to ``_dispatch`` below when
  a profiler is installed. The engine's module-level jits
  (``olap/tpu/engine.py``) and eager device passes
  (``ops/epoch_merge``) route through :func:`profiled` explicitly.
* **Compile accounting**: a cache MISS is detected per call from the
  jit's ``_cache_size()`` delta — one miss == one new static shape
  bucket compiled; backend compile wall time is attributed through a
  ``jax.monitoring`` duration listener + a thread-local call context
  (eager-op compiles inside a profiled window are attributed too).
* **Compile spans** (ISSUE 25): the same listener journals ONE
  ``compile`` span per executable the process builds or loads — a
  ``jit_once`` kernel or an eager program, inside a profiled call or
  not — into the tracer current on the thread (``obs/tracing.scope``),
  else the trace ``compile``: key, static arguments, trace / lower /
  backend milliseconds, persistent-cache hit / miss / off. Needs the
  tracer and a profiler both on (the defaults).
* **Kernel spans** (ISSUE 38): every call through ``_dispatch`` hands
  the smallest array of its output to ONE watcher thread, which takes
  the calls in dispatch order, blocks until that array is ready and
  stamps the moment. One chip runs its programs in dispatch order, so
  ``device_ms = ready - max(dispatched, previous ready)`` is the
  program's time on the device and ``queued_ms = max(previous ready -
  dispatched, 0)`` the time it waited behind the program before it.
  The stamp feeds ``device.exec.ms{kernel}`` and, under a tracer's
  scope, ONE ``kernel`` span under the span current where the call was
  dispatched. Nothing is added on the dispatching
  thread but a queue put; an output that was donated or deleted before
  the watcher reached it is ``stamped: false``
  (``device.exec.unstamped{kernel}``) and its time falls to the next
  stamped program.
* **Transfer accounting**: the upload/readback seams
  (``engine._device_graph_single``, ``bfs_hybrid.build_chunked_csr``,
  the overlay's delta pages, result readbacks) call
  :func:`count_h2d` / :func:`count_d2h` with their byte counts.
* **Export**: ``device.compile.*`` / ``device.exec.*`` /
  ``device.xfer.*`` metric families through the labeled-metrics core
  (children keyed by ``{kernel}`` / ``{site}``), scraped by the
  Prometheus exposition like every other family
  (docs/monitoring.md table, pinned by tests/test_docs_metrics.py).

Profilers install process-wide (kernel caches are process-wide state);
more than one may be installed (tests, bench windows) — measurement
happens ONCE per call and fans out. With no profiler installed every
hook is one module-global load + None check; the profiler never touches
the device computation itself, so kernel results are bit-equal with
profiling on or off (pinned by tests/test_devprof.py, alongside the
1.15x overhead guard).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Optional

from titan_tpu.obs import tracing
from titan_tpu.utils import jitcache
from titan_tpu.utils.metrics import MetricManager

log = logging.getLogger(__name__)

#: installed profilers, in install order (process-wide — kernel caches
#: are process-wide; tier-1 runs serially so tests stay deterministic)
_PROFILERS: list = []
_INSTALL_LOCK = threading.Lock()
_TLS = threading.local()
_LISTENER = {"on": False}


#: the jax.monitoring names read here (jax/_src/dispatch.py, compiler.py)
_EV_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_EV_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_EV_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _pending() -> dict:
    """This thread's executable in the making: JAX reports its trace,
    its lowering and its cache look-up before the backend compile that
    closes it."""
    rec = getattr(_TLS, "pending", None)
    if rec is None:
        rec = _TLS.pending = {"trace_s": 0.0, "lower_s": 0.0,
                              "retrieval_s": 0.0, "cache": "off"}
    return rec


def _on_jax_event(name: str, **_kw) -> None:
    """jax.monitoring event listener: was the persistent cache asked
    for the executable in the making, and did it have it."""
    if not _PROFILERS:
        return
    if name == _EV_CACHE_ASKED:
        _pending()["cache"] = "miss"
    elif name == _EV_CACHE_HIT:
        _pending()["cache"] = "hit"


def _on_jax_duration(name: str, duration_s: float, fun_name=None,
                     **_kw) -> None:
    """jax.monitoring duration listener. Every backend compile (which
    in this JAX wraps the persistent cache's look-up, so a load counts)
    becomes one ``compile`` span, whichever code asked for it — a
    ``jit_once`` kernel or an eager ``x[:cap]``; inside a profiled call
    its wall is also attributed to that call, as before."""
    if not _PROFILERS:
        return
    if name == _EV_TRACE:
        _pending()["trace_s"] += duration_s
    elif name == _EV_LOWER:
        _pending()["lower_s"] += duration_s
    elif name == _EV_RETRIEVAL:
        _pending()["retrieval_s"] += duration_s
    elif name == _EV_BACKEND:
        ctx = getattr(_TLS, "ctx", None)
        if ctx is not None:
            ctx["compile_s"] += duration_s
            ctx["compile_events"] += 1
        rec, _TLS.pending = _pending(), None
        _compile_span(rec, duration_s, fun_name, ctx)


def _statics(kwargs: dict) -> dict:
    """A call's static arguments, as far as the shim can tell them from
    arrays: the integer, boolean and string keywords."""
    return {k: v for k, v in kwargs.items()
            if isinstance(v, (bool, int, str))}


def _compile_span(rec: dict, backend_s: float, fun_name, ctx) -> None:
    """Journal one built-or-loaded executable, made after the fact from
    the listener's durations: under the span current on this thread (the
    batch and level it stalled), else in the trace ``compile``."""
    cur = tracing.current_span()
    if cur is not None:
        tracer, trace_id, parent = cur
    else:
        tracer, trace_id, parent = tracing.current(), "compile", None
        if tracer is None:
            return
    attrs: dict = {}
    if ctx is not None:
        attrs.update(_statics(ctx["kwargs"]))
        attrs["key"] = ctx["key"]
    else:
        name = str(fun_name or "?")
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        attrs["key"] = "eager:" + name
    attrs.update(trace_ms=round(rec["trace_s"] * 1e3, 3),
                 lower_ms=round(rec["lower_s"] * 1e3, 3),
                 backend_ms=round(backend_s * 1e3, 3),
                 cache=rec["cache"],
                 thread=threading.current_thread().name)
    if rec["cache"] == "hit":
        attrs["retrieval_ms"] = round(rec["retrieval_s"] * 1e3, 3)
    now = tracer.clock()
    tracer.event(trace_id, "compile", parent=parent,
                 t0=now - rec["trace_s"] - rec["lower_s"] - backend_s,
                 t1=now, **attrs)


def _ensure_listener() -> None:
    # jax has no per-listener unregister; register once, gate on
    # _PROFILERS inside the callbacks
    if _LISTENER["on"]:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        monitoring.register_event_listener(_on_jax_event)
        _LISTENER["on"] = True
    except Exception:
        pass


class _Watcher:
    """The one thread that stamps when each dispatched program's output
    became ready on the device (process-wide, as the device's order
    is). It holds the smallest array of an output until that array is
    ready, and nothing after."""

    #: calls waiting for their stamp; a full queue drops the stamp
    #: (counted), it never blocks a dispatch
    MAX_PENDING = 4096

    def __init__(self):
        self._q: queue.Queue = queue.Queue(self.MAX_PENDING)
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._prev_ready = 0.0
        # dispatch time of the first unstamped program since the last
        # stamp: the next stamped program's interval starts no later
        self._carry: Optional[float] = None

    @property
    def alive(self) -> bool:
        t = self._thread
        return t is not None and t.is_alive()

    def submit(self, key: str, fn, kwargs: dict, out, wall_s: float
               ) -> None:
        """Dispatching thread: queue one call for its stamp."""
        where = tracing.current_span()
        clock = where[0].clock if where is not None else time.time
        attrs = dict(_statics(kwargs), key=key,
                     fn=getattr(fn, "__name__", key),
                     dispatch_ms=round(wall_s * 1e3, 3))
        profs = _installed()
        try:
            self._q.put_nowait((_smallest_array(out),
                                (attrs, where, clock, clock(), profs)))
        except queue.Full:
            for prof, counts in profs:
                prof.on_stamp(key, 0.0, False, counts)
            return
        if not self.alive:
            self._start()

    def _start(self) -> None:
        with self._lock:
            if not self.alive:
                self._thread = threading.Thread(
                    target=self._run, name="devprof-watcher", daemon=True)
                self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            if isinstance(item, threading.Event):
                item.set()
                continue
            leaf, info = item
            del item
            stamped = False
            if leaf is not None:
                try:
                    leaf.block_until_ready()
                    stamped = True
                except Exception:   # donated or deleted since (a
                    pass            # RuntimeError), or it failed
                del leaf        # the moment it is stamped
            try:
                self._stamp(info, stamped)
            except Exception:   # the watcher outlives a stamp it lost
                log.exception("devprof: stamp of %s lost", info[0]["key"])

    def _stamp(self, info: tuple, stamped: bool) -> None:
        attrs, where, clock, dispatched, profs = info
        prev = self._prev_ready
        if stamped:
            ready = clock()
            carry, self._carry = self._carry, None
            start = min(max(prev, dispatched if carry is None else carry),
                        ready)
            self._prev_ready = max(ready, prev)
        else:
            # an instant where it was dispatched; the next stamped
            # program's interval reaches back to here
            start = ready = dispatched
            if self._carry is None:
                self._carry = dispatched
        device_s = ready - start
        for prof, counts in profs:
            prof.on_stamp(attrs["key"], device_s, stamped, counts)
        if where is not None:
            tracer, trace_id, parent = where
            tracer.event(trace_id, "kernel", parent=parent, t0=start,
                         t1=ready, **attrs,
                         device_ms=round(device_s * 1e3, 3),
                         queued_ms=round(
                             max(prev - dispatched, 0.0) * 1e3, 3),
                         stamped=stamped)

    def drain(self, timeout: float) -> bool:
        """Wait until every call queued so far has its stamp."""
        if not self.alive:
            return True
        done = threading.Event()
        try:
            self._q.put(done, timeout=timeout)
        except queue.Full:
            return False
        return done.wait(timeout)

    def stop(self, timeout: float) -> None:
        """Stamp what is queued, then end the thread (the last profiler
        left; the next profiled call starts another)."""
        t = self._thread
        if t is None or not t.is_alive():
            return
        try:
            self._q.put(None, timeout=timeout)
        except queue.Full:
            return
        t.join(timeout)


def _smallest_array(out):
    """The smallest device array among an output's leaves (None where
    it has none): the one the watcher blocks on and holds meanwhile."""
    import jax
    best = None
    for leaf in jax.tree_util.tree_leaves(out):
        # (a call made while an outer jit traces returns tracers)
        if isinstance(leaf, jax.Array) \
                and not isinstance(leaf, jax.core.Tracer) \
                and (best is None or leaf.size < best.size):
            best = leaf
    return best


_WATCHER = _Watcher()


def drain(timeout: float = 5.0) -> bool:
    """Wait until every profiled call dispatched so far has its stamp
    (its ``kernel`` span journaled, its ``device.exec.ms`` counted):
    what a reader of the journal calls first. False on a timeout."""
    return _WATCHER.drain(timeout)


def _dispatch(key: str, fn, args, kwargs):
    """The jitcache profile dispatch: measure once, fan out to every
    installed profiler. ``fn`` is the RAW jitted function (its
    ``_cache_size`` delta detects a per-shape-bucket compile). The
    call's wall is the host's dispatch; the device's time comes from
    the watcher's stamp."""
    if not _PROFILERS:
        return fn(*args, **kwargs)
    cache_size = getattr(fn, "_cache_size", None)
    before = cache_size() if cache_size is not None else -1
    prev = getattr(_TLS, "ctx", None)
    ctx = _TLS.ctx = {"compile_s": 0.0, "compile_events": 0,
                      "key": key, "kwargs": kwargs}
    t0 = time.perf_counter()
    try:
        out = fn(*args, **kwargs)
    finally:
        _TLS.ctx = prev
        wall = time.perf_counter() - t0
        after = cache_size() if cache_size is not None else -1
        compiled = after > before >= 0
        for prof, counts in _installed():
            prof.on_call(key, wall, compiled, ctx["compile_s"],
                         ctx["compile_events"], counts)
    _WATCHER.submit(key, fn, kwargs, out, wall)
    return out


def profiled(key: str, fn, *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` under the active profilers — the
    explicit form for device entry points that don't come from
    jit_once (the engine's module jits, eager epoch-merge passes)."""
    if not _PROFILERS:
        return fn(*args, **kwargs)
    return _dispatch(key, fn, args, kwargs)


def _installed() -> list:
    """``[(profiler, counts)]`` of the installed profilers: ``counts``
    is False for one whose metric registry an earlier one of the list
    shares. An event counts once a REGISTRY, however many profilers are
    installed over it: a scheduler that was never closed leaves its
    profiler installed, and the next scheduler's shares the process-wide
    default registry with it (every counter then read double)."""
    seen: set = set()
    out = []
    for prof in list(_PROFILERS):
        out.append((prof, id(prof.metrics) not in seen))
        seen.add(id(prof.metrics))
    return out


def _registries() -> list:
    """The installed profilers' metric registries, each once."""
    return [prof.metrics for prof, counts in _installed() if counts]


def count_h2d(site: str, nbytes: int) -> None:
    """Attribute ``nbytes`` of host→device transfer to ``site``."""
    if _PROFILERS and nbytes:
        for prof, counts in _installed():
            prof.on_xfer("h2d", site, int(nbytes), counts)


def count_d2h(site: str, nbytes: int) -> None:
    """Attribute ``nbytes`` of device→host readback to ``site``."""
    if _PROFILERS and nbytes:
        for prof, counts in _installed():
            prof.on_xfer("d2h", site, int(nbytes), counts)


def count_level(direction: str, road: str, levels: int = 1) -> None:
    """Count ``levels`` BFS levels by the direction they took and by
    where a push had its frontier's pairs from. The batched loop
    (``frontier_bfs_batched``; models/bfs_hybrid._td_cap): ``"td"`` push
    or ``"bu"`` pull; ``"carried"``: the program before left the list;
    ``"scan"``: listed from dist, n wide; ``"none"`` for a pull. The
    single-source family (``frontier_bfs_hybrid``): road ``"single"``,
    direction ``"head"`` | ``"td"`` | ``"bu"`` | ``"end"``, the fused
    head and endgame counting every level their one dispatch ran."""
    if levels > 0:
        for metrics in _registries():
            metrics.counter("device.bfs.levels",
                            labels={"dir": direction,
                                    "list": road}).inc(int(levels))


def count_pull_rung(c_cap: int) -> None:
    """Count one pulled level of the batched loop by the rung of the
    pull's ladder its candidates took (``bfs_hybrid._bu_caps``: the
    ``c_cap`` its ``bstep`` ran at)."""
    for metrics in _registries():
        metrics.counter("device.bfs.pull_rung",
                        labels={"c_cap": str(int(c_cap))}).inc()


def count_opener(impl: str) -> None:
    """Count one pulled level of the single-source family by its
    opener: ``"dense"`` the split-lane opener (``hybrid_bu_startL``:
    the first lanes n-wide over the leading-lane image), ``"plain"``
    the opener that tests every lane at once over the candidates' list
    (under ``bfs_hybrid.SPLIT_LANE_MIN`` candidates)."""
    for metrics in _registries():
        metrics.counter("device.bfs.opener",
                        labels={"impl": impl}).inc()


def count_frontier_test(prog: str, impl: str) -> None:
    """Count one call of a single-source program that tests parents
    against the frontier (``prog``: the jitted function, ``bu0a``,
    ``bu0b``, ``bu``, ``ex``, ``bu0``, ``end``) by what served the
    test's random reads (``bfs_hybrid._frontier_road``): ``"vmem"`` the
    frontier as a table in VMEM, ``"xla"`` the bitmap's byte gather."""
    for metrics in _registries():
        metrics.counter("device.bfs.frontier_test",
                        labels={"prog": prog, "impl": impl}).inc()


def count_wcc_rounds(rounds: int) -> None:
    """Count the min-label propagation rounds of one WCC run (the
    rounds ``_frontier_run`` planned after the peel)."""
    if rounds > 0:
        for metrics in _registries():
            metrics.counter("device.wcc.rounds").inc(int(rounds))


def count_wcc_plan(domain: str) -> None:
    """Count one round plan of a WCC run by the road it took:
    ``"list"`` over the peel's remainder (``frontier._list_plan``),
    ``"n"`` over every vertex (``_band_plan``: no peel, or a remainder
    past the list's cap)."""
    for metrics in _registries():
        metrics.counter("device.wcc.plans",
                        labels={"domain": domain}).inc()


def count_pr_iteration() -> None:
    """Count one iteration of ``frontier.pagerank_dense`` (its sweep
    and its finish dispatched)."""
    for metrics in _registries():
        metrics.counter("device.pr.iterations").inc()


def count_pr_gather(impl: str, lanes: int) -> None:
    """Count the lanes one iteration of the uniform PageRank pull
    gathered (8 x the pull image's columns, pad lanes included) by what
    served them: ``"vmem"`` the Pallas kernel's table, ``"xla"`` XLA's
    gather (ops/vmem_gather.gather_impl)."""
    for metrics in _registries():
        metrics.counter("device.pr.gather_lanes",
                        labels={"impl": impl}).inc(int(lanes))


def count_cdlp_round(impl: str, classes: tuple, keys: int) -> None:
    """Count one round of ``models/cdlp.cdlp`` (its gather, sort and
    vote dispatched), the lanes it gathered (every lane of the row
    image, pad lanes included) by what served them, ``"vmem"`` or
    ``"xla"`` (ops/vmem_gather.gather_impl), and the lanes it sorted by
    the class of their rows (``classes``: ``(rows, width)`` small, then
    wide) and by the sort's operands (``keys``: 1 word a lane, or the
    pair)."""
    by_class = {name: rows * 8 * width
                for name, (rows, width) in zip(("small", "wide"), classes)}
    for metrics in _registries():
        metrics.counter("device.cdlp.rounds").inc()
        metrics.counter("device.cdlp.lanes", labels={"impl": impl}) \
            .inc(sum(by_class.values()))
        for name, lanes in by_class.items():
            metrics.counter(
                "device.cdlp.sort_lanes",
                labels={"class": name, "keys": str(keys)}).inc(lanes)


def count_lcc(part: str, edges: int, wedges: Optional[int] = None
              ) -> None:
    """Count one part of ``models/lcc.lcc`` dispatched: ``"hub"`` (a
    level's pass and column sums; ``edges``: the ANDs of two rows they
    made, one an undirected edge) or ``"tail"`` (``edges``: the low
    graph's, once each; ``wedges``: the oriented wedges its compares
    decide)."""
    for metrics in _registries():
        metrics.counter("device.lcc.edges",
                        labels={"part": part}).inc(int(edges))
        if part == "hub":
            metrics.counter("device.lcc.levels").inc()
        if wedges is not None:
            metrics.counter("device.lcc.wedges",
                            labels={"part": part}).inc(int(wedges))


def count_bc(part: str, levels, width: int) -> None:
    """Count one phase of one group of ``models/bc.bc`` run: each
    root's levels by ``part`` (``levels``, one a root of the group;
    ``"forward"``: the levels that hold a vertex, the last pull finds
    nobody; ``"backward"``: two fewer), the pulls the group paid for
    them, its deepest root's count, by ``part`` and ``width`` (the
    roots that shared each: levels less pulls are the passes over the
    image saved), and each root once, with its forward phase."""
    for metrics in _registries():
        metrics.counter("device.bc.levels",
                        labels={"part": part}).inc(int(sum(levels)))
        metrics.counter("device.bc.pulls", labels={
            "part": part, "width": str(width)}).inc(int(max(levels)))
        if part == "forward":
            metrics.counter("device.bc.roots").inc(len(levels))


def current() -> Optional["DeviceCostProfiler"]:
    """The most recently installed profiler, or None."""
    return _PROFILERS[-1] if _PROFILERS else None


class DeviceCostProfiler:
    """Process-wide device-cost accounting into a metrics registry.

    Per profiled call: ``device.exec.calls`` (labeled ``{kernel}``); a
    compile (new static shape bucket) counts on ``device.compile.count``
    + ``device.compile.ms``, a warm call on
    ``device.compile.cache_hits``. Per stamp of the watcher:
    ``device.exec.ms`` (the program's time on the device) or
    ``device.exec.unstamped``. Transfer seams land on
    ``device.xfer.h2d_bytes`` / ``device.xfer.d2h_bytes`` (labeled
    ``{site}``). A bounded ``compile_log`` keeps the recent compile
    events for postmortem bundles, and ``window()`` captures totals
    deltas for per-stage / per-job attribution.

    ``recorder`` (obs/flightrec.FlightRecorder) receives a compact
    device event per call when attached.
    """

    def __init__(self, metrics: Optional[MetricManager] = None,
                 recorder=None, max_compile_log: int = 256):
        self.metrics = metrics or MetricManager.instance()
        self.recorder = recorder
        self.max_compile_log = int(max_compile_log)
        self._lock = threading.Lock()
        self._kernels: dict[str, dict] = {}
        self._compile_log: list[dict] = []
        self._totals = {"calls": 0, "compiles": 0, "cache_hits": 0,
                        "compile_s": 0.0, "exec_s": 0.0,
                        "h2d_bytes": 0, "d2h_bytes": 0}

    # -- lifecycle -----------------------------------------------------------

    def install(self) -> "DeviceCostProfiler":
        with _INSTALL_LOCK:
            if self not in _PROFILERS:
                _PROFILERS.append(self)
            _ensure_listener()
            jitcache.set_profile_dispatch(_dispatch)
        return self

    def uninstall(self) -> None:
        with _INSTALL_LOCK:
            if self in _PROFILERS:
                _PROFILERS.remove(self)
            last = not _PROFILERS
            if last:
                jitcache.set_profile_dispatch(None)
        if last:
            _WATCHER.stop(5.0)

    def __enter__(self) -> "DeviceCostProfiler":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    @property
    def installed(self) -> bool:
        return self in _PROFILERS

    # -- record side ---------------------------------------------------------

    def on_call(self, key: str, wall_s: float, compiled: bool,
                compile_s: float, compile_events: int,
                counts: bool = True) -> None:
        """``counts``: this profiler is the one that counts the call on
        its registry (``_installed``); its own totals take every call."""
        m = self.metrics
        if counts:
            m.counter("device.exec.calls", labels={"kernel": key}).inc()
            if compiled:
                m.counter("device.compile.count",
                          labels={"kernel": key}).inc()
                m.histogram("device.compile.ms", labels={"kernel": key}) \
                    .update(compile_s * 1e3)
            else:
                m.counter("device.compile.cache_hits",
                          labels={"kernel": key}).inc()
        with self._lock:
            k = self._kernels.setdefault(
                key, {"calls": 0, "compiles": 0, "cache_hits": 0,
                      "compile_s": 0.0, "compile_events": 0,
                      "exec_s": 0.0})
            k["calls"] += 1
            k["compile_s"] += compile_s
            k["compile_events"] += compile_events
            t = self._totals
            t["calls"] += 1
            t["compile_s"] += compile_s
            if compiled:
                k["compiles"] += 1
                t["compiles"] += 1
                self._compile_log.append(
                    {"t": time.time(), "kernel": key,
                     "compile_ms": round(compile_s * 1e3, 3),
                     "call_ms": round(wall_s * 1e3, 3)})
                if len(self._compile_log) > self.max_compile_log:
                    del self._compile_log[0]
            else:
                k["cache_hits"] += 1
                t["cache_hits"] += 1
        rec = self.recorder
        if rec is not None:
            rec.record("device", kernel=key,
                       dispatch_ms=round(wall_s * 1e3, 3),
                       compiled=compiled,
                       **({"compile_ms": round(compile_s * 1e3, 3)}
                          if compiled else {}))

    def on_stamp(self, key: str, device_s: float, stamped: bool,
                 counts: bool = True) -> None:
        """Watcher thread: one profiled call's time on the device, or
        that it could not be stamped."""
        if not stamped:
            if counts:
                self.metrics.counter("device.exec.unstamped",
                                     labels={"kernel": key}).inc()
            return
        if counts:
            self.metrics.histogram("device.exec.ms",
                                   labels={"kernel": key}).update(
                                       device_s * 1e3)
        with self._lock:
            k = self._kernels.get(key)
            if k is not None:
                k["exec_s"] += device_s
            self._totals["exec_s"] += device_s

    def on_xfer(self, direction: str, site: str, nbytes: int,
                counts: bool = True) -> None:
        name = "device.xfer.h2d_bytes" if direction == "h2d" \
            else "device.xfer.d2h_bytes"
        if counts:
            self.metrics.counter(name, labels={"site": site}).inc(nbytes)
        with self._lock:
            self._totals[f"{direction}_bytes"] += nbytes
        rec = self.recorder
        if rec is not None:
            rec.record("xfer", dir=direction, site=site, bytes=nbytes)

    # -- read side -----------------------------------------------------------

    def kernel_stats(self) -> dict:
        """Per-kernel accumulated stats (calls / compiles / cache hits /
        compile seconds; ``exec_s``: stamped seconds on the device, as
        far as the watcher has come), keyed by jit_once key."""
        with self._lock:
            return {k: dict(v) for k, v in sorted(self._kernels.items())}

    def compiles(self, key: Optional[str] = None) -> int:
        """Compilations so far — one per (kernel, static shape bucket)
        cache miss; total when ``key`` is None."""
        with self._lock:
            if key is not None:
                k = self._kernels.get(key)
                return k["compiles"] if k is not None else 0
            return self._totals["compiles"]

    def compile_log(self) -> list:
        """The last ``max_compile_log`` compile events (newest last) —
        the postmortem/evidence "compile log" section."""
        with self._lock:
            return [dict(e) for e in self._compile_log]

    def stats(self) -> dict:
        """Process totals: calls / compiles / cache hits, compile wall
        and stamped device seconds (``exec_s``), H2D/D2H bytes."""
        with self._lock:
            out = dict(self._totals)
        out["compile_s"] = round(out["compile_s"], 6)
        out["exec_s"] = round(out["exec_s"], 6)
        return out

    def window(self) -> "ProfileWindow":
        """Open a totals-delta window (per-stage / per-batch
        attribution). Concurrent activity from other threads lands in
        every open window — windows measure the process, not a thread."""
        return ProfileWindow(self)


class ProfileWindow:
    """Totals snapshot at open; ``close()`` returns the delta."""

    __slots__ = ("_prof", "_t0", "_base")

    def __init__(self, prof: DeviceCostProfiler):
        self._prof = prof
        self._t0 = time.time()
        self._base = prof.stats()

    def close(self) -> dict:
        now = self._prof.stats()
        out = {k: now[k] - self._base[k] for k in now}
        out["compile_s"] = round(out["compile_s"], 6)
        out["exec_s"] = round(out["exec_s"], 6)
        out["wall_s"] = round(time.time() - self._t0, 6)
        return out
