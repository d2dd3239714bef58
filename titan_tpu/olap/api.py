"""OLAP contracts: scan jobs, vertex programs, memory.

Re-creation of the reference's OLAP seam (reference: titan-core
diskstorage/keycolumnvalue/scan/ScanJob.java:17-130,
graphdb/olap/VertexScanJob.java:16, TinkerPop VertexProgram +
graphdb/olap/computer/FulgoraMemory.java/FulgoraVertexMemory.java):

* ``ScanJob`` — raw row-level job run by the scanner (storage/scan.py):
  declares the column slices it needs, processes each (key, entries) row.
* ``VertexScanJob`` — vertex-level job; bridged onto ScanJob by the engine.
* ``VertexProgram`` — BSP program executed per vertex per superstep with
  message passing (host computer, olap/computer.py).
* ``DenseProgram`` — the TPU-native program contract: the whole superstep is
  expressed as pure jnp transforms over dense per-vertex state plus a
  gather → per-edge message → segment-combine → apply pipeline, compiled
  once and iterated under ``lax.while_loop`` (olap/tpu/engine.py). This is
  the redesign of FulgoraGraphComputer's scan loop as batched SpMV.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Sequence


class ScanMetrics:
    """(reference: scan/ScanMetrics.java) simple thread-safe counters."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}

    def increment(self, metric: str, delta: int = 1):
        with self._lock:
            self._counts[metric] = self._counts.get(metric, 0) + delta

    def get(self, metric: str) -> int:
        with self._lock:
            return self._counts.get(metric, 0)

    SUCCESS = "success"
    FAILURE = "failure"


class ScanJob(abc.ABC):
    def setup(self, graph, config, metrics: ScanMetrics) -> None:
        pass

    def get_queries(self) -> Sequence:
        """SliceQuery list; the FIRST is the primary query driving iteration
        (reference: ScanJob.getQueries)."""
        raise NotImplementedError

    @abc.abstractmethod
    def process(self, key: bytes, entries_by_query: dict, metrics: ScanMetrics
                ) -> None:
        """``entries_by_query``: SliceQuery -> EntryList for this row."""

    def worker_iteration_start(self, config, metrics: ScanMetrics) -> None:
        pass

    def worker_iteration_end(self, metrics: ScanMetrics) -> None:
        pass


class VertexScanJob(abc.ABC):
    def setup(self, graph, config, metrics: ScanMetrics) -> None:
        pass

    @abc.abstractmethod
    def process(self, vertex, metrics: ScanMetrics) -> None: ...

    def get_queries(self, query_container) -> None:
        """Declare adjacency slices to preload via the QueryContainer."""


class Memory:
    """Global BSP memory (reference: FulgoraMemory.java:131)."""

    def __init__(self):
        self._values: dict[str, Any] = {}
        self.iteration = 0

    def get(self, key: str, default=None):
        return self._values.get(key, default)

    def set(self, key: str, value):
        self._values[key] = value

    def add(self, key: str, value):
        self._values[key] = self._values.get(key, 0) + value

    def keys(self):
        return list(self._values)


class Messenger:
    """Per-vertex message access during execute()."""

    def __init__(self, vertex_memory, vertex_id: int):
        self._vm = vertex_memory
        self._vid = vertex_id

    def receive(self) -> list:
        return self._vm.messages_for(self._vid)

    def send(self, message, target_ids) -> None:
        for t in target_ids:
            self._vm.send(t, message)


class VertexProgram(abc.ABC):
    """Host BSP program (reference: TinkerPop VertexProgram executed by
    FulgoraGraphComputer.java:151-189)."""

    def setup(self, memory: Memory) -> None:
        pass

    @abc.abstractmethod
    def execute(self, vertex, messenger: Messenger, memory: Memory) -> None: ...

    @abc.abstractmethod
    def terminate(self, memory: Memory) -> bool: ...

    def combiner(self) -> Optional[Callable[[Any, Any], Any]]:
        """Optional associative message combiner
        (reference: MessageCombiner)."""
        return None

    @property
    def state_keys(self) -> Sequence[str]:
        """Vertex state property names this program writes."""
        return ()


class MapEmitter:
    """Collects (key, value) pairs from map() (reference:
    FulgoraMapEmitter)."""

    def __init__(self):
        self.pairs: list = []

    def emit(self, key, value) -> None:
        self.pairs.append((key, value))


class ReduceEmitter:
    """Collects (key, value) pairs from combine()/reduce() (reference:
    FulgoraReduceEmitter)."""

    def __init__(self):
        self.pairs: list = []

    def emit(self, key, value) -> None:
        self.pairs.append((key, value))


class MapReduce(abc.ABC):
    """Post-BSP aggregation stage (reference: TinkerPop MapReduce executed
    at FulgoraGraphComputer.java:192-246 — map over all vertices, optional
    per-worker combine, grouped reduce, result stored in Memory under
    ``memory_key``)."""

    memory_key: str = "mapreduce"

    @abc.abstractmethod
    def map(self, vertex, emitter: MapEmitter) -> None: ...

    def has_combine(self) -> bool:
        return type(self).combine is not MapReduce.combine

    def combine(self, key, values: list, emitter: ReduceEmitter) -> None:
        """Optional associative pre-reduce applied per worker chunk."""
        self.reduce(key, values, emitter)

    def has_reduce(self) -> bool:
        return type(self).reduce is not MapReduce.reduce

    def reduce(self, key, values: list, emitter: ReduceEmitter) -> None:
        """Default: pass map output through unchanged."""
        for v in values:
            emitter.emit(key, v)

    def finalize(self, results: dict):
        """Grouped {key: [values]} → the object stored in Memory
        (reference: MapReduce.generateFinalResult)."""
        return results


def execute_map_reduce(mr: MapReduce, vertices, chunk: int = 4096) -> Any:
    """Run one MapReduce over an iterable of vertex views: map → per-chunk
    combine → grouped reduce → finalize. Shared by the host computer and the
    TPU computer's host-side fallback path."""
    combined: dict = {}

    def absorb(pairs):
        if mr.has_combine():
            by_key: dict = {}
            for k, v in pairs:
                by_key.setdefault(k, []).append(v)
            em = ReduceEmitter()
            for k, vs in by_key.items():
                mr.combine(k, vs, em)
            pairs = em.pairs
        for k, v in pairs:
            combined.setdefault(k, []).append(v)

    em = MapEmitter()
    n_in_chunk = 0
    for v in vertices:
        mr.map(v, em)
        n_in_chunk += 1
        if n_in_chunk >= chunk:
            absorb(em.pairs)
            em = MapEmitter()
            n_in_chunk = 0
    absorb(em.pairs)

    if mr.has_reduce():
        rem = ReduceEmitter()
        for k, vs in combined.items():
            mr.reduce(k, vs, rem)
        grouped: dict = {}
        for k, v in rem.pairs:
            grouped.setdefault(k, []).append(v)
    else:
        grouped = combined
    return mr.finalize(grouped)


class DenseMapReduce(abc.ABC):
    """TPU-native post-BSP aggregation: instead of per-vertex map/reduce
    callbacks, one array program over the final dense state (SURVEY §7:
    MapReduce stages → jnp reductions). ``compute`` receives the program's
    output arrays (shape [n]) and must be expressible in numpy/jnp ops."""

    memory_key: str = "mapreduce"

    @abc.abstractmethod
    def compute(self, state: dict, snapshot, params: dict): ...


@dataclass
class EdgeData:
    """Per-edge arrays aligned with the snapshot's edge order."""
    values: dict = field(default_factory=dict)   # name -> np/jnp array [E]


@dataclass
class JobSpec:
    """Declarative vertex-program job for the async serving layer
    (olap/serving — the rebuild of the reference's L7→L4b seam where
    gremlin-server requests feed FulgoraGraphComputer's executor, here
    as an admission-controlled queue over the TPU engine).

    ``kind`` names a row of ``olap/serving/kinds.KINDS`` (what the kind
    reads, reserves, checkpoints and runs; docs/serving.md lists every
    kind's ``params`` and result keys).

    ``deadline`` is an absolute ``time.time()`` by which the job must
    START — jobs still queued past it are EXPIRED by admission control.
    ``timeout_s`` bounds RUNTIME; for batched BFS it is enforced at
    level boundaries through the per-job early-exit mask.
    ``labels``/``edge_keys``/``directed`` select the snapshot the job
    runs against (SnapshotPool parameters; ``directed=False``
    symmetrizes, which the direction-optimizing BFS kernels require).
    For 'dense' jobs the scheduler derives ``edge_keys`` from the
    program's ``edge_keys()`` when unset.

    Recovery plane (olap/recovery): ``max_retries`` lets a RUNNING job
    that dies (worker exception, injected fault, snapshot eviction)
    requeue as RETRYING — with exponential backoff starting at
    ``retry_backoff_s`` — up to that many extra attempts before FAILED;
    ``checkpoint_every > 0`` (with a scheduler-level
    ``checkpoint_dir``) captures the program state every N round
    boundaries so a retried attempt resumes from the newest valid
    checkpoint instead of restarting, bit-equal to an uninterrupted
    run. Cancellation, timeout and param errors never retry.

    Tenancy (olap/serving/tenants): ``tenant`` attributes the job's
    queue-ms / device-seconds / HBM-byte-seconds / replayed-rounds to a
    named tenant, labels its metrics and trace, and subjects it to that
    tenant's quota when the scheduler enforces quotas; unset/empty
    falls back to ``"default"`` everywhere.

    Fleet failover (olap/fleet): ``idempotency_key`` names the LOGICAL
    job across processes — schedulers key this job's checkpoints by it
    (instead of the per-scheduler private namespace), so a redispatch
    of the same logical job onto a surviving replica adopts the dead
    replica's newest checkpoint over the shared store and resumes
    rather than restarts, on its FIRST local attempt."""

    kind: str
    params: dict = field(default_factory=dict)
    priority: int = 0
    deadline: Optional[float] = None
    timeout_s: Optional[float] = None
    labels: Optional[Sequence[str]] = None
    edge_keys: Sequence[str] = ()
    directed: bool = False
    max_retries: int = 0
    checkpoint_every: int = 0
    retry_backoff_s: float = 0.05
    tenant: Optional[str] = None
    idempotency_key: Optional[str] = None


class DenseProgram(abc.ABC):
    """TPU-native vertex program: one compiled superstep, iterated on device.

    State is a dict[str, array] of per-vertex arrays. Each superstep the
    engine computes::

        src_state = {k: state[k][src] for k}            # gather over edges
        msg       = self.message(src_state, edge_data)  # [E] per-edge values
        agg       = segment_<combine>(msg, dst, n)      # combine per vertex
        state'    = self.apply(state, agg, iteration)

    and stops when ``self.done(state, state', agg, iteration)`` is True or
    ``max_iterations`` is reached. All callbacks must be jax-traceable.
    """

    combine: str = "sum"          # 'sum' | 'min' | 'max'
    max_iterations: int = 50

    @abc.abstractmethod
    def init(self, n: int, params: dict) -> dict: ...

    @abc.abstractmethod
    def message(self, src_state: dict, edge_data: dict, params: dict): ...

    @abc.abstractmethod
    def apply(self, state: dict, agg, iteration, params: dict) -> dict: ...

    def identity(self, params: dict):
        import jax.numpy as jnp
        return {"sum": 0.0, "min": jnp.inf, "max": -jnp.inf}[self.combine]

    def done(self, state: dict, new_state: dict, agg, iteration, params: dict):
        import jax.numpy as jnp
        return jnp.array(False)

    def edge_keys(self) -> Sequence[str]:
        """Edge property names required in EdgeData (e.g. ('weight',))."""
        return ()

    def outputs(self, state: dict, params: dict) -> dict:
        """Final state → user-facing arrays (default: identity)."""
        return state
