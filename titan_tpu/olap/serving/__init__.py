"""OLAP serving layer: concurrent job scheduling + multi-source batching.

This package rebuilds the reference's L7→L4b serving seam — gremlin-server
YAML endpoints feeding ``FulgoraGraphComputer``'s executor service
(reference: titan-dist conf/gremlin-server/gremlin-server.yaml +
graphdb/olap/computer/FulgoraGraphComputer.java:48-120) — as an
admission-controlled asynchronous job plane over the TPU engine:

* ``jobs``      — job/handle lifecycle (queued → running → terminal).
* ``pool``      — epoch-aware snapshot pool: concurrent jobs share one
                  ``GraphSnapshot`` per parameter set, refreshed through
                  the epoch/refresh() freshness contract before hand-out.
* ``hbm``       — device-memory accounting (the bench ``_DEV_GRAPHS``
                  budget/eviction logic as a library) backing admission.
* ``kinds``     — one row a job kind: what it reads, reserves,
                  checkpoints and runs; the scheduler, the batcher and
                  the server read it.
* ``batcher``   — multi-source fusion: compatible same-snapshot BFS jobs
                  execute as ONE batched [K, n] device run
                  (models/bfs_hybrid.frontier_bfs_batched), amortizing
                  the per-level plan floor K-fold.
* ``scheduler`` — priority queue + admission + worker, with per-job
                  latency / queue-depth / batch-occupancy metrics
                  through utils/metrics.
* ``autotune``  — the closed-loop decision plane (ROADMAP #4): a
                  per-scheduler Controller ticks over the metric/SLO
                  registries and journals bounded, replayable knob
                  decisions (batch K, tenant quota scaling, compaction
                  triggers, checkpoint cadence); shadow by default,
                  ``autotune="enforce"`` applies them.
                  ``GET /controller`` serves the journal.
* ``tenants``   — per-tenant resource attribution (queue-ms /
                  device-seconds / HBM byte-seconds / replayed rounds)
                  and quota admission (``TenantQuota``, enforced at
                  submit behind ``JobScheduler(enforce_quotas=True)``;
                  shadow-counted otherwise). ``GET /tenants`` +
                  ``GET /slo`` expose the plane; docs/monitoring.md
                  documents the label/tenant model.

``server.py`` exposes this as ``POST /jobs`` / ``GET /jobs/<id>`` /
``DELETE /jobs/<id>``; docs/serving.md documents the contract. The
checkpoint & recovery plane (preemption-safe jobs: RETRYING + backoff
requeue + deterministic resume from superstep checkpoints) lives in
``olap/recovery`` and plugs in through ``JobScheduler(checkpoint_dir=)``
+ ``JobSpec.max_retries`` / ``checkpoint_every``; docs/recovery.md.
"""

from titan_tpu.olap.serving.jobs import Job, JobState            # noqa: F401
from titan_tpu.olap.serving.scheduler import JobScheduler        # noqa: F401
from titan_tpu.olap.serving.tenants import (DEFAULT_TENANT,      # noqa: F401
                                            QuotaExceeded,
                                            TenantQuota)
