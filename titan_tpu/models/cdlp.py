"""Community detection by label propagation (LDBC Graphalytics' CDLP,
specification v1.0 section 2.3.4: the deterministic, synchronous
variant of Raghavan et al.) over PageRank's pull image.

    L_0(v) = v
    L_i(v) = the smallest label among the most frequent of
             { L_{i-1}(u) : u an in-neighbour of v }

every L_i from L_{i-1} alone; a vertex without a neighbour keeps its
label. The served snapshot holds an undirected edge in both directions,
so the in-neighbours are the neighbours, each once; a directed
snapshot's vote is over its in-edges alone (Graphalytics counts both
directions there).

The mode of a multiset is the one combiner here that no pass over the
edges in any order can fold (``ops/segment.py``), so a round is three
programs, each under a ``jit_once`` key of its own, and no host sync:

* ``cdlp_gather``: every lane of the in-edge image ``srcT`` reads its
  neighbour's label. What serves the reads is
  ``vmem_gather.gather_impl``'s to say, as for PageRank: on a TPU whose
  VMEM holds the table, the Pallas kernel a lane at a time (the labels
  as float32, exact below 2^24, which the table's 64 MiB keeps them
  under); elsewhere XLA's gather. A pad lane reads the pad entry n + 1,
  whose label is its own id, above every vertex's.
* ``cdlp_sort``: the image is vertex-ordered, so only the order inside
  a vertex is missing: one ``lax.sort`` of the (owner, label) pairs,
  the owner of a column counted from the image's first-of-its-vertex
  flags. Pad lanes stay with their vertex and sort behind its labels.
* ``cdlp_vote``: ``segment.mode_vote`` over the sorted pairs; a
  vertex's answer stands at its last lane (8 x its last column + 7),
  read by one sorted gather.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.ops import vmem_gather
from titan_tpu.utils.jitcache import jit_once

#: lane-wide int32 arrays a job's rounds may keep at once: what the
#: chip's compiler counts for the widest program, ``cdlp_vote``, at
#: graph500-22 (7.5: the sorted pair and 5.5 of temporaries, the scans'
#: two generations of count, label and flags: tests/test_chip_compile.py),
#: rounded up. The chip's allocator read 6.1 at its peak (PERF.md 4, PR
#: 40) with the loop below one round ahead of the device, and 22 with all
#: ten rounds dispatched at once: what is dispatched and has not run
#: holds its outputs
WORK_LANE_WORDS = 8


def work_bytes(n: int, q_in: int) -> int:
    """Device bytes a job's rounds need beside the images: the lane-wide
    operands and temporaries of its widest program, and two label
    vectors."""
    return WORK_LANE_WORDS * 4 * 8 * q_in + 2 * 4 * n


def _gather():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("impl", "n_"))
        def gather(labels, idx, impl: str, n_: int):
            table = jnp.concatenate(
                [labels, jnp.arange(n_, n_ + 2, dtype=jnp.int32)])
            if impl == "vmem":
                lanes = vmem_gather.colsum_vmem(
                    idx, vmem_gather.as_table(table.astype(jnp.float32)),
                    rows=1)
                return lanes.astype(jnp.int32)
            return table[idx]
        return gather
    return jit_once("cdlp_gather", build)


def _sort():
    def build():
        import jax
        import jax.numpy as jnp

        # no donation of the lanes: the watcher stamps the gather by its
        # output (obs/devprof), which a donation deletes under it
        @jax.jit
        def sort(first, lanes):
            owner = jnp.tile(jnp.cumsum(first, dtype=jnp.int32) - 1, 8)
            # equal pairs are interchangeable: a stable sort would carry
            # a third operand, the positions, through every pass
            return jax.lax.sort((owner, lanes), num_keys=2,
                                is_stable=False)
        return sort
    return jit_once("cdlp_sort", build)


def _vote():
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.segment import mode_vote

        @functools.partial(jax.jit, static_argnames=("seg_max", "n_"))
        def vote(owner, lanes, labels, last, has, seg_max: int, n_: int):
            best = mode_vote(owner, lanes, pad=n_ + 1,
                             max_len=8 * seg_max)
            return jnp.where(has, best[8 * last + 7], labels)
        return vote
    return jit_once("cdlp_vote", build)


def cdlp(snap, iterations: int = 10, on_round=None, checkpoint=None,
         resume: dict | None = None, overlay=None):
    """(labels int32 [n] on the host, rounds run): ``iterations``
    synchronous rounds from L_0(v) = v. A fixpoint is not looked for:
    the rounds behind one return the same labels, and finding it would
    cost the loop a readback. The loop runs ONE round ahead of the
    device: with round ``it`` dispatched it waits for round ``it - 1``'s
    labels, so the device always has a round queued, the rounds' lane-wide
    outputs (allocated at dispatch) are two rounds' and not ten's, and a
    veto takes effect within two rounds of its cause.

    ``on_round(it)``: veto before round it + 1 (RoundInterrupted), the
    serving layer's cancel and timeout hook. ``checkpoint(it,
    {"labels": L_it})`` after each round; ``resume``: ``{"labels",
    "it"}``: a round reads its labels alone, so the continuation is
    bit-equal to a straight run."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.models.frontier import RoundInterrupted
    from titan_tpu.models.pagerank_pull import pull_image
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    ov = overlay if overlay is not None \
        else getattr(snap, "_live_overlay", None)
    if ov is not None and not ov.empty:
        raise RuntimeError(
            "cdlp on a live overlay: compact the overlay first "
            "(LiveGraphPlane.compact_if_dirty); the pull image has no "
            "overlay seam")
    im = pull_image(snap)
    n = im["n"]
    impl = vmem_gather.gather_impl(n)
    gather, sort, vote = _gather(), _sort(), _vote()
    it0 = 0
    if resume is not None:
        labels = jnp.asarray(np.asarray(resume["labels"], np.int32))
        it0 = int(resume["it"])
    else:
        labels = jnp.asarray(np.arange(n, dtype=np.int32))
    it = it0
    for it in range(it0 + 1, iterations + 1):
        if on_round is not None and not on_round(it - 1):
            raise RoundInterrupted(it - 1)
        with phase("cdlp.round", it=it, impl=impl) as ph:
            behind = labels
            lanes = gather(labels, im["idx"], impl=impl, n_=n)
            owner, lanes = sort(im["first"], lanes)
            labels = vote(owner, lanes, labels, im["last"], im["has"],
                          seg_max=im["seg_max"], n_=n)
            del owner, lanes
            with ph.sync():
                jax.block_until_ready(behind)
        devprof.count_cdlp_round(impl, 8 * im["q_in"])
        if checkpoint is not None:
            checkpoint(it, {"labels": labels})
    with phase("cdlp.result", bytes=int(labels.nbytes)) as ph:
        devprof.count_d2h("cdlp.result", labels.nbytes)
        with ph.sync():
            out = np.asarray(labels)
    return out, it
