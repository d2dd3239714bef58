"""Betweenness centrality from named roots (GAP Benchmark Suite's BC
kernel: Brandes' dependency accumulation from a few roots a trial) as
two phases over stored BFS levels, every level one pull over PageRank's
in-edge image.

For a root s:

    depth[v]  = BFS distance from s (unreached: -1)
    sigma[s]  = 1
    sigma[v]  = sum of sigma[u] over in-neighbours u, depth[u] = depth[v] - 1
    delta[u]  = sigma[u] * sum over out-neighbours v, depth[v] = depth[u] + 1,
                of (1 + delta[v]) / sigma[v];     delta[s] = 0
    scores    = sum over the roots of delta, divided by its largest entry
                where that is positive

Every directed edge slot is a path step (parallel edges are parallel
paths); a root named twice counts twice. The served snapshot holds an
undirected edge in both directions, so a vertex's out-neighbours are
its in-neighbours and BOTH recurrences are "sum over a vertex's
in-neighbours of a table masked to one level": the gather, segment scan
and last-column read of ``pagerank_pull.pull_sum``, chosen by
``vmem_gather.gather_impl`` as PageRank's is. A directed snapshot's
backward phase needs the out-edges' image: not implemented, refused at
``submit`` (serving/kinds.py) and, for a snapshot handed in whose
degrees differ, here.

The forward phase labels one level a pull (level d: the table is sigma
at depth d - 1; a vertex without a depth whose sum is positive joins
level d) until a pull finds nobody; the levels stay stored in ``depth``.
The backward phase walks them from the deepest to the root's
neighbours, a level a pull (the table is (1 + delta) / sigma at depth
d; depth d - 1 takes sigma times its sum). A root of L levels is L
forward and L - 2 backward pulls. Every array is n wide whatever the
root and whatever the level, so the two level programs
(``bc_forward_level``, ``bc_backward_level``) are built once a
snapshot shape; ``bc_seed`` and ``bc_result`` beside them are a few
elementwise passes. The level loop lives on the host: the forward phase
needs one scalar a level (how many joined) and the veto a boundary a
level, so the host waits for every level's output before it dispatches
the next (a dispatch of idle device time against a pull of 128 M lanes).

float32 throughout. Every term of both recurrences is non-negative, so
rounding is all that separates the result from float64's; sigma is
exact while it stays under 2^24.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.ops import vmem_gather
from titan_tpu.utils.jitcache import dev_scalar, jit_once

#: the most roots a job names
MAX_ROOTS = 16


def work_bytes(n: int, q_in: int, roots: int = MAX_ROOTS) -> int:
    """Device bytes a job works on beside the images: a root's depth,
    sigma and delta, the outputs of the level in flight, every root's
    delta kept for the sum (``roots``: admission prices the most a job
    may name), and a level's temporaries, which are as wide as the pull
    image's ``q_in`` columns (the column sums and the scan's passes:
    9.1 bytes a column in the chip's compiler's count,
    tests/test_chip_compile.py)."""
    return 4 * n * (3 + 2 + roots) + 10 * q_in


def _seed():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def seed(root, n_: int):
            at = jnp.arange(n_, dtype=jnp.int32) == root
            return (jnp.where(at, 0, -1).astype(jnp.int32),
                    at.astype(jnp.float32), jnp.zeros(n_, jnp.float32))
        return seed
    return jit_once("bc_seed", build)


def _table(values):
    """``values`` [n] as the pull's table [n + 1]: the sink reads 0."""
    import jax.numpy as jnp
    return jnp.concatenate([values, jnp.zeros(1, values.dtype)])


def forward_level(depth, sigma, d, idx, first, last, has, impl: str,
                  seg_max: int):
    """Level ``d`` of the forward phase: ``(depth, sigma, joined)``."""
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_sum

    table = _table(jnp.where(depth == d - 1, sigma, 0.0))
    paths = pull_sum(table, idx, first, last, has, impl, seg_max)
    new = (depth < 0) & (paths > 0)
    return (jnp.where(new, d, depth), jnp.where(new, paths, sigma),
            new.sum(dtype=jnp.int32))


def backward_level(depth, sigma, delta, d, idx, first, last, has,
                   impl: str, seg_max: int):
    """Level ``d`` of the backward phase: ``delta`` with depth ``d - 1``
    filled in from depth ``d``."""
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_sum

    table = _table(jnp.where(depth == d,
                             (1.0 + delta) / jnp.maximum(sigma, 1.0), 0.0))
    share = pull_sum(table, idx, first, last, has, impl, seg_max)
    return jnp.where(depth == d - 1, sigma * share, delta)


def _level(key: str, body):
    def build():
        import jax
        return jax.jit(body, static_argnames=("impl", "seg_max"))
    return jit_once(key, build)


def _result():
    def build():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def result(deltas):
            total = functools.reduce(jnp.add, deltas)
            top = total.max()
            return total / jnp.where(top > 0, top, 1.0)
        return result
    return jit_once("bc_result", build)


def dense_roots(snap, params: dict) -> list:
    """A job's roots as dense indices: ``sources_dense`` wins, else
    ``sources`` are original vertex ids mapped through the snapshot.
    Raises ValueError for any malformed value, an unknown id, no root
    or more than ``MAX_ROOTS``."""
    key = "sources_dense" if "sources_dense" in params else "sources"
    roots = params.get(key)
    if not isinstance(roots, (list, tuple)) or \
            not 1 <= len(roots) <= MAX_ROOTS:
        raise ValueError(
            f"job params need 'sources' (1 to {MAX_ROOTS} vertex ids) "
            "or 'sources_dense'")
    try:
        if key == "sources":
            return [snap.dense_of(int(r)) for r in roots]
        out = [int(r) for r in roots]
    except KeyError as e:                 # dense_of: unknown vertex
        raise ValueError(str(e)) from e
    except (TypeError, ValueError) as e:
        raise ValueError(f"bad sources value: {e}") from e
    for r in out:
        if not 0 <= r < snap.n:
            raise ValueError(f"unknown vertex (dense index {r})")
    return out


def bc(snap, roots, on_round=None, overlay=None):
    """``(scores float32 [n] on the host, levels, reached)``: the sum
    of the roots' dependencies over its largest entry, and for each
    root the BFS levels that hold a vertex and the vertices it reached.
    ``roots``: dense indices, one after another.

    ``on_round(i)``: veto at the i-th level boundary, counted over both
    phases and all roots (RoundInterrupted): the serving layer's cancel
    and timeout hook, within one pull of its cause. No checkpoint: a
    retried job starts over, the image still resident."""
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
            "bc on a live overlay: compact the overlay first "
            "(LiveGraphPlane.compact_if_dirty); the pull image has no "
            "overlay seam")
    n = snap.n
    if not np.array_equal(np.diff(snap.indptr_in[:n + 1]),
                          snap.out_degree):
        raise ValueError("bc: in- and out-degrees differ: the snapshot "
                         "is not an undirected graph held in both "
                         "directions")
    im = pull_image(snap)
    impl = vmem_gather.gather_impl(n)
    image = (im["idx"], im["first"], im["last"], im["has"])
    statics = {"impl": impl, "seg_max": im["seg_max"]}
    seed = _seed()
    forward = _level("bc_forward_level", forward_level)
    backward = _level("bc_backward_level", backward_level)
    done = 0

    def boundary():
        nonlocal done
        done += 1
        if on_round is not None and not on_round(done):
            raise RoundInterrupted(done)

    deltas, levels, reached = [], [], []
    for root in roots:
        with phase("bc.forward", root=int(root), impl=impl) as ph:
            depth, sigma, delta = seed(jnp.asarray(root, jnp.int32), n_=n)
            d, seen = 0, 1
            while True:
                d += 1
                depth, sigma, joined = forward(depth, sigma, dev_scalar(d),
                                               *image, **statics)
                with ph.sync():
                    joined = int(joined)
                boundary()
                if not joined:
                    break
                seen += joined
            ph.set(levels=d, reached=seen)
        devprof.count_bc("forward", d)
        # levels 0 .. d - 1 hold a vertex; the deepest has no dependency
        # and the root takes none
        back = max(d - 2, 0)
        with phase("bc.backward", root=int(root), impl=impl,
                   levels=back) as ph:
            for k in range(d - 1, 1, -1):
                delta = backward(depth, sigma, delta, dev_scalar(k),
                                 *image, **statics)
                with ph.sync():
                    jax.block_until_ready(delta)
                boundary()
        devprof.count_bc("backward", back)
        deltas.append(delta)
        levels.append(d)
        reached.append(seen)
    with phase("bc.result", bytes=4 * n, roots=len(deltas)) as ph:
        scores = _result()(tuple(deltas))
        devprof.count_d2h("bc.result", 4 * n)
        with ph.sync():
            out = np.asarray(scores)
    return out, levels, reached
