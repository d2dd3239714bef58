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
forward and L - 2 backward levels.

**A job's roots run in groups that share every pull** (PERF.md 6, PR
47): a pull's price is the index, not the table, so the masked tables
of w roots stand side by side in one VMEM table
(``vmem_gather.as_table`` of ``[w, n + 1]``) and one pass over the
image serves them all. A group is the largest power of two that the
roots left, the kernel's eight selector rows and the table's cap at
this ``n`` allow (``vmem_gather.shared_width``: four at graph500-22,
38.3 MB of 64 MiB; a job of five roots is 4 + 1): what the code
observes, never a flag, a request field or the environment; a root
alone is the group of width 1, not a second path. ``depth``, ``sigma`` and ``delta`` are ``[w, n]``;
level d masks every root's own ``depth == d - 1``; the forward phase
ends when NO root gained a vertex (w counts read back a level); a root
that is done has nobody at the level asked for, pulls zeros and stays
as it is, in both phases, so every root's ``delta`` is what the root
gives alone (on XLA's road to the bit; under the kernel the MXU sums a
column's lanes, which stand elsewhere at another width, in another
order: a last-bit matter). A group whose deepest root has L levels is L
forward and L - 2 backward PULLS.

Every array is n wide whatever the roots and whatever the level, so the
two level programs (``bc_forward_level``, ``bc_backward_level``) are
built once a snapshot shape and group width; ``bc_seed`` and
``bc_result`` beside them are a few elementwise passes. The level loop
lives on the host: the forward phase needs the counts a level (who
joined) and the veto a boundary a level, so the host waits for every
level's output before it dispatches the next (a dispatch of idle device
time against a pull of 128 M lanes).

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


def level_bytes(n: int, q_in: int, width: int) -> int:
    """Device bytes a level of a group of ``width`` roots works on: the
    group's depth, sigma and delta, the level's outputs, the masked
    table as the roots hold it and as the gather reads it (seven
    n-vectors a root), and the temporaries as wide as the pull image's
    ``q_in`` columns (the kernel's sums a root, their stack, the scan's
    passes: 16 bytes a column and root; the chip's compiler counts 14
    at width 4 and 9.1 at width 1, tests/test_chip_compile.py)."""
    return width * (4 * n * (3 + 2 + 2) + 16 * q_in)


def work_bytes(n: int, q_in: int, roots: int = MAX_ROOTS) -> int:
    """Device bytes a job works on beside the images: a level of its
    widest group (``level_bytes``) and every root's delta kept for the
    sum (``roots``: admission prices the most a job may name)."""
    return level_bytes(n, q_in, vmem_gather.shared_width(n, roots)) \
        + 4 * n * roots


def _seed():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("n_",))
        def seed(roots, n_: int):
            at = jnp.arange(n_, dtype=jnp.int32)[None, :] == roots[:, None]
            return (jnp.where(at, 0, -1).astype(jnp.int32),
                    at.astype(jnp.float32), jnp.zeros(at.shape, jnp.float32))
        return seed
    return jit_once("bc_seed", build)


def _table(values):
    """``values`` [w, n] as the pull's table [w, n + 1]: the sink reads
    0."""
    import jax.numpy as jnp
    return jnp.pad(values, ((0, 0), (0, 1)))


def forward_level(depth, sigma, d, idx, first, last, has, impl: str,
                  seg_max: int, width: int):
    """Level ``d`` of a group's forward phase: ``(depth, sigma,
    joined)``, [w, n], [w, n] and the vertices each root gained [w].
    Every root's ``depth == d - 1`` masks its own sigma and ONE pull
    serves them all; a root that gained nobody at an earlier level has
    nobody at ``d - 1``, pulls zeros and stays as it is. ``width`` is
    the arrays' leading axis (static, so that the ``kernel`` span
    carries it)."""
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_sum

    assert depth.shape[0] == width, (depth.shape, width)
    table = _table(jnp.where(depth == d - 1, sigma, 0.0))
    paths = pull_sum(table, idx, first, last, has, impl, seg_max)
    new = (depth < 0) & (paths > 0)
    return (jnp.where(new, d, depth), jnp.where(new, paths, sigma),
            new.sum(axis=1, dtype=jnp.int32))


def backward_level(depth, sigma, delta, d, idx, first, last, has,
                   impl: str, seg_max: int, width: int):
    """Level ``d`` of a group's backward phase: ``delta`` [w, n] with
    each root's depth ``d - 1`` filled in from its depth ``d``. A root
    whose levels end before ``d`` pulls zeros: its deepest level takes
    sigma times 0, the 0.0 it was seeded with."""
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_sum

    assert depth.shape[0] == width, (depth.shape, width)
    table = _table(jnp.where(depth == d,
                             (1.0 + delta) / jnp.maximum(sigma, 1.0), 0.0))
    share = pull_sum(table, idx, first, last, has, impl, seg_max)
    return jnp.where(depth == d - 1, sigma * share, delta)


def _level(key: str, body):
    def build():
        import jax
        return jax.jit(body, static_argnames=("impl", "seg_max", "width"))
    return jit_once(key, build)


def _result():
    def build():
        import jax
        import jax.numpy as jnp

        @jax.jit
        def result(deltas):
            # a row a root, summed in the order the job named them
            total = functools.reduce(
                jnp.add, [rows[r] for rows in deltas
                          for r in range(rows.shape[0])])
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
    ``roots``: dense indices; they run in groups of ``shared_width``, in
    the order named, and a group's roots share every pull.

    ``on_round(i)``: veto at the i-th level boundary, counted over both
    phases and all groups (RoundInterrupted): the serving layer's cancel
    and timeout hook, within one pull of its cause. No checkpoint: a
    retried job starts over, the image still resident."""
    import jax

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
    image = (im["idx"], im["first"], im["last"], im["has"])
    seed = _seed()
    forward = _level("bc_forward_level", forward_level)
    backward = _level("bc_backward_level", backward_level)
    done = 0

    def boundary():
        nonlocal done
        done += 1
        if on_round is not None and not on_round(done):
            raise RoundInterrupted(done)

    roots = [int(r) for r in roots]
    deltas, levels, reached = [], [], []
    while len(levels) < len(roots):
        width = vmem_gather.shared_width(n, len(roots) - len(levels))
        group = roots[len(levels):len(levels) + width]
        impl = vmem_gather.gather_impl(n, width)
        statics = {"impl": impl, "seg_max": im["seg_max"], "width": width}
        said = {"roots": group, "width": width, "impl": impl}
        with phase("bc.forward", **said) as ph:
            depth, sigma, delta = seed(np.asarray(group, np.int32), n_=n)
            d, seen, deep = 0, [1] * width, [0] * width
            while not all(deep):
                d += 1
                depth, sigma, joined = forward(depth, sigma, dev_scalar(d),
                                               *image, **statics)
                with ph.sync():
                    joined = np.asarray(joined).tolist()
                boundary()
                for r, gained in enumerate(joined):
                    # a root's levels end at its first pull that finds
                    # nobody; it gains nobody at any later one
                    seen[r] += gained
                    if not gained and not deep[r]:
                        deep[r] = d
            ph.set(levels=d, reached=sum(seen), root_levels=deep,
                   root_reached=seen)
        devprof.count_bc("forward", deep, width)
        # levels 0 .. d - 1 hold a vertex; the deepest has no dependency
        # and the root takes none
        back = [max(lv - 2, 0) for lv in deep]
        with phase("bc.backward", levels=max(back), root_levels=back,
                   **said) as ph:
            for k in range(d - 1, 1, -1):
                delta = backward(depth, sigma, delta, dev_scalar(k),
                                 *image, **statics)
                with ph.sync():
                    jax.block_until_ready(delta)
                boundary()
        devprof.count_bc("backward", back, width)
        deltas.append(delta)
        levels += deep
        reached += seen
    with phase("bc.result", bytes=4 * n, roots=len(roots)) as ph:
        scores = _result()(tuple(deltas))
        devprof.count_d2h("bc.result", 4 * n)
        with ph.sync():
            out = np.asarray(scores)
    return out, levels, reached
