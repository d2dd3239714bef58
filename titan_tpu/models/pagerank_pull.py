"""The uniform PageRank sweep as a PULL over the in-edges, its random
reads served from a table in VMEM by one Pallas program.

``rank'[v] = (1-d)/n + d * sum over u -> v of rank[u]/outdeg[u]`` read
from v's side: exact for any graph, directed or not, no test for
symmetry. Three pieces, one program an iteration (``pagerank_pull``
under ``jit_once``):

* **the image** (:func:`pull_image`, host, once a snapshot): the
  in-edge chunked CSR ``srcT [8, q_in]`` (pad = n + 1, a table entry
  that reads 0.0) — the snapshot's arrays are dst-sorted, so
  ``snap.src`` / ``snap.indptr_in`` ARE its payload and index, laid out
  by ``bfs_hybrid.layout_slot_positions`` like every other chunked
  layout — stored flat, row after row, the column count rounded up to
  a whole block so that no block reads past it. With it, from the same
  pass, what the segment sum needs: each column's first-of-its-vertex
  flag and each vertex's last column.
* **the gather**: ``colsum[q]`` = the sum of ``contrib`` over column
  q's eight in-neighbours. On a TPU whose VMEM holds the table, the
  Pallas kernel of ``ops/vmem_gather.py`` (``contrib`` whole in VMEM,
  the indices a block at a time in SMEM). Elsewhere (the CPU, a table
  that outgrows VMEM) XLA's gather over the same image,
  ``contrib[srcT].sum(0)``.
* **the segment sum**: a vertex's columns are consecutive, so
  ``ops/segment.seg_scan`` sums inside a vertex in a fixed tree order
  (no prefix-sum differences: a float32 cumsum over 17 M columns loses
  the small ranks) and one sorted gather reads each vertex's last
  column.

The gather and the segment sum take any ``[n + 1]`` float32 table
(:func:`pull_sum`): PageRank's ``rank / deg``, and the level-masked
tables of ``models/bc.py``, which hands the tables of a group of roots
at once, ``[w, n + 1]``: ONE pass over the image gathers all w values
at each index (``vmem_gather.colsum_vmem`` at ``width`` w), the scan
and the last-column read run over ``[w, q_in]``.

What chooses the gather is what the code can observe — the backend and
the table's size (``vmem_gather.gather_impl``) — never a flag, an
argument or the environment.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.ops import vmem_gather
from titan_tpu.utils.jitcache import jit_once


def pull_columns(indptr_in: np.ndarray, n: int) -> int:
    """Columns of the pull image, BEFORE it is built (admission sizes
    it from the in-degrees): sum(ceil(deg/8)) + 1 sink column, rounded
    up to a whole block."""
    deg = np.diff(np.asarray(indptr_in[:n + 1], np.int64))
    q = int((-(-deg // 8)).sum()) + 1
    return vmem_gather.padded_columns(q)


def pull_image_bytes(n: int, q_in: int) -> int:
    """Device bytes of the pull image: indices [8 x q_in] int32, a flag
    a column, last column + has-columns + out-degree a vertex."""
    return q_in * 8 * 4 + q_in + n * 4 + n + (n + 1) * 4


def pull_image(snap) -> dict:
    """Host-side (cached on the snapshot as ``_pull_csr``, dropped with
    the other layouts): the in-edge image of the module docstring, on
    device. ``idx`` int32 [8 * q_in] (``srcT``, row after row),
    ``first`` bool [q_in], ``last`` int32 [n], ``has`` bool [n], ``deg``
    float32 [n + 1] (OUT-degrees, 0 for the sink), ``seg_max`` the most
    columns a vertex has."""
    cached = getattr(snap, "_pull_csr", None)
    if cached is not None:
        return cached
    import jax.numpy as jnp

    from titan_tpu.models.bfs_hybrid import layout_slot_positions
    from titan_tpu.obs import devprof
    from titan_tpu.ops.segment import segment_metadata

    n = snap.n
    deg_in = np.diff(snap.indptr_in).astype(np.int64)
    pos, colstart, degc = layout_slot_positions(snap.indptr_in, deg_in, n)
    q_in = pull_columns(snap.indptr_in, n)
    if q_in >= (1 << 28):
        raise NotImplementedError(
            "the pull image indexes slots in int32; shard below 2^28 "
            "columns")
    flat = np.full(q_in * 8, n + 1, np.int32)
    flat[pos] = snap.src
    idx = np.ascontiguousarray(flat.reshape(q_in, 8).T).reshape(-1)
    last, has = segment_metadata(colstart)
    first = np.zeros(q_in, bool)
    first[colstart[:n][has]] = True
    first[int(colstart[n]):] = True          # sink and pad columns
    outdeg = np.concatenate(
        [snap.out_degree.astype(np.float32), np.zeros(1, np.float32)])
    devprof.count_h2d("pagerank.pull_image", pull_image_bytes(n, q_in))
    out = {
        "idx": jnp.asarray(idx),
        "first": jnp.asarray(first),
        "last": jnp.asarray(np.maximum(last, 0)),
        "has": jnp.asarray(has),
        "deg": jnp.asarray(outdeg),
        "seg_max": int(degc.max()) if n else 1,
        "q_in": q_in,
        "n": n,
    }
    snap._pull_csr = out
    return out


def _colsum_xla(idx, table, width: int = 1):
    """XLA's gather over the same image: ``contrib[srcT].sum(0)``; at
    ``width`` values an entry, the entries' rows ``[width]`` gathered
    by the one index and summed, value r's sums in row r."""
    lanes = idx.reshape(8, -1)
    if width == 1:
        return table.reshape(-1)[lanes].sum(axis=0)
    return table.reshape(-1, width)[lanes].sum(axis=0).T


def pull_sum(table, idx, first, last, has, impl: str, seg_max: int):
    """``acc`` [n]: for every vertex the sum of ``table`` (float32
    [n + 1], entry n the sink's 0.0) over its in-neighbours: the column
    sums by the gather ``impl`` names, the segment scan, each vertex's
    last column. Traced inside its caller's program: ``pagerank_pull``
    hands it ``rank / deg``, ``models/bc.py`` a level's masked tables.

    ``table`` [w, n + 1] (w a power of two up to
    ``vmem_gather.MAX_WIDTH``) is w tables pulled in ONE pass over the
    image: ``acc`` [w, n], row r what ``table[r]`` gives alone. The
    tables stay major throughout (the column sums and the scan are
    [w, q_in]): w minor would pad every value to a row of lanes."""
    import jax.numpy as jnp

    from titan_tpu.ops.segment import seg_scan

    lead = table.shape[:-1]
    if lead == (1,):
        # one table is PageRank's program: a [1, q_in] array would fill
        # one sublane of eight in every pass of the scan
        return pull_sum(table[0], idx, first, last, has, impl,
                        seg_max)[None]
    colsum = (vmem_gather.colsum_vmem if impl == "vmem"
              else _colsum_xla)(idx, vmem_gather.as_table(table),
                                width=lead[0] if lead else 1)
    run = seg_scan(colsum.reshape(lead + (-1,)), first, "sum",
                   max_len=seg_max)
    return jnp.where(has, run[..., last], 0.0)


def pull_step():
    """``pagerank_pull``: one iteration's sums, ``acc`` [n] from
    ``rank`` [n + 1]. ``contrib`` is computed here, from ``rank`` and
    the out-degrees, by the expressions ``pagerank_finish`` uses."""
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit,
                           static_argnames=("impl", "seg_max"))
        def step(rank, deg, idx, first, last, has, impl: str,
                 seg_max: int):
            contrib = jnp.where(deg > 0, rank / jnp.maximum(deg, 1), 0.0)
            return pull_sum(contrib, idx, first, last, has, impl,
                            seg_max)
        return step
    return jit_once("pagerank_pull", build)
