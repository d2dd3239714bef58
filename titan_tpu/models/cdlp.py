"""Community detection by label propagation (LDBC Graphalytics' CDLP,
specification v1.0 section 2.3.4: the deterministic, synchronous
variant of Raghavan et al.) over a lane image of its own, packed in
rows that hold whole vertices.

    L_0(v) = v
    L_i(v) = the smallest label among the most frequent of
             { L_{i-1}(u) : u an in-neighbour of v }

every L_i from L_{i-1} alone; a vertex without a neighbour keeps its
label. The served snapshot holds an undirected edge in both directions,
so the in-neighbours are the neighbours, each once; a directed
snapshot's vote is over its in-edges alone (Graphalytics counts both
directions there).

The mode of a multiset is the one combiner here that no pass over the
edges in any order can fold (``ops/segment.py``): a vertex has to see
its neighbours' labels GROUPED, and only the order inside a vertex is
missing. So the image (:func:`cdlp_image`, host, once a snapshot) puts
every vertex's lanes (its in-edges rounded up to whole columns of 8, as
every chunked layout here) into ONE row of a 2-D array, and a row holds
whole vertices and nothing of any other: sorting each row alone groups
every vertex's labels. Two classes of rows: the small row (at most
``SMALL_MAX`` columns: 8,192 lanes, a sort that stays in VMEM) takes
every vertex that fits it, the wide row (the next power of two at or
above the largest vertex's columns) the rest; both filled next-fit by
decreasing column count (:func:`row_plan`). The order of vertices in
the image is free: nothing else reads it.

A round is three programs, each under a ``jit_once`` key of its own,
and no host sync:

* ``cdlp_gather``: every lane of the image reads its neighbour's label.
  What serves the reads is ``vmem_gather.gather_impl``'s to say, as for
  PageRank: on a TPU whose VMEM holds the table, the Pallas kernel a
  lane at a time (the labels as float32, exact below 2^24, which the
  table's 64 MiB keeps them under); elsewhere XLA's gather. A pad lane
  reads the pad entry n + 1, whose label is its own id, above every
  vertex's.
* ``cdlp_sort``: ONE 32-bit operand a lane where the bits allow it: the
  lane's owner INSIDE its row (a row of w columns holds at most w
  vertices: every vertex has a column) above the label's
  ``(n + 1).bit_length()`` bits, ``lax.sort`` along the rows, a class at
  a time; then the word is split again by arithmetic alone. Where the
  bits do not fit (``row_plan``: n and the largest degree decide), the
  same row sort runs on the pair (owner, label). Pad lanes stay with
  their vertex and sort behind its labels; a row's unused lanes ride on
  its LAST vertex (a row exactly full of one-column vertices leaves no
  owner number free for a pad of its own).
* ``cdlp_vote``: ``segment.mode_vote`` over the sorted pairs; a
  vertex's answer stands at its last lane, which the image knows
  beforehand (the sort only groups: a vertex's lanes keep their range),
  read by one sorted gather.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.ops import vmem_gather
from titan_tpu.utils.jitcache import jit_once

#: bits of the sort's one operand: the owner inside the row above the
#: label
KEY_BITS = 32
#: columns of a small row at most: 8,192 lanes, 32 KB, sorted in VMEM
SMALL_MAX = 1024

#: lane-wide 32-bit arrays a job's rounds may keep at once beside the
#: image: what the chip's compiler counts for the widest program,
#: ``cdlp_vote``, at graph500-22 (7.5: the sorted pair and 5.5 of
#: temporaries, the scans' two generations of count, label and flags;
#: ``cdlp_sort`` holds 5: the gathered lanes, the word sorted and the
#: pair split from it: tests/test_chip_compile.py), rounded up. The
#: chip's allocator read 6.1 at its peak (PERF.md 4, PR 40) with the
#: loop below one round ahead of the device, and 22 with all ten rounds
#: dispatched at once: what is dispatched and has not run holds its
#: outputs
WORK_LANE_WORDS = 8


def work_bytes(n: int, lanes: int) -> int:
    """Device bytes a job's rounds need beside the images: the lane-wide
    operands and temporaries of its widest program, and two label
    vectors."""
    return WORK_LANE_WORDS * 4 * lanes + 2 * 4 * n


def image_bytes(n: int, lanes: int) -> int:
    """Device bytes of the row image: a neighbour id and the key's owner
    part a lane, last lane + has-lanes a vertex."""
    return lanes * (4 + 4) + n * 4 + n


def _next_fit(sizes: np.ndarray, width: int):
    """Rows of ``width`` columns filled next-fit with whole items of
    ``sizes`` columns (each at most ``width``) in the order given:
    ``(row of each item, its first column in its row, rows)``. The row
    breaks are one chain of jumps, a step a ROW: item i opens a row
    that item ``nxt[i]`` is the first not to fit."""
    ends = np.cumsum(sizes, dtype=np.int64)
    starts = ends - sizes
    nxt = np.searchsorted(ends, starts + width, side="right")
    heads, i = [], 0
    while i < len(sizes):
        heads.append(i)
        i = int(nxt[i])
    heads = np.asarray(heads, np.int64)
    row = np.zeros(len(sizes), np.int64)
    row[heads[1:]] = 1
    row = np.cumsum(row)
    return row, starts - starts[heads][row], len(heads)


def _place(sizes: np.ndarray, width: int):
    """``(row, first column, rows)`` of items of ``sizes`` columns, by
    decreasing size, in rows of ``width``: next-fit for the items of two
    columns and more, which leaves every row but the last a gap under
    the next item's size (8 % of graph500-22's small rows: the vertices
    of 65 to 512 columns); then the one-column items, any of which fits
    anywhere, take the free columns in row order, and what is left of
    them rows of their own. Where there are as many one-column vertices
    as free columns, as on a skewed graph, only the last row has a gap."""
    many = int(np.count_nonzero(sizes > 1))
    row, col, rows = _next_fit(sizes[:many], width)
    used = np.bincount(row, weights=sizes[:many], minlength=rows) \
        .astype(np.int64)
    free = np.cumsum(width - used)
    gaps = int(free[-1]) if rows else 0
    u = np.arange(len(sizes) - many, dtype=np.int64)
    into = np.searchsorted(free, u[:gaps], side="right")
    over = u[gaps:] - gaps
    return (np.concatenate([row, into, rows + over // width]),
            np.concatenate([col, width - (free[into] - u[:gaps]),
                            over % width]),
            rows + -(-len(over) // width))


def row_plan(degc: np.ndarray, n: int) -> dict:
    """Where every vertex stands in the row image, from its column
    count ``degc`` [n] (ceil(in-degree / 8)) alone; numpy array passes
    and one jump a row, no loop over vertices. What the key can hold
    follows from what the code observes, n and the largest degree:
    ``keys`` 1 where the owner inside a row fits above the label in
    ``KEY_BITS`` bits (a small row then is ``min(SMALL_MAX, 2^owner
    bits)`` columns, and a wide row, whose vertices all outgrow a small
    one, holds under ``wide / small`` of them), else 2 (the pair
    (owner, label), rows of ``SMALL_MAX``). Returns ``classes``
    ``((rows, width) small, (rows, width) wide)`` (each class a whole
    number of ``vmem_gather.BLOCK``s of lanes; the wide class may have
    no row, the small one has one at least), ``lanes``, ``keys``,
    ``label_bits``, and ``parts``, a class's placed vertices in image
    order: ``vertex``, ``start`` (its first lane), ``span`` (its lanes:
    its columns, and behind a row's last vertex the row's unused
    lanes), ``local`` (its number inside its row), ``filled`` (the
    class's rows that hold a vertex)."""
    degc = np.asarray(degc, np.int64)
    label_bits = (n + 1).bit_length()
    owner_bits = KEY_BITS - label_bits
    top = 1 << max(int(degc.max()) - 1 if n else 0, 0).bit_length()
    keys, small = 1, min(SMALL_MAX, 1 << max(owner_bits, 0))
    if max(top, small) // small > 1 << max(owner_bits, 0) \
            or owner_bits < 0:
        keys, small = 2, SMALL_MAX
    wide = max(top, small)
    order = np.argsort(-degc, kind="stable")
    placed = int(np.count_nonzero(degc))
    n_wide = int(np.count_nonzero(degc > small))
    classes, parts, base = [], [], 0
    for width, items, least in ((small, order[n_wide:placed], 1),
                                (wide, order[:n_wide], 0)):
        sizes = degc[items]
        row, col, filled = _place(sizes, width)
        by_lane = np.argsort(row * width + col, kind="stable")
        items, sizes, row, col = (a[by_lane]
                                  for a in (items, sizes, row, col))
        used = np.bincount(row, weights=sizes, minlength=filled) \
            .astype(np.int64)
        tail = (width - used)[row] * (col + sizes == used[row])
        whole = max(1, vmem_gather.BLOCK // (8 * width))
        rows = -(-max(filled, least) // whole) * whole
        parts.append({
            "vertex": items,
            "start": base + 8 * (row * width + col),
            "span": 8 * (sizes + tail),
            "local": np.arange(len(items), dtype=np.int64)
            - np.searchsorted(row, row),
            "filled": filled})
        classes.append((rows, width))
        base += rows * 8 * width
    return {"classes": tuple(classes), "lanes": base, "keys": keys,
            "label_bits": label_bits, "parts": parts}


def _plan(snap) -> dict:
    """``row_plan`` of a snapshot's in-degrees, kept on it as
    ``_cdlp_plan`` from the admission that prices the image to the
    build that consumes it."""
    plan = getattr(snap, "_cdlp_plan", None)
    if plan is None:
        deg = np.diff(np.asarray(snap.indptr_in[:snap.n + 1], np.int64))
        plan = snap._cdlp_plan = row_plan(-(-deg // 8), snap.n)
    return plan


def image_lanes(snap) -> int:
    """Lanes of the row image BEFORE it is built (admission sizes it
    and a run's working set from them): the packing planned, one pass
    over the in-degrees."""
    return _plan(snap)["lanes"]


def cdlp_image(snap) -> dict:
    """Host-side (cached on the snapshot as ``_cdlp_csr``, dropped with
    the other layouts): the row image of the module docstring, on
    device. ``idx`` int32 [lanes] (the neighbour id of every lane, pad =
    n + 1; small rows, then wide rows), ``key_hi`` uint32 [lanes] (the
    lane's owner inside its row, shifted past the label where ``keys``
    is 1), ``last_lane`` int32 [n] (each vertex's last lane, before and
    behind the sort alike), ``has`` bool [n], and ``row_plan``'s
    ``classes``, ``lanes``, ``keys``, ``label_bits``; ``max_len``: the
    longest run of one owner behind the sort, a row's unused lanes
    included (8 x the widest row); ``pad_share``: the lanes that carry
    no edge, over all."""
    cached = getattr(snap, "_cdlp_csr", None)
    if cached is not None:
        return cached
    import jax.numpy as jnp

    from titan_tpu.obs import devprof

    n = snap.n
    indptr = np.asarray(snap.indptr_in[:n + 1], np.int64)
    deg = np.diff(indptr)
    plan = _plan(snap)
    lanes, shift = plan["lanes"], \
        plan["label_bits"] if plan["keys"] == 1 else 0
    if lanes >= 1 << 31:
        raise NotImplementedError(
            "the row image indexes lanes in int32; shard below 2^31 "
            "lanes")
    start = np.zeros(n, np.int64)
    last = np.zeros(n, np.int32)
    key_hi = np.zeros(lanes, np.uint32)
    at = 0
    for (rows, width), part in zip(plan["classes"], plan["parts"]):
        start[part["vertex"]] = part["start"]
        last[part["vertex"]] = part["start"] + part["span"] - 1
        filled = part["filled"] * 8 * width
        key_hi[at:at + filled] = np.repeat(
            (part["local"] << shift).astype(np.uint32), part["span"])
        at += rows * 8 * width
    idx = np.full(lanes, n + 1, np.int32)
    idx[np.repeat(start - indptr[:n], deg)
        + np.arange(int(indptr[n]), dtype=np.int64)] = snap.src
    devprof.count_h2d("cdlp.image", image_bytes(n, lanes))
    out = {
        "idx": jnp.asarray(idx),
        "key_hi": jnp.asarray(key_hi),
        "last_lane": jnp.asarray(last),
        "has": jnp.asarray(deg > 0),
        "classes": plan["classes"],
        "lanes": lanes,
        "keys": plan["keys"],
        "label_bits": plan["label_bits"],
        "max_len": 8 * max(w for r, w in plan["classes"] if r),
        "pad_share": 1.0 - int(indptr[n]) / lanes,
        "n": n,
    }
    snap._cdlp_csr = out
    del snap._cdlp_plan
    return out


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
        # output (obs/devprof), which a donation deletes under it.
        # ``rows`` and ``width``: "small+wide", and ``pad_share``, which
        # the program does not read: strings, so that the call's
        # ``kernel`` span carries them (devprof journals the integer and
        # string keywords)
        @functools.partial(jax.jit, static_argnames=(
            "rows", "width", "keys", "label_bits", "pad_share"))
        def sort(key_hi, lanes, rows: str, width: str, keys: int,
                 label_bits: int, pad_share: str):
            label = lanes.astype(jnp.uint32)
            owners, labels, at, base = [], [], 0, 0
            for r, w in zip(map(int, rows.split("+")),
                            map(int, width.split("+"))):
                if not r:
                    continue
                size = r * 8 * w
                o = key_hi[at:at + size].reshape(r, 8 * w)
                lab = label[at:at + size].reshape(r, 8 * w)
                # equal keys are interchangeable: a stable sort would
                # carry a second operand, the positions, through every
                # pass
                if keys == 1:
                    key = jax.lax.sort(o | lab, dimension=1,
                                       is_stable=False)
                    o, lab = key >> label_bits, \
                        key & ((1 << label_bits) - 1)
                else:
                    o, lab = jax.lax.sort((o, lab), dimension=1,
                                          num_keys=2, is_stable=False)
                # a number inside a row is under the row's columns
                row = base + w * jax.lax.broadcasted_iota(
                    jnp.int32, (r, 1), 0)
                owners.append((row + o.astype(jnp.int32)).reshape(-1))
                labels.append(lab.astype(jnp.int32).reshape(-1))
                at, base = at + size, base + r * w
            return jnp.concatenate(owners), jnp.concatenate(labels)
        return sort
    return jit_once("cdlp_sort", build)


def sort_statics(im: dict) -> dict:
    """The static keywords of ``cdlp_sort`` for an image."""
    rows, width = zip(*im["classes"])
    return {"rows": "+".join(map(str, rows)),
            "width": "+".join(map(str, width)), "keys": im["keys"],
            "label_bits": im["label_bits"],
            "pad_share": f"{im['pad_share']:.4f}"}


def _vote():
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.segment import mode_vote

        @functools.partial(jax.jit, static_argnames=("max_len", "n_"))
        def vote(owner, lanes, labels, last, has, max_len: int, n_: int):
            best = mode_vote(owner, lanes, pad=n_ + 1, max_len=max_len)
            return jnp.where(has, best[last], labels)
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
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    ov = overlay if overlay is not None \
        else getattr(snap, "_live_overlay", None)
    if ov is not None and not ov.empty:
        raise RuntimeError(
            "cdlp on a live overlay: compact the overlay first "
            "(LiveGraphPlane.compact_if_dirty); the row image has no "
            "overlay seam")
    im = cdlp_image(snap)
    n = im["n"]
    impl = vmem_gather.gather_impl(n)
    gather, sort, vote = _gather(), _sort(), _vote()
    statics = sort_statics(im)
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
            owner, lanes = sort(im["key_hi"], lanes, **statics)
            labels = vote(owner, lanes, labels, im["last_lane"], im["has"],
                          max_len=im["max_len"], n_=n)
            del owner, lanes
            with ph.sync():
                jax.block_until_ready(behind)
        devprof.count_cdlp_round(impl, im["classes"], im["keys"])
        if checkpoint is not None:
            checkpoint(it, {"labels": labels})
    with phase("cdlp.result", bytes=int(labels.nbytes)) as ph:
        devprof.count_d2h("cdlp.result", labels.nbytes)
        with ph.sync():
            out = np.asarray(labels)
    return out, it
