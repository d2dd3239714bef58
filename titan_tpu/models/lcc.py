"""Local clustering coefficient (LDBC Graphalytics' LCC, specification
v1.0) of an undirected snapshot: exact triangle counts from a hub bit
table, the first job here whose work is wedges and not edge slots.

    N(v)   = the neighbours of v, v itself never
    LCC(v) = 2 T(v) / (d(v) (d(v) - 1))   if d(v) >= 2, else 0

with T(v) the triangles through v. The vertices of largest degree are
hubs, at most ``HUBS`` of them (all of one degree or none of it), the
rest are low. ``R`` uint32
``[n + 2, HUBS / 32]``: bit h of row x set where x is adjacent to hub h
(rows n and n + 1, the sink's and the pad's, are zero). For an edge
(x, y), ``c(x, y) = popcount(R[x] & R[y])`` is the triangles on that
edge whose third vertex is a hub. Three parts, every count int32:

* **the pass** (``lcc_pass``), over the lanes (owner x, neighbour y) of
  PageRank's pull image, a row where a rank was: per vertex
  ``A(x) = 1/2 * sum of c over x's hub neighbours + sum of c over x's
  low neighbours``, the triangles through x that hold a hub among their
  OTHER two vertices. The case table (x any vertex; y, z the others):

      y, z          seen from x as                         counted
      hub, hub      c(x, y) holds z AND c(x, z) holds y    2 x 1/2 = 1
      hub, low      c(x, z) holds y (z low: weight 1);     1
                    c(x, y) holds only hubs, so not z
      low, low      in no c                                0 (below)

  The program carries 2 A: each lane's count times 2 - hub(y), summed a
  column, then ``ops/segment.seg_scan`` a vertex.
* **the column sums** (``lcc_colsum``): a hub x's low-low triangles
  ``Q(x)`` = the low-low edges (y, z) inside N(x) = the column sums,
  over every low-low edge once, of the bits of ``R[y] & R[z]``.
* **the tail** (``lcc_tail``): ``T_ll``, the triangles of the graph the
  low vertices induce, whose degrees the hubs' leaving has cut (under
  1,005 at graph500-22, 154 higher neighbours at most). Oriented by its
  own (degree, id) rank, a triangle v < u < w is found once, at v: w in
  N+(v) and in N+(u). Every N+(u) stands in rows of ``TAIL_ROW`` of
  one table; centres in blocks by class (the larger of d+(v) and the
  rows its middles fill): an all-pairs compare of the centre's higher
  neighbours with its middles' gathered rows, a gather a SLOT and not a
  wedge, and the three credits fall out as sums along the block's axes
  (the centre's, a middle's as u, a neighbour's as w: a neighbour and
  the middle it is share a place, so one scatter a place credits
  both).

``T(x) = A(x) + Q(x)`` for a hub, ``A(x) + T_ll(x)`` for a low vertex.
Exact: no sampling, no cut-off. int32 end to end: a count is bounded by
the edges (64.15 M at graph500-22) and passes 2^24, so nothing sums in
float32 but the coefficient itself, made last.

The table, the lanes' hub flags, the low-low edges and the tail's blocks
are made once a snapshot epoch, on the host, and sent (:func:`lcc_image`,
kept on the snapshot as ``_lcc_csr``, dropped with the other layouts;
the table a slab at a time: ``_send_table``). The snapshot has to
be a simple symmetric graph, as an undirected data set loads: a
neighbour counted twice is a triangle counted twice, so a self-loop or
a doubled edge is refused at the build.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.utils.jitcache import dev_scalar, jit_once

#: the most hubs, a power of two (the table's width): chosen on the chip
#: (PERF.md 5, PR 42: the readings at 8,192 and 32,768 beside it)
HUBS = 16384
#: columns a tile of the pass (8 x 1,024 rows gathered: 16 MB at 512
#: words a row) and a dispatch of it
PASS_TILE = 1024
PASS_CHUNK = 1 << 20
#: rows of the table the host makes and sends at a time (256 MB at 512
#: words a row)
TABLE_SLAB = 1 << 17
#: edges a tile of the column sums (two rows an edge) and a dispatch
COL_TILE = 2048
COL_CHUNK = 1 << 20
#: rows a byte-wide partial sum of the column sums takes in (below 256)
_GROUP = 128
#: slots (centre x padded neighbour) a tile of the tail
TAIL_SLOTS = 8192
#: neighbours a row of the tail's table: a vertex's higher neighbours
#: stand in as many rows as they fill (one for all but 0.1 % of
#: graph500-22's low vertices; the widest has 154), so a compare is
#: against 128 and not against the widest vertex's count
TAIL_ROW = 128
#: the classes of the tail's centres: a centre's width padded to the
#: next of 8, 12, 16, 24, 32, 48, ... (a compare costs the square of it)
_CLASSES = np.sort(np.concatenate(
    [(8 << np.arange(24)), (12 << np.arange(24))]))


def hub_words(hubs: int) -> int:
    return max(-(-hubs // 32), 1)


def table_bytes(n: int, hubs: int) -> int:
    """Device bytes of the hub bit table for ``hubs`` hubs."""
    return (n + 2) * hub_words(min(hubs, max(n, 1))) * 4


def image_bytes(n: int, q_in: int, hubs: int) -> int:
    """Device bytes admission holds for what :func:`lcc_image` keeps
    resident, from ``n``, the pull image's columns and the hub count
    alone: the table, a column's owner and a lane's hub flag (12 bytes
    a column), the tail's rows (a piece a vertex and one more a
    ``TAIL_ROW`` of the low graph's edges, which are under 4 a column),
    the low-low edges (under 32 bytes a column) and the tail's blocks,
    which the degree sequence pads and no admission can know before the
    build: priced at 32 bytes a column (20 at graph500-22: PERF.md 4,
    PR 42). 7.76 GB at graph500-22, where the build reads 6.71. The
    build is held to it."""
    rows = 4 * TAIL_ROW * (n + 1) + 16 * q_in
    return (table_bytes(n, hubs) + 12 * q_in + rows + 32 * q_in
            + 32 * q_in + (16 << 20))


def work_bytes(n: int, q_in: int, hubs: int) -> int:
    """Device bytes a job works on beside the images: a tile's gathered
    rows and their ANDs (the pass's eight a column, the column sums' two
    an edge), the tail's rows and compares, the column sums of the pass
    and the finish's scans over them, and the vertex-wide results."""
    w = hub_words(min(hubs, max(n, 1)))
    tiles = 4 * (8 * PASS_TILE + 2 * COL_TILE) * w * 4 + (256 << 20)
    return tiles + 8 * 4 * q_in + 6 * 4 * (n + 2)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _starts(total: int, chunk: int) -> list:
    """Dispatch offsets covering ``total`` in steps of ``chunk``, the
    last one moved back to end at ``total`` (it computes some of its
    neighbour's columns again, and the caller drops them)."""
    starts = list(range(0, total - chunk + 1, chunk))
    if starts[-1] + chunk < total:
        starts.append(total - chunk)
    return starts


def _assert_simple(snap) -> None:
    """Refuse a snapshot that is no simple symmetric graph (see the
    module docstring): one sort of each vertex range's (dst, src) keys,
    the ranges on a thread pool."""
    from concurrent.futures import ThreadPoolExecutor

    n, src, dst = snap.n, snap.src, snap.dst
    indptr = snap.indptr_in
    if (src == dst).any():
        raise ValueError("lcc: the snapshot holds a self-loop; the "
                         "neighbourhood of the specification is a set "
                         "without the vertex itself")
    if not np.array_equal(np.diff(indptr[:n + 1]), snap.out_degree):
        raise ValueError("lcc: in- and out-degrees differ: the snapshot "
                         "is not an undirected graph held in both "
                         "directions")
    cuts = indptr[np.linspace(0, n, 9).astype(np.int64)]

    def doubled(k: int) -> bool:
        lo, hi = int(cuts[k]), int(cuts[k + 1])
        key = dst[lo:hi].astype(np.int64) * n + src[lo:hi]
        key.sort()
        return bool((key[1:] == key[:-1]).any())

    with ThreadPoolExecutor(8) as pool:
        if any(pool.map(doubled, range(8))):
            raise ValueError("lcc: the snapshot holds an edge twice; "
                             "the neighbourhood of the specification is "
                             "a set")


def _flags():
    """``lcc_flags``: which lanes of the pull image read a hub."""
    def build():
        import jax

        @jax.jit
        def flags(is_hub, idx8):
            return is_hub[idx8]
        return flags
    return jit_once("lcc_flags", build)


def _place():
    """``lcc_place``: a slab of the table's rows written where it
    belongs, the table donated."""
    def build():
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def place(r, slab, r0):
            return jax.lax.dynamic_update_slice(r, slab, (r0, 0))
        return place
    return jit_once("lcc_place", build)


def _send_table(n: int, words: int, x, h):
    """uint32 ``[n + 2, words]`` on the device: bit ``h`` of row ``x``
    set for every pair (``x`` rising, the pairs distinct: a simple
    graph, so the sum of a word's bits is their OR). Made a slab of
    ``TABLE_SLAB`` rows at a time in ONE buffer of the host's and sent:
    4.9 GB of fresh host memory is 44 s of page faults in the sandbox,
    and the device's scatter works on a flat table whose copy into rows
    would double it (tests/test_chip_compile.py has the gathers)."""
    import jax.numpy as jnp

    rows = n + 2
    slab_rows = min(TABLE_SLAB, rows)
    slab = np.zeros((slab_rows, words), np.uint32)
    bit = np.uint32(1) << (h & 31).astype(np.uint32)
    word = x.astype(np.int64) * words + (h >> 5)
    place = _place()
    r = jnp.zeros((rows, words), jnp.uint32)
    for r0 in _starts(rows, slab_rows):
        lo, hi = np.searchsorted(x, [r0, r0 + slab_rows])
        slab[...] = 0
        np.add.at(slab.reshape(-1), word[lo:hi] - r0 * words, bit[lo:hi])
        r = place(r, jnp.asarray(slab), dev_scalar(r0))
    return r


def plan(snap, hubs: int, q: int) -> dict:
    """Host arrays of everything :func:`lcc_image` puts on the device:
    the hubs and the (vertex, hub) adjacencies their bit table is made
    of, each column's owner in the pull image's ``q`` columns, the
    low-low edges once each, and the tail's rows and blocks."""
    n = snap.n
    src, dst = snap.src, snap.dst
    deg = np.diff(snap.indptr_in[:n + 1]).astype(np.int64)
    _assert_simple(snap)
    order = np.argsort(deg, kind="stable")          # (degree, id) rising
    # at most ``hubs`` of them, and no vertex a hub where another of its
    # degree is none: which vertices are hubs, and with it every shape
    # below, then follows from the degrees and not from the ids, which a
    # relabelling changes (a benchmark seed)
    words = hub_words(min(hubs, n))     # the table as wide as was asked
    cut = max(n - hubs, 0)
    if cut and deg[order[cut - 1]] == deg[order[cut]]:
        cut = int(np.searchsorted(deg[order], deg[order[cut]], "right"))
    hub_ids = order[cut:].astype(np.int32)
    hubs = len(hub_ids)
    hub_of = np.full(n + 2, -1, np.int32)
    hub_of[hub_ids] = np.arange(hubs, dtype=np.int32)
    to_hub = hub_of[src] >= 0
    at = np.flatnonzero(to_hub)
    hub_pairs = dst[at], hub_of[src[at]]    # (row, hub), the rows rising
    del at
    # the pull image holds the vertices' columns one vertex after another
    degc = -(-deg // 8)
    own = np.full(q, n + 1, np.int32)
    own[:int(degc.sum())] = np.repeat(np.arange(n, dtype=np.int32), degc)
    low = ~to_hub & (hub_of[dst] < 0)
    s2, d2 = src[low], dst[low]         # the low graph, both directions
    once = s2 < d2
    ll = np.stack([s2[once], d2[once]])
    # the tail: the low graph oriented by its own (degree, id) rank,
    # centre -> the neighbours that rank higher
    rank = np.empty(n, np.int32)
    rank[np.argsort(np.bincount(d2, minlength=n), kind="stable")] = \
        np.arange(n, dtype=np.int32)
    up = rank[s2] > rank[d2]
    nb, ce = s2[up], d2[up]             # ce rising: a centre's are adjacent
    dplus = np.bincount(ce, minlength=n).astype(np.int64)
    wedges = int((dplus * (dplus - 1) // 2).sum())
    # N+(u) in pieces of TAIL_ROW: rows of one width whatever the
    # degrees (a vertex without a higher neighbour has none, and stands
    # in the middle of no triangle)
    pieces = -(-dplus // TAIL_ROW)
    first_row = np.cumsum(pieces) - pieces
    empty = int(pieces.sum())
    start = np.cumsum(dplus) - dplus
    slot = np.arange(len(ce)) - start[ce]
    # the count rounded up, so that a tie in the ranking that moves a
    # vertex's d+ past a row's end under another relabelling moves no
    # program's shape
    rows = np.full((_round_up(empty + 1, 4096), TAIL_ROW), -2, np.int32)
    rows[first_row[ce] + slot // TAIL_ROW, slot % TAIL_ROW] = nb
    # a centre's middles: its higher neighbours in their own order (each
    # with the first row of its N+, or the empty one), then one entry
    # more a further row of a neighbour whose N+ fills several: a middle
    # shares its place with the neighbour it is, so one credit serves
    # both roles
    first = np.where(pieces[nb] > 0, first_row[nb], empty)
    rep = np.maximum(pieces[nb] - 1, 0)
    more_centre, more = np.repeat(ce, rep), np.repeat(nb, rep)
    more_row = np.repeat(first_row[nb] + 1 - (np.cumsum(rep) - rep), rep) \
        + np.arange(len(more))
    width = dplus + np.bincount(more_centre, minlength=n)
    live = dplus >= 2
    cls = _CLASSES[np.searchsorted(_CLASSES, width)]
    blocks = []
    for d in sorted(set(cls[live].tolist())):
        member = live & (cls == d)
        centres = np.flatnonzero(member).astype(np.int32)
        per = max(TAIL_SLOTS // d, 1)
        # four tiles at a time: the ties of the tail's own ranking move
        # a few centres between classes under a relabelling, and should
        # move no program's shape
        padded = _round_up(len(centres), 4 * per)
        place = np.arange(d)
        own_part = place < dplus[centres][:, None]
        more_part = ~own_part & (place < width[centres][:, None])
        mine, theirs = member[ce], member[more_centre]

        def block(fill, own_values, more_values=None):
            out = np.full((padded, d), fill, np.int32)
            out[:len(centres)][own_part] = own_values
            if more_values is not None:
                out[:len(centres)][more_part] = more_values
            return out

        ids = np.full(padded, n + 1, np.int32)
        ids[:len(centres)] = centres
        blocks.append({"centres": ids, "per": per,
                       "nbr": block(-1, nb[mine]),
                       "mid": block(-1, nb[mine], more[theirs]),
                       "rows": block(empty, first[mine],
                                     more_row[theirs])})
    return {"n": n, "hubs": hubs, "words": words, "hub_ids": hub_ids,
            "hub_pairs": hub_pairs, "own": own,
            "is_hub": hub_of >= 0,
            "deg": deg.astype(np.int32), "ll": ll, "rows": rows,
            "blocks": blocks, "wedges": wedges,
            "tail_edges": int(len(ce))}


def _pass():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("chunk", "tile"))
        def hub_pass(r, idx8, own, hubl8, c0, chunk: int, tile: int):
            t = chunk // tile
            ix = jax.lax.dynamic_slice(idx8, (0, c0), (8, chunk))
            ow = jax.lax.dynamic_slice(own, (c0,), (chunk,))
            hb = jax.lax.dynamic_slice(hubl8, (0, c0), (8, chunk))

            def one(args):
                i, o, h = args
                both = r[i] & r[o][None]
                # a row's popcount is at most its 32 x words bits
                c = jax.lax.population_count(both).astype(
                    jnp.int32).sum(-1)
                return (c * jnp.where(h, 1, 2)).sum(0)

            out = jax.lax.map(one, (
                ix.reshape(8, t, tile).transpose(1, 0, 2),
                ow.reshape(t, tile),
                hb.reshape(8, t, tile).transpose(1, 0, 2)))
            return out.reshape(chunk)
        return hub_pass
    return jit_once("lcc_pass", build)


def bit_column_sums(words):
    """int32 ``[W, 32]``: for each bit of each word column, how many of
    the rows of ``words`` (uint32 ``[T, W]``, T a multiple of 128) have
    it set. Bits 8 apart share a word as byte-wide counters, summed over
    128 rows (a byte holds them: 128 < 256), so a row costs 8 masked
    adds a word and not 32; the groups' bytes then sum in int32, whose
    bound is the rows of a job (the edges: under 2^31), not 2^24."""
    import jax.numpy as jnp

    t, w = words.shape
    g = words.reshape(t // _GROUP, _GROUP, w)
    cols = [None] * 32
    for k in range(8):
        packed = ((g >> k) & jnp.uint32(0x01010101)).sum(
            1, dtype=jnp.uint32)
        for b in range(4):
            cols[8 * b + k] = ((packed >> (8 * b)) & 0xFF).astype(
                jnp.int32).sum(0)
    return jnp.stack(cols, axis=1)


def _colsum():
    def build():
        import jax

        @functools.partial(jax.jit, static_argnames=("chunk", "tile"))
        def colsum(r, ll, e0, chunk: int, tile: int):
            ab = jax.lax.dynamic_slice(ll, (0, e0), (2, chunk))
            ab = ab.reshape(2, chunk // tile, tile).transpose(1, 0, 2)

            def one(acc, yz):
                return acc + bit_column_sums(r[yz[0]] & r[yz[1]]), None

            import jax.numpy as jnp
            acc, _ = jax.lax.scan(
                one, jnp.zeros((r.shape[1], 32), jnp.int32), ab)
            return acc.reshape(-1)
        return colsum
    return jit_once("lcc_colsum", build)


def _tail():
    def build():
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("per",))
        def tail(rows, nbr, nrow, per: int):
            """Closed wedges of a class's centres: ``nbr`` [B, d] a
            centre's higher neighbours, ``nrow`` [B, d] the rows of its
            middles, a neighbour's first row in the neighbour's place;
            the counts a place (its middle's as u and its neighbour's
            as w, one vertex) and a centre."""
            b, d = nbr.shape

            def one(args):
                nb, rw = args                       # [per, d]
                theirs = rows[rw]                   # [per, d, TAIL_ROW]
                hit = (theirs[:, :, None, :]
                       == nb[:, None, :, None]).any(-1).astype(jnp.int32)
                as_u, as_w = hit.sum(2), hit.sum(1)
                return as_u + as_w, as_u.sum(1)

            place, centre = jax.lax.map(one, (
                nbr.reshape(b // per, per, d),
                nrow.reshape(b // per, per, d)))
            return place.reshape(b, d), centre.reshape(b)
        return tail
    return jit_once("lcc_tail", build)


def _finish():
    def build():
        import jax
        import jax.numpy as jnp

        from titan_tpu.ops.segment import seg_scan

        @functools.partial(jax.jit, static_argnames=("seg_max", "trim"))
        def finish(cols, first, last, has, hub_ids, hub_sums, deg,
                   credits, seg_max: int, trim: int = 0):
            """``cols``: the pass's dispatches, the last one less its
            first ``trim`` columns (its neighbour's); ``hub_sums``: the
            column sums' dispatches; ``credits``: (vertex ids, counts)
            of the tail."""
            n = deg.shape[0]
            cols2 = jnp.concatenate(cols[:-1] + (cols[-1][trim:],))
            run = seg_scan(cols2, first, "sum", max_len=seg_max)
            # 2 A is even: the hub neighbours' share counts ordered pairs
            t = jnp.where(has, run[last], 0) // 2
            hubs = hub_ids.shape[0]
            t = t.at[hub_ids].add(sum((part[:hubs] for part in hub_sums),
                                      jnp.zeros(hubs, jnp.int32)))
            t = jnp.concatenate([t, jnp.zeros(2, jnp.int32)])
            for ids, counts in credits:
                t = t.at[ids.reshape(-1)].add(counts.reshape(-1))
            t = t[:n]
            d = deg.astype(jnp.float32)
            # float32 from here on: 2 T passes 2^24 and rounds to 6e-8,
            # the rule (1e-4) is three orders above
            coeff = jnp.where(deg >= 2, 2.0 * t.astype(jnp.float32)
                              / jnp.maximum(d * (d - 1.0), 1.0), 0.0)
            return t, coeff
        return finish
    return jit_once("lcc_finish", build)


def lcc_image(snap, hubs: int | None = None) -> dict:
    """(cached on the snapshot as ``_lcc_csr``, dropped with the other
    layouts): everything of the module docstring a job reads and no job
    changes, on the device. ``hubs``: ``HUBS`` unless a test says
    otherwise."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_image
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    hubs = HUBS if hubs is None else hubs
    cached = getattr(snap, "_lcc_csr", None)
    if cached is not None and cached["asked"] == hubs:
        with phase("lcc.image", hubs=cached["hubs"],
                   bytes=cached["bytes"], cache="hit"):
            return cached
    with phase("lcc.image", hubs=min(hubs, snap.n), cache="miss") as ph:
        im = pull_image(snap)
        n, q = im["n"], im["q_in"]
        p = plan(snap, hubs, q)
        pad = _round_up(max(p["ll"].shape[1], 1), COL_TILE)
        col_chunk = min(COL_CHUNK, pad)
        ll = np.full((2, _round_up(pad, col_chunk)), n + 1, np.int32)
        ll[:, :p["ll"].shape[1]] = p["ll"]
        host = [p["own"], p["is_hub"], ll, p["rows"], p["hub_ids"],
                p["deg"]]
        for blk in p["blocks"]:
            host += [blk["centres"], blk["nbr"], blk["mid"], blk["rows"]]
        nbytes = sum(int(a.nbytes) for a in host) \
            + 4 * (n + 2) * p["words"] + 8 * q
        priced = image_bytes(n, q, hubs)
        if nbytes > priced:
            raise RuntimeError(
                f"lcc: the image is {nbytes} bytes, admission priced "
                f"{priced} (models/lcc.image_bytes): the low graph "
                f"holds {p['tail_edges']} edges in "
                f"{sum(b['nbr'].size for b in p['blocks'])} slots")
        devprof.count_h2d("lcc.image", nbytes - 8 * q)  # flags: made there
        out = {
            "asked": hubs, "hubs": p["hubs"], "n": n, "bytes": nbytes,
            "table": _send_table(n, p["words"], *p["hub_pairs"]),
            "own": jnp.asarray(p["own"]),
            "hubl": _flags()(jnp.asarray(p["is_hub"]),
                             im["idx"].reshape(8, q)),
            "ll": jnp.asarray(ll), "ll_edges": int(p["ll"].shape[1]),
            "col_chunk": col_chunk,
            "rows": jnp.asarray(p["rows"]),
            "hub_ids": jnp.asarray(p["hub_ids"]),
            "deg": jnp.asarray(p["deg"]),
            "blocks": [{"centres": jnp.asarray(b["centres"]),
                        "nbr": jnp.asarray(b["nbr"]),
                        "mid": jnp.asarray(b["mid"]),
                        "rows": jnp.asarray(b["rows"]),
                        "per": b["per"]} for b in p["blocks"]],
            "wedges": p["wedges"], "tail_edges": p["tail_edges"],
        }
        jax.block_until_ready(out["table"])
        ph.set(bytes=nbytes, hubs=p["hubs"])
    snap._lcc_csr = out
    return out


def lcc(snap, on_round=None, overlay=None, hubs: int | None = None):
    """(triangle_counts int32 [n], lcc float32 [n]), both on the host.
    The dispatches (the pass's and the column sums' chunks, the tail's
    classes) run ONE ahead of the device: with dispatch i queued the
    loop waits for i - 1's output, so the device always has work, what
    is dispatched and has not run holds two tiles' rows and not a
    job's, and a veto takes effect within two dispatches of its cause.

    ``on_round(i)``: veto before dispatch i + 1 (RoundInterrupted), the
    serving layer's cancel and timeout hook. No checkpoint: a retried
    job starts over, with the image still resident."""
    import jax

    from titan_tpu.models.frontier import RoundInterrupted
    from titan_tpu.models.pagerank_pull import pull_image
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    ov = overlay if overlay is not None \
        else getattr(snap, "_live_overlay", None)
    if ov is not None and not ov.empty:
        raise RuntimeError(
            "lcc on a live overlay: compact the overlay first "
            "(LiveGraphPlane.compact_if_dirty); the pull image has no "
            "overlay seam")
    pim = pull_image(snap)
    im = lcc_image(snap, hubs)
    n, q = pim["n"], pim["q_in"]
    idx8 = pim["idx"].reshape(8, q)
    r = im["table"]
    done = 0
    behind = None

    def step(out):
        """One dispatch made: the veto, then the wait for the one
        before it."""
        nonlocal done, behind
        if behind is not None:
            jax.block_until_ready(behind)
        behind = out
        done += 1
        if on_round is not None and not on_round(done):
            raise RoundInterrupted(done)

    chunk = min(PASS_CHUNK, q)
    starts = _starts(q, chunk)
    col_chunk = im["col_chunk"]
    col_starts = range(0, im["ll"].shape[1], col_chunk) \
        if im["ll_edges"] else ()
    with phase("lcc.hub", level=1, hubs=im["hubs"],
               edges=8 * q, tiles=len(starts) + len(col_starts)):
        hub_pass, colsum = _pass(), _colsum()
        cols, hub_sums = [], []
        for c0 in starts:
            cols.append(hub_pass(r, idx8, im["own"], im["hubl"],
                                 dev_scalar(c0), chunk=chunk,
                                 tile=min(PASS_TILE, chunk)))
            step(cols[-1])
        for e0 in col_starts:
            hub_sums.append(colsum(r, im["ll"], dev_scalar(e0),
                                   chunk=col_chunk, tile=COL_TILE))
            step(hub_sums[-1])
    devprof.count_lcc("hub", 8 * q)
    credits = []
    with phase("lcc.tail", wedges=im["wedges"], edges=im["tail_edges"],
               tiles=len(im["blocks"])):
        tail = _tail()
        for blk in im["blocks"]:
            place, centre = tail(im["rows"], blk["nbr"], blk["rows"],
                                 per=blk["per"])
            step(centre)
            # a pad place's -1 wraps to the scratch entry n + 1
            credits += [(blk["mid"], place), (blk["centres"], centre)]
    devprof.count_lcc("tail", im["tail_edges"], wedges=im["wedges"])
    with phase("lcc.result", bytes=8 * n) as ph:
        counts, coeff = _finish()(
            tuple(cols), pim["first"], pim["last"], pim["has"],
            im["hub_ids"], tuple(hub_sums), im["deg"], tuple(credits),
            seg_max=pim["seg_max"],
            trim=starts[-2] + chunk - starts[-1] if len(starts) > 1
            else 0)
        devprof.count_d2h("lcc.result", 8 * n)
        with ph.sync():
            out = np.asarray(counts), np.asarray(coeff)
    return out
