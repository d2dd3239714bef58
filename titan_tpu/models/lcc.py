"""Local clustering coefficient (LDBC Graphalytics' LCC, specification
v1.0) of an undirected snapshot: exact triangle counts from a hub bit
table, the first job here whose work is wedges and not edge slots. It
reads no other kind's image.

    N(v)   = the neighbours of v, v itself never
    LCC(v) = 2 T(v) / (d(v) (d(v) - 1))   if d(v) >= 2, else 0

with T(v) the triangles through v. The vertices of largest degree are
hubs, at most ``HUBS`` of them (all of one degree or none of it), the
rest are low. ``R`` uint32
``[n + 2, HUBS / 32]``: bit h of row x set where x is adjacent to hub h
(rows n and n + 1, the sink's and the pad's, are zero). For an edge
(x, y), ``c(x, y) = popcount(R[x] & R[y])`` is the triangles on that
edge whose third vertex is a hub. Three programs and a finish, every
count int32:

* **the pass** (``lcc_pass``) and **the column sums** (``lcc_colsum``)
  make every edge's ``c`` ONCE between them, and the finish credits it
  to both ends. Per vertex ``A(x) = 1/2 * sum of c over x's hub
  neighbours + sum of c over x's low neighbours``, the triangles
  through x that hold a hub among their OTHER two vertices. The case
  table (x any vertex; y, z the others):

      y, z          seen from x as                         counted
      hub, hub      c(x, y) holds z AND c(x, z) holds y    2 x 1/2 = 1
      hub, low      c(x, z) holds y (z low: weight 1);     1
                    c(x, y) holds only hubs, so not z
      low, low      in no c                                0 (below)

  so an edge's c goes into 2 A by its ends' classes:

      edge (x, y)   made by                    to x    to y
      low, low      the column sums            2 c     2 c
      hub, low      the pass, at x's lane      2 c     c
      hub, hub      the pass, at the owner's   c       c

  The pass runs over an image of its own (:func:`_hub_lanes`): every
  edge with a hub at an end once, a lane of its OWNER's columns (the
  hub; of two hubs the higher in (degree, id) order), 8 neighbours a
  column, a 2 KB row of ``R`` gathered a lane: out come the column's
  sum weighted for the owner (2 c at a low neighbour, c at a hub) and
  the lanes' c themselves for the neighbours.
  The column sums: a hub x's low-low triangles ``Q(x)`` = the low-low
  edges (y, z) inside N(x) = the column sums, over every low-low edge
  once, of the bits of ``R[y] & R[z]``, whose popcount is that edge's
  c. The finish (``lcc_finish``) sorts the counts by the vertex each
  names (names the image knows, so it also knows where a vertex's run
  ends), sums along the runs (``ops/segment.seg_scan``) and the owners'
  columns along theirs, and halves.
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

The table, the pass's lanes and their hub flags, the low-low edges and
the tail's blocks are made once a snapshot epoch, on the host, and sent
(:func:`lcc_image`, kept on the snapshot as ``_lcc_csr``, dropped with
the other layouts; the table a slab at a time: ``_send_table``). The
snapshot has to be a simple symmetric graph, as an undirected data set
loads: a neighbour counted twice is a triangle counted twice, so a
self-loop or a doubled edge is refused at the build.
"""

from __future__ import annotations

import functools

import numpy as np

from titan_tpu.utils.jitcache import dev_scalar, jit_once

#: the most hubs, a power of two (the table's width): chosen on the chip
#: (PERF.md 5, PR 42: the readings at 8,192 and 32,768 beside it)
HUBS = 16384
#: columns a tile of the pass (8 x 1,024 rows gathered: 16 MB at 512
#: words a row) and the most a dispatch of it (:func:`pass_chunk`)
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
    alone: the table; the pass's lanes and the low-low edges, which hold
    every edge once between them (a low-low pair is 8 bytes; a lane, its
    flag and its eighth of a column's owner and start under 6, twice
    where two hubs of one degree both keep room for it: 12 bytes an
    edge, and a column of the pull image holds 4 edges) and a part-filled
    column an owner; where a vertex's credits end (4 bytes); the tail's
    rows (a piece a vertex and one more a ``TAIL_ROW`` of the low graph's
    edges, which are under 4 a column) and its blocks, which the degree
    sequence pads and no admission can know before the build: priced at
    32 bytes a column (20 at graph500-22: PERF.md 4, PR 42). 7.84 GB at
    graph500-22, where the build reads 6.75. The build is held to it."""
    hubs = min(hubs, max(n, 1))
    rows = 4 * TAIL_ROW * (n + 1) + 16 * q_in
    lanes = 48 * q_in + 45 * (hubs + PASS_TILE)
    return (table_bytes(n, hubs) + lanes + 4 * n + rows + 32 * q_in
            + (16 << 20))


def work_bytes(n: int, q_in: int, hubs: int) -> int:
    """Device bytes a job works on beside the image: a tile's gathered
    rows and their ANDs (the pass's eight a column, the column sums' two
    an edge), the tail's rows and compares, and what the finish sorts: a
    count and the vertex it names, a lane's once and a low-low edge's
    twice (2 an edge at most, so 8 a column of the pull image), 20 bytes
    each: 4 as the programs leave them, 16 for the sort's two operands
    and what it returns, which the scan's passes then reuse (the
    compiler's count at graph500-22 is 17.7 bytes each of the 88.2 M
    that are there: tests/test_chip_compile.py)."""
    w = hub_words(min(hubs, max(n, 1)))
    tiles = 4 * (8 * PASS_TILE + 2 * COL_TILE) * w * 4 + (256 << 20)
    return tiles + 20 * 8 * q_in + 6 * 4 * (n + 2)


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


def pass_chunk(columns: int) -> int:
    """Columns a dispatch of the pass over ``columns`` (whole tiles):
    the fewest dispatches of at most ``PASS_CHUNK``, all of one size, so
    that the last, moved back to end with the image (:func:`_starts`),
    repeats under a tile a dispatch of its neighbour's columns and not
    most of a chunk (graph500-22's 5,252,096 columns are 5.009 chunks
    of 2^20: six dispatches of 875,520)."""
    dispatches = -(-columns // PASS_CHUNK)
    return _round_up(-(-columns // dispatches), PASS_TILE)


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
    """``lcc_flags``: which lanes of the pass's image read a hub."""
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


def _hub_lanes(n: int, src, dst, deg, hs, hd) -> dict:
    """The pass's image: every edge with a hub at an end ONCE, at the end
    that owns it: the hub, of two hubs the higher in the (degree, id)
    order (its place among the hubs, ``hs`` / ``hd`` of an edge's ends,
    -1 at a low one, rises with it). Columns of 8 neighbours an owner,
    the owners rising (``dst`` rises, so an owner's neighbours are
    adjacent). Which of two hubs of one degree owns their edge follows
    from the ids, so a hub's columns have ROOM for every such edge: the
    shapes, and the bounds on a segment's length, follow from the
    degrees alone. ``got`` [n]: the lanes that name each vertex as the
    neighbour; ``most`` [n]: the most it could be named in."""
    mine = hd > hs
    nbr, ow = src[mine], dst[mine]
    tie = np.flatnonzero((hs >= 0) & (hd >= 0))
    tie = tie[deg[src[tie]] == deg[dst[tie]]]
    won = hd[tie] > hs[tie]
    owned = np.bincount(ow, minlength=n)
    room = owned + np.bincount(dst[tie[~won]], minlength=n)
    owners = np.flatnonzero(room).astype(np.int32)
    cols = -(-room[owners] // 8)
    colstart = np.cumsum(cols) - cols
    held = int(cols.sum())
    columns = _round_up(max(held, 1), PASS_TILE)
    idx = np.full(8 * columns, n + 1, np.int32)
    start = np.cumsum(owned) - owned
    for v, c0 in zip(owners.tolist(), (8 * colstart).tolist()):
        idx[c0:c0 + owned[v]] = nbr[start[v]:start[v] + owned[v]]
    own = np.full(columns, n + 1, np.int32)
    own[:held] = np.repeat(owners, cols)
    first = np.ones(columns, bool)              # the pad columns too
    first[:held] = False
    first[colstart] = True
    got = np.bincount(nbr, minlength=n)
    return {"idx8": np.ascontiguousarray(idx.reshape(columns, 8).T),
            "own": own, "first": first, "owners": owners,
            "last": (colstart + cols - 1).astype(np.int32),
            "seg_max": int(cols.max()) if len(cols) else 1,
            "edges": int(len(ow)), "got": got,
            "most": got + np.bincount(dst[tie[won]], minlength=n)}


def plan(snap, hubs: int) -> dict:
    """Host arrays of everything :func:`lcc_image` puts on the device:
    the hubs and the (vertex, hub) adjacencies their bit table is made
    of, the pass's lanes (:func:`_hub_lanes`), the low-low edges once
    each, where each vertex's credits end once the finish has sorted
    them, and the tail's rows and blocks."""
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
    hs, hd = hub_of[src], hub_of[dst]       # an edge's ends among the hubs
    at = np.flatnonzero(hs >= 0)
    hub_pairs = dst[at], hs[at]             # (row, hub), the rows rising
    del at
    lanes = _hub_lanes(n, src, dst, deg, hs, hd)
    low = (hs < 0) & (hd < 0)
    del hs, hd
    s2, d2 = src[low], dst[low]         # the low graph, both directions
    once = s2 < d2
    ll = np.stack([s2[once], d2[once]])
    # the finish sorts an edge's credits by the vertex they name (a
    # lane's neighbour, a low-low edge's two ends; the pads' n + 1 goes
    # last): where each vertex's run ends, and the longest run's bound
    low_deg = np.bincount(d2, minlength=n)
    named = lanes.pop("got") + low_deg
    credit_last = np.where(named > 0, np.cumsum(named) - 1,
                           -1).astype(np.int32)
    credit_max = max(int((lanes.pop("most") + low_deg).max()), 1) \
        if n else 1
    # the tail: the low graph oriented by its own (degree, id) rank,
    # centre -> the neighbours that rank higher
    rank = np.empty(n, np.int32)
    rank[np.argsort(low_deg, kind="stable")] = np.arange(n, dtype=np.int32)
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
            "hub_pairs": hub_pairs, "lanes": lanes,
            "credit_last": credit_last, "credit_max": credit_max,
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
                return (c * jnp.where(h, 1, 2)).sum(0), c

            out, lanes = jax.lax.map(one, (
                ix.reshape(8, t, tile).transpose(1, 0, 2),
                ow.reshape(t, tile),
                hb.reshape(8, t, tile).transpose(1, 0, 2)))
            return out.reshape(chunk), \
                lanes.transpose(1, 0, 2).reshape(8, chunk)
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
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("chunk", "tile"))
        def colsum(r, ll, e0, chunk: int, tile: int):
            ab = jax.lax.dynamic_slice(ll, (0, e0), (2, chunk))
            ab = ab.reshape(2, chunk // tile, tile).transpose(1, 0, 2)

            def one(acc, yz):
                both = r[yz[0]] & r[yz[1]]
                c = jax.lax.population_count(both).astype(
                    jnp.int32).sum(-1)
                return acc + bit_column_sums(both), c

            acc, counts = jax.lax.scan(
                one, jnp.zeros((r.shape[1], 32), jnp.int32), ab)
            return acc.reshape(-1), counts.reshape(chunk)
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

        @functools.partial(jax.jit, static_argnames=(
            "seg_max", "credit_max", "trim"))
        def finish(cols, lanes, ll_counts, hub_sums, credits, im,
                   seg_max: int, credit_max: int, trim: int = 0):
            """``cols``, ``lanes``: the pass's dispatches, the last one
            less its first ``trim`` columns (its neighbour's);
            ``ll_counts``, ``hub_sums``: the column sums' dispatches;
            ``credits``: (vertex ids, counts) of the tail; ``im``: the
            image's ``first``, ``owners``, ``last``, ``idx8``, ``ll``,
            ``credit_last``, ``hub_ids``, ``deg``."""
            n = im["deg"].shape[0]
            cols2 = jnp.concatenate(cols[:-1] + (cols[-1][trim:],))
            lanes2 = jnp.concatenate(
                lanes[:-1] + (lanes[-1][:, trim:],), axis=1)
            # a low-low edge counts twice at either end
            twice = 2 * jnp.concatenate(
                ll_counts + (jnp.zeros(0, jnp.int32),))
            ll = im["ll"][:, :twice.shape[0]]
            # 2 A: a lane's count to its neighbour, a low-low edge's to
            # both ends, sorted by the vertex named (the names are the
            # image's, so where a vertex's run ends is known), summed
            # along the runs; the owners' columns along theirs
            named, counts = jax.lax.sort(
                (jnp.concatenate([im["idx8"].reshape(-1), ll[0], ll[1]]),
                 jnp.concatenate([lanes2.reshape(-1), twice, twice])),
                num_keys=1, is_stable=False)
            starts = jnp.concatenate(
                [jnp.ones(1, bool), named[1:] != named[:-1]])
            run = seg_scan(counts, starts, "sum", max_len=credit_max)
            last = im["credit_last"]
            t = jnp.where(last >= 0, run[jnp.maximum(last, 0)], 0)
            own = seg_scan(cols2, im["first"], "sum", max_len=seg_max)
            # 2 A is even: the hub neighbours' share counts ordered pairs
            t = t.at[im["owners"]].add(own[im["last"]]) // 2
            hubs = im["hub_ids"].shape[0]
            t = t.at[im["hub_ids"]].add(
                sum((part[:hubs] for part in hub_sums),
                    jnp.zeros(hubs, jnp.int32)))
            t = jnp.concatenate([t, jnp.zeros(2, jnp.int32)])
            for ids, tail_counts in credits:
                t = t.at[ids.reshape(-1)].add(tail_counts.reshape(-1))
            t = t[:n]
            deg = im["deg"]
            d = deg.astype(jnp.float32)
            # float32 from here on: 2 T passes 2^24 and rounds to 6e-8,
            # the rule (1e-4) is three orders above
            coeff = jnp.where(deg >= 2, 2.0 * t.astype(jnp.float32)
                              / jnp.maximum(d * (d - 1.0), 1.0), 0.0)
            return t, coeff
        return finish
    return jit_once("lcc_finish", build)


#: what of the image ``lcc_finish`` reads
_FINISH_READS = ("first", "owners", "last", "idx8", "ll", "credit_last",
                 "hub_ids", "deg")


def lcc_image(snap, hubs: int | None = None) -> dict:
    """(cached on the snapshot as ``_lcc_csr``, dropped with the other
    layouts): everything of the module docstring a job reads and no job
    changes, on the device. ``hubs``: ``HUBS`` unless a test says
    otherwise."""
    import jax
    import jax.numpy as jnp

    from titan_tpu.models.pagerank_pull import pull_columns
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    hubs = HUBS if hubs is None else hubs
    cached = getattr(snap, "_lcc_csr", None)
    if cached is not None and cached["asked"] == hubs:
        with phase("lcc.image", hubs=cached["hubs"],
                   bytes=cached["bytes"], cache="hit"):
            return cached
    with phase("lcc.image", hubs=min(hubs, snap.n), cache="miss") as ph:
        n = snap.n
        p = plan(snap, hubs)
        lanes = p["lanes"]
        pad = _round_up(max(p["ll"].shape[1], 1), COL_TILE)
        col_chunk = min(COL_CHUNK, pad)
        ll = np.full((2, _round_up(pad, col_chunk)), n + 1, np.int32)
        ll[:, :p["ll"].shape[1]] = p["ll"]
        sent = {"idx8": lanes["idx8"], "own": lanes["own"],
                "first": lanes["first"], "owners": lanes["owners"],
                "last": lanes["last"], "ll": ll,
                "credit_last": p["credit_last"], "rows": p["rows"],
                "hub_ids": p["hub_ids"], "deg": p["deg"]}
        host = list(sent.values()) + [p["is_hub"]]
        for blk in p["blocks"]:
            host += [blk["centres"], blk["nbr"], blk["mid"], blk["rows"]]
        flags = lanes["idx8"].size              # a byte a lane, made there
        nbytes = sum(int(a.nbytes) for a in host) \
            + 4 * (n + 2) * p["words"] + flags
        priced = image_bytes(n, pull_columns(snap.indptr_in, n), hubs)
        if nbytes > priced:
            raise RuntimeError(
                f"lcc: the image is {nbytes} bytes, admission priced "
                f"{priced} (models/lcc.image_bytes): the pass holds "
                f"{lanes['idx8'].shape[1]} columns, the low graph "
                f"{p['tail_edges']} edges in "
                f"{sum(b['nbr'].size for b in p['blocks'])} slots")
        devprof.count_h2d("lcc.image", nbytes - flags)
        out = {k: jnp.asarray(v) for k, v in sent.items()}
        out.update({
            "asked": hubs, "hubs": p["hubs"], "n": n, "bytes": nbytes,
            "table": _send_table(n, p["words"], *p["hub_pairs"]),
            "hubl": _flags()(jnp.asarray(p["is_hub"]), out["idx8"]),
            "seg_max": lanes["seg_max"], "credit_max": p["credit_max"],
            "hub_edges": lanes["edges"],
            "ll_edges": int(p["ll"].shape[1]), "col_chunk": col_chunk,
            "blocks": [{"centres": jnp.asarray(b["centres"]),
                        "nbr": jnp.asarray(b["nbr"]),
                        "mid": jnp.asarray(b["mid"]),
                        "rows": jnp.asarray(b["rows"]),
                        "per": b["per"]} for b in p["blocks"]],
            "wedges": p["wedges"], "tail_edges": p["tail_edges"],
        })
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
    from titan_tpu.obs import devprof
    from titan_tpu.obs.tracing import phase

    ov = overlay if overlay is not None \
        else getattr(snap, "_live_overlay", None)
    if ov is not None and not ov.empty:
        raise RuntimeError(
            "lcc on a live overlay: compact the overlay first "
            "(LiveGraphPlane.compact_if_dirty); the image has no "
            "overlay seam")
    im = lcc_image(snap, hubs)
    n, r = im["n"], im["table"]
    columns = im["idx8"].shape[1]
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

    chunk = pass_chunk(columns)
    starts = _starts(columns, chunk)
    col_chunk = im["col_chunk"]
    col_starts = range(0, im["ll"].shape[1], col_chunk) \
        if im["ll_edges"] else ()
    # an edge's AND is made once: at its hub's lane, or as a low-low edge
    edges = im["hub_edges"] + im["ll_edges"]
    with phase("lcc.hub", level=1, hubs=im["hubs"], edges=edges,
               tiles=len(starts) + len(col_starts)):
        hub_pass, colsum = _pass(), _colsum()
        cols, lanes, hub_sums, ll_counts = [], [], [], []
        for c0 in starts:
            col, lane = hub_pass(r, im["idx8"], im["own"], im["hubl"],
                                 dev_scalar(c0), chunk=chunk,
                                 tile=PASS_TILE)
            cols.append(col)
            lanes.append(lane)
            step(col)
        for e0 in col_starts:
            sums, counts = colsum(r, im["ll"], dev_scalar(e0),
                                  chunk=col_chunk, tile=COL_TILE)
            hub_sums.append(sums)
            ll_counts.append(counts)
            step(sums)
    devprof.count_lcc("hub", edges)
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
            tuple(cols), tuple(lanes), tuple(ll_counts), tuple(hub_sums),
            tuple(credits), {k: im[k] for k in _FINISH_READS},
            seg_max=im["seg_max"], credit_max=im["credit_max"],
            trim=starts[-2] + chunk - starts[-1] if len(starts) > 1
            else 0)
        devprof.count_d2h("lcc.result", 8 * n)
        with ph.sync():
            out = np.asarray(counts), np.asarray(coeff)
    return out
