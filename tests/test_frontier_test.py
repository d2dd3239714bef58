"""The bottom-up frontier test of the single-source family is written
once (``bfs_hybrid._frontier_test``) and every program that tests
parents against the frontier calls it: the opener ``bu0``, the split
opener ``bu0a``, ``bu0b``, the chunk rounds ``bu``, the sweep ``ex`` and
the endgame ``end``. Two roads serve its random reads: XLA's byte gather
over the plane bitmap, and the frontier as a 0/1 float32 table in VMEM
under the Pallas gather (here in Pallas's interpreter). Which one is
read from two things alone: ``vmem_gather.gather_impl(n)`` (the backend
and the table's size) and the block's column count (whole grid steps of
``vmem_gather.BLOCK`` columns). Whatever serves it, ``dist`` is the
plain loop's, bit for bit. All on the CPU: counts and equality, never a
time.
"""

import numpy as np
import pytest

import titan_tpu.models.bfs_hybrid as H
from test_dense_opener import (FRONTIER_TEST_KEYS, _sym, bfs_numpy,
                               kernel_in_the_interpreter, source_of, traced)
from titan_tpu.models.bfs import INF
from titan_tpu.ops import vmem_gather as vg

__all__ = ["kernel_in_the_interpreter"]          # a fixture, used by name

#: a block under ``BLOCK`` columns, one at it, one above it
COLUMNS = [vg.BLOCK // 2, vg.BLOCK, 2 * vg.BLOCK]
#: the jitted function behind each key: the counter's ``prog``
PROG = {"hybrid_bu_start": "bu0", "hybrid_bu_startL": "bu0a",
        "hybrid_bu_finish0": "bu0b", "hybrid_bu_more": "bu",
        "hybrid_ex": "ex", "hybrid_endgame": "end"}


def crowd(seed=4):
    """n = 6,001: a source joined to a tenth of the vertices of a random
    graph of mean degree 20 (three chunks a vertex), so that a level
    pulled behind the source leaves thousands of candidates to every
    program of the level: blocks of whole grid steps."""
    rng = np.random.default_rng(seed)
    n, spokes, m = 6001, 600, 60_000
    src = np.concatenate([np.zeros(spokes, np.int64),
                          rng.integers(1, n, m)])
    dst = np.concatenate([rng.permutation(np.arange(1, n))[:spokes],
                          rng.integers(1, n, m)])
    return _sym(n, src, dst)


@pytest.fixture(scope="module")
def graph():
    snap = crowd()
    src = source_of(snap)
    assert src == 0
    return snap, H.build_chunked_csr(snap), bfs_numpy(snap, src)


def state_under(g, ref, level, columns, seed):
    """``dist`` with the levels up to ``level`` decided and the
    candidates thinned by hand (the others marked visited at level 0,
    which no test reads) until they and their chunks fit ``columns``:
    the guarantee the host loop gives ``end`` (and more than ``bu0b``
    asks)."""
    n = g["n"]
    degc = g["_host"]["degc"][:n]
    state = np.where(ref <= level, ref, INF).astype(np.int32)
    open_ = np.random.default_rng(seed).permutation(
        np.flatnonzero((state >= INF) & (degc > 0)))
    keep = int(np.searchsorted(np.cumsum(degc[open_]), columns,
                               side="right"))
    assert 0 < keep <= columns
    state[open_[keep:]] = 0
    return np.concatenate([state, [INF]]).astype(np.int32)


def parents_of(g, v, chunk=0):
    """The eight lanes of vertex ``v``'s chunk ``chunk``, pads (n + 1)
    dropped."""
    host = g["_host"]
    lanes = host["dstT"][:, host["colstart"][v] + chunk]
    return lanes[lanes <= g["n"]]


def end_numpy(g, state, level):
    """The endgame as a loop: whole bottom-up levels until one finds
    nothing; (``dist``, the levels that found something)."""
    n = g["n"]
    degc = g["_host"]["degc"]
    dist, iters = state.copy(), 0
    while True:
        found = [v for v in np.flatnonzero((dist[:n] >= INF)
                                           & (degc[:n] > 0))
                 if any(np.any(dist[parents_of(g, v, c)] == level)
                        for c in range(degc[v]))]
        if not found:
            return dist, iters
        dist[found] = level + 1
        level, iters = level + 1, iters + 1


def call_end(g, state, level, columns, impl):
    import jax.numpy as jnp

    dist, iters = H._endgame()(
        jnp.asarray(state), jnp.int32(level), jnp.int32(1000), g["dstT"],
        g["colstart"], g["degc"], c_cap=columns, p_cap=columns, n_=g["n"],
        impl=impl)
    return np.asarray(dist), int(iters)


@pytest.mark.parametrize("columns", COLUMNS)
def test_the_endgame_under_each_road_is_the_plain_loop(
        graph, columns, kernel_in_the_interpreter):
    _snap, g, ref = graph
    state = state_under(g, ref, 1, columns, seed=columns)
    want = end_numpy(g, state, 1)
    assert want[1] >= 2                     # more than one body ran
    for impl in ("xla", "vmem"):
        dist, iters = call_end(g, state, 1, columns, impl)
        assert np.array_equal(dist, want[0]) and iters == want[1], impl


def bu0b_numpy(g, state, cand, level):
    """``bu0b`` as a loop over the list: a candidate with one of the
    frontier in its first chunk is found; one that misses and has a
    second chunk is handed on, in the list's order."""
    n = g["n"]
    degc = g["_host"]["degc"]
    dist = state.copy()
    left = []
    for v in cand[cand < n]:
        if np.any(state[parents_of(g, v)] == level):
            dist[v] = level + 1
        elif degc[v] > 1:
            left.append(v)
    rem8 = int(sum(degc[v] - 1 for v in left))
    st = np.zeros(4, np.int32)
    if not left:
        unvis = dist[:n] >= INF
        new = dist[:n] == level + 1
        st[:] = [new.sum(), degc[:n][new].sum(), degc[:n][unvis].sum(),
                 (unvis & (degc[:n] > 0)).sum()]
    return dist, np.asarray(left, np.int32), [len(left), rem8], st


def call_bu0b(g, state, cand, level, impl):
    import jax.numpy as jnp

    n = g["n"]
    dist = jnp.asarray(state)
    fbits = H._pack_bits(dist, jnp.int32(level), n)
    dist, cand2, prog, st = H._bu_finish_chunk0()(
        dist, fbits, jnp.asarray(cand), jnp.int32(level), g["dstT"],
        g["colstart"], g["degc"], c_cap=cand.shape[0], n_=n, impl=impl)
    nc = int(prog[0])
    cand2 = np.asarray(cand2)
    assert np.all(cand2[nc:] == n)
    return (np.asarray(dist), cand2[:nc], [int(x) for x in prog],
            np.asarray(st))


@pytest.mark.parametrize("level", [1, 2])
@pytest.mark.parametrize("columns", COLUMNS)
def test_bu0b_under_each_road_is_the_plain_loop(
        graph, columns, level, kernel_in_the_interpreter):
    """Behind level 1 most candidates miss their first chunk and are
    handed on; behind level 2 every one is found and the level's
    statistics come with it."""
    _snap, g, ref = graph
    n = g["n"]
    state = state_under(g, ref, level, columns, seed=columns + level)
    open_ = np.flatnonzero((state[:n] >= INF)
                           & (g["_host"]["degc"][:n] > 0))
    cand = np.full(columns, n, np.int32)
    cand[:len(open_)] = open_
    want = bu0b_numpy(g, state, cand, level)
    assert (want[2][0] > 0) == (level == 1)
    for impl in ("xla", "vmem"):
        got = call_bu0b(g, state, cand, level, impl)
        for a, b in zip(got, want):
            assert np.array_equal(a, b), impl


@pytest.mark.parametrize("rows", [1, 2, 8])
@pytest.mark.parametrize("columns", COLUMNS + [vg.BLOCK + 128])
def test_the_road_is_read_from_the_table_and_the_block_alone(
        graph, columns, rows):
    """Under ``"vmem"`` a block of whole grid steps reads the table, any
    other the bitmap; under ``"xla"`` every block reads the bitmap: and
    both give each column's any-hit."""
    import jax.numpy as jnp

    _snap, g, ref = graph
    n = g["n"]
    assert H._frontier_road("xla", columns) == "xla"
    whole = columns % vg.BLOCK == 0
    assert H._frontier_road("vmem", columns) == ("vmem" if whole else "xla")
    dist = jnp.asarray(np.concatenate([ref, [INF]]).astype(np.int32))
    # ids 0 .. n + 1: the sink and the pad among them, never a hit
    parents = np.random.default_rng(rows).integers(
        0, n + 2, (rows, columns)).astype(np.int32)
    on = np.concatenate([ref == 1, [False, False]])
    want = on[parents].any(axis=0)
    assert want.any() and not want.all()
    seen = []

    def interpreted(idx, table, rows):
        seen.append(rows)
        return colsum(idx, table, interpret=True, rows=rows)

    colsum, vg.colsum_vmem = vg.colsum_vmem, interpreted
    try:
        for impl in ("xla", "vmem"):
            hit = H._frontier_test(dist, jnp.int32(1), n, impl,
                                   columns)(jnp.asarray(parents))
            assert np.array_equal(np.asarray(hit), want), impl
    finally:
        vg.colsum_vmem = colsum
    assert seen == ([rows] if whole else [])


# -- whole runs --------------------------------------------------------------

def run_traced(run):
    """The run's result, its frontier-test programs' calls as (key,
    ``impl``, the block's columns), and the counter's non-zero counts."""
    out, spans, mm, _stats = traced(run)
    calls = [(s.attrs["key"], s.attrs["impl"],
              s.attrs.get("p_cap", s.attrs["c_cap"]))
             for s in spans if s.name == "kernel"
             and s.attrs["key"] in FRONTIER_TEST_KEYS]
    counted = {(prog, impl): mm.counter(
        "device.bfs.frontier_test",
        labels={"prog": prog, "impl": impl}).count
        for prog in PROG.values() for impl in ("xla", "vmem")}
    return out, calls, {k: v for k, v in counted.items() if v}


#: thresholds that take a run through the programs named: (the split
#: opener from, the endgame's caps, the chunk rounds before the sweep)
ROUTES = {
    "dense": ((2, 2048, 8), {"hybrid_bu_startL", "hybrid_bu_finish0",
                             "hybrid_bu_more", "hybrid_endgame"}),
    "plain": ((1 << 30, 0, 2), {"hybrid_bu_start", "hybrid_bu_more",
                                "hybrid_ex"}),
}


@pytest.mark.parametrize("impl", ["xla", "vmem"])
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_a_whole_run_under_each_road(graph, route, impl, monkeypatch,
                                     kernel_in_the_interpreter):
    """One BFS through ``bu0a``, ``bu0b``, ``bu`` and ``end`` (the WCC
    cell's road) and one through ``bu0``, ``bu`` and ``ex``: each
    program's ``kernel`` span carries what served its test, the counter
    counts the same calls, and ``gather_impl`` is asked once a run."""
    (split_min, end_cap, rounds), through = ROUTES[route]
    for name, value in (("SPLIT_LANE_MIN", split_min), ("HEAD_F_CAP", 1),
                        ("ALPHA", 1e9), ("END_C_CAP", end_cap),
                        ("END_P_CAP", end_cap),
                        ("BU_CHUNK_ROUNDS", rounds)):
        monkeypatch.setattr(H, name, value)
    asked = []
    monkeypatch.setattr(vg, "gather_impl",
                        lambda n: asked.append(n) or impl)
    snap, g, ref = graph
    (dist, _levels), calls, counted = run_traced(
        lambda: H.frontier_bfs_hybrid(snap, 0))
    assert np.array_equal(dist, ref)
    assert asked == [g["n"]]
    # every span says the road of its own block: the table where the
    # graph's `impl` allows it and the block is whole grid steps
    for key, served, columns in calls:
        if key == "hybrid_bu_startL":
            columns = vg.padded_columns(g["n"] + 1)
        assert served == H._frontier_road(impl, columns), (key, columns)
    assert {key for key, served, _ in calls if served == impl} >= through
    want: dict = {}
    for key, served, _ in calls:
        want[PROG[key], served] = want.get((PROG[key], served), 0) + 1
    assert counted == want
    if impl == "xla":
        assert {served for _, served, _ in calls} == {"xla"}


def test_on_the_cpu_every_program_takes_the_bitmap(graph, monkeypatch):
    """Nothing patched: ``gather_impl`` says ``"xla"`` here (as it does
    on a chip past ``VMEM_TABLE_MAX`` of table), and no program of the
    run sees another ``impl``."""
    for name, value in (("SPLIT_LANE_MIN", 2), ("HEAD_F_CAP", 1),
                        ("ALPHA", 1e9), ("END_C_CAP", 2048),
                        ("END_P_CAP", 2048)):
        monkeypatch.setattr(H, name, value)
    snap, g, ref = graph
    assert vg.gather_impl(g["n"]) == "xla"
    (dist, _), calls, counted = run_traced(
        lambda: H.frontier_bfs_hybrid(snap, 0))
    assert np.array_equal(dist, ref)
    assert len(calls) >= 4 and {served for _, served, _ in calls} == {"xla"}
    assert sum(counted.values()) == len(calls)
    assert {impl for _, impl in counted} == {"xla"}


def test_past_the_tables_limit_a_chip_takes_the_bitmap(monkeypatch):
    """The choice is ``gather_impl``'s: on a TPU a table past
    ``VMEM_TABLE_MAX`` is ``"xla"``, whatever the block."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 8_871_268
    assert vg.gather_impl(n) == "vmem"
    assert H._frontier_road(vg.gather_impl(n), 1 << 20) == "vmem"
    assert H._frontier_road(vg.gather_impl(n), 512) == "xla"
    past = vg.VMEM_TABLE_MAX // 4
    assert vg.gather_impl(past) == "xla"
    assert H._frontier_road(vg.gather_impl(past), 1 << 20) == "xla"
