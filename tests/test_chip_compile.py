"""Ask the chip's compiler, without the chip.

libtpu compiles for a TPU that is described, not attached
(``topologies.get_topology_desc``), so the kernels ``chip_smoke.py``
dispatches are compiled here at their real sizes — scale-20 R-MAT,
n = 2^20, 33.5 M symmetric edges in Q = 4,563,400 chunk columns, K = 8
— and what the compiler refuses costs no chip time. This is the only
file that describes the chip. The topology and everything built from it
live in module-scoped fixtures: only the worker that RUNS this file
loads libtpu (one process at a time may), every worker collects the
same tests, and the compiles happen in the test's own process.

A compile that passes here is not a chip run.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest

N = 1 << 20            # vertices, scale 20
E = 2 * 16 * N         # symmetrised R-MAT edges, edge factor 16
Q = 4_563_400          # chunk columns of that graph (seed 2)
K = 8                  # fused BFS cohort
NB = (N + 2 + 7) // 8  # frontier bitmap bytes per job


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one described v5e chip. The persistent compile cache
    is off around these compiles: tests/conftest.py keeps it on with a
    zero threshold, and an entry written for a TPU cannot be read back
    without one (every later run would warn and recompile)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **static):
    """Lower the registered jitted function (jit_once hands out a
    profile shim; the jit itself is ``__wrapped__``) or jit a plain one."""
    raw = getattr(fn, "__wrapped__", None) or jax.jit(fn)
    compiled = raw.lower(*args, **static).compile()
    assert compiled.memory_analysis() is not None
    return compiled


# -- the engine's combine: the branch only the chip takes ------------------

@pytest.mark.parametrize("combine,dtype", [("sum", jnp.float32),
                                           ("min", jnp.int32)])
def test_sorted_segment_combine(spec, combine, dtype):
    from titan_tpu.ops.segment import sorted_segment_combine

    _compile(functools.partial(sorted_segment_combine, combine=combine),
             spec((E,), dtype), spec((E,), jnp.int32),
             spec((N,), jnp.int32), spec((N,), jnp.bool_))


# -- what phase 3 of the smoke dispatches (keys and shapes taken from a
#    devprof'd CPU rehearsal at scale 20: the most-called jit_once keys) ----

def test_batched_bfs_plan(spec):
    from titan_tpu.models.bfs_hybrid import _batched_plan

    _compile(_batched_plan(), spec((K, N + 1), jnp.int32),
             spec((K,), jnp.bool_), spec((), jnp.int32),
             spec((N + 1,), jnp.int32), c_cap=N, n_=N, expand=False)


def test_batched_bfs_bottom_up(spec):
    from titan_tpu.models.bfs_hybrid import _batched_bu

    _compile(_batched_bu(), spec((K, N + 1), jnp.int32),
             spec((K, NB), jnp.uint8), spec((N,), jnp.int32),
             spec((N,), jnp.int32), spec((2,), jnp.int32),
             spec((), jnp.int32), spec((8, Q), jnp.int32),
             spec((N + 1,), jnp.int32), spec((N + 1,), jnp.int32),
             spec((1,), jnp.uint8),
             c_cap=N, n_=N, fuse=8, masked=False, expand=False)


@pytest.mark.parametrize("k,expand", [(16, True), (1, True), (K, False)],
                         ids=["lane-hops-16", "lane-hops-1", "jobs-bfs-8"])
def test_batched_bfs_top_down(spec, k, expand):
    """Every rung of the push ladder, as the lane (16 fused hops
    queries, and the one query of a median batch) and the job batcher
    (8 BFS jobs) call it: the benchmark must not be the first to show
    the chip's compiler a rung, the four a factor of two apart between
    the middle and the top among them. Each takes the frontier as a pair
    list and the lowest rung holds the claim dedup that hands on the
    next."""
    from titan_tpu.models.bfs_hybrid import (_batched_td, _td_caps,
                                             _td_lists)

    caps = _td_caps({"q_total": Q})
    assert caps == (1 << 12, 1 << 17, 1 << 18, 1 << 19, 1 << 20, 1 << 21)
    assert [_td_lists(p_cap, N) for p_cap in caps] == [True] + [False] * 5
    for p_cap in caps:
        _compile(_batched_td(), spec((k, N + 1), jnp.int32),
                 spec((caps[-1],), jnp.int32), spec((caps[-1],), jnp.int32),
                 spec((), jnp.int32), spec((k,), jnp.bool_),
                 spec((), jnp.int32), spec((), jnp.int32),
                 spec((8, Q), jnp.int32), spec((N + 1,), jnp.int32),
                 spec((N + 1,), jnp.int32), p_cap=p_cap, n_=N,
                 expand=expand, lists=_td_lists(p_cap, N))


@pytest.mark.parametrize("k,expand", [(16, True), (K, False)],
                         ids=["lane-hops-16", "jobs-bfs-8"])
def test_batched_bfs_seed_list_extract(spec, k, expand):
    """The rest of a pushed query's programs: the seed (state, job mask
    and the start level's pair list in one), the scan road's listing
    (every rung's branch in one executable) and the lane's extract."""
    from titan_tpu.models.bfs_hybrid import (_batched_list, _batched_seed,
                                             _td_caps, hop_extract)

    caps = _td_caps({"q_total": Q})
    _compile(_batched_seed(), spec((k,), jnp.int32), spec((), jnp.int32),
             n_=N, cap=caps[-1], expand=expand)
    _compile(_batched_list(), spec((k, N + 1), jnp.int32),
             spec((k,), jnp.bool_), spec((), jnp.int32),
             spec((), jnp.int32), spec((N + 1,), jnp.int32),
             caps=caps, n_=N)
    _compile(hop_extract(), spec((k, N + 1), jnp.int32),
             spec((k,), jnp.int32), n_=N)


def test_frontier_push_list_sssp(spec):
    from titan_tpu.models.frontier import _push_list

    _compile(_push_list("sssp"), spec((N + 1,), jnp.float32),
             spec((N + 1,), jnp.float32), spec((N,), jnp.int32),
             spec((65,), jnp.int32), spec((), jnp.int32),
             spec((), jnp.float32), spec((8, Q), jnp.int32),
             spec((N + 1,), jnp.int32), spec((N + 1,), jnp.int32),
             spec((2,), jnp.float32), spec((1,), jnp.uint8),
             f_cap=N, p_cap=1 << 22, n_=N, masked=False)


# LDBC Graphalytics graph500-22 as the benchmark's generator makes it
# (benchmark/configs/graphalytics-g500-22.json; CPU count, PR 33): the
# shapes of the cell g500-22.pr-c2, whose job is these two executables
N22 = 2_396_390
Q22 = 17_447_196


def test_pagerank_job_at_graph500_22(spec):
    from titan_tpu.models.frontier import (DENSE_WINDOW, _pr_finish,
                                           _pr_window)

    win = _compile(_pr_window(), spec((N22 + 1,), jnp.float32),
                   spec((N22 + 1,), jnp.float32), spec((), jnp.int32),
                   spec((8, Q22), jnp.int32), spec((Q22,), jnp.int32),
                   W=DENSE_WINDOW)
    _compile(_pr_finish(), spec((N22 + 1,), jnp.float32),
             spec((N22 + 1,), jnp.float32), spec((N22 + 1,), jnp.float32),
             spec((), jnp.float32), n_=N22)
    # the window's scratch beside the 0.63 GB image: well inside 16 GB
    assert win.memory_analysis().temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("parents", [False, True],
                         ids=["depths", "tree"])
@pytest.mark.parametrize("prog, caps", [
    ("bstep", (1 << 22,)), ("bex", (1 << 12, 1 << 16)),
    ("bex", (1 << 22, 1 << 25))],
    ids=["bstep-top", "bex-lowest", "bex-top"])
def test_batched_pull_ladder_at_graph500_22(spec, prog, caps, parents):
    """The cell kron-s22.bfs-tree-c2's pulled level at K = 1 (ISSUE 49,
    ISSUE 50): the ladder's top ``bstep`` rung, and the lowest and the
    top ``bex`` pair (the sweep over 2^25 chunk columns is the largest
    program of the set: 8 x 2^25 parents gathered at once), each as a
    depth-only job takes it and with the parent plane beside ``dist``
    (the lane that hit reduced to an id, a second scatter). The lists
    come in at the ladder's top width and the programs read their first
    ``c_cap``."""
    from titan_tpu.models.bfs_hybrid import (_batched_bu, _batched_exhaust,
                                             _bu_caps, _fbits_bytes)

    c_caps, ex_pairs = _bu_caps({"n": N22, "q_total": Q22})
    assert caps in ex_pairs or caps[0] in c_caps
    top = c_caps[-1]
    state = spec((1, N22 + 1), jnp.int32)
    args = ((state, state) if parents else state,
            spec((1, _fbits_bytes(N22)), jnp.uint8),
            spec((top,), jnp.int32), spec((top,), jnp.int32),
            spec((2,), jnp.int32), spec((), jnp.int32),
            spec((8, Q22), jnp.int32), spec((N22 + 1,), jnp.int32),
            spec((N22 + 1,), jnp.int32), spec((1,), jnp.uint8))
    if prog == "bstep":
        got = _compile(_batched_bu(), *args, c_cap=caps[0], n_=N22,
                       fuse=8, masked=False, expand=False)
    else:
        got = _compile(_batched_exhaust(), *args, c_cap=caps[0],
                       p_cap=caps[1], n_=N22, masked=False, expand=False)
    # beside the 0.59 GB image and a few n-vectors: well inside 16 GB
    assert got.memory_analysis().temp_size_in_bytes < 4 << 30


def test_batched_push_with_parents_at_graph500_22(spec):
    """The cell's pushed levels with the parent plane: the lowest rung
    (the one that dedups its targets and hands the next level its list)
    and the top one, and the seed that makes both planes."""
    from titan_tpu.models.bfs_hybrid import (_batched_seed, _batched_td,
                                             _td_caps, _td_lists)

    caps = _td_caps({"q_total": Q22})
    state = spec((1, N22 + 1), jnp.int32)
    for p_cap in (caps[0], caps[-1]):
        _compile(_batched_td(), (state, state),
                 spec((caps[-1],), jnp.int32), spec((caps[-1],), jnp.int32),
                 spec((), jnp.int32), spec((1,), jnp.bool_),
                 spec((), jnp.int32), spec((), jnp.int32),
                 spec((8, Q22), jnp.int32), spec((N22 + 1,), jnp.int32),
                 spec((N22 + 1,), jnp.int32), p_cap=p_cap, n_=N22,
                 expand=False, lists=_td_lists(p_cap, N22))
    _compile(_batched_seed(), spec((1,), jnp.int32), spec((), jnp.int32),
             n_=N22, cap=caps[-1], expand=False, parents=True)


def test_pagerank_pull_at_graph500_22(spec):
    """The served job's iteration since ISSUE 35: the Pallas gather
    with the 9.6 MB table in VMEM and 8,192 indices a grid step in
    SMEM, the segment sum, the cut to [n]."""
    from titan_tpu.models import pagerank_pull as pp
    from titan_tpu.models.frontier import _pr_result
    from titan_tpu.ops.vmem_gather import padded_columns

    q_in = padded_columns(Q22)
    pull = _compile(pp.pull_step(), spec((N22 + 1,), jnp.float32),
                    spec((N22 + 1,), jnp.float32),
                    spec((8 * q_in,), jnp.int32), spec((q_in,), jnp.bool_),
                    spec((N22,), jnp.int32), spec((N22,), jnp.bool_),
                    impl="vmem", seg_max=20_413)
    assert "tpu_custom_call" in pull.as_text()
    assert pull.memory_analysis().temp_size_in_bytes < 1 << 30
    _compile(_pr_result(), spec((N22 + 1,), jnp.float32), n_=N22)


@pytest.mark.parametrize("width", [4, 1])
def test_bc_levels_at_graph500_22(spec, width):
    """The two level programs of the served BC job (ISSUE 46) at the
    shapes of the cell kron-s22.bc-c2, which are g500-22.pr-c2's: the
    same Pallas gather, its table a level's masked sigma or (1 + delta)
    / sigma. Since ISSUE 47 at the cell's width, four roots side by
    side in one 38.3 MB table (and at width 1: a fifth root alone); a
    level's temporaries and outputs stay inside what admission reserves
    for them (``models/bc.level_bytes``, no root's delta kept)."""
    from titan_tpu.models import bc as B
    from titan_tpu.ops.vmem_gather import (VMEM_TABLE_MAX, padded_columns,
                                           shared_width, table_rows)

    assert shared_width(N22, B.MAX_ROOTS) == 4
    assert table_rows(N22, 4) * 512 <= VMEM_TABLE_MAX < \
        table_rows(N22, 8) * 512
    q_in = padded_columns(Q22)
    image = (spec((8 * q_in,), jnp.int32), spec((q_in,), jnp.bool_),
             spec((N22,), jnp.int32), spec((N22,), jnp.bool_))
    depth = spec((width, N22), jnp.int32)
    vec = spec((width, N22), jnp.float32)
    level = spec((), jnp.int32)
    statics = {"impl": "vmem", "seg_max": 20_413, "width": width}
    forward = _compile(B._level("bc_forward_level", B.forward_level),
                       depth, vec, level, *image, **statics)
    backward = _compile(B._level("bc_backward_level", B.backward_level),
                        depth, vec, vec, level, *image, **statics)
    for program in (forward, backward):
        text = program.as_text()
        assert "tpu_custom_call" in text
        # no array with the roots minor: it would pad every value to a
        # row of lanes (1.2 GB a table at this n)
        assert f"f32[{N22 + 1},{width}]{{1,0" not in text
        m = program.memory_analysis()
        # the column sums and the scan's passes are q_in wide, not n
        assert m.temp_size_in_bytes + m.output_size_in_bytes \
            <= B.level_bytes(N22, q_in, width)
    _compile(B._seed(), spec((width,), jnp.int32), n_=N22)
    _compile(B._result(), (vec,))


def test_cdlp_round_at_graph500_22(spec):
    """A round of the served CDLP job (ISSUE 40; the row image: ISSUE
    44) at graph500-22's shapes (CPU counts, PR 44: 14,420 small rows of
    1,024 columns and 85 wide rows of 32,768; 140,410,880 lanes): the
    Pallas gather a lane at a time (rows = 1) with the labels as a
    float32 table in VMEM, the sort along the rows of ONE uint32 word a
    lane (10 bits of owner above 22 of label), the vote. What the
    rounds keep on the device at once is what admission reserves for
    them (``models/cdlp.work_bytes``)."""
    from titan_tpu.models import cdlp as C

    classes = ((14_420, 1024), (85, 32_768))
    wide = sum(r * 8 * w for r, w in classes)
    lanes = spec((wide,), jnp.int32)
    labels = spec((N22,), jnp.int32)
    gather = _compile(C._gather(), labels, lanes, impl="vmem", n_=N22)
    assert "tpu_custom_call" in gather.as_text()
    statics = C.sort_statics({"classes": classes, "keys": 1,
                              "label_bits": 22, "pad_share": 0.0862})
    sort = _compile(C._sort(), spec((wide,), jnp.uint32), lanes, **statics)
    text = sort.as_text()
    # one operand a sort, a sort a class, and the word split again by
    # arithmetic: no gather
    assert text.count(" sort(") == 2 and " gather(" not in text
    assert sort.memory_analysis().output_size_in_bytes // (4 * wide) == 2
    vote = _compile(C._vote(), lanes, lanes, labels, labels,
                    spec((N22,), jnp.bool_), max_len=8 * 32_768, n_=N22)
    # the widest program: the sorted pair and its temporaries, with the
    # gathered lanes that may outlive the sort, inside what is reserved
    held = {}
    for name, program in (("gather", gather), ("sort", sort),
                          ("vote", vote)):
        m = program.memory_analysis()
        held[name] = m.argument_size_in_bytes + m.output_size_in_bytes \
            + m.temp_size_in_bytes
    print({k: round(v / (4 * wide), 2) for k, v in held.items()})
    # the image's half of the sort's arguments is not the rounds' to hold
    assert held["sort"] - 4 * wide + 4 * wide <= C.work_bytes(N22, wide)
    assert held["vote"] + 4 * wide <= C.work_bytes(N22, wide) + 4 * wide
    m = vote.memory_analysis()
    assert m.temp_size_in_bytes < 6 * 4 * wide


def test_lcc_programs_at_graph500_22(spec):
    """The programs of the served LCC job (ISSUE 42; the pass's own
    lanes and the sorted credits ISSUE 48) at graph500-22's shapes (n
    2,396,390; 16,384 asked hubs: a table of 512 words a row; the pass's
    5,252,096 columns, every edge with a hub at an end once; 22.2 M
    low-low edges padded to 22 chunks; the finish's sort of 88.2 M
    counts by the vertex each names; the tail's rows of 128 and its
    widest and most populous classes: CPU counts, PR 48, the same under
    two relabellings): ``lax.population_count``, a gather of 512-word
    rows and the AND-reduce over them. The gathers stay gathers (a row
    a lane, not a dynamic-slice a word), a dispatch's temporaries stay
    inside what admission reserves for them, and none builds for over a
    minute (build times printed)."""
    import time

    from titan_tpu.models import lcc as L
    from titan_tpu.ops.vmem_gather import padded_columns

    q = padded_columns(Q22)
    columns, low_low, hubs = 5_252_096, 22 * L.COL_CHUNK, 16_273
    chunk = L.pass_chunk(columns)
    passes = len(L._starts(columns, chunk))
    assert (chunk, passes) == (875_520, 6)
    words = L.hub_words(L.HUBS)
    table = spec((N22 + 2, words), jnp.uint32)
    at = spec((), jnp.int32)
    rows = spec((2_031_616, L.TAIL_ROW), jnp.int32)
    blocks = [((856_064, 8), 1024), ((59_840, 96), 85), ((1_344, 192), 42)]
    credits = tuple(
        (spec(shape, jnp.int32), spec(shape, jnp.int32))
        for (b, d), _per in blocks for shape in ((b, d), (b,)))
    image = {"first": spec((columns,), jnp.bool_),
             "owners": spec((hubs,), jnp.int32),
             "last": spec((hubs,), jnp.int32),
             "idx8": spec((8, columns), jnp.int32),
             "ll": spec((2, low_low), jnp.int32),
             "credit_last": spec((N22,), jnp.int32),
             "hub_ids": spec((hubs,), jnp.int32),
             "deg": spec((N22,), jnp.int32)}
    programs = {
        "lcc_pass": lambda: _compile(
            L._pass(), table, image["idx8"], spec((columns,), jnp.int32),
            spec((8, columns), jnp.bool_), at, chunk=chunk,
            tile=L.PASS_TILE),
        "lcc_colsum": lambda: _compile(
            L._colsum(), table, image["ll"], at, chunk=L.COL_CHUNK,
            tile=L.COL_TILE),
        "lcc_finish": lambda: _compile(
            L._finish(),
            (spec((chunk,), jnp.int32),) * passes,
            (spec((8, chunk), jnp.int32),) * passes,
            (spec((L.COL_CHUNK,), jnp.int32),) * 22,
            (spec((32 * words,), jnp.int32),) * 22, credits, image,
            seg_max=20_413, credit_max=1_048,
            trim=passes * chunk - columns),
    }
    for (b, d), per in blocks:
        programs[f"lcc_tail d={d}"] = functools.partial(
            _compile, L._tail(), rows, spec((b, d), jnp.int32),
            spec((b, d), jnp.int32), per=per)
    work = L.work_bytes(N22, q, L.HUBS)
    texts, temps = {}, {}
    for name, build in programs.items():
        t0 = time.time()
        program = build()
        took = time.time() - t0
        m = program.memory_analysis()
        temps[name] = m.temp_size_in_bytes
        print(f"{name}: built in {took:.1f} s, temporaries "
              f"{temps[name] >> 20} MiB")
        assert took < 60, (name, took)
        assert temps[name] + m.output_size_in_bytes < work, name
        texts[name] = program.as_text()
        assert name == "lcc_finish" or " gather(" in texts[name], name
    text = texts["lcc_pass"]
    assert "u32[8192,512]" in text          # a tile's rows, whole
    assert "popcnt" in text or "population" in text
    assert "cummax" not in text
    assert "popcnt" in texts["lcc_colsum"] \
        or "population" in texts["lcc_colsum"]
    # the finish sorts the counts once, both operands in one sort
    entries = 8 * columns + 2 * low_low
    assert f"s32[{entries}]" in texts["lcc_finish"] \
        and " sort(" in texts["lcc_finish"]
    # what the finish holds (its arguments from the programs before it
    # and its temporaries) stays inside the 20 bytes a named count of
    # ``work_bytes``, priced at the pull image's columns
    handed = 4 * (9 * passes * chunk + low_low)
    assert temps["lcc_finish"] + handed < 20 * entries < 20 * 8 * q
    # what admission prices holds what the build reads (CPU count, PR
    # 48: the image's arrays at graph500-22), with under a fifth to spare
    assert L.table_bytes(N22, L.HUBS) == 4_907_810_816
    assert 6_748_937_780 < L.image_bytes(N22, q, L.HUBS) \
        < 1.2 * 6_748_937_780
    # forward image, LCC image and working set inside the ledger's budget
    from titan_tpu.olap.serving.hbm import (DEFAULT_BUDGET_BYTES,
                                            chunked_csr_bytes)
    assert chunked_csr_bytes(N22, Q22) + L.image_bytes(N22, q, L.HUBS) \
        + work < DEFAULT_BUDGET_BYTES


def test_wcc_propagation_on_the_remainder_at_graph500_24(spec):
    """What ISSUE 37 added to a WCC job of g500-24.wcc-c2 (n 8,871,268:
    CPU count, PR 36): the seeding that lists the remainder at n / 8
    and a round's plan over 2^13 of the list."""
    from titan_tpu.models import frontier as F

    n = 8_871_268
    r_cap = F._remainder_cap(n)
    assert r_cap == 1 << 20
    state = spec((n + 1,), jnp.int32)
    _compile(F._wcc_seed_labels(), spec((n,), jnp.int32), state,
             n_=n, r_cap=r_cap)
    plan = _compile(F._list_plan("wcc"), state, state, state,
                    spec((r_cap,), jnp.int32), spec((), jnp.int32),
                    n_=n, w=1 << 13, k_max=F.SLICE_K_MAX,
                    budget=(1 << 23) - 50_835)
    # nothing of a plan over the list is n wide
    assert plan.memory_analysis().temp_size_in_bytes < 1 << 20


def test_dense_opener_at_graph500_24(spec):
    """What ISSUE 39 put on g500-24.wcc-c2's one pulled level: the
    split-lane opener n wide over the leading-lane image, its frontier
    test the 35.5 MB 0/1 table in VMEM; and the image's builder."""
    from titan_tpu.models import bfs_hybrid as H
    from titan_tpu.ops import vmem_gather as vg

    n, q, lanes = 8_871_268, 70_278_271, H.SPLIT_LANES
    c_cap = 1 << 24
    assert vg.table_rows(n) * 512 <= vg.VMEM_TABLE_MAX
    width = vg.padded_columns(n + 1)
    state = spec((n + 1,), jnp.int32)
    bu0a = _compile(H._bu_startL(), state, spec((), jnp.int32),
                    spec((lanes * width,), jnp.int32), state, state,
                    c_cap=c_cap, n_=n, lanes=lanes, impl="vmem")
    assert "tpu_custom_call" in bu0a.as_text()
    # nothing of it is a list of 2^24 but the untested handed on (the
    # list of the candidates it replaced kept 0.8 GB of temporaries)
    assert bu0a.memory_analysis().temp_size_in_bytes < 128 << 20
    lead = _compile(H._lead_image(), spec((8, q), jnp.int32), state, state,
                    lanes=lanes)
    assert lead.memory_analysis().output_size_in_bytes == lanes * width * 4


@pytest.mark.parametrize("prog", ["end", "bu0b"])
def test_frontier_test_in_vmem_at_graph500_24(spec, prog):
    """What ISSUE 43 put on the two list-wide programs of a
    g500-24.wcc-c2 job (one call each, on 2^20 columns): their frontier
    test reads the 35.5 MB 0/1 table in VMEM through the Pallas gather
    at eight rows a column (in ``end`` inside the ``while_loop``'s
    body), and XLA's gather of 8 x 2^20 single bytes, 68 ms a test on
    the chip (PERF.md 5), is in neither."""
    from titan_tpu.models import bfs_hybrid as H

    n, q, cap = 8_871_268, 70_278_271, 1 << 20
    assert H._frontier_road("vmem", cap) == "vmem"
    state = spec((n + 1,), jnp.int32)
    at = spec((), jnp.int32)
    image = spec((8, q), jnp.int32)
    if prog == "end":
        program = _compile(H._endgame(), state, at, at, image, state,
                           state, c_cap=cap, p_cap=cap, n_=n, impl="vmem")
    else:
        program = _compile(H._bu_finish_chunk0(), state,
                           spec((H._fbits_width(n),), jnp.uint8),
                           spec((cap,), jnp.int32), at, image, state,
                           state, c_cap=cap, n_=n, impl="vmem")
    text = program.as_text()
    assert "tpu_custom_call" in text
    assert "u8[8388608]" not in text and "u8[8,1048576]" not in text
    # lists of 2^20 and the table, nothing as wide as the image
    assert program.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("rows", [None, 1], ids=["pagerank", "ppr"])
def test_pagerank_window(spec, rows):
    from titan_tpu.models.frontier import _pr_window
    from titan_tpu.models.pagerank import _ppr_window_batched

    fn = _pr_window() if rows is None else _ppr_window_batched()
    lead = () if rows is None else (rows,)
    _compile(fn, spec(lead + (N + 1,), jnp.float32),
             spec(lead + (N + 1,), jnp.float32), spec((), jnp.int32),
             spec((8, Q), jnp.int32), spec((Q,), jnp.int32), W=1 << 22)

