"""ops/compaction property + contract tests (CPU).

The library's whole value is a CONTRACT: each primitive is bit-equal to
the ``jnp.nonzero(mask, size=cap, fill_value=fill)`` formulation it
replaced in the round loops (ascending survivor order, fill past the
count, overflow truncation), while running at p-scale. These tests pin
that contract against numpy oracles over random masks/bands, check the
cap-overflow and claim-reset behavior the consumers rely on, and hold
the op-scan ban (ISSUE r6) through graftlint rule R1 — auto-discovered
over the whole tree since ISSUE 15, replacing the per-directory
module-count pins that lived here (differential end-to-end coverage of
the refactored BFS/SSSP/WCC consumers lives in test_frontier_models.py
/ test_frontier_bfs.py / test_sharded_bfs.py against independent
oracles)."""

import numpy as np
import pytest

from titan_tpu.ops.compaction import (CLAIM_SENTINEL, banded_frontier,
                                      claim_dedup, claim_reset,
                                      compact_ids, scatter_compact)


def _np_compact(mask, payload, cap, fill):
    """Oracle: the pre-refactor nonzero+gather formulation."""
    idx = np.nonzero(mask)[0][:cap]
    out = np.full((cap,), fill, payload.dtype)
    out[: len(idx)] = payload[idx]
    return out


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("density", [0.0, 0.03, 0.5, 1.0])
def test_scatter_compact_matches_nonzero_oracle(seed, density):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    L = int(rng.integers(1, 3000))
    cap = int(rng.integers(1, 2 * L))
    mask = rng.random(L) < density
    ids = np.arange(L, dtype=np.int32)
    vals = rng.integers(-50, 50, L).astype(np.int32)
    count, (o_ids, o_vals) = scatter_compact(
        jnp.asarray(mask), (jnp.asarray(ids), jnp.asarray(vals)),
        cap, (L, -1))
    assert int(count) == int(mask.sum())       # TOTAL bits, pre-truncation
    assert (np.asarray(o_ids) == _np_compact(mask, ids, cap, L)).all()
    assert (np.asarray(o_vals) == _np_compact(mask, vals, cap, -1)).all()


@pytest.mark.parametrize("seed", range(4))
def test_compact_ids_bit_equal_vs_jnp_nonzero(seed):
    """compact_ids must be indistinguishable from the jnp.nonzero call
    it replaced — same dtype, same order, same fill, same truncation."""
    import jax.numpy as jnp

    rng = np.random.default_rng(100 + seed)
    L = int(rng.integers(1, 2000))
    cap = int(rng.integers(1, L + 10))
    mask = jnp.asarray(rng.random(L) < rng.random())
    ref = jnp.nonzero(mask, size=cap, fill_value=L)[0].astype(jnp.int32)
    count, got = compact_ids(mask, cap, L)
    assert got.dtype == ref.dtype
    assert (np.asarray(got) == np.asarray(ref)).all()
    assert int(count) == int(np.asarray(mask).sum())


def test_scatter_compact_overflow_cap_drops_tail():
    """Survivors past cap are dropped (not wrapped or clamped), and the
    returned count still reports the TRUE total so callers can detect
    the truncation (the _band_plan soundness contract rides on this)."""
    import jax.numpy as jnp

    mask = jnp.ones((10,), bool)
    count, out = compact_ids(mask, 4, 99)
    assert int(count) == 10
    assert np.asarray(out).tolist() == [0, 1, 2, 3]


def test_claim_dedup_single_winner_and_reset_idempotent():
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    n = 64
    lanes = 48
    claim = jnp.full((n + 2,), CLAIM_SENTINEL, jnp.int32)
    # heavy duplication: many lanes race on few keys; pad lanes carry
    # the out-of-band key n+1 (the BFS usage), masked out by validity
    keys_np = rng.integers(0, 8, lanes).astype(np.int32)
    keys_np[rng.random(lanes) < 0.3] = n + 1
    keys = jnp.asarray(keys_np)
    ticket = jnp.arange(lanes, dtype=jnp.int32)
    claim, won = claim_dedup(claim, keys, ticket)
    winner = np.asarray(won) & (keys_np <= n)
    for k in np.unique(keys_np[keys_np <= n]):
        at_k = winner[keys_np == k]
        assert at_k.sum() == 1, f"key {k}: {at_k.sum()} winners"
        # the minimum ticket wins (scatter-min semantics)
        assert at_k[0], f"key {k}: winner is not the min ticket"
    # reset restores the virgin state at every touched position ...
    claim = claim_reset(claim, keys)
    assert (np.asarray(claim) == CLAIM_SENTINEL).all()
    # ... and is idempotent
    claim2 = claim_reset(claim, keys)
    assert (np.asarray(claim2) == np.asarray(claim)).all()
    # a fresh dedup after the reset behaves exactly like the first
    _, won2 = claim_dedup(claim2, keys, ticket)
    assert (np.asarray(won2) == np.asarray(won)).all()


def test_claim_dedup_out_of_range_keys_never_win():
    """An out-of-range key must not report a phantom win via the
    clamped readback gather (the scatter drops it; the winner mask
    must too)."""
    import jax.numpy as jnp

    claim = jnp.full((4,), CLAIM_SENTINEL, jnp.int32)
    #          in-range, OOB high, OOB high matching last slot, negative
    keys = jnp.asarray([3, 100, 4, -7], jnp.int32)
    ticket = jnp.asarray([0, 1, 0, 2], jnp.int32)
    claim, won = claim_dedup(claim, keys, ticket)
    # lane 2 presents ticket 0 == the value lane 0 legitimately wrote
    # to the LAST slot (index 3) — the clamp would read it back equal
    assert np.asarray(won).tolist() == [True, False, False, False]
    assert np.asarray(claim).tolist() == [CLAIM_SENTINEL] * 3 + [0]


@pytest.mark.parametrize("named", [False, True], ids=["positions", "ids"])
@pytest.mark.parametrize("seed", range(3))
def test_banded_frontier_matches_oracle(seed, named):
    """``named``: the band over a LIST of candidates (``ids``: the
    vertices the positions stand for, ascending), whose members come
    out under those names with the masses and bounds of the positions."""
    import jax.numpy as jnp

    rng = np.random.default_rng(200 + seed)
    L = int(rng.integers(10, 1500))
    cap = int(rng.integers(4, L + 20))
    k_max = int(rng.integers(1, 12))
    budget = int(rng.integers(1, 300))
    mask = rng.random(L) < rng.random()
    mass = rng.integers(0, 40, L).astype(np.int32)
    names = np.sort(rng.choice(10 * L, L, replace=False)).astype(np.int32) \
        if named else np.arange(L, dtype=np.int32)
    fill = 10 * L if named else L
    nf, m8, overflow, flist, bounds = banded_frontier(
        jnp.asarray(mask), jnp.asarray(mass), cap, k_max, budget, fill,
        **({"ids": jnp.asarray(names)} if named else {}))
    # oracle: nonzero-compacted list, cumsum + searchsorted bounds
    idx = np.nonzero(mask)[0][:cap]
    ref_list = np.full((cap,), fill, np.int32)
    ref_list[: len(idx)] = names[idx]
    ref_mass = np.zeros((cap,), np.int64)
    ref_mass[: len(idx)] = mass[idx]
    cmass = np.cumsum(ref_mass)
    targets = np.arange(1, k_max + 1) * budget
    ref_bounds = np.concatenate(
        [[0], np.minimum(np.searchsorted(cmass, targets, side="right"),
                         cap)])
    assert int(nf) == len(idx)
    assert int(m8) == int(cmass[-1])
    assert int(overflow) == 0
    assert (np.asarray(flist) == ref_list).all()
    assert (np.asarray(bounds) == ref_bounds).all()
    # segment sanity: bounds are monotone list positions
    assert (np.diff(np.asarray(bounds)) >= 0).all()


def test_banded_frontier_flags_int32_mass_overflow():
    """A point-mass band whose listed chunk mass exceeds int32 must be
    DETECTED, not silently wrapped into corrupt segment bounds (ADVICE
    r5 #3). Without x64 the cumsum wraps — the monotonicity break sets
    the overflow flag; the host refuses the round (_frontier_run)."""
    import jax
    import jax.numpy as jnp

    if jax.config.jax_enable_x64:
        pytest.skip("x64 accumulates in int64 — wrap impossible")
    mask = jnp.ones((4,), bool)
    mass = jnp.full((4,), 1 << 30, jnp.int32)    # 2^32 total: wraps
    _, _, overflow, _, _ = banded_frontier(mask, mass, 4, 2, 100, 4)
    assert int(overflow) != 0
    # the sane-mass case on the same shapes stays clean
    _, _, ok_flag, _, _ = banded_frontier(
        mask, jnp.full((4,), 3, jnp.int32), 4, 2, 100, 4)
    assert int(ok_flag) == 0


def test_op_scan_ban_auto_discovers_the_tree():
    """Op-scan regression guard (ISSUE r6, generalized in ISSUE 15):
    n-wide ``jnp.nonzero`` is banned — every compaction goes through
    ops.compaction. The guard used to be a hand-maintained module list
    with per-directory count pins here that every PR had to bump;
    it is now graftlint rule R1 (tools/graftlint, scope
    ``titan_tpu/``), which AUTO-DISCOVERS the tree. This test keeps the
    coverage contract explicit: the walk must still reach every
    previously-pinned directory, and the reference-model exemption
    (bfs.py — not a round-loop hot path) must be a VISIBLE file-level
    suppression, not a blind spot."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.graftlint.engine import Linter

    result = Linter(root=repo).run(["titan_tpu"])
    assert [f"{f.path}:{f.line}: {f.message}"
            for f in result.unsuppressed
            if f.rule == "opscan"] == []
    # auto-discovery really covered every directory the old pins named
    # (plus anything newer — no count to bump ever again)
    scanned = set(result.files)
    for must in ("titan_tpu/models/frontier.py",
                 "titan_tpu/models/bfs_hybrid.py",
                 "titan_tpu/models/bfs_hybrid_sharded.py",
                 "titan_tpu/ops/epoch_merge.py"):
        assert must in scanned, must
    for pkg in ("titan_tpu/olap/serving/",
                "titan_tpu/olap/serving/interactive/",
                "titan_tpu/olap/recovery/", "titan_tpu/olap/live/",
                "titan_tpu/obs/", "titan_tpu/parallel/",
                # ISSUE 19: the fleet tier joined with zero config
                "titan_tpu/olap/fleet/"):
        assert any(p.startswith(pkg) for p in scanned), pkg
    # the exemption stays visible: suppressed findings with reasons
    exempt = [f for f in result.findings
              if f.rule == "opscan" and f.suppressed == "file"]
    assert {f.path for f in exempt} == {"titan_tpu/models/bfs.py"}


def test_op_scan_ban_covers_new_subdirectories_zero_config(tmp_path):
    """The reason the pins died: a brand-new ``titan_tpu/`` subsystem
    directory must be inside the ban the moment it exists, with no
    list to extend and no count to bump."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.graftlint.engine import Linter

    pkg = tmp_path / "titan_tpu" / "brand_new_subsystem" / "deeper"
    pkg.mkdir(parents=True)
    (pkg / "kernels.py").write_text(
        "import jax.numpy as jnp\n\n"
        "def scan(mask):\n"
        "    return jnp.nonzero(mask)[0]\n")
    # ISSUE 19 regression: the fleet tier landed as a NEW directory —
    # pin that the walk needs no config change for exactly that shape
    # (a fresh package under an existing olap/ parent)
    fleet = tmp_path / "titan_tpu" / "olap" / "fleet"
    fleet.mkdir(parents=True)
    (fleet / "router.py").write_text(
        "import jax.numpy as jnp\n\n"
        "def pick(mask):\n"
        "    return jnp.nonzero(mask)[0]\n")
    result = Linter(root=str(tmp_path)).run(["titan_tpu"])
    assert len(result.unsuppressed) == 2
    assert {(f.rule, f.path) for f in result.unsuppressed} == {
        ("opscan", "titan_tpu/brand_new_subsystem/deeper/kernels.py"),
        ("opscan", "titan_tpu/olap/fleet/router.py")}


def _wide_ops(jaxpr, width: int) -> list:
    """``(primitive, elements)`` of every cumsum (its operand) and
    scatter (its updates) at or above ``width``, sub-programs (cond
    branches, inner jits) included."""
    import jax

    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith("cum"):
            size = int(np.prod(eqn.invars[0].aval.shape))
        elif name.startswith("scatter"):
            size = int(np.prod(eqn.invars[2].aval.shape))
        else:
            size = 0
        if size >= width:
            found.append((name, size))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _wide_ops(sub, width)
    return found


@pytest.mark.parametrize("K", [1, 16])
@pytest.mark.parametrize("expand", [False, True], ids=["bfs", "hops"])
def test_op_scan_a_push_from_a_list_holds_nothing_n_wide(K, expand):
    """The op scan of the PROGRAM, not of its source (ISSUE 29): a push
    that takes its frontier as a pair list and hands on the next one
    holds no cumsum and no scatter as wide as n — a compaction costs
    its input's width whatever it finds (7 ms a million on a v5e), and
    the level loop's n-wide ones were most of a point query. Its widest
    are the scatter's own 8 x p_cap. The scan road's listing, the
    control, holds the n-wide compaction this one is rid of."""
    import functools

    import jax
    import jax.numpy as jnp

    from titan_tpu.models import bfs_hybrid as bh

    n, q, p_cap = 1 << 12, 9000, 1 << 6
    caps = (p_cap, 1 << 12)
    i32, b = jnp.int32, jnp.bool_
    S = jax.ShapeDtypeStruct
    scalar = S((), i32)
    push = jax.make_jaxpr(
        functools.partial(bh._batched_td().__wrapped__, p_cap=p_cap,
                          n_=n, expand=expand, lists=True))(
        S((K, n + 1), i32), S((caps[-1],), i32), S((caps[-1],), i32),
        scalar, S((K,), b), scalar, scalar, S((8, q), i32),
        S((n + 1,), i32), S((n + 1,), i32))
    assert _wide_ops(push.jaxpr, n) == []
    assert max(size for _name, size in _wide_ops(push.jaxpr, 1)) \
        == 8 * p_cap
    listing = jax.make_jaxpr(
        functools.partial(bh._batched_list().__wrapped__, caps=caps,
                          n_=n))(
        S((K, n + 1), i32), S((K,), b), scalar, scalar, S((n + 1,), i32))
    assert _wide_ops(listing.jaxpr, n)


@pytest.mark.parametrize("seed", [3, 11])
def test_sssp_delta_band_plan_differential(seed):
    """The delta-stepping path now runs through the same banded plan as
    quantile/plain (r6 unification) — all three modes must agree with
    each other bit-for-bit on the final distances."""
    from titan_tpu.models.frontier import frontier_sssp
    from titan_tpu.olap.tpu import snapshot as snap_mod

    rng = np.random.default_rng(seed)
    n, m = 180, 700
    src = rng.integers(0, n, m).astype(np.int32)
    dst = rng.integers(0, n, m).astype(np.int32)
    snap = snap_mod.from_arrays(n, np.concatenate([src, dst]),
                                np.concatenate([dst, src]))
    source = int(np.flatnonzero(snap.out_degree > 0)[0])
    plain, _ = frontier_sssp(snap, source, quantile_mass=0)
    delta, _ = frontier_sssp(snap, source, delta=0.25)
    quant, _ = frontier_sssp(snap, source, quantile_mass=64)
    assert np.asarray(delta) == pytest.approx(np.asarray(plain),
                                              rel=1e-6)
    assert np.asarray(quant) == pytest.approx(np.asarray(plain),
                                              rel=1e-6)
