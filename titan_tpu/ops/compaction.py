"""Shared p-scale stream-compaction primitives for round-loop kernels.

Every host-driven round loop in the traversal models needs the same
operation: turn a boolean mask (new frontier members, surviving
candidates, in-band vertices) into a dense list sized to a static cap.
``jnp.nonzero(mask, size=cap)`` does that, but XLA lowers it through a
sort-flavored path whose cost scales with the MASK length, not the
output: an n-wide nonzero measured ~0.9s at scale 26 (n = 2^26) on a
v5e — paid once per round regardless of how sparse the frontier is
(PERF_NOTES.md, SSSP floor analysis). That is the classic
scan-then-scatter stream compaction problem (Merrill, Garland &
Grimshaw, "Scalable GPU Graph Traversal", PPoPP 2012), and the scan
formulation is strictly cheaper on TPU too: one mask cumsum feeding
scatters measured 1.76s -> 1.07s on the scale-26 bottom-up candidate
build when it replaced nonzero + a 268MB-table gather (r5).

Three primitives, all shape-static and traceable inside jit:

* ``scatter_compact`` — cumsum-fed shared-index multi-scatter: ONE mask
  cumsum computes every survivor's output slot, then each payload is
  scattered through the SAME index vector. XLA fuses scatters with
  identical indices, so compacting k payloads costs one pass — and
  payloads are read CONTIGUOUSLY (elementwise), which is what lets
  callers compact a value alongside the id list instead of re-gathering
  it from an HBM-resident table afterwards (the gather-free opener
  trick, bfs_hybrid).
* ``claim_dedup`` / ``claim_reset`` — claim-array deduplication: lanes
  that scattered the same key race on a persistent claim array
  (scatter-min of the lane id), exactly one lane wins, and the claim
  entries are reset by re-scattering sentinels at the SAME positions —
  every op is p-scale, so a round loop never pays an n-wide pass to
  dedup or to clean up (the claim-dedup head, bfs_hybrid).
* ``banded_frontier`` — the segmented/banded variant: extract a priority
  band's frontier list PLUS per-member masses PLUS mass-balanced segment
  bounds in one fused pass, with no n-wide nonzero and no cap-wide
  random gather. The listed-mass cumsum accumulates in int64 when x64
  is enabled and carries an explicit overflow flag otherwise, so a
  pathological point-mass band can never silently corrupt the segment
  bounds (ADVICE r5 #3).

Contract shared by all compactions here (bit-equal to the
``jnp.nonzero(mask, size=cap, fill_value=fill)`` formulation they
replace): survivors keep ascending input order, slots past the survivor
count hold the fill value, and survivors past ``cap`` are dropped.

n-wide ``jnp.nonzero`` is BANNED inside per-round loops — reach for one
of these instead (docs/performance.md has the decision table; an op-scan
test enforces the ban on the frontier/bfs_hybrid round kernels).
"""

from __future__ import annotations

CLAIM_SENTINEL = 2**31 - 1


def scatter_compact(mask, payloads, cap: int, fills):
    """Compact ``payloads`` by ``mask`` into ``cap``-sized outputs.

    ``mask`` [L] bool; each payload [L] is read elementwise (contiguous
    — never a gather). Returns ``(count, outs)`` where ``count`` is the
    TOTAL number of set mask bits (may exceed ``cap``; survivors beyond
    cap are dropped) and ``outs[k][i]`` holds payload k's value at the
    i-th set position for i < min(count, cap), ``fills[k]`` elsewhere.

    One cumsum computes the shared target index; the per-payload
    scatters all use it, so XLA fuses them into a single pass. Dead
    lanes target slot ``cap`` and are dropped by the scatter — there is
    no branch, no sort, and no dependence of cost on sparsity.
    """
    import jax.numpy as jnp

    cs = jnp.cumsum(mask.astype(jnp.int32))
    count = cs[-1]
    tgt = jnp.where(mask, cs - 1, cap)
    outs = tuple(
        jnp.full((cap,), fill, p.dtype).at[tgt].set(p, mode="drop")
        for p, fill in zip(payloads, fills))
    return count, outs


def compact_ids(mask, cap: int, fill):
    """Dense ascending index list of ``mask``'s set positions —
    bit-equal to ``jnp.nonzero(mask, size=cap, fill_value=fill)[0]``
    (int32) without the nonzero. Returns ``(count, ids)``."""
    import jax.numpy as jnp

    ids = jnp.arange(mask.shape[0], dtype=jnp.int32)
    count, (out,) = scatter_compact(mask, (ids,), cap, (fill,))
    return count, out


def claim_dedup(claim, keys, ticket):
    """Scatter-claim deduplication: among all lanes presenting the same
    key, exactly one wins (the minimum ``ticket``). Returns
    ``(claim, winner)`` with the claims applied; ``winner`` has the
    shape of ``keys``. Out-of-range keys drop and never win (the
    scatter drops them; the winner check masks them — the readback
    gather alone would CLAMP an out-of-range key onto the last claim
    slot and could report a phantom win). Callers still mask semantic
    validity on top (e.g. ``winner & (keys <= n)``). Every op is
    keys-scale.

    The claim array must hold ``CLAIM_SENTINEL`` at every key this call
    touches (the virgin state, or the state ``claim_reset`` restores) —
    tickets are compared against leftovers otherwise.
    """
    claim = claim.at[keys].min(ticket, mode="drop")
    won = (claim[keys] == ticket) & (keys >= 0) \
        & (keys < claim.shape[0])
    return claim, won


def claim_reset(claim, keys, sentinel: int = CLAIM_SENTINEL):
    """Re-scatter ``sentinel`` at every position ``keys`` touched,
    restoring the virgin claim state without an array-wide pass —
    idempotent, keys-scale. Pair every ``claim_dedup`` with one reset
    over the SAME keys before the next dedup round."""
    import jax.numpy as jnp

    return claim.at[keys].set(jnp.int32(sentinel), mode="drop")


def banded_frontier(mask, mass, cap: int, k_max: int, budget: int,
                    fill, ids=None):
    """Band extraction for priority-batched schedulers: compact the
    member ids AND their per-member masses in one shared-index double
    scatter (no cap-wide ``mass[list]`` re-gather), then cut the listed
    mass into ~``budget``-sized segments.

    ``mask`` [L] selects the band, ``mass`` [L] is each item's weight
    (chunks) read contiguously; ``ids`` [L] names the items (ascending;
    default: their positions — a band over a LIST of candidates hands
    the list itself, so the members come out as vertex ids). Returns ``(nf, m8, overflow, flist,
    bounds)``: ``nf`` listed members (min(count, cap)), ``m8`` their
    total mass (int32, clamped), ``overflow`` nonzero iff the mass
    cumsum wrapped int32 (accumulation runs in int64 when x64 is
    enabled; without it the wrap is DETECTED — nonnegative masses make
    the first wrap land negative — and flagged so the host can refuse
    the corrupt bounds instead of pushing garbage segments), ``flist``
    [cap] member ids (ascending, ``fill`` past nf), ``bounds``
    [k_max+1] list positions such that segment k =
    flist[bounds[k]:bounds[k+1]] carries ~budget mass (a straddling
    member lands wholly in its segment).
    """
    import jax
    import jax.numpy as jnp

    if ids is None:
        ids = jnp.arange(mask.shape[0], dtype=jnp.int32)
    count, (flist, mlist) = scatter_compact(
        mask, (ids, mass), cap, (fill, 0))
    nf = jnp.minimum(count, cap)
    acc_dt = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    cmass = jnp.cumsum(mlist.astype(acc_dt))
    total = cmass[-1]
    # masses are nonnegative int32, so the FIRST int32 wrap always
    # lands in (-2^31, 0): a negative prefix IS the overflow signal.
    # (A diff-based monotonicity check would NOT work — the wrapped
    # difference folds back to the positive mass value.)
    overflow = (cmass < 0).any().astype(jnp.int32)
    m8 = jnp.minimum(total, jnp.asarray(2**31 - 1, acc_dt)) \
        .astype(jnp.int32)
    targets = (jnp.arange(1, k_max + 1, dtype=jnp.int32)
               * jnp.int32(budget)).astype(acc_dt)
    bounds = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.minimum(jnp.searchsorted(cmass, targets, side="right"),
                     cap).astype(jnp.int32)])
    return nf, m8, overflow, flist, bounds
