"""Micro-traversal → batched-frontier compilation (the interactive lane).

The OLTP-shaped read lane on the OLAP plane (ROADMAP #3): bounded-depth
Gremlin point queries — ``g.V(x).out().out().dedup().id_()``-class
chains from ``traversal/dsl.py`` — lower onto the batched ``[K, n]``
frontier machinery (``models/bfs_hybrid.frontier_bfs_batched``,
``mode="hops"``) so MANY users' micro-queries fuse into ONE device
dispatch sharing every plan and edge-chunk gather.

Semantics: hops mode computes exact per-hop frontier SETS (a vertex
reached at hop h is reached again at hop h' > h when a path exists —
what BFS levels cannot express), so the compilable subset is the
set-semantics one:

    V(id, ...)                       >= 1 explicit start id
    .out(*L) | .in_(*L) | .both(*L)  1..max_depth hops, ONE direction
                                     and ONE label set for the chain
                                     (labels select a label-filtered
                                     snapshot from the pool)
    [.repeat(<hop>).times(k)]        expands to k copies of the hop
    .dedup()                         REQUIRED — the terminal dedup is
                                     what makes set semantics equal the
                                     interpreter's bulked multiset
    .id_() | .count() | .values(k)   terminal

Everything else — mixed directions, per-hop label changes, missing
dedup (path-multiplicity counts), predicates, paths — returns ``None``
from :func:`compile_steps` and the caller falls back LOUDLY to the
``dsl.py`` interpreter (``serving.interactive.fallbacks``; the seam is
``traversal/olap_compile.FallbackToInterpreter``, raised at run time
when the leased snapshot cannot answer a compiled plan faithfully).

Direction lowering: the hops-mode sweep is bottom-up — candidate ``w``
joins the next hop when one of w's CSR chunk neighbors is in the
frontier — so ``both()`` runs on the symmetrized lease's forward CSR
(overlay-aware: the live plane's key), ``in_()`` on the directed
lease's forward CSR (w's out-neighbors ARE its in_-expansion parents),
and ``out()`` on the REVERSED layout, which is free to build: the
snapshot's dst-sorted arrays are already the in-CSR
(:func:`reversed_chunked_csr` — no argsort, one O(E) layout pass).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Union

import numpy as np

from titan_tpu.core.defs import Direction
from titan_tpu.traversal.olap_compile import FallbackToInterpreter

__all__ = ["TraversalPlan", "PPRPlan", "compile_steps",
           "compile_traversal", "plan_from_wire", "traversal_from_plan",
           "reversed_chunked_csr", "hop_label_masks",
           "FallbackToInterpreter", "DEFAULT_MAX_DEPTH"]

#: default bounded-depth ceiling (LDBC IS3 is a 4-hop; anything deeper
#: is an analytics job for the heavy queue, not a point query)
DEFAULT_MAX_DEPTH = 4

_DIR_NAMES = {"out": Direction.OUT, "in": Direction.IN,
              "both": Direction.BOTH}
_NAME_OF_DIR = {v: k for k, v in _DIR_NAMES.items()}


@dataclass(frozen=True)
class TraversalPlan:
    """One compiled point query: fuses with plans sharing
    :meth:`fuse_key` (snapshot selection — direction + labels; DEPTH is
    NOT part of the key, shallower members deactivate early through the
    kernel's per-job keep mask).

    Mixed-label chains (ISSUE 13): ``hop_labels`` — a per-hop tuple of
    label tuples (length == depth) when the chain changes labels
    between hops. ``labels`` is then the UNION (the snapshot the lane
    leases) and each hop masks the union layout down to its own set
    through the kernel's per-level slot bitmaps
    (:func:`hop_label_masks` → ``frontier_bfs_batched(level_masks=)``).
    Mixed chains fuse only with identical chains (the masks are shared
    batch-wide), so ``hop_labels`` joins the fuse key."""

    start_ids: tuple
    direction: Direction
    labels: Optional[tuple]          # None = all labels (union if mixed)
    depth: int
    terminal: Union[str, tuple]      # "id" | "count" | ("values", key)
    hop_labels: Optional[tuple] = None   # per-hop label tuples (mixed)

    def fuse_key(self) -> tuple:
        return ("traverse", self.direction, self.labels,
                self.hop_labels)

    def describe(self) -> str:
        hop = _NAME_OF_DIR[self.direction]
        if self.hop_labels is not None:
            hops = "".join(f".{hop}({','.join(ls)})"
                           for ls in self.hop_labels)
            term = self.terminal if isinstance(self.terminal, str) \
                else f"values({self.terminal[1]})"
            return (f"V({','.join(str(i) for i in self.start_ids)})"
                    f"{hops}.dedup().{term}")
        labs = ",".join(self.labels) if self.labels else ""
        term = self.terminal if isinstance(self.terminal, str) \
            else f"values({self.terminal[1]})"
        return (f"V({','.join(str(i) for i in self.start_ids)})"
                f".{hop}({labs})x{self.depth}.dedup().{term}")


@dataclass(frozen=True)
class PPRPlan:
    """One user's personalized-PageRank recommendation query: fuses
    with plans sharing the iteration budget / damping / snapshot
    selection into one ``[S, n]`` vmapped run
    (``models/pagerank.pagerank_personalized_batched``)."""

    source: int                      # original vertex id
    iterations: int = 20
    damping: float = 0.85
    top_k: int = 10
    labels: Optional[tuple] = None
    directed: bool = False
    include_source: bool = False

    def fuse_key(self) -> tuple:
        return ("ppr", self.iterations, round(float(self.damping), 9),
                self.labels, self.directed)

    def describe(self) -> str:
        return (f"ppr({self.source}, it={self.iterations}, "
                f"d={self.damping}, top{self.top_k})")


def _expand_hops(steps: list, i: int, max_depth: int):
    """Consume the hop run at ``steps[i:]``: plain vsteps and
    repeat(<single vstep>).times(k). Returns (hops, next_i) or None."""
    hops: list = []
    while i < len(steps):
        name, args = steps[i][0], steps[i][1]
        if name == "vstep":
            direction, labels, kind = args
            if kind != "vertex":
                return None
            hops.append((direction, tuple(labels)))
            i += 1
        elif name == "repeat" and i + 1 < len(steps) \
                and steps[i + 1][0] == "times":
            sub, times = args[0], steps[i + 1][1][0]
            body = []
            for sname, sargs in sub._steps:
                if sname != "vstep" or sargs[2] != "vertex":
                    return None
                body.append((sargs[0], tuple(sargs[1])))
            if times < 1:
                return None
            hops.extend(h for _ in range(times) for h in body)
            i += 2
        else:
            break
        if len(hops) > max_depth:
            return None
    return hops, i


def compile_steps(steps: list,
                  max_depth: int = DEFAULT_MAX_DEPTH
                  ) -> Optional[TraversalPlan]:
    """Match a folded dsl step list against the compilable subset;
    None = interpret instead (the LOUD fallback is the caller's)."""
    if not steps or steps[0][0] != "V" or not steps[0][1]:
        return None
    got = _expand_hops(steps, 1, max_depth)
    if got is None:
        return None
    hops, i = got
    if not hops:
        return None
    directions = {h[0] for h in hops}
    label_sets = {h[1] for h in hops}
    if len(directions) != 1:
        # mixed directions would need a different CSR orientation per
        # level — the interpreter's job
        return None
    hop_labels = None
    if len(label_sets) != 1:
        # per-hop label changes compile since ISSUE 13: lease the
        # UNION-label snapshot and mask each level down to its hop's
        # set through the kernel's per-level slot bitmaps — but an
        # all-labels hop (empty set) inside a labeled chain would need
        # the unfiltered snapshot, whose extra edges no union lease
        # carries; that stays with the interpreter
        if any(not h[1] for h in hops):
            return None
        hop_labels = tuple(h[1] for h in hops)
    if i >= len(steps) or steps[i][0] != "dedup":
        # no terminal dedup = path-multiplicity semantics, which a
        # frontier SET machine cannot carry (olap_compile's count
        # vectors can — that path still exists on the tpu computer)
        return None
    i += 1
    if i >= len(steps):
        return None
    name, args = steps[i][0], steps[i][1]
    if name == "count" and i == len(steps) - 1:
        terminal = "count"
    elif name == "id" and i == len(steps) - 1:
        terminal = "id"
    elif name == "values" and i == len(steps) - 1 \
            and len(args[0]) == 1:
        terminal = ("values", args[0][0])
    else:
        return None
    if hop_labels is not None:
        labels = tuple(sorted({name for ls in hop_labels
                               for name in ls}))
    else:
        labels = label_sets.pop() or None
    return TraversalPlan(tuple(steps[0][1]), directions.pop(), labels,
                         len(hops), terminal, hop_labels=hop_labels)


def compile_traversal(t, max_depth: int = DEFAULT_MAX_DEPTH
                      ) -> Optional[TraversalPlan]:
    """Compile a dsl ``Traversal`` (folds has-into-start first, exactly
    like the execution path, so ``V(ids)``-rooted chains normalize the
    same way)."""
    from titan_tpu.traversal.dsl import Traversal
    steps = Traversal._fold_has_into_start(list(t._steps))
    return compile_steps(steps, max_depth)


def plan_from_wire(body: dict):
    """Structured ``POST /traverse`` body → plan. Raises ValueError on
    malformed requests (the 400 path). Depth is NOT gated here: the
    lane's ceiling raises FallbackToInterpreter at submit, so a
    too-deep chain still answers (loudly) via the interpreter."""
    kind = body.get("kind", "traverse")
    if kind == "ppr":
        if "source" not in body:
            raise ValueError("ppr needs 'source' (vertex id)")
        iterations = int(body.get("iterations", 20))
        if not 1 <= iterations <= 1000:
            raise ValueError("iterations must be in [1, 1000], "
                             f"got {iterations}")
        damping = float(body.get("damping", 0.85))
        if not 0.0 <= damping < 1.0:
            raise ValueError(f"damping must be in [0, 1), got {damping}")
        top_k = int(body.get("top_k", 10))
        if not 1 <= top_k <= 1000:
            # a negative/huge k would answer with (almost) the whole
            # graph — a recommendation query is bounded by contract
            raise ValueError(f"top_k must be in [1, 1000], got {top_k}")
        labels = _wire_labels(body)
        return PPRPlan(int(body["source"]),
                       iterations=iterations,
                       damping=damping,
                       top_k=top_k,
                       labels=labels,
                       directed=bool(body.get("directed", False)),
                       include_source=bool(
                           body.get("include_source", False)))
    if kind != "traverse":
        raise ValueError(f"unknown interactive kind {kind!r} "
                         "(traverse | ppr)")
    start = body.get("start")
    if not isinstance(start, (list, tuple)):
        # scalar form: a bare vertex id (0 is a valid id — no falsy
        # shortcut)
        start = [start] if start is not None else []
    if not start:
        raise ValueError("traverse needs 'start': [vertex id, ...]")
    dir_name = body.get("dir", "out")
    if dir_name not in _DIR_NAMES:
        raise ValueError(f"dir must be out|in|both, got {dir_name!r}")
    hops = int(body.get("hops", 1))
    if not 1 <= hops <= 32:
        # deeper than the lane ceiling still answers (interpreter
        # fallback), but an unbounded value would build an unbounded
        # step chain host-side — 32 is already analytics territory
        raise ValueError(f"hops must be in [1, 32], got {hops}")
    term = body.get("terminal", "id")
    if isinstance(term, dict) and "values" in term:
        terminal = ("values", str(term["values"]))
    elif term in ("id", "count"):
        terminal = term
    else:
        raise ValueError("terminal must be 'id', 'count' or "
                         "{'values': <key>}")
    labels = body.get("labels")
    hop_labels = None
    if isinstance(labels, (list, tuple)) and labels \
            and all(isinstance(x, (list, tuple)) for x in labels):
        # per-hop label form: "labels": [["a"], ["b"]] — one label set
        # per hop (the mixed-label chain seam, ISSUE 13)
        if len(labels) != hops:
            raise ValueError(
                f"per-hop labels must list one set per hop "
                f"({hops}), got {len(labels)}")
        sets = []
        for ls in labels:
            if not ls or not all(isinstance(x, str) for x in ls):
                raise ValueError(
                    "each per-hop label set must be a non-empty list "
                    f"of label names, got {ls!r}")
            sets.append(tuple(ls))
        if len(set(sets)) > 1:
            hop_labels = tuple(sets)
            wire_labels = tuple(sorted({n for ls in sets for n in ls}))
        else:
            wire_labels = sets[0]
    else:
        wire_labels = _wire_labels(body)
    return TraversalPlan(tuple(int(v) for v in start),
                         _DIR_NAMES[dir_name],
                         wire_labels,
                         hops, terminal, hop_labels=hop_labels)


def _wire_labels(body: dict) -> Optional[tuple]:
    """``labels`` must be a list of names — a bare string would
    tuple() into per-character labels the snapshot build silently
    drops, answering every query from an EMPTY edge set with 200."""
    labels = body.get("labels")
    if labels is None or labels == []:
        return None
    if not isinstance(labels, (list, tuple)) \
            or not all(isinstance(x, str) for x in labels):
        raise ValueError("labels must be a list of label names, got "
                         f"{labels!r}")
    return tuple(labels)


def traversal_from_plan(plan: TraversalPlan, g):
    """Rebuild the equivalent dsl traversal (the interpreter-fallback
    executor and the bit-equality property tests both run it)."""
    t = g.V(*plan.start_ids)
    step = {"out": "out", "in": "in_", "both": "both"}[
        _NAME_OF_DIR[plan.direction]]
    if plan.hop_labels is not None:
        for ls in plan.hop_labels:
            t = getattr(t, step)(*ls)
    else:
        labels = plan.labels or ()
        for _ in range(plan.depth):
            t = getattr(t, step)(*labels)
    t = t.dedup()
    if plan.terminal == "count":
        return t.count()
    if plan.terminal == "id":
        return t.id_()
    return t.values(plan.terminal[1])


# -- reversed device layout ---------------------------------------------------

def reversed_chunked_csr(snap) -> dict:
    """Chunked CSR of the REVERSED edges — the ``out()``-expansion
    orientation (candidate w's chunks must hold w's IN-neighbors).

    Free of any sort: the snapshot's arrays are dst-sorted, so
    ``snap.src`` IS the in-CSR payload and ``snap.indptr_in`` its
    index — one O(E) layout scatter into the 8-aligned transposed
    form, cached on the snapshot (``_hybrid_csr_rev``, dropped by
    ``_invalidate_layout_caches`` with the other device layouts)."""
    cached = getattr(snap, "_hybrid_csr_rev", None)
    if cached is not None:
        return cached
    import jax.numpy as jnp

    from titan_tpu.models.bfs_hybrid import chunked_layout

    n = snap.n
    deg = np.diff(snap.indptr_in).astype(np.int64)       # in-degree
    dstT, colstart, degc, q_total = chunked_layout(
        snap.src, snap.indptr_in, deg, n)
    from titan_tpu.obs import devprof
    devprof.count_h2d("interactive.rev_csr",
                      dstT.nbytes + 3 * (n + 1) * 4)
    out = {
        "dstT": jnp.asarray(dstT),
        "colstart": jnp.asarray(colstart.astype(np.int32)),
        "degc": jnp.asarray(np.concatenate(
            [degc, [0]]).astype(np.int32)),
        "deg": jnp.asarray(np.concatenate(
            [deg, [0]]).astype(np.int32)),
        "q_total": q_total,
        "n": n,
        # columns hold a vertex's parents and not its children: the
        # batched BFS pulls at every level (bfs_hybrid._td_cap)
        "directed": True,
    }
    snap._hybrid_csr_rev = out
    return out


# -- per-hop label masks (mixed-label chains, ISSUE 13) -----------------------


def hop_label_masks(snap, plan: TraversalPlan, direction) -> list:
    """Per-hop edge-slot bitmaps for a mixed-label chain over the
    UNION-label lease: hop h's bitmap sets the bit of every slot whose
    edge label is NOT in hop h's set (1 = not a parent this level —
    the same packing as the overlay tombstone bitmap, byte = chunk
    column / bit = lane), ready for
    ``frontier_bfs_batched(level_masks=)``.

    Built on whichever layout the chain sweeps — the forward chunked
    CSR (``both``/``in_``: payload in ``out_csr`` order, labels
    permuted through the cached ``_out_csr_order``) or the REVERSED
    layout (``out()``: payload in the snapshot's native dst-sorted
    order, labels align directly). Hops sharing a label set share one
    bitmap; masks cache on the snapshot per (direction, hop chain) and
    upload once (the devprof ``interactive.label_masks`` H2D site).

    Raises FallbackToInterpreter when the lease carries no label codes
    (an unlabeled snapshot cannot answer a label-filtered chain
    faithfully)."""
    if snap.labels is None:
        raise FallbackToInterpreter(
            "mixed-label chain over a snapshot without label codes")
    cache = getattr(snap, "_hop_label_masks", None)
    if cache is None:
        cache = snap._hop_label_masks = {}
    key = (direction, plan.hop_labels)
    got = cache.get(key)
    if got is not None:
        return got
    import jax.numpy as jnp

    n = snap.n
    from titan_tpu.models.bfs_hybrid import layout_slot_positions
    if direction is Direction.OUT:
        # reversed layout: payload is snap.src in native dst-sorted
        # order — labels align 1:1
        deg = np.diff(snap.indptr_in).astype(np.int64)
        pos, colstart, _degc = layout_slot_positions(
            snap.indptr_in, deg, n)
        labs = snap.labels
    else:
        _dst_by_src, indptr_out = snap.out_csr()
        deg = snap.out_degree.astype(np.int64)
        pos, colstart, _degc = layout_slot_positions(
            indptr_out, deg, n)
        labs = snap.labels[snap._out_csr_order]
    q_total = int(colstart[-1]) + 1
    name_of = snap.label_names
    code_of = {v: k for k, v in name_of.items()}
    masks: list = []
    by_set: dict = {}
    total_bytes = 0
    for ls in plan.hop_labels:
        dev = by_set.get(ls)
        if dev is None:
            codes = [code_of[name] for name in ls if name in code_of]
            dead = ~np.isin(labs, np.asarray(codes, np.int32))
            tomb = np.zeros(q_total, np.uint8)
            p = pos[dead]
            np.bitwise_or.at(tomb, p >> 3,
                             np.uint8(1) << (p & 7).astype(np.uint8))
            dev = jnp.asarray(tomb)
            by_set[ls] = dev
            total_bytes += tomb.nbytes
        masks.append(dev)
    if total_bytes:
        from titan_tpu.obs import devprof
        devprof.count_h2d("interactive.label_masks", total_bytes)
    cache[key] = masks
    return masks
