"""R1 — the op-scan ban (docs/performance.md, ISSUE r6).

Historical bug: data-dependent ``jnp.nonzero`` scans sneaking back
into per-round kernels. XLA lowers them through an n-wide sort (or a
host sync for the unbounded form); ops/compaction.py exists precisely
so no kernel pays that. The old guard was a hand-maintained module
list with per-directory count pins in tests/test_compaction.py; this
rule auto-discovers every ``titan_tpu/`` module instead.

Two tiers:

* ``jnp.nonzero`` / ``jnp.flatnonzero`` / ``jnp.argwhere`` are banned
  OUTRIGHT (size= or not) — bounded forms must go through
  ops.compaction so the contract stays in one place. The
  non-round-loop reference model (models/bfs.py) carries a file-level
  suppression.
* the METHOD spellings ``x.nonzero()`` / ``x.flatnonzero()`` are the
  same op-scan wearing an attribute — banned too (the tree's host-side
  idiom is the ``np.nonzero(...)`` function form, which stays legal);
* ``jnp.unique`` and single-argument ``jnp.where`` (with or without
  ``size=`` — the sized form is ``jnp.nonzero(size=)`` renamed) are
  banned everywhere.
* boolean-mask indexing (``arr[mask > 0]``) inside a registered jitted
  kernel is a data-dependent gather — banned (``.at[mask]`` scatter
  updates are fixed-shape and stay legal).
"""

from __future__ import annotations

import ast
from typing import Iterator

from tools.graftlint.engine import Finding, Rule
from tools.graftlint.jitgraph import jitted_functions

_HARD_BANNED = {"jnp.nonzero", "jnp.flatnonzero", "jnp.argwhere"}


def _canon(ms, func) -> str:
    d = ms.canonical(func) or ""
    # `import jax` modules reach jax.numpy.X without a jnp alias
    if d.startswith("jax.numpy."):
        d = "jnp." + d[len("jax.numpy."):]
    return d


def _is_bool_mask(node) -> bool:
    if isinstance(node, (ast.Compare, ast.BoolOp)):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Invert):
        return _is_bool_mask(node.operand)
    return False


class OpScanRule(Rule):
    id = "opscan"
    alias = "R1"
    description = ("n-wide jnp op-scans (nonzero/flatnonzero/unique/"
                   "1-arg where) and boolean-mask indexing in kernels "
                   "— use ops.compaction")

    def check(self, ms, ctx) -> Iterator[Finding]:
        for node in ast.walk(ms.tree):
            if not isinstance(node, ast.Call):
                continue
            name = _canon(ms, node.func)
            if name in _HARD_BANNED:
                sized = any(k.arg == "size" for k in node.keywords)
                how = ("bounded, but the op-scan contract lives in "
                       "ops.compaction — use compact_ids/scatter_compact"
                       if sized else
                       "unbounded: data-dependent output shape")
                yield Finding(
                    rule="", path="", line=node.lineno,
                    col=node.col_offset,
                    message=f"{name} is banned in titan_tpu/ ({how})")
            elif name == "jnp.unique":
                yield Finding(
                    rule="", path="", line=node.lineno,
                    col=node.col_offset,
                    message="jnp.unique is banned: data-dependent "
                            "output shape (sort + scan per call)")
            elif name == "jnp.where" and len(node.args) == 1:
                sized = any(k.arg == "size" for k in node.keywords)
                yield Finding(
                    rule="", path="", line=node.lineno,
                    col=node.col_offset,
                    message="single-argument jnp.where is jnp.nonzero "
                            "in disguise ("
                            + ("bounded by size=, but the op-scan "
                               "contract lives in ops.compaction"
                               if sized else "unbounded op-scan")
                            + ") — use compact_ids/scatter_compact")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("nonzero", "flatnonzero") \
                    and not node.args and not node.keywords:
                yield Finding(
                    rule="", path="", line=node.lineno,
                    col=node.col_offset,
                    message=f".{node.func.attr}() method call is the "
                            "same op-scan as the banned function form "
                            "— use ops.compaction (host code uses the "
                            "np.nonzero(...) function spelling)")
        # boolean-mask indexing only means a data-dependent gather when
        # the array is traced — check inside registered kernels only
        for jf in jitted_functions(ms):
            for node in ast.walk(jf.node):
                if not isinstance(node, ast.Subscript):
                    continue
                if isinstance(node.value, ast.Attribute) \
                        and node.value.attr == "at":
                    continue    # .at[mask].set() is a fixed-shape scatter
                if _is_bool_mask(node.slice):
                    yield Finding(
                        rule="", path="", line=node.lineno,
                        col=node.col_offset,
                        message="boolean-mask indexing inside a jitted "
                                "kernel is a data-dependent gather — "
                                "compact through ops.compaction (kernel "
                                f"registered at line {jf.reg_line})")
