"""R2 — host syncs inside registered jitted kernels.

Historical bug: a ``.item()`` / ``int(...)`` coercion or a Python
``if`` on a traced value inside a kernel forces a device->host
round trip per dispatch, and they don't pipeline. The kernels are found by following the
``jit_once`` / ``mesh_jit`` registration call sites (tools/graftlint/
jitgraph.py), NOT by name heuristics; parameters listed in
static_argnames/static_argnums are compile-time constants and stay
fair game for Python control flow.

``x.shape`` / ``x.ndim`` / ``x.dtype`` off a traced array are static
metadata — expressions that only touch those are never flagged.
"""

from __future__ import annotations

import ast
from typing import Iterator

from tools.graftlint.engine import Finding, Rule
from tools.graftlint.jitgraph import jitted_functions

_STATIC_ATTRS = {"shape", "ndim", "dtype"}
_COERCIONS = {"int", "float", "bool"}


def _refs_traced(node, traced) -> bool:
    """Does this expression read a traced parameter (outside static
    .shape/.ndim/.dtype metadata access)?"""
    if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
        return False
    if isinstance(node, ast.Name):
        return node.id in traced
    return any(_refs_traced(c, traced)
               for c in ast.iter_child_nodes(node))


class HostSyncRule(Rule):
    id = "host-sync"
    alias = "R2"
    description = (".item()/int()/np.asarray/device_get/Python-if on "
                   "traced values inside jit_once/mesh_jit kernels")

    def check(self, ms, ctx) -> Iterator[Finding]:
        for jf in jitted_functions(ms):
            where = (f"kernel {jf.key!r}" if jf.key
                     else f"kernel registered at line {jf.reg_line}")
            for node in ast.walk(jf.node):
                if isinstance(node, ast.Call):
                    yield from self._check_call(ms, node, jf, where)
                elif isinstance(node, (ast.If, ast.While)):
                    if _refs_traced(node.test, jf.traced):
                        kw = ("if" if isinstance(node, ast.If)
                              else "while")
                        yield Finding(
                            rule="", path="", line=node.lineno,
                            col=node.col_offset,
                            message=f"Python `{kw}` on a traced value "
                                    f"inside {where} forces a host "
                                    "sync per dispatch — use "
                                    "lax.cond/jnp.where/lax.while_loop")

    def _check_call(self, ms, node, jf, where) -> Iterator[Finding]:
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "item" \
                and not node.args:
            yield Finding(
                rule="", path="", line=node.lineno, col=node.col_offset,
                message=f".item() inside {where} is a blocking "
                        "device->host transfer per dispatch")
            return
        canon = ms.canonical(func) or ""
        if canon == "jax.device_get":
            yield Finding(
                rule="", path="", line=node.lineno, col=node.col_offset,
                message=f"jax.device_get inside {where} is a blocking "
                        "device->host transfer")
        elif canon in ("np.asarray", "np.array"):
            yield Finding(
                rule="", path="", line=node.lineno, col=node.col_offset,
                message=f"{canon} inside {where} materializes a traced "
                        "value on host (use jnp.asarray)")
        elif isinstance(func, ast.Name) and func.id in _COERCIONS \
                and node.args \
                and _refs_traced(node.args[0], jf.traced):
            yield Finding(
                rule="", path="", line=node.lineno, col=node.col_offset,
                message=f"{func.id}() coerces a traced value inside "
                        f"{where} — a host sync per dispatch (keep it "
                        "on device, or make the argument static)")
