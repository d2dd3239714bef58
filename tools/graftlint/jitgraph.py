"""Resolve the jitted kernels a module registers — by call site.

R1's boolean-mask check and R2's host-sync checks only make sense
inside code that is actually TRACED. Name heuristics ("looks like a
kernel") rot; the repo has exactly three registration seams every
traced kernel flows through — ``utils/jitcache.jit_once(key,
builder)``, ``parallel/mesh.mesh_jit(name, mesh, builder, ...)`` and
``pl.pallas_call(kernel, ...)`` — so this module follows those call
sites instead:

    registration call -> builder (local def or lambda)
                      -> the callable the builder returns
                      -> through jax.jit / functools.partial(jax.jit)
                         / shard_map wrappers, collecting
                         static_argnames / static_argnums on the way

The resolved function's non-static parameters are the traced values.
Pallas kernels invert the convention: ``pallas_call`` passes only the
refs, positionally, so the kernel's POSITIONAL parameters are the
traced refs while keyword-only parameters (bound through
``functools.partial`` at the call site) are compile-time constants —
Python control flow on them is legal and expected
(a ``while d < block`` unroll ladder).
Resolution is best-effort and PURELY lexical: a builder whose return
can't be followed (e.g. mesh.py's own generic ``builder(mesh)``
trampoline) contributes nothing rather than guessing.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Optional

_FN = (ast.FunctionDef, ast.AsyncFunctionDef)
_SCOPES = _FN + (ast.Lambda,)


@dataclasses.dataclass
class JittedFn:
    node: ast.AST            # FunctionDef / Lambda — the traced body
    traced: frozenset        # parameter names traced at call time
    reg_line: int            # the jit_once/mesh_jit call that owns it
    key: Optional[str]       # registration key when it's a literal


def jitted_functions(ms) -> list:
    """All jitted kernels registered by this module (cached on
    ``ms.cache`` so R1 and R2 share one resolution pass)."""
    got = ms.cache.get("jitted")
    if got is None:
        got = _Resolver(ms).resolve()
        ms.cache["jitted"] = got
    return got


def walk_no_nested_fns(body):
    """Yield nodes of ``body`` statements without entering nested
    function/lambda scopes (lexical-only traversals)."""
    stack = list(body)
    while stack:
        node = stack.pop()
        if isinstance(node, _SCOPES):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


class _Resolver:
    def __init__(self, ms):
        self.ms = ms
        # id(scope node) -> {name: FunctionDef} for defs bound
        # directly in that scope (module, function, or lambda)
        self.defs: dict = {}
        # id(scope node) -> {name: value expr} for single-target
        # assignments (follows `kern = functools.partial(...)` locals)
        self.assigns: dict = {}
        self.reg_calls: list = []   # (Call, scope chain)
        self._index(ms.tree, (ms.tree,))

    def _index(self, node, chain) -> None:
        scope = chain[-1]
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FN):
                self.defs.setdefault(id(scope), {})[child.name] = child
                self._index(child, chain + (child,))
            elif isinstance(child, ast.Lambda):
                self._index(child, chain + (child,))
            else:
                if isinstance(child, ast.Assign) \
                        and len(child.targets) == 1 \
                        and isinstance(child.targets[0], ast.Name):
                    self.assigns.setdefault(id(scope), {})[
                        child.targets[0].id] = child.value
                if isinstance(child, ast.Call):
                    kind = self._reg_kind(child.func)
                    if kind:
                        self.reg_calls.append((child, chain, kind))
                self._index(child, chain)

    def _reg_kind(self, func) -> Optional[str]:
        d = self.ms.dotted(func)
        if not d:
            return None
        last = d.rsplit(".", 1)[-1]
        if last == "jit_once" or d in self.ms.jitonce_names:
            return "jit_once"
        if last == "mesh_jit" or d in self.ms.meshjit_names:
            return "mesh_jit"
        if last == "pallas_call":
            return "pallas_call"
        return None

    # -- scope-chain name lookup ------------------------------------------

    def _find_def(self, name: str, chain):
        for scope in reversed(chain):
            got = self.defs.get(id(scope), {}).get(name)
            if got is not None:
                return got
        return None

    def _find_assign(self, name: str, chain):
        for scope in reversed(chain):
            got = self.assigns.get(id(scope), {}).get(name)
            if got is not None:
                return got
        return None

    # -- jit-wrapper unwrapping -------------------------------------------

    def _unwrap_call(self, call: ast.Call, chain):
        """(fn node, statics) for jax.jit(f, ...) / partial(jax.jit,
        ...) / shard_map(f, ...) expressions; (None, set()) when the
        wrapper isn't one we know."""
        d = self.ms.canonical(call.func) or ""
        last = d.rsplit(".", 1)[-1]
        statics = _static_names(call)
        target = None
        if last == "jit" and call.args:
            target = call.args[0]
        elif last == "partial" and len(call.args) >= 2:
            inner = self.ms.canonical(call.args[0]) or ""
            if inner.rsplit(".", 1)[-1] == "jit":
                target = call.args[1]
        elif last == "shard_map" and call.args:
            target = call.args[0]
        if target is None:
            return None, statics
        fn = self._as_callable(target, chain)
        if fn is not None:
            # static_argnums on the wrapper CALL resolve to names here,
            # where the function's positional order is known
            statics = statics | _static_nums_to_names(call, fn)
        return fn, statics

    def _as_callable(self, node, chain):
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Name):
            return self._find_def(node.id, chain)
        return None

    def _returned_callable(self, builder, chain):
        """Follow a builder FunctionDef to the callable it returns."""
        b_chain = chain + (builder,)
        for node in walk_no_nested_fns(builder.body):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            val = node.value
            if isinstance(val, (ast.Name, ast.Lambda)):
                fn = self._as_callable(val, b_chain)
                if fn is not None:
                    return fn, set()
            elif isinstance(val, ast.Call):
                fn, statics = self._unwrap_call(val, b_chain)
                if fn is not None:
                    return fn, statics
        return None, set()

    # -- pallas_call kernels ----------------------------------------------

    def _pallas_kernel(self, call: ast.Call, chain):
        """(kernel fn node, statics) for ``pl.pallas_call(kern, ...)``:
        arg0 as a def/lambda, a ``functools.partial(kernel, **consts)``
        binding compile-time keywords, or a local name assigned one of
        those."""
        target = _arg(call, 0, "kernel")
        statics: set = set()
        for _hop in range(4):
            if not isinstance(target, ast.Name):
                break
            fn = self._find_def(target.id, chain)
            if fn is not None:
                return fn, statics
            target = self._find_assign(target.id, chain)
        if isinstance(target, ast.Lambda):
            return target, statics
        if isinstance(target, ast.Call):
            d = (self.ms.canonical(target.func) or "").rsplit(".", 1)[-1]
            if d == "partial" and target.args:
                statics |= {k.arg for k in target.keywords if k.arg}
                inner = target.args[0]
                if isinstance(inner, ast.Lambda):
                    return inner, statics
                if isinstance(inner, ast.Name):
                    fn = self._find_def(inner.id, chain)
                    if fn is not None:
                        return fn, statics
        return None, statics

    # -- entry -------------------------------------------------------------

    def resolve(self) -> list:
        out: list = []
        seen: set = set()
        for call, chain, kind in self.reg_calls:
            if kind == "pallas_call":
                fn, statics = self._pallas_kernel(call, chain)
                if fn is None or id(fn) in seen:
                    continue
                seen.add(id(fn))
                # only the positional refs are traced: keyword-only
                # params never receive refs through pallas_call
                out.append(JittedFn(
                    node=fn,
                    traced=frozenset(
                        set(_positional_params(fn)) - statics),
                    reg_line=call.lineno,
                    key=None))
                continue
            is_mesh = kind == "mesh_jit"
            builder = _arg(call, 2 if is_mesh else 1, "builder")
            if builder is None:
                continue
            statics = _static_names(call) if is_mesh else set()
            fn = None
            if isinstance(builder, ast.Lambda):
                body = builder.body
                if isinstance(body, ast.Call):
                    fn, s2 = self._unwrap_call(body, chain)
                    statics |= s2
                else:
                    fn = self._as_callable(body, chain)
            elif isinstance(builder, ast.Name):
                b = self._find_def(builder.id, chain)
                if b is not None:
                    fn, s2 = self._returned_callable(b, chain)
                    statics |= s2
            if fn is None or id(fn) in seen:
                continue
            seen.add(id(fn))
            statics |= _decorator_statics(self.ms, fn)
            out.append(JittedFn(
                node=fn,
                traced=frozenset(_param_names(fn) - statics),
                reg_line=call.lineno,
                key=_literal_key(call)))
        return out


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _arg(call: ast.Call, pos: int, kw: str):
    if len(call.args) > pos:
        return call.args[pos]
    for k in call.keywords:
        if k.arg == kw:
            return k.value
    return None


def _literal_key(call: ast.Call) -> Optional[str]:
    if call.args and isinstance(call.args[0], ast.Constant) \
            and isinstance(call.args[0].value, str):
        return call.args[0].value
    return None


def _const_str_seq(node) -> set:
    out: set = set()
    if isinstance(node, (ast.Tuple, ast.List)):
        elts = node.elts
    elif isinstance(node, ast.Constant) and isinstance(node.value, str):
        elts = [node]
    else:
        return out
    for e in elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, str):
            out.add(e.value)
    return out


def _static_names(call: ast.Call) -> set:
    """static_argnames off a jit/mesh_jit call (static_argnums are
    resolved to names later, at the function, where positions exist)."""
    out: set = set()
    for k in call.keywords:
        if k.arg == "static_argnames":
            out |= _const_str_seq(k.value)
    return out


def _positional_params(fn) -> list:
    if isinstance(fn, ast.Lambda):
        a = fn.args
    else:
        a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _param_names(fn) -> set:
    a = fn.args
    return {p.arg for p in (*a.posonlyargs, *a.args, *a.kwonlyargs)}


def _static_nums_to_names(call: ast.Call, fn) -> set:
    pos = _positional_params(fn)
    out: set = set()
    for k in call.keywords:
        if k.arg != "static_argnums":
            continue
        nums = []
        if isinstance(k.value, ast.Constant) \
                and isinstance(k.value.value, int):
            nums = [k.value.value]
        elif isinstance(k.value, (ast.Tuple, ast.List)):
            nums = [e.value for e in k.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, int)]
        for n in nums:
            if 0 <= n < len(pos):
                out.add(pos[n])
    return out


def _decorator_statics(ms, fn) -> set:
    """static_argnames/static_argnums from @jax.jit /
    @functools.partial(jax.jit, ...) decorators."""
    if isinstance(fn, ast.Lambda):
        return set()
    out: set = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        d = (ms.canonical(dec.func) or "").rsplit(".", 1)[-1]
        if d == "jit":
            out |= _static_names(dec) | _static_nums_to_names(dec, fn)
        elif d == "partial" and dec.args:
            inner = (ms.canonical(dec.args[0]) or "").rsplit(".", 1)[-1]
            if inner == "jit":
                out |= _static_names(dec) | _static_nums_to_names(dec, fn)
    return out
