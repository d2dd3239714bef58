"""graftlint core: module model, suppressions, baseline, the Linter.

The engine is deliberately boring: parse each file once, hand the
shared :class:`ModuleSource` (AST + alias tables + per-module caches)
to every rule whose scope matches, then fold inline suppressions and
the checked-in baseline over the raw findings. Rules never do I/O and
never import the code under analysis — everything is AST-only, so the
full tree lints in low single-digit seconds on serial CPU (guarded at
30 s by tests/test_lint.py to protect the tier-1 budget).
"""

from __future__ import annotations

import ast
import dataclasses
import json
import os
import re
import time
from typing import Iterable, Iterator, Optional

from tools.graftlint.config import in_scope, merged_config

# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

#: suppression channels, in the order they are applied
SUPPRESSED_INLINE = "inline"
SUPPRESSED_FILE = "file"
SUPPRESSED_BASELINE = "baseline"


@dataclasses.dataclass
class Finding:
    rule: str
    path: str            # root-relative posix path
    line: int            # 1-based
    col: int             # 0-based
    message: str
    snippet: str = ""    # stripped source line (baseline fingerprint)
    suppressed: Optional[str] = None
    reason: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# suppression comments
# ---------------------------------------------------------------------------

# `# graftlint: allow[rule-id] reason=...`        — this line (or, when
#     the comment stands alone, the next line)
# `# graftlint: allow-file[rule-id] reason=...`   — the whole file
# Multiple ids separate with commas; a missing reason makes the
# suppression INERT (reported as a bare-allow note) — every grandfather
# must say why. Scanned over tokenize COMMENT tokens only: the
# directive syntax QUOTED in a docstring or string literal (e.g. docs
# of the convention itself) is text, not a suppression.
_ALLOW_RE = re.compile(
    r"#\s*graftlint:\s*(allow|allow-file)\[([^\]]+)\]"
    r"(?:\s+reason=(\S[^#]*))?")


@dataclasses.dataclass
class _Allow:
    kind: str            # "allow" | "allow-file"
    ids: frozenset
    reason: str
    line: int            # line the comment sits on
    target_line: int     # line it covers (allow only)


def _scan_allows(text: str) -> list:
    import io
    import tokenize

    allows = []
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(text).readline))
    except (tokenize.TokenError, IndentationError):
        return allows       # unparsable files surface as parse-error
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _ALLOW_RE.search(tok.string)
        if not m:
            continue
        kind = m.group(1)
        ids = frozenset(s.strip() for s in m.group(2).split(",")
                        if s.strip())
        reason = (m.group(3) or "").strip()
        i = tok.start[0]
        # a comment-only line covers the NEXT line; trailing comments
        # cover their own line
        standalone = tok.line[: tok.start[1]].strip() == ""
        target = i + 1 if (kind == "allow" and standalone) else i
        allows.append(_Allow(kind, ids, reason, i, target))
    return allows


# ---------------------------------------------------------------------------
# module model
# ---------------------------------------------------------------------------

class ModuleSource:
    """One parsed file plus the alias tables every rule needs.

    ``cache`` is a per-module scratch dict rules share expensive
    derived structure through (e.g. the resolved jitted-function set
    used by both R1 and R2)."""

    def __init__(self, path: str, relpath: str, text: str):
        self.path = path
        self.relpath = relpath
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=path)
        self.allows = _scan_allows(text)
        self.cache: dict = {}
        # names bound to the modules rules care about
        self.jnp_names: set = set()     # jax.numpy
        self.np_names: set = set()      # numpy
        self.jax_names: set = set()     # jax
        self.time_names: set = set()    # time
        self.sleep_names: set = set()   # from time import sleep
        self.clockfn_names: set = set() # from time import time/monotonic
        self.jitonce_names: set = set()  # from-import bindings of jit_once
        self.meshjit_names: set = set()  # ... and mesh_jit
        self._collect_aliases()

    def _collect_aliases(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    name = a.asname or a.name.split(".")[0]
                    if a.name == "jax.numpy" and a.asname:
                        self.jnp_names.add(a.asname)
                    elif a.name.split(".")[0] == "jax":
                        self.jax_names.add(name)
                    elif a.name == "numpy":
                        self.np_names.add(name)
                    elif a.name == "time":
                        self.time_names.add(name)
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    bound = a.asname or a.name
                    if node.module == "jax" and a.name == "numpy":
                        self.jnp_names.add(bound)
                    elif node.module == "time":
                        if a.name == "sleep":
                            self.sleep_names.add(bound)
                        elif a.name in ("time", "monotonic"):
                            self.clockfn_names.add(bound)
                    elif a.name == "jit_once":
                        self.jitonce_names.add(bound)
                    elif a.name == "mesh_jit":
                        self.meshjit_names.add(bound)

    def dotted(self, node) -> Optional[str]:
        """``jnp.nonzero`` for a pure Name/Attribute chain, else None."""
        parts = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        parts.append(node.id)
        return ".".join(reversed(parts))

    def canonical(self, node) -> Optional[str]:
        """Alias-normalized dotted name: whatever the module called
        jax.numpy comes back as ``jnp.<...>``, numpy as ``np.<...>``,
        jax as ``jax.<...>``, time as ``time.<...>``."""
        d = self.dotted(node)
        if d is None:
            return None
        root, _, rest = d.partition(".")
        for names, canon in ((self.jnp_names, "jnp"),
                             (self.np_names, "np"),
                             (self.jax_names, "jax"),
                             (self.time_names, "time")):
            if root in names:
                return f"{canon}.{rest}" if rest else canon
        return d

    def snippet(self, line: int) -> str:
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""


# ---------------------------------------------------------------------------
# rule base
# ---------------------------------------------------------------------------

class Rule:
    """One invariant. ``check`` yields findings with rule/snippet left
    blank — the engine stamps those (and the relpath) so rules stay
    one-screen visitors."""

    id: str = ""
    alias: str = ""          # the catalog number (R1..R5)
    description: str = ""

    def __init__(self, options: dict):
        self.options = options

    def check(self, ms: ModuleSource, ctx: "Linter") -> Iterator[Finding]:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

class Baseline:
    """Checked-in grandfather list. Keyed on (rule, path, stripped
    source line) — line NUMBERS move too easily to be a fingerprint —
    with a count per key so duplicate lines stay honest. A finding
    consumes one unit of its key's budget; anything past the budget
    reports as new."""

    def __init__(self, entries: Optional[dict] = None):
        self.entries: dict = dict(entries or {})

    @staticmethod
    def key(f: Finding) -> str:
        return f"{f.rule}::{f.path}::{f.snippet}"

    @classmethod
    def load(cls, path: str) -> "Baseline":
        with open(path) as fh:
            data = json.load(fh)
        if data.get("version") != 1:
            raise ValueError(f"unsupported baseline version in {path}")
        return cls(data.get("entries", {}))

    @classmethod
    def from_findings(cls, findings: Iterable[Finding]) -> "Baseline":
        entries: dict = {}
        for f in findings:
            if f.suppressed in (SUPPRESSED_INLINE, SUPPRESSED_FILE):
                continue            # inline allows own their findings
            k = cls.key(f)
            entries[k] = entries.get(k, 0) + 1
        return cls(entries)

    def write(self, path: str) -> None:
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump({"version": 1,
                       "entries": dict(sorted(self.entries.items()))},
                      fh, indent=1, sort_keys=False)
            fh.write("\n")
        os.replace(tmp, path)

    def apply(self, findings: Iterable[Finding]) -> None:
        budget = dict(self.entries)
        for f in findings:
            if f.suppressed is not None:
                continue
            k = self.key(f)
            if budget.get(k, 0) > 0:
                budget[k] -= 1
                f.suppressed = SUPPRESSED_BASELINE
                f.reason = "baselined"


# ---------------------------------------------------------------------------
# the linter
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Result:
    findings: list               # every finding, suppressed included
    files: list                  # relpaths scanned
    wall_s: float
    bare_allows: list            # (path, line) allows ignored for no reason=

    @property
    def unsuppressed(self) -> list:
        return [f for f in self.findings if f.suppressed is None]

    def by_rule(self, rule_id: str) -> list:
        return [f for f in self.findings if f.rule == rule_id]


_SKIP_DIRS = {"__pycache__", ".git", ".bench_cache", ".pytest_cache",
              "node_modules"}


#: the checked-in grandfather list, auto-loaded (root-relative) by
#: EVERY Linter unless a baseline is passed explicitly — the CLI and
#: the tier-1 tests must agree about the same tree (pass
#: ``baseline=Baseline()`` to opt out)
DEFAULT_BASELINE_RELPATH = os.path.join("tools", "graftlint",
                                        "baseline.json")


class Linter:
    def __init__(self, root: str, config: Optional[dict] = None,
                 rules: Optional[list] = None,
                 baseline: Optional[Baseline] = None):
        from tools.graftlint.rules import default_rules

        self.root = os.path.abspath(root)
        self.config = merged_config(config)
        rule_classes = rules if rules is not None else default_rules()
        self.rules = [cls(self.config.get(cls.id, {}))
                      for cls in rule_classes]
        if baseline is None:
            default = os.path.join(self.root, DEFAULT_BASELINE_RELPATH)
            baseline = Baseline.load(default) \
                if os.path.exists(default) else Baseline()
        self.baseline = baseline
        self._doc_names: Optional[set] = None
        self._doc_loaded = False

    # -- shared context ----------------------------------------------------

    def doc_metric_names(self, doc_rel: str) -> Optional[set]:
        """Metric names documented as table rows in docs/monitoring.md
        (None when the file doesn't exist under this root — fixture
        trees — in which case the doc-row check is skipped)."""
        if not self._doc_loaded:
            self._doc_loaded = True
            path = os.path.join(self.root, doc_rel)
            if os.path.exists(path):
                with open(path) as fh:
                    text = fh.read()
                row = re.compile(r"^\|\s*`([a-z0-9_.]+)`\s*\|",
                                 re.MULTILINE)
                self._doc_names = set(row.findall(text))
        return self._doc_names

    # -- file discovery ----------------------------------------------------

    def discover(self, paths: Iterable[str]) -> list:
        files: list = []
        seen: set = set()
        for p in paths:
            p = p if os.path.isabs(p) else os.path.join(self.root, p)
            if os.path.isdir(p):
                for dirpath, dirnames, filenames in os.walk(p):
                    dirnames[:] = sorted(d for d in dirnames
                                         if d not in _SKIP_DIRS)
                    for fn in sorted(filenames):
                        if fn.endswith(".py"):
                            full = os.path.join(dirpath, fn)
                            if full not in seen:
                                seen.add(full)
                                files.append(full)
            elif p.endswith(".py") and os.path.exists(p):
                if p not in seen:
                    seen.add(p)
                    files.append(p)
        return files

    # -- run ---------------------------------------------------------------

    def run(self, paths: Iterable[str]) -> Result:
        t0 = time.monotonic()
        findings: list = []
        scanned: list = []
        bare_allows: list = []
        for path in self.discover(paths):
            rel = os.path.relpath(path, self.root).replace(os.sep, "/")
            scanned.append(rel)
            active = [r for r in self.rules
                      if in_scope(rel, r.options.get("scope", []))]
            if not active:
                continue
            with open(path, encoding="utf-8") as fh:
                text = fh.read()
            try:
                ms = ModuleSource(path, rel, text)
            except SyntaxError as e:
                findings.append(Finding(
                    rule="parse-error", path=rel, line=e.lineno or 0,
                    col=e.offset or 0, message=f"syntax error: {e.msg}",
                    snippet=""))
                continue
            bare_allows.extend(
                (rel, a.line) for a in ms.allows if not a.reason)
            for rule in active:
                for f in rule.check(ms, self):
                    f.rule = rule.id
                    f.path = rel
                    if not f.snippet:
                        f.snippet = ms.snippet(f.line)
                    self._suppress_inline(f, rule, ms)
                    findings.append(f)
        findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
        self.baseline.apply(findings)
        return Result(findings=findings, files=scanned,
                      wall_s=time.monotonic() - t0,
                      bare_allows=bare_allows)

    @staticmethod
    def _suppress_inline(f: Finding, rule: Rule, ms: ModuleSource) -> None:
        ids_for = {rule.id, rule.alias, "*"}
        for a in ms.allows:
            if not a.reason or not (a.ids & ids_for):
                continue
            if a.kind == "allow-file":
                f.suppressed = SUPPRESSED_FILE
                f.reason = a.reason
                return
            if a.target_line == f.line:
                f.suppressed = SUPPRESSED_INLINE
                f.reason = a.reason
                return
