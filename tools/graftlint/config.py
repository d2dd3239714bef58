"""Per-rule configuration: scopes and options.

A rule runs on a file iff the file's root-relative posix path matches
one of the rule's ``scope`` entries (an entry ending in ``/`` is a
directory prefix, anything else an exact path). Scopes are PREFIXES,
not pins: a brand-new ``titan_tpu/anything/`` subdirectory is covered
the moment it exists — that auto-discovery is the whole point (the
per-directory module-count pins this engine replaced had to be bumped
by hand in every PR; see docs/static-analysis.md).

Tests lint fixture trees by pointing ``Linter(root=...)`` at a
directory whose layout mirrors these prefixes — the shipped scopes
apply unchanged, so a fixture proves the rule as configured, not a
laboratory variant.
"""

from __future__ import annotations

import copy

DEFAULT_CONFIG: dict = {
    # R1 — the op-scan ban (docs/performance.md, ISSUE r6): the whole
    # package. Everything else (tests, experiments) may use op-scans
    # as oracles.
    "opscan": {
        "scope": ["titan_tpu/"],
    },
    # R2 — host syncs inside kernels registered through
    # utils/jitcache.jit_once / parallel/mesh.mesh_jit. The scope is
    # wide; the rule itself only fires inside functions it resolved
    # from a registration call site.
    "host-sync": {
        "scope": ["titan_tpu/"],
    },
    # R3 — blocking work under the serving/live locks (the PR-10
    # `_requeue` postmortem-write stall).
    "lock-discipline": {
        "scope": ["titan_tpu/olap/serving/", "titan_tpu/olap/live/"],
    },
    # R4 — literal metric names must parse into a guarded family and
    # have a docs/monitoring.md row (tests/test_docs_metrics.py pins
    # the same families; keep the two lists in sync).
    "metric-name": {
        "scope": ["titan_tpu/"],
        "families": ["serving", "device", "flightrec", "controller",
                     "scan", "obs", "fleet"],
        "doc": "docs/monitoring.md",
    },
    # R5 — modules that declare an injectable clock seam (a `clock`
    # parameter) must not also read the wall clock directly.
    "clock-seam": {
        "scope": ["titan_tpu/obs/", "titan_tpu/olap/serving/"],
    },
}


def merged_config(overrides: dict | None) -> dict:
    """DEFAULT_CONFIG with per-rule overrides merged in (an override
    replaces keys, not the whole rule entry)."""
    cfg = copy.deepcopy(DEFAULT_CONFIG)
    for rule_id, entry in (overrides or {}).items():
        cfg.setdefault(rule_id, {}).update(entry)
    return cfg


def in_scope(relpath: str, scope: list) -> bool:
    return any(relpath == s or (s.endswith("/") and relpath.startswith(s))
               for s in scope)
