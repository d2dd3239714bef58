"""graftlint engine + rule-catalog tests (ISSUE 15).

Two jobs:

* ENFORCEMENT — the full titan_tpu/ + tests/ tree must lint clean
  (zero unsuppressed findings) inside the 30 s serial-CPU wall budget.
  This is the tier-1 teeth of the op-scan ban and its sibling
  invariants; the per-directory module-count pins it replaced lived in
  test_compaction.py.
* CATALOG — every rule (R1-R5) demonstrably fires on its positive
  fixture and stays quiet on its negative fixture
  (tests/fixtures/graftlint/ mirrors the real scope layout, so the
  SHIPPED config is what's exercised), plus suppression-comment,
  baseline-file, reporter-schema, and CLI semantics.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:          # bare `pytest` from anywhere
    sys.path.insert(0, REPO)

from tools.graftlint.engine import (Baseline, Linter,      # noqa: E402
                                    SUPPRESSED_BASELINE,
                                    SUPPRESSED_FILE, SUPPRESSED_INLINE)
from tools.graftlint.report import render_json             # noqa: E402
from tools.graftlint.rules import default_rules, rule_ids  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")


@pytest.fixture(scope="module")
def fixture_result():
    return Linter(root=FIXTURES).run(["titan_tpu"])


@pytest.fixture(scope="module")
def repo_result():
    return Linter(root=REPO).run(["titan_tpu", "tests"])


def _in(result, rel):
    return [f for f in result.findings if f.path == rel]


def _msgs(findings):
    return " | ".join(f.message for f in findings)


# ---------------------------------------------------------------------------
# the catalog: each rule fires on its positive fixture, not its negative
# ---------------------------------------------------------------------------

def test_r1_opscan_fires_on_every_banned_shape(fixture_result):
    got = _in(fixture_result, "titan_tpu/models/opscan_pos.py")
    assert {f.rule for f in got} == {"opscan"}
    msgs = _msgs(got)
    assert len(got) == 8
    assert "unbounded: data-dependent output shape" in msgs
    assert "bounded, but the op-scan contract lives in ops.compaction" \
        in msgs
    assert "jnp.flatnonzero" in msgs
    assert "jnp.unique" in msgs
    assert "single-argument jnp.where is jnp.nonzero in disguise" \
        in msgs
    assert "bounded by size=" in msgs        # sized 1-arg where: banned
    assert ".nonzero() method call" in msgs  # method spelling: banned
    assert "boolean-mask indexing inside a jitted kernel" in msgs


def test_r1_opscan_negative(fixture_result):
    assert _in(fixture_result, "titan_tpu/models/opscan_ok.py") == []


def test_r2_hostsync_fires_via_both_registration_seams(fixture_result):
    got = _in(fixture_result, "titan_tpu/models/hostsync_pos.py")
    assert {f.rule for f in got} == {"host-sync"}
    msgs = _msgs(got)
    assert len(got) == 7
    # the jit_once kernel: all five host-sync shapes
    assert "Python `if` on a traced value" in msgs
    assert "int() coerces a traced value" in msgs
    assert "np.asarray" in msgs
    assert "jax.device_get" in msgs
    assert ".item()" in msgs
    # the mesh_jit kernel resolves too (call-site following, not names)
    assert "fixture_mesh_sync" in msgs
    assert "Python `while` on a traced value" in msgs


def test_r2_hostsync_negative_statics_and_shape_metadata(fixture_result):
    assert _in(fixture_result, "titan_tpu/models/hostsync_ok.py") == []


def test_r1_r2_see_inside_pallas_kernels(fixture_result):
    """ISSUE 16: ``pl.pallas_call`` is the third registration seam —
    the kernel resolves through both spellings (inline
    ``functools.partial`` and a local ``kern = partial(...)`` name) and
    traced-ref abuse inside the kernel body is flagged, not invisibly
    exempt."""
    got = _in(fixture_result, "titan_tpu/models/pallas_pos.py")
    assert {f.rule for f in got} == {"opscan", "host-sync"}
    assert len(got) == 5
    msgs = _msgs(got)
    assert "Python `if` on a traced value" in msgs
    assert "Python `while` on a traced value" in msgs
    assert "int() coerces a traced value" in msgs
    assert ".item()" in msgs
    assert "boolean-mask indexing inside a jitted kernel" in msgs
    # pallas kernels have no literal key: messages cite the call line
    assert "registered at line" in msgs


def test_pallas_kernel_static_config_params_stay_legal(fixture_result):
    """Keyword-only params bound through ``functools.partial`` are
    compile-time constants: ``while d < block`` ladders and
    ``if masked`` config branches must NOT read as host syncs."""
    assert _in(fixture_result, "titan_tpu/models/pallas_ok.py") == []


def test_r3_lock_discipline_fires(fixture_result):
    got = _in(fixture_result,
              "titan_tpu/olap/serving/lock_pos.py")
    assert {f.rule for f in got} == {"lock-discipline"}
    msgs = _msgs(got)
    assert len(got) == 9
    for needle in ("file I/O (open)", "json.dump", "os.replace",
                   "time.sleep", "urllib.request.urlopen",
                   "subprocess spawn", "device dispatch (jnp.zeros)",
                   "jax.device_put", ".block_until_ready"):
        assert needle in msgs, needle
    # both lock spellings observed
    assert "while holding _cv" in msgs
    assert "while holding _lock" in msgs


def test_r3_lock_discipline_negative(fixture_result):
    assert _in(fixture_result,
               "titan_tpu/olap/serving/lock_ok.py") == []


def test_r4_metric_name_fires(fixture_result):
    got = _in(fixture_result,
              "titan_tpu/olap/serving/metric_pos.py")
    assert {f.rule for f in got} == {"metric-name"}
    msgs = _msgs(got)
    assert len(got) == 3
    assert "'bogus.name' is outside the pinned families" in msgs
    assert "'unpinned.family.name' is outside the pinned" in msgs
    assert "'serving.fixture.undocumented' has no docs/monitoring.md" \
        in msgs


def test_r4_metric_name_negative(fixture_result):
    assert _in(fixture_result,
               "titan_tpu/olap/serving/metric_ok.py") == []


def test_r5_clock_seam_fires(fixture_result):
    got = _in(fixture_result, "titan_tpu/obs/clock_pos.py")
    assert {f.rule for f in got} == {"clock-seam"}
    assert len(got) == 2
    assert "time.time" in got[0].message
    assert "time.monotonic" in got[1].message


def test_r5_clock_seam_negatives(fixture_result):
    assert _in(fixture_result, "titan_tpu/obs/clock_ok.py") == []
    assert _in(fixture_result,
               "titan_tpu/obs/clock_noseam_ok.py") == []


# ---------------------------------------------------------------------------
# suppression semantics
# ---------------------------------------------------------------------------

def test_inline_suppressions_and_bare_allow(fixture_result):
    got = _in(fixture_result, "titan_tpu/suppress_demo.py")
    assert len(got) == 3
    by_line = {f.line: f for f in got}
    trailing = by_line[8]
    assert trailing.suppressed == SUPPRESSED_INLINE
    assert "trailing-line" in trailing.reason
    standalone = by_line[13]       # comment on 12 covers line 13, by alias
    assert standalone.suppressed == SUPPRESSED_INLINE
    assert "next-line" in standalone.reason
    bare = by_line[17]             # allow without reason= stays INERT
    assert bare.suppressed is None
    assert ("titan_tpu/suppress_demo.py", 17) in \
        fixture_result.bare_allows
    # the allow-file directive QUOTED in suppress_demo's string literal
    # is text, not a suppression: had it been honored, every finding in
    # the file (incl. `bare` above) would read suppressed='file'
    assert not any(f.suppressed == SUPPRESSED_FILE for f in got)


def test_allow_file_suppresses_reference_models(repo_result):
    """The non-round-loop reference model carries a file-level
    suppression for the op-scan ban — the findings still EXIST (the
    exemption is visible, not invisible) but are suppressed with the
    recorded reason."""
    got = _in(repo_result, "titan_tpu/models/bfs.py")
    assert got, "expected suppressed opscan findings in models/bfs.py"
    assert all(f.suppressed == SUPPRESSED_FILE for f in got)
    assert all("not a round-loop hot path" in f.reason for f in got)


# ---------------------------------------------------------------------------
# baseline semantics
# ---------------------------------------------------------------------------

_BAD = textwrap.dedent("""\
    import jax.numpy as jnp

    def f(mask):
        return jnp.flatnonzero(mask)
""")


def _mktree(tmp_path, body=_BAD):
    pkg = tmp_path / "titan_tpu" / "newmod"
    pkg.mkdir(parents=True, exist_ok=True)
    (pkg / "gen.py").write_text(body)
    return tmp_path


def test_baseline_grandfathers_then_catches_new(tmp_path):
    root = _mktree(tmp_path)
    first = Linter(root=str(root)).run(["titan_tpu"])
    assert len(first.unsuppressed) == 1
    bl_path = str(tmp_path / "baseline.json")
    Baseline.from_findings(first.findings).write(bl_path)

    # grandfathered: same tree + baseline -> clean
    again = Linter(root=str(root),
                   baseline=Baseline.load(bl_path)).run(["titan_tpu"])
    assert again.unsuppressed == []
    assert [f.suppressed for f in again.findings] == [SUPPRESSED_BASELINE]

    # a NEW finding in the same file is not hidden by the grandfather
    _mktree(tmp_path, _BAD + "\n\ndef g(m):\n    return jnp.unique(m)\n")
    third = Linter(root=str(root),
                   baseline=Baseline.load(bl_path)).run(["titan_tpu"])
    assert len(third.unsuppressed) == 1
    assert "jnp.unique" in third.unsuppressed[0].message


def test_baseline_auto_loaded_by_every_surface(tmp_path):
    """The checked-in baseline must bind EVERY enforcement surface the
    same way: a bare Linter(root=...) auto-loads
    tools/graftlint/baseline.json under its root (the CLI and the
    tier-1 tests can never disagree about the same tree). Opt out
    explicitly with baseline=Baseline()."""
    root = _mktree(tmp_path)
    first = Linter(root=str(root)).run(["titan_tpu"])
    assert len(first.unsuppressed) == 1
    bl_dir = tmp_path / "tools" / "graftlint"
    bl_dir.mkdir(parents=True)
    Baseline.from_findings(first.findings).write(
        str(bl_dir / "baseline.json"))
    # same bare construction now grandfathers via the checked-in file
    auto = Linter(root=str(root)).run(["titan_tpu"])
    assert auto.unsuppressed == []
    assert [f.suppressed for f in auto.findings] == [SUPPRESSED_BASELINE]
    # the explicit opt-out still sees the raw finding
    raw = Linter(root=str(root), baseline=Baseline()).run(["titan_tpu"])
    assert len(raw.unsuppressed) == 1


def test_baseline_counts_duplicate_lines(tmp_path):
    body = _BAD + "\n\ndef g(mask):\n    return jnp.flatnonzero(mask)\n"
    root = _mktree(tmp_path, body)
    first = Linter(root=str(root)).run(["titan_tpu"])
    assert len(first.unsuppressed) == 2
    bl = Baseline.from_findings(first.findings)
    # identical snippets share a key with count 2 — both consumed, a
    # third identical line would NOT be
    assert sum(bl.entries.values()) == 2
    again = Linter(root=str(root), baseline=bl).run(["titan_tpu"])
    assert again.unsuppressed == []


# ---------------------------------------------------------------------------
# reporters + CLI
# ---------------------------------------------------------------------------

def test_json_reporter_schema(fixture_result):
    doc = json.loads(render_json(fixture_result, FIXTURES))
    assert doc["format"] == "graftlint-v1"
    assert set(doc["summary"]) == {"files", "findings", "unsuppressed",
                                   "suppressed", "bare_allows", "wall_s"}
    assert doc["summary"]["files"] == len(fixture_result.files)
    assert doc["summary"]["findings"] == len(fixture_result.findings)
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "col", "message",
                          "snippet", "suppressed", "reason"}
        assert isinstance(f["line"], int) and f["line"] >= 1


def test_cli_exit_codes_and_json():
    env = dict(os.environ, PYTHONPATH=REPO)
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", FIXTURES,
         "--json", "titan_tpu"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert dirty.returncode == 1
    doc = json.loads(dirty.stdout)
    assert doc["summary"]["unsuppressed"] > 0

    unknown = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--rules", "bogus"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert unknown.returncode == 2

    only_r5 = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--root", FIXTURES,
         "--rules", "R5", "--json", "titan_tpu"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert only_r5.returncode == 1
    doc = json.loads(only_r5.stdout)
    assert {f["rule"] for f in doc["findings"]} == {"clock-seam"}


def test_cli_write_baseline_bootstraps_missing_file(tmp_path):
    """--write-baseline with a target that doesn't exist yet is the
    bootstrap case, not a crash; a missing baseline WITHOUT
    --write-baseline is a clean usage error (exit 2)."""
    pkg = tmp_path / "titan_tpu"
    pkg.mkdir()
    (pkg / "x.py").write_text(
        "import jax.numpy as jnp\n\ndef f(m):\n"
        "    return jnp.flatnonzero(m)\n")
    env = dict(os.environ, PYTHONPATH=REPO)
    bl = str(tmp_path / "bl.json")
    base = [sys.executable, "-m", "tools.graftlint",
            "--root", str(tmp_path), "--baseline", bl]
    boot = subprocess.run([*base, "--write-baseline", "titan_tpu"],
                          cwd=REPO, env=env, capture_output=True,
                          text=True)
    assert boot.returncode == 0, boot.stderr
    assert os.path.exists(bl)
    clean = subprocess.run([*base, "titan_tpu"], cwd=REPO, env=env,
                           capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout
    missing = subprocess.run(
        [*base[:-2], "--baseline", str(tmp_path / "nope.json"),
         "titan_tpu"],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert missing.returncode == 2
    assert "baseline file not found" in missing.stderr


def test_rule_catalog_ids_and_aliases():
    ids = rule_ids()
    assert {ids[a] for a in ("R1", "R2", "R3", "R4", "R5")} == \
        {"opscan", "host-sync", "lock-discipline", "metric-name",
         "clock-seam"}
    assert len(default_rules()) == 5


# ---------------------------------------------------------------------------
# enforcement: the real tree, inside the wall budget
# ---------------------------------------------------------------------------

def test_full_tree_zero_unsuppressed_findings(repo_result):
    """THE invariant gate (acceptance: `python -m tools.graftlint
    titan_tpu tests` exits 0). A finding here means new code
    broke an invariant — fix it or suppress inline WITH a reason."""
    pretty = "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}"
        for f in repo_result.unsuppressed)
    assert repo_result.unsuppressed == [], f"\n{pretty}"
    assert not any(f.rule == "parse-error" for f in repo_result.findings)
    # sanity: the walk really covered the tree
    assert len(repo_result.files) > 150


def test_full_tree_wall_clock_under_30s(repo_result):
    """Lint rides tier-1 — keep it a rounding error."""
    assert repo_result.wall_s < 30.0, repo_result.wall_s
