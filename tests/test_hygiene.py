"""Package-tree hygiene (ISSUE 14 satellite).

An aborted build once left ``titan_tpu/olap/serving/fleet/`` behind as
a directory containing nothing but a stale ``__pycache__`` — invisible
to imports, confusing to every reader, and a trap for tooling that
walks the tree. This guard keeps the package tree honest:

* every directory under ``titan_tpu/`` that contains ``.py`` files is a
  real package (has ``__init__.py``) — a module that cannot be imported
  is dead code wearing a live extension;
* no directory under ``titan_tpu/`` is pycache-only (its only contents,
  recursively, are ``__pycache__`` artifacts) — compiled leftovers must
  not outlive the source tree that produced them;
* no kernel is chosen by an environment variable: nothing under
  ``titan_tpu/models`` or ``titan_tpu/ops`` reads the environment (one
  known debt, listed);
* every path the lint CLI defaults to exists, so a deleted file cannot
  stay in its scope unnoticed.
"""

import os
import re

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "titan_tpu")


def _real_contents(dirpath: str) -> bool:
    """True when the tree under ``dirpath`` holds anything that is not
    a ``__pycache__`` artifact."""
    for root, dirnames, filenames in os.walk(dirpath):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        if filenames:
            return True
    return False


def test_every_py_dir_is_a_package():
    missing = []
    for dirpath, dirnames, filenames in os.walk(_PKG):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        if any(f.endswith(".py") for f in filenames) \
                and "__init__.py" not in filenames:
            missing.append(os.path.relpath(dirpath, _REPO))
    assert not missing, (
        f"directories with .py files but no __init__.py: {missing} — "
        f"either make them packages or remove the orphans")


def test_no_pycache_only_directories():
    ghosts = []
    for dirpath, dirnames, filenames in os.walk(_PKG):
        if "__pycache__" in dirnames and not _real_contents(dirpath):
            ghosts.append(os.path.relpath(dirpath, _REPO))
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
    assert not ghosts, (
        f"pycache-only directories (stale build leftovers): {ghosts} — "
        f"delete them; compiled artifacts must not outlive their "
        f"source")


#: none left: ROADMAP D3's TITAN_TPU_SEGMENT_KERNEL went with PR 45
_ENV_READ_DEBTS: set = set()


def test_no_kernel_reads_the_environment():
    readers = set()
    for sub in ("models", "ops"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(_PKG, sub)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for name in filenames:
                path = os.path.join(dirpath, name)
                if name.endswith(".py") and re.search(
                        r"\bos\.(environ|getenv)\b|\bfrom os import",
                        open(path, encoding="utf-8").read()):
                    readers.add(os.path.relpath(path, _REPO))
    assert readers == _ENV_READ_DEBTS, (
        f"kernel modules that read the environment: "
        f"{sorted(readers - _ENV_READ_DEBTS)} — which program runs is "
        f"decided from what the code can observe, not by a variable; "
        f"settled debts to strike: {sorted(_ENV_READ_DEBTS - readers)}")


def test_lint_default_paths_exist():
    import sys
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    from tools.graftlint.__main__ import DEFAULT_PATHS

    missing = [p for p in DEFAULT_PATHS
               if not os.path.exists(os.path.join(_REPO, p))]
    assert DEFAULT_PATHS and not missing, missing
