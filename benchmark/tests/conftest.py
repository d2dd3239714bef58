"""Tests of the benchmark's own code. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are not part of the repository's tier-1 tests.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import files  # noqa: E402


def small_config(graph: str, scale: int) -> dict:
    with open(os.path.join(HERE, "data", f"{graph}-s{scale}.json")) as f:
        return json.load(f)


@pytest.fixture
def small_bench(monkeypatch, tmp_path):
    """``BENCHMARK.json`` with every configuration swapped for its
    scale-10 twin, and the chip check stubbed: the rehearsal drives all
    the rest of a run on the CPU."""
    import run

    bench = files.benchmark_json()
    for c in bench["configs"]:
        graph = json.load(open(os.path.join(files.ROOT, c["file"])))[
            "generator"]
        c["file"] = os.path.relpath(
            os.path.join(HERE, "data", f"{graph}-s10.json"), files.ROOT)
    monkeypatch.setattr(files, "benchmark_json", lambda: bench)
    monkeypatch.setattr(run, "require_tpu", lambda devices, chips: None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax-cache"))
    return bench
