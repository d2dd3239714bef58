"""chip_smoke.py rehearsed on the CPU, and the compile-cache rule.

The script has no CPU path of its own (without a TPU it fails in phase
0), so the phases are driven in-process with the device check stubbed
HERE, not by an option of the script.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from titan_tpu.utils.jitcache import enable_compile_cache  # noqa: E402

_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    """enable_compile_cache() writes process-wide jax config; put back
    what tests/conftest.py chose."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.fixture(params=["alone", "behind a leaked profiler"])
def leaked_profiler(request):
    """An earlier test of the worker (a scheduler it never closed) left
    its profiler installed over the process-wide default registry, the
    one the smoke's own profiler counts on: an event still counts once
    (``devprof._installed``; the smoke read its WCC job's endgame twice
    behind such a leak until ISSUE 49)."""
    from titan_tpu.obs import devprof

    if request.param == "alone":
        yield
        return
    leaked = devprof.DeviceCostProfiler().install()
    yield
    leaked.uninstall()


def test_smoke_passes_under_cpu_rehearsal(monkeypatch, capsys, cache_config,
                                          leaked_profiler):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda dev: None)
    assert chip_smoke.main(["--scale", "10"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == len(jax.devices())
    body = "\n".join(lines[:-1])
    for phase in ("phase 0", "phase 1", "phase 2", "phase 3 bfs",
                  "phase 3 traverse", "phase 4 second pass"):
        assert phase in body
    assert "pass2 compiles=0" in body and "batch_k=8" in body
    # a lone BFS job builds ahead; the one behind it builds nothing
    assert "phase 4 lone bfs: 2 jobs" in body and "lone2 compiles=0" in body
    # the WCC job's peel says what served its endgame's frontier test
    assert "end's frontier test impl=xla x1" in body


def test_smoke_failed_comparison_exits_without_ok_line(monkeypatch, capsys,
                                                       cache_config):
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda dev: None)
    monkeypatch.setattr(chip_smoke, "ref_pagerank",
                        lambda src, dst, n, **kw: np.zeros(n))
    with pytest.raises(chip_smoke.SmokeFailure, match="pagerank L1"):
        chip_smoke.main(["--scale", "10"])
    assert '"ok"' not in capsys.readouterr().out


def test_smoke_without_a_tpu_fails_before_phase_1(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--scale", "10"])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "phase 1" not in out and '"ok"' not in out


def test_compile_cache_dir_comes_from_the_environment_or_the_checkout(
        monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.append((key, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/given/from/outside")
    enable_compile_cache()          # JAX reads the variable itself
    assert "jax_compilation_cache_dir" not in dict(updates)
    assert "jax_persistent_cache_min_compile_time_secs" in dict(updates)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    enable_compile_cache()
    assert dict(updates)["jax_compilation_cache_dir"] == os.path.join(
        ROOT, ".bench_cache", "xla")
