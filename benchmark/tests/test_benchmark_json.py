"""``BENCHMARK.json`` keeps to the characters and limits the driver
allows, every file it names exists, and without a chip the command exits
non-zero with no result line."""

import json
import os
import re
import subprocess
import sys

import files

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_and_limits():
    b = files.benchmark_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(files.ROOT,
                                        "BENCHMARK.json")) <= 64 << 10
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert all(line(w) for w in b["command"]) and len(b["command"]) <= 32
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"] for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        assert os.path.isfile(os.path.join(files.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        cfg = json.load(open(os.path.join(files.ROOT, c["file"])))
        assert all(k in cfg for k in c["reduced"])
        assert os.path.isfile(files.path("graphs", cfg["generator"] + ".py"))
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        mix = files.load_json("traffic", w["traffic"] + ".json")
        assert os.path.isfile(files.path("drivers", mix["driver"] + ".py"))
        assert os.path.isfile(files.path("reference", mix["op"] + ".py"))
    assert configs == {w["config"] for w in b["workloads"]}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert os.path.isfile(files.path("end_to_end", m["name"] + ".py"))
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and line(m["layer"])
        assert os.path.isfile(files.path("layer_metrics", m["name"] + ".py"))
        moved = next(e for e in b["end_to_end"] if e["name"] == m["moves"])
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cells
    for w in b["workloads"]:           # every cell reports enough
        mine = [m for m in b["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(w["name"] in m.get("workloads", cells)
                   for m in b["per_layer"])


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    out = subprocess.run(["git", "ls-files", "benchmark"], cwd=files.ROOT,
                         capture_output=True, text=True).stdout.split()
    assert all(ok.match(p) for p in out)


def test_run_py_names_no_cell_config_mix_or_metric():
    b = files.benchmark_json()
    text = open(files.path("run.py")).read()
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in b[k]]
    names += [w["traffic"] for w in b["workloads"]]
    assert [n for n in names if n in text] == []


def test_without_a_chip_no_result_line():
    b = files.benchmark_json()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, files.path("run.py"), "--workload",
         b["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=files.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert not any(ln.startswith("{") for ln in out.stdout.splitlines())
    assert "TPU" in out.stderr
