"""Where the benchmark's files are, and how a name becomes a file.

Every configuration, traffic mix, graph generator, driver, reference and
metric reader is a file of its own under a directory named for its kind;
the harness finds it by the name that ``BENCHMARK.json`` or another data
file gives. Nothing here knows any of those names.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

if HERE not in sys.path:            # readers import ``stats``, ``reference``
    sys.path.insert(0, HERE)

_MODULES: dict = {}


def path(*parts: str) -> str:
    return os.path.join(HERE, *parts)


def load_json(*parts: str) -> dict:
    with open(path(*parts)) as f:
        return json.load(f)


def benchmark_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_files(workload: str):
    """(bench, cell, config, mix) of the cell named ``workload``: its entry
    in ``BENCHMARK.json``, its configuration's file and its mix's file."""
    bench = benchmark_json()
    try:
        cell = next(w for w in bench["workloads"] if w["name"] == workload)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    except StopIteration:
        raise SystemExit(f"benchmark: no workload named {workload!r} with "
                         "a configuration in BENCHMARK.json") from None
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    return bench, cell, config, load_json("traffic",
                                          cell["traffic"] + ".json")


def load_module(kind: str, name: str):
    """The module ``<kind>/<name>.py`` (any name a metric may have, so by
    file and not by import path)."""
    key = (kind, name)
    mod = _MODULES.get(key)
    if mod is None:
        file = path(kind, name + ".py")
        if not os.path.isfile(file):
            raise FileNotFoundError(f"no {kind} named {name!r}: {file}")
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{name}".replace(".", "_").replace("-", "_"),
            file)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[key] = mod
    return mod
