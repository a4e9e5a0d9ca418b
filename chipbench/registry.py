"""Name → file lookups: the harness is driven by ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each lives in a file of its
own, found by name, so a later change adds a cell by adding files:

* ``configs/<name>.json`` — the deployment (the configuration's ``file`` in
  ``BENCHMARK.json``); its ``"reference"`` names the plain reference module
  in ``reference/``;
* ``traffic/<name>.json`` — the traffic mix; its ``"generator"`` names the
  generator module in ``traffic/``;
* ``metrics/<name>.py`` — one per-layer metric's reader, ``read(run)``.

An unknown name is an error, never a default.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(name: str, bench: dict | None = None) -> dict:
    return _by_name((bench or load_benchmark())["workloads"], name, "workload")


def _data(kind: str, name: str) -> dict:
    path = os.path.join(HERE, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} file named {name!r} ({path})")
    with open(path) as f:
        data = json.load(f)
    data["name"] = name
    return data


def config(name: str) -> dict:
    """The deployment ``configs/<name>.json``."""
    return _data("configs", name)


def traffic(name: str) -> dict:
    """The traffic mix ``traffic/<name>.json``."""
    return _data("traffic", name)


def _module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} module named {name!r} ({path})")
    key = f"chipbench.{kind}.{name}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def generator(name: str):
    """The traffic generator module ``traffic/<name>.py``."""
    return _module("traffic", name)


def reference(name: str):
    """The plain reference module ``reference/<name>.py``."""
    return _module("reference", name)


def metric_reader(name: str):
    """``read(run) -> float | None`` of the per-layer metric ``name``."""
    return _module("metrics", name).read


def cell_metrics(cell_name: str, kind: str, bench: dict | None = None) -> list:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports."""
    bench = bench or load_benchmark()
    return [
        m
        for m in bench[kind]
        if "workloads" not in m or cell_name in m["workloads"]
    ]
