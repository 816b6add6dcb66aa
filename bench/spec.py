"""BENCHMARK.json and the files it names, found by name.

A cell names a configuration and a traffic mix; the harness finds
`bench/configs/<config>.json`, `bench/traffic/<mix>.json` and, for each
per-layer metric, the reader `bench/metrics/<metric>.py`.  Adding a
deployment, a mix or a metric is adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
from types import ModuleType
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SpecError(ValueError):
    """A cell, configuration, mix or metric that BENCHMARK.json or the
    benchmark's files do not hold."""


def _json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing {os.path.relpath(path, ROOT)}") from None


def load(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _json(os.path.join(BENCH, "configs", f"{name}.json"))


def traffic(name: str) -> dict:
    return _json(os.path.join(BENCH, "traffic", f"{name}.json"))


def metric_reader(name: str) -> ModuleType:
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SpecError(f"missing bench/metrics/{name}.py")
    mod_spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, cell: str, kind: str) -> List[dict]:
    """The `end_to_end` or `per_layer` metrics a cell reports."""
    return [m for m in spec[kind] if cell in m.get("workloads", [cell])]


def limits() -> Dict[str, float]:
    """The limits of the numbers `correct` compares (bench/limits.json)."""
    return _json(os.path.join(BENCH, "limits.json"))["limits"]


def peaks(device_kind: str) -> dict:
    """Published peaks of a device kind; an unknown kind is an error."""
    table = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]
