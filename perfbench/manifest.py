"""Find a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a configuration, a mix, a driver or a metric by
name: ``BENCHMARK.json`` names them, and each name is a file under
``perfbench/`` (README.md, "Adding ...").
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")


class ManifestError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


def _json(path: str) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e


def _checked(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError(f"{name!r} is not a plain name "
                            "([A-Za-z0-9][A-Za-z0-9_.-]*, at most 64)")
    return name


def load_benchmark() -> Dict[str, Any]:
    return _json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(directory: str, name: str) -> ModuleType:
    """``perfbench/<directory>/<name>.py``, loaded by path (metric names
    contain dots, so they are not importable module names)."""
    path = os.path.join(HERE, directory, _checked(name) + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"{os.path.relpath(path, ROOT)} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"perfbench.{directory}.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peak_for(device_kind: str) -> Dict[str, Any]:
    """The published peaks of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, never a default."""
    table = _json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in table:
        raise ManifestError(
            f"device_kind {device_kind!r} is not in perfbench/peaks.json "
            f"(known: {sorted(table)}); add it with its source")
    return table[device_kind]


@dataclass
class Cell:
    name: str
    chips: int
    run_seconds: int               # the benchmark's one window length
    config: str
    traffic: str
    sizes: Dict[str, Any]          # the configuration's file
    mix: Dict[str, Any]            # the traffic mix's file
    config_module: ModuleType
    driver: ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    found = [e for e in entries if e.get("name") == name]
    if len(found) != 1:
        raise ManifestError(
            f"{what} {name!r}: {len(found)} entries in BENCHMARK.json "
            f"(known: {[e.get('name') for e in entries]})")
    return found[0]


def _in_cell(metrics: List[Dict[str, Any]], cell: str):
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def _rehearsed(d: Dict[str, Any]) -> Dict[str, Any]:
    """Toy sizes for ``--rehearse``: the file's own ``rehearse`` group laid
    over its top level."""
    return {**d, **d.get("rehearse", {})}


def resolve(workload: str, rehearse: bool = False) -> Cell:
    bench = load_benchmark()
    w = _by_name(bench["workloads"], _checked(workload), "workload")
    c = _by_name(bench["configs"], _checked(w["config"]), "config")
    sizes = _json(os.path.join(ROOT, c["file"]))
    mix = _json(os.path.join(HERE, "traffic", _checked(w["traffic"]) + ".json"))
    if rehearse:
        sizes, mix = _rehearsed(sizes), _rehearsed(mix)
    per_layer = _in_cell(bench["per_layer"], workload)
    for m in per_layer:
        _by_name(bench["end_to_end"], m["moves"], f"{m['name']}.moves")
    return Cell(
        name=workload, chips=int(w["chips"]),
        run_seconds=bench["run_seconds"], config=c["name"],
        traffic=w["traffic"], sizes=sizes, mix=mix,
        config_module=load_module("configs", c["name"]),
        driver=load_module("drivers", mix["kind"]),
        end_to_end=_in_cell(bench["end_to_end"], workload),
        per_layer=per_layer)
