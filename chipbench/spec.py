"""Find what a cell needs by the names in `BENCHMARK.json`. No JAX here.

`root` is the directory that holds `BENCHMARK.json` and `chipbench/`: the
checkout, or in a test a temporary copy with files added. Data files are
read from `<root>/chipbench/<kind>/<name>.json`; code found by name (a
reader, a family's loop, a reference) is `<root>/chipbench/<kind>/<name>.py`.
"""

from __future__ import annotations

import hashlib
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "chipbench"


def read_json(root: str, *parts: str) -> Dict[str, Any]:
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return read_json(root, "BENCHMARK.json")


def by_name(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(
        f"BENCHMARK.json has no {what} named {name!r}; it has "
        f"{[e['name'] for e in entries]}"
    )


def load_cell(root: str, workload: str) -> Dict[str, Any]:
    """The cell with its configuration and traffic as they are run."""
    bench = load_benchmark(root)
    cell = by_name(bench["workloads"], workload, "workload")
    entry = by_name(bench["configs"], cell["config"], "config")
    config = read_json(root, entry["file"])
    traffic = read_json(root, PACKAGE, "traffic", cell["traffic"] + ".json")
    return {"workload": cell, "config": config, "traffic": traffic}


def metrics_of(bench: Dict[str, Any], workload: str, kind: str
               ) -> List[Dict[str, Any]]:
    """The `end_to_end` or `per_layer` metrics this cell reports: those
    without a `workloads` key, and those that list the cell."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def load_code(root: str, kind: str, name: str):
    """The module `<root>/chipbench/<kind>/<name>.py`. In the checkout it is
    imported by its package name, so that a function it defines pickles by
    reference; in a temporary root it is loaded from the file."""
    if os.path.realpath(root) == os.path.realpath(ROOT):
        return importlib.import_module(f"{PACKAGE}.{kind}.{name}")
    path = os.path.join(root, PACKAGE, kind, name + ".py")
    digest = hashlib.sha1(os.path.realpath(path).encode()).hexdigest()[:12]
    mod_name = f"_chipbench_{kind}_{name}_{digest}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"no {kind} named {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def read_metric(root: str, name: str, run: Dict[str, Any]):
    """One metric's value from a run's record, through the reader its file
    names; None when the reader finds nothing to read."""
    spec = read_json(root, PACKAGE, "metrics", name + ".json")
    reader = load_code(root, "readers", spec["reader"])
    return reader.read(run, spec.get("params", {}))


def metric_lines(root: str, bench: Dict[str, Any], workload: str, kind: str,
                 run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """`{"name": {"value": ..., "unit": ...}}` for the cell's metrics of this
    kind; a metric whose reader returns None is left out."""
    out = {}
    for metric in metrics_of(bench, workload, kind):
        value = read_metric(root, metric["name"], run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
