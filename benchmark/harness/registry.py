"""Finding the benchmark's data by name.

Everything that belongs to one cell, one configuration or one metric is
a file of its own under ``benchmark/``; this module finds it from the
name ``BENCHMARK.json`` gives, so a later PR adds files and entries and
edits nothing that is here::

    benchmark/workloads/<cell>.json      one cell: configuration, runner,
                                         chips, traffic parameters, why
    benchmark/configs/<config>.json      the configuration as it is run
    benchmark/configs/<config>.reference.py   its plain reference
    benchmark/models/<family>.py         how a training step is built,
                                         and the model FLOPs of one item
    benchmark/runners/<runner>.py        run(cell, ...) -> observations
    benchmark/metrics/<metric>.py        read(run) -> number or None;
                                         unit, layer and what it moves
                                         are in BENCHMARK.json alone

``root`` is the directory that holds ``BENCHMARK.json`` and
``benchmark/`` (the checkout; a temporary directory in the tests).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path (its name may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def benchmark_json(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's own file, with its configuration file read in under
    ``config_values``.  Where ``BENCHMARK.json`` lists the cell, the two
    have to agree on configuration, traffic and chips."""
    path = os.path.join(root, "benchmark", "workloads", name + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no cell {name!r} ({path} is missing)")
    cell = _read_json(path)
    cell["name"] = name
    cell["root"] = root
    cell["config_values"] = _read_json(os.path.join(
        root, "benchmark", "configs", cell["config"] + ".json"))
    for entry in benchmark_json(root).get("workloads", []):
        if entry["name"] != name:
            continue
        for key in ("config", "traffic", "chips"):
            if entry[key] != cell[key]:
                raise SystemExit(
                    f"benchmark: cell {name!r}: BENCHMARK.json says "
                    f"{key}={entry[key]!r}, its file {cell[key]!r}")
    return cell


def load_runner(name: str, root: str = ROOT):
    return load_module(os.path.join(root, "benchmark", "runners",
                                    name + ".py"))


# What a family's file has to define (benchmark/models/common.py says
# what each returns).  Checked here, before anything is built: a file
# that lacks one would otherwise fail after the measured window.
BUILDER_FUNCTIONS = ("build", "train_flops_per_item")


def load_model_builder(family: str, root: str = ROOT):
    path = os.path.join(root, "benchmark", "models", family + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no builder for family {family!r} "
                         f"({path} is missing)")
    module = load_module(path)
    for name in BUILDER_FUNCTIONS:
        if not callable(getattr(module, name, None)):
            raise SystemExit(
                f"benchmark: {path} defines no {name}(): every family "
                f"states its own {', '.join(BUILDER_FUNCTIONS)}")
    return module


def load_reference(config_name: str, root: str = ROOT):
    return load_module(os.path.join(root, "benchmark", "configs",
                                    config_name + ".reference.py"))


def sibling_metric(file: str, name: str):
    """Another metric's reader, from the directory of the reader ``file``
    (``flash_roofline`` is a bound over ``flash_ms``, and so on)."""
    return load_module(os.path.join(os.path.dirname(os.path.abspath(file)),
                                    name + ".py"))


def metric_entries(kind: str, cell_name: str, root: str = ROOT
                   ) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to a cell."""
    return [m for m in benchmark_json(root).get(kind, [])
            if "workloads" not in m or cell_name in m["workloads"]]


def available_metrics(root: str = ROOT) -> List[str]:
    directory = os.path.join(root, "benchmark", "metrics")
    return sorted(f[:-3] for f in os.listdir(directory)
                  if f.endswith(".py") and not f.startswith("_"))


def reader_scopes(root: str = ROOT) -> List[str]:
    """The ``SCOPE`` of every reader that states one: the scopes the
    ``breakdown``'s ``device_scopes`` files device time under.  A later
    PR's reader of a new scope joins them by being there."""
    scopes = []
    for name in available_metrics(root):
        reader = load_module(os.path.join(root, "benchmark", "metrics",
                                          name + ".py"))
        if getattr(reader, "SCOPE", None):
            scopes.append(reader.SCOPE)
    return scopes


def read_metrics(entries: List[dict], run: Dict[str, Any],
                 root: str = ROOT) -> Dict[str, dict]:
    """Apply each listed metric's reader; one that finds nothing to read
    returns None and is left out of the line."""
    found = set(available_metrics(root))
    out: Dict[str, dict] = {}
    for entry in entries:
        name = entry["name"]
        if name not in found:
            raise SystemExit(
                f"benchmark: BENCHMARK.json lists metric {name!r} but "
                f"benchmark/metrics/{name}.py is missing")
        reader = load_module(os.path.join(root, "benchmark", "metrics",
                                          name + ".py"))
        value: Optional[float] = reader.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": entry["unit"]}
    return out
