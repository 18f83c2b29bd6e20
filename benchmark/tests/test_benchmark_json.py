"""BENCHMARK.json against the builder's contract, as far as a test can
hold it, and against the files it names."""

import json
import os
import re

from benchmark.harness import registry
from helpers import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    return registry.benchmark_json(ROOT)


def test_top_level_keys_and_limits():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_entries_have_exactly_the_contract_keys():
    bench = _bench()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith("benchmark/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert w["config"] in {c["name"] for c in bench["configs"]}
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_every_cell_reports_setup_another_metric_and_a_layer_metric():
    bench = _bench()
    assert "setup_s" in {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        e2e = {m["name"] for m in registry.metric_entries(
            "end_to_end", w["name"], ROOT)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = registry.metric_entries("per_layer", w["name"], ROOT)
        assert per_layer
        for m in per_layer:  # what it moves is reported in the same cell
            assert m["moves"] in e2e


def test_cells_and_metric_files_agree_with_their_entries():
    bench = _bench()
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"], ROOT)  # raises on mismatch
        assert cell["why"] == w["why"]
        assert cell["config_values"]["source"].startswith(
            next(c["source"] for c in bench["configs"]
                 if c["name"] == w["config"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        reader = registry.load_module(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
        assert callable(reader.read), m["name"]
