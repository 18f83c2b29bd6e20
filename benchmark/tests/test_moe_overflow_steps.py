"""The reader ``moe_overflow_steps`` and its entry in ``BENCHMARK.json``,
pinned by name: the steps in which an expert layer passed its row bound,
summed over the layers of ``ran["moe_counters"]``; ``None`` for a program
that keeps no such counter (the parent of the PR that added it), so that
its line leaves the metric out."""

import os

from helpers import ROOT

NAME = "moe_overflow_steps"
CELLS = ["glm47f_train_s8192", "trinitym_train_s8192"]


def _read(counters):
    from benchmark.harness import registry

    ran = {} if counters is None else {"moe_counters": counters}
    reader = registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", NAME + ".py"))
    return reader.read({"ran": ran, "chips": 1})


def test_it_sums_the_layers_counters():
    assert _read({"block1": {"rows_held": 40, "overflow_steps": 0},
                  "block2": {"rows_held": 99, "overflow_steps": 3},
                  "mtp/block": {"rows_held": 50, "overflow_steps": 1}}) == 4
    assert _read({"block1": {"rows_held": 40, "overflow_steps": 0}}) == 0


def test_a_program_without_the_counter_reads_nothing():
    assert _read(None) is None
    assert _read({}) is None
    assert _read({"block1": {"rows_held": 40, "rows_dropped": 0}}) is None


def test_the_entry_by_name():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    entry = {m["name"]: m for m in bench["per_layer"]}[NAME]
    assert entry == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Models",
        "moves": "train_throughput", "workloads": entry["workloads"]}
    assert set(CELLS) <= set(entry["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
