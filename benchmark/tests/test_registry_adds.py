"""A later PR adds a cell, a configuration and a per-layer metric as new
files and entries, and edits no file that is there."""

import os

from benchmark.harness import registry
from helpers import TINY_GPT, add_cell, make_root

METRIC = '''
"""Throw-away metric: steps the window completed."""


def read(run):
    stamps = run.get("stamps")
    return None if not stamps else len(stamps) - 1
'''


def test_new_cell_and_metric_are_found_without_an_edit(tmp_path):
    import run as cli

    root = make_root(tmp_path)
    before = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name != "BENCHMARK.json":
                before[path] = open(path, "rb").read()

    add_cell(root, "tiny_gpt_cell", "gpt2m_train_s1024", TINY_GPT,
             traffic="tiny_traffic",
             config_edits={"program": {"size": "nano"}},
             metrics={"window_steps": {
                 "kind": "per_layer", "unit": "steps", "better": "higher",
                 "source": "program_counter", "layer": "Step builder",
                 "moves": "train_throughput"}})
    with open(os.path.join(root, "benchmark", "metrics",
                           "window_steps.py"), "w") as f:
        f.write(METRIC)

    for path, content in before.items():  # nothing that was there changed
        assert open(path, "rb").read() == content, path

    assert "window_steps" in registry.available_metrics(root)
    cell = registry.load_cell("tiny_gpt_cell", root)
    assert cell["config"] == "tiny_gpt_cell-config"
    line = cli.execute("tiny_gpt_cell", seed=3, seconds=0.5, trace=True,
                       root=root, allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert line["metrics"]["window_steps"]["unit"] == "steps"
    assert line["metrics"]["window_steps"]["value"] >= 1
    assert "compile_s" in line["metrics"]
    # a reader that finds nothing to read is left out: no device trace here
    assert "flash_ms" not in line["metrics"]
    # and the old cells know nothing of the new metric
    old = {m["name"] for m in registry.metric_entries(
        "per_layer", "gpt2m_train_s1024", root)}
    assert "window_steps" not in old
