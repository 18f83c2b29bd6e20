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


# ---- a configuration of a family the harness has not seen ----

NEW_FAMILY = '''
"""Throw-away family: a two-layer classifier in plain jax, float32."""

import os

from benchmark.models.common import Built, seed_key


def train_flops_per_item(config, ran):
    return 3.0 * 2 * (ran["n_in"] * config["n_hidden"]
                      + config["n_hidden"] * config["n_classes"])


def _logits(p, x):
    import jax.numpy as jnp

    return jnp.tanh(x @ p["w1"]) @ p["w2"]


def build(config, params, seed, described_mesh=None):
    import jax
    import jax.numpy as jnp

    marker = os.environ.get("NEWFAM_BUILD_MARKER")
    if marker:
        open(marker, "w").close()
    n_in, batch = params["n_in"], params["per_chip_batch"]
    k1, k2, k3, k4 = jax.random.split(seed_key(seed), 4)
    p = {"w1": jax.random.normal(k1, (n_in, config["n_hidden"])) * 0.1,
         "w2": jax.random.normal(k2, (config["n_hidden"],
                                      config["n_classes"])) * 0.1}
    x = jax.random.normal(k3, (batch, n_in))
    y = jax.random.randint(k4, (batch,), 0, config["n_classes"])

    def logprob(p, b):
        picked = jax.nn.log_softmax(_logits(p, b["x"]))
        return jnp.take_along_axis(picked, b["y"][:, None], 1)[:, 0]

    def loss(p, b):
        return -logprob(p, b).mean()

    @jax.jit
    def step(p, x, y):
        value, grads = jax.value_and_grad(loss)(p, {"x": x, "y": y})
        return jax.tree.map(lambda a, g: a - 0.1 * g, p, grads), value

    def sample(n):
        kx, ky = jax.random.split(jax.random.fold_in(seed_key(seed), 7))
        return {"x": jax.random.normal(kx, (n, n_in)),
                "y": jax.random.randint(ky, (n,), 0, config["n_classes"])}

    return Built(step=step, state=(p, x, y), carry_len=1,
                 items_per_step=batch, chips=1, mesh=None,
                 program_loss=jax.jit(loss), sample=sample,
                 variables=lambda state: state[0],
                 ran={"n_in": n_in, "global_batch": batch})
'''
NEW_REFERENCE = '''
import jax
import jax.numpy as jnp


def logprob(config, params, batch):
    logits = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    picked = jax.nn.log_softmax(logits)
    return jnp.take_along_axis(picked, batch["y"][:, None], 1)[:, 0]


def loss(config, params, batch):
    return -logprob(config, params, batch).mean()
'''
NEW_CONFIG = {
    "name": "newfam-tiny", "family": "newfam", "source": "test",
    "n_hidden": 32, "n_classes": 10,
    # no logprob_abs: this family's program_loss returns the loss alone
    "reference_tolerance": {"loss_abs": 1e-5, "grad_rel": 1e-4}}
NEW_CELL = {
    "config": "newfam-tiny", "traffic": "tiny", "runner": "train",
    "chips": 1, "why": "test",
    "params": {"n_in": 16, "per_chip_batch": 64, "warmup_steps": 2,
               "trace_steps": 3, "reference_items": 8}}


def _add_family(root, family=NEW_FAMILY):
    """Files and entries only; returns what was there, byte for byte."""
    import json

    before = {}
    for folder, _, files in os.walk(root):
        for name in files:
            path = os.path.join(folder, name)
            if name != "BENCHMARK.json":
                before[path] = open(path, "rb").read()

    def write(text, *parts):
        with open(os.path.join(root, "benchmark", *parts), "w") as f:
            f.write(text)

    write(family, "models", "newfam.py")
    write(NEW_REFERENCE, "configs", "newfam-tiny.reference.py")
    write(json.dumps(NEW_CONFIG), "configs", "newfam-tiny.json")
    write(json.dumps(NEW_CELL), "workloads", "newfam_cell.json")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "newfam-tiny", "source": "test",
        "file": "benchmark/configs/newfam-tiny.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": "newfam_cell", "chips": 1,
                               "config": "newfam-tiny", "traffic": "tiny",
                               "why": "test"})
    for entry in bench["end_to_end"] + bench["per_layer"]:
        if "resnet50_train_b256" in entry.get("workloads", ()):
            entry["workloads"].append("newfam_cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return before


def test_new_family_runs_as_new_files_and_entries_alone(tmp_path):
    """What a model_config PR does: its builder with its FLOP count, its
    reference, its configuration, its cell, and no edit.  (Before PR 27
    this died after the window: ``no FLOP count for family 'newfam'``.)"""
    import run as cli

    root = make_root(tmp_path)
    before = _add_family(root)
    for path, content in before.items():
        assert open(path, "rb").read() == content, path

    line = cli.execute("newfam_cell", seed=2**31 + 5, seconds=0.5,
                       trace=True, root=root, allow_cpu=True)
    assert line["correct"] is True, line["checks"]
    assert set(line["checks"]) == {
        "losses_finite", "loss_falls", "nothing_built_in_window",
        "matches_reference", "gradient_matches_reference"}
    assert line["notes"]["model_flops_per_item"] == 3.0 * 2 * (16 * 32
                                                               + 32 * 10)
    assert "compile_s" in line["metrics"]
    # no scope of the transformer's in this family: nothing to read
    assert "attn_ms" not in line["metrics"]
    line = cli.execute("newfam_cell", seed=2**31 + 5, seconds=0.5,
                       trace=False, root=root, allow_cpu=True)
    assert set(line["metrics"]) == {"train_throughput", "step_ms_p90",
                                    "setup_s"}


def test_family_without_a_flop_count_is_refused_before_it_builds(
        tmp_path, monkeypatch):
    import pytest

    import run as cli

    root = make_root(tmp_path)
    marker = tmp_path / "built"
    monkeypatch.setenv("NEWFAM_BUILD_MARKER", str(marker))
    _add_family(root, NEW_FAMILY.replace("def train_flops_per_item",
                                         "def some_other_name"))
    with pytest.raises(SystemExit) as refused:
        cli.execute("newfam_cell", seed=1, seconds=0.5, trace=False,
                    root=root, allow_cpu=True)
    message = str(refused.value)
    assert os.path.join("benchmark", "models", "newfam.py") in message
    assert "train_flops_per_item" in message
    assert not marker.exists()            # build was never called
    with pytest.raises(SystemExit, match="nofamily.py is missing"):
        registry.load_model_builder("nofamily", root)
