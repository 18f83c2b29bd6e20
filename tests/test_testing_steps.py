"""The package's synthetic step builders (``horovod_tpu/testing/steps.py``)
on the 8-device CPU mesh: every gradient plane trains, the loss the step
returns is the global one, and the builders make the parameter tree the
benchmark's own family builders make — so ``chip_smoke.py`` smokes the
program the cells measure.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.testing.steps import build_gpt_step, build_step

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 8

# (builder, its arguments, entries of ``state`` that are the batch)
GPT = (build_gpt_step, ("nano", "fp32", 2, 64), 1)
RESNET = (build_step, ("resnet18", "fp32", 2, 32), 2)


def _build(which, **kwargs):
    builder, args, n_const = which
    if builder is build_gpt_step:
        kwargs.setdefault("attention", "reference")
    step, state, static = builder(*args, **kwargs)
    assert static["n_chips"] == N
    assert static["global_batch"] == 2 * N
    assert static["carry_len"] == len(state) - n_const
    return step, state, static


@pytest.mark.parametrize("mode", ["off", "bucket", "bucket+zero1"])
@pytest.mark.parametrize("which", [GPT, RESNET], ids=["gpt_nano", "resnet18"])
def test_every_gradient_plane_trains(which, mode, monkeypatch):
    """Two steps under each ``overlap_mode``: the loss is finite and
    falls on the fixed batch, and the second call reuses the first's
    trace (the carry comes back in the shapes, dtypes and shardings it
    went in with)."""
    # 1 MB buckets: several buckets even at these sizes
    monkeypatch.setenv("HVDTPU_GRAD_BUCKET_MB", "1")
    step, state, static = _build(which, overlap_mode=mode)
    carry = list(state[:static["carry_len"]])
    const = list(state[static["carry_len"]:])
    losses = []
    for _ in range(2):
        *carry, loss = step(*carry, *const)
        losses.append(float(loss))
    assert np.isfinite(losses).all(), losses
    assert losses[1] < losses[0], losses
    assert step._cache_size() == 1


def _shard_losses_gpt(state):
    from horovod_tpu.models.transformer import gpt

    params, _, tokens = state
    model = gpt("nano", dtype=jnp.float32, max_len=64,
                attention_impl="reference")

    def loss(toks):
        logits = model.apply(params, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]).mean()

    loss = jax.jit(loss)  # one trace for the eight shards
    return [float(loss(shard)) for shard in np.split(np.asarray(tokens), N)]


def _shard_losses_resnet(state):
    from horovod_tpu import models

    params, batch_stats, _, images, labels = state
    model = models.ResNet18(num_classes=1000, compute_dtype=jnp.float32)

    def loss(x, y):
        # train=True: each shard normalises by its own batch, as in the step
        logits, _ = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    loss = jax.jit(loss)  # one trace for the eight shards
    return [float(loss(x, y)) for x, y in zip(
        np.split(np.asarray(images), N), np.split(np.asarray(labels), N))]


@pytest.mark.parametrize("which,shard_losses", [
    (GPT, _shard_losses_gpt), (RESNET, _shard_losses_resnet),
], ids=["gpt_nano", "resnet18"])
def test_returned_loss_is_the_global_mean(which, shard_losses):
    """``out_specs=P()`` presents the loss as replicated, so the step has
    to make it so: the mean over every shard's batch, not shard 0's."""
    step, state, static = _build(which)
    want = shard_losses(state)  # before the call: the step donates its carry
    assert max(want) - min(want) > 1e-3, want  # the shards do differ
    loss = step(*state)[-1]
    assert abs(float(loss) - np.mean(want)) < 1e-4, (float(loss), want)
    assert abs(float(loss) - want[0]) > 1e-4


# ------------------------------------------ the tree the cells measure

def _tree(variables):
    return sorted(
        (jax.tree_util.keystr(path), leaf.shape, str(leaf.dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(variables)[0])


def _benchmark_variables(family: str, config: str, params: dict):
    """What the benchmark's family builder makes (read-only use of
    ``benchmark/``), at a tiny size through its own ``overrides``."""
    from benchmark.harness import registry

    with open(os.path.join(REPO_ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        values = json.load(f)
    assert values["family"] == family
    built = registry.load_model_builder(family).build(values, params, seed=0)
    return built.variables(built.state)


def test_gpt_builders_make_the_same_parameter_tree():
    from horovod_tpu.models.transformer import GPT_CONFIGS

    nano = GPT_CONFIGS["nano"]
    _, state, _ = _build(GPT)
    theirs = _benchmark_variables("gpt2", "gpt2-medium", {
        "seq_len": 64, "per_chip_batch": 2, "attention": "reference",
        "learning_rate": 1e-4,
        "overrides": {"num_layers": nano.num_layers,
                      "num_heads": nano.num_heads,
                      "emb_dim": nano.emb_dim,
                      "vocab_size": nano.vocab_size}})
    assert _tree(state[0]) == _tree(theirs)


def test_resnet_builders_make_the_same_parameter_tree():
    _, state, _ = _build(RESNET)
    theirs = _benchmark_variables("resnet", "resnet50-v1.5", {
        "per_chip_batch": 2, "learning_rate": 0.01, "momentum": 0.9,
        "overrides": {"factory": "ResNet18", "image_size": 32}})
    assert _tree({"params": state[0], "batch_stats": state[1]}) \
        == _tree(theirs)
