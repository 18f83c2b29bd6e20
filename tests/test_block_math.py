"""``block_math`` on stub callables: what is the block's own (the two
norms, the scaled residual adds with their post-norms, the mixer's scope
by layer type, the router's decision into the feed-forward, the value a
layer hands on), whatever the mixer is; and ``require_gpt2_block`` over
every field of the configuration."""

from dataclasses import fields, replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (RAW_BLOCK_SETTINGS,
                                            TransformerConfig, block_math,
                                            require_gpt2_block)

X = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16), jnp.float32)
# stubs a float tells apart: each step of the wiring leaves its own mark
STUBS = dict(ln1=lambda x: x + 1.0, ln2=lambda x: x - 2.0,
             post_attn_norm=lambda d: d * 3.0, post_mlp_norm=lambda d: d * 5.0)


# the five mixers (mamba_mixer, selective_scan_mixer, gmu_mixer,
# mla_mixer, attention_mixer) by the layer type that takes each, the
# scope it traces under and what such a layer may hand on
@pytest.mark.parametrize("hands_on", [False, True],
                         ids=["keeps_its_value", "hands_on"])
@pytest.mark.parametrize("layer_type,scope,hand_on", [
    ("mamba", "ssm", "memory"), ("selective_scan", "ssm", "memory"),
    ("gmu", "gmu", "memory"), ("conv", "short_conv", "memory"),
    ("mla", "attn", "kv"), ("attention", "attn", "kv")])
def test_block_math_adds_the_scaled_post_normed_delta(layer_type, scope,
                                                      hand_on, hands_on):
    """``x + m * post(mixer(ln1(x)))``, then the same around the
    feed-forward, ``m`` the configuration's residual multiplier; under
    ``hand_on`` the mixer's second result comes back beside the stream;
    the mixer traces under its layer type's scope and the feed-forward
    under ``mlp``."""
    cfg = TransformerConfig(residual_multiplier=0.25)
    hand_on = hand_on if hands_on else None
    mixer = lambda h: (h * 7.0, h - 11.0) if hands_on else h * 7.0
    mlp = lambda h: h * 13.0

    def block(x):
        return block_math(cfg, x, mixer=mixer, mlp=mlp,
                          layer_type=layer_type, hand_on=hand_on, **STUBS)

    out = block(X)
    mixed = X + 0.25 * ((X + 1.0) * 7.0 * 3.0)
    want = mixed + 0.25 * ((mixed - 2.0) * 13.0 * 5.0)
    if hands_on:
        out, handed = out
        np.testing.assert_array_equal(handed, (X + 1.0) - 11.0)
    np.testing.assert_allclose(out, want, rtol=1e-6)
    text = jax.jit(block).lower(X).as_text(debug_info=True)
    others = {"ssm", "gmu", "short_conv", "attn"} - {scope}
    assert f"/{scope}/mul" in text and "/mlp/mul" in text
    assert not any(f"/{other}/" in text for other in others)


def test_block_math_hands_the_routers_decision_to_the_feed_forward():
    """``route`` reads the block's input ahead of ``ln1`` and what it
    decides reaches ``mlp`` beside ``ln2`` of the stream after the
    mixer; without post-norms and at a multiplier of 1 the adds are
    plain."""
    cfg = TransformerConfig()
    seen = {}

    def mlp(h, decided):
        seen["decided"] = decided
        return h * decided

    out = block_math(cfg, X, ln1=STUBS["ln1"], mixer=lambda h: h * 7.0,
                     ln2=STUBS["ln2"], mlp=mlp, route=lambda x: x.sum())
    mixed = X + (X + 1.0) * 7.0
    np.testing.assert_allclose(seen["decided"], X.sum(), rtol=1e-6)
    np.testing.assert_allclose(out, mixed + (mixed - 2.0) * X.sum(),
                               rtol=1e-5)


# a value no default equals, of a kind the refusal can print; the few
# fields the check reads beyond comparing them get one they can mean
ANOTHER = {"layer_types": ("attention",) * TransformerConfig().num_layers,
           "pos_embedding": "rope"}


@pytest.mark.parametrize("setting",
                         [f.name for f in fields(TransformerConfig)])
def test_raw_block_paths_refuse_every_setting_they_do_not_honour(setting):
    """Decode, serving, tensor and pipeline parallelism build GPT-2's
    block from raw weights.  A configuration that differs from the
    defaults in one field passes exactly where that field is one they
    honour (``RAW_BLOCK_SETTINGS``) and is refused by the field's name
    anywhere else: a field a later architecture adds is refused until
    it is listed."""
    cfg = replace(TransformerConfig())
    object.__setattr__(cfg, setting, ANOTHER.get(setting, "another value"))
    if setting in RAW_BLOCK_SETTINGS:
        require_gpt2_block(cfg, "a raw-weights path")
    else:
        with pytest.raises(ValueError, match=f"GPT-2's block only "
                                             f"\\({setting}="):
            require_gpt2_block(cfg, "a raw-weights path")


def test_the_settings_the_raw_block_paths_honour_are_fields():
    assert RAW_BLOCK_SETTINGS <= {f.name for f in fields(TransformerConfig)}
    assert len(fields(TransformerConfig)) == 92
