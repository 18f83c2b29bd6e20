"""SDAR-30B-A3B's mechanisms on the training path (``model_type:
sdar_moe``): block-diffusion training (a noised copy beside the clean one
under the block-diffusion mask, repeated positions, the head over the
noised half, a masked loss weighted by ``1 / t``) of a grouped-query
transformer with head norms and rotary positions whose every layer holds
routed experts behind a softmax-over-the-chosen router.  The program
(``models/transformer.py``, ``models/block_diffusion.py``) against the
benchmark's own plain reference
(``benchmark/configs/sdar-30b-a3b-chat.reference.py``) on seeded weights;
each of the reference's departures told at that size; the eight shares of
the experts adding up to the uncut layer; the noise's masked share and
its freshness; the published values of the named size and the counts of
the model and its cut; the paths that refuse the new setting.
All on the CPU at small sizes: hidden 64, 8 query heads over 2 key/value
heads of 16, 8 experts of width 32, 3 a token, 32 tokens (64 rows) in
blocks of 4.
"""

import functools
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import block_diffusion as bd
from horovod_tpu.models.transformer import (GPT_CONFIGS, Block,
                                            TransformerConfig, gpt)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "sdar-30b-a3b-chat"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("sdar_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

COEF = 0.05
SEQ, BLOCK, VOCAB = 32, 4, 256
MASK_TOKEN = VOCAB - 1
SMALL = dict(
    num_layers=2, vocab_size=VOCAB, emb_dim=64, num_heads=8, num_kv_heads=2,
    head_size=16, routed_experts=8, routed_held=2, routed_first_held=4,
    routed_top_k=3, routed_width=32, routed_balance_coef=COEF, max_len=64,
    block_diffusion=BLOCK, attention_impl="reference",
    # several tiles a copy
    flash_block_q=16, flash_block_k=8, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6, num_hidden_layers=2,
    num_experts=2, first_held_expert=4, num_experts_per_tok=3,
    balance_loss_coef=COEF, block_length=BLOCK, mask_token_id=MASK_TOKEN)
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0, MASK_TOKEN)
NOISE = bd.draw(jax.random.PRNGKey(7), 2, SEQ, BLOCK)
BATCH = {"tokens": TOKENS, "masked": NOISE.masked, "t": NOISE.t}


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


@functools.lru_cache(maxsize=None)
def init(key=1):
    """Seeded parameters; the router ten times its initial size so that
    the logits spread at this width, and the norms' weights away from
    1."""
    variables = jax.jit(small_model().init)(
        jax.random.PRNGKey(key), jnp.zeros((2, 2 * SEQ), jnp.int32))

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if "scale" in name:
            return leaf + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf

    return {"params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program(model, variables, batch=BATCH):
    """As the step writes it: the pair, the noised rows' logits, the
    weighted masked loss plus the coefficient times the balance losses
    the expert layers sowed; and the masked positions' log-probabilities."""
    noise = bd.Noise(batch["masked"], batch["t"])
    pair, positions = bd.paired(batch["tokens"], noise, MASK_TOKEN)
    logits, sown = model.apply(variables, pair, positions=positions,
                               mutable=["losses"])
    picked = bd.label_logprobs(logits, batch["tokens"])
    loss = bd.loss(picked, noise) + model.cfg.routed_balance_coef * sum(
        jax.tree.leaves(sown.get("losses", {})))
    return loss, jnp.where(noise.masked, picked, 0.0)


@functools.lru_cache(maxsize=None)
def _program_side(attention):
    """(loss, masked log-probabilities, gradient) of the program on the
    seeded weights, one jitted program an attention schedule."""
    model = small_model(attention_impl=attention)
    with jax.default_matmul_precision("highest"):
        (loss, logp), grad = jax.jit(jax.value_and_grad(
            lambda v: program(model, v), has_aux=True))(init())
    return loss, logp, grad


def _compare(attention="reference", depart=None):
    """(loss apart, largest log-probability apart, gradients apart over
    the reference's norm) between the program and the reference."""
    got, got_logp, got_grad = _program_side(attention)
    variables = init()
    with jax.default_matmul_precision("highest"):
        want, want_grad = jax.jit(jax.value_and_grad(
            lambda v: ref.loss(CONFIG, v, BATCH, depart=depart)))(variables)
        want_logp = jax.jit(lambda v: ref.logprob(
            CONFIG, v, BATCH, depart=depart))(variables)
    norm = lambda t: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree.leaves(t))))
    apart = jax.tree.map(lambda a, b: a - b, got_grad, want_grad)
    return (abs(float(got) - float(want)),
            float(jnp.abs(got_logp - want_logp).max()),
            norm(apart) / norm(want_grad))


@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_model_matches_plain_reference(attention):
    model = small_model(attention_impl=attention)
    variables = init()
    loss, logp, grad = _compare(attention)
    assert loss <= 2e-5 and logp <= 2e-4 and grad <= 2e-4, (loss, logp,
                                                            grad)
    # the logits are the noised half's alone, at repeated positions
    pair, positions = bd.paired(TOKENS, NOISE, MASK_TOKEN)
    assert pair.shape == (2, 2 * SEQ)
    np.testing.assert_array_equal(positions[:SEQ], positions[SEQ:])
    logits, default = jax.jit(lambda v: (
        model.apply(v, pair, positions=positions), model.apply(v, pair)))(
            variables)
    assert logits.shape == (2, SEQ, VOCAB)
    np.testing.assert_allclose(logits, default, atol=1e-6)  # they repeat


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart):
    """Each fault seeded into the reference's mathematics moves the loss
    or the gradient far past what the sound comparison reads."""
    loss, logp, grad = _compare(depart=depart)
    assert loss > 1e-2 or grad > 2e-2, (depart, loss, logp, grad)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of eight, three a token.  Every
    share computes the same attention under the mask and the same router
    decision, and its own experts' part of the routed sum: the routed
    parts of all, with the rest counted ONCE, are the whole layer as the
    uncut reference gives it."""
    from horovod_tpu.ops.rope import rope_tables

    cfg = small_model(routed_held=8, routed_first_held=0).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 2 * SEQ, 64))
    positions = jnp.concatenate([jnp.arange(SEQ)] * 2)
    tabs = rope_tables(positions, cfg.rope_dim, cfg.rope_theta)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), "attention", "routed")

    variables = jax.jit(block(0, 8).init)(jax.random.PRNGKey(4), x,
                                          positions, tabs)
    assert "moe_state" not in variables          # no selection bias
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0

    @functools.partial(jax.jit, static_argnums=0)
    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply({"params": mine}, x, positions, tabs)

    config = {**CONFIG, "num_experts": 8, "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)       # the stream and attention
        total = alike + sum(share(first) - alike
                            for first in range(0, 8, 2))
        uncut, _ = jax.jit(lambda p, x: ref._block(config, p, x, None))(p, x)
        one = share(2)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    # and one share alone is NOT the layer: it leaves out six experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


def test_the_noise_masks_half_the_tokens_and_is_fresh_each_step():
    """A level a block, uniform in ``[0.001, 1)``; a token masked with
    its block's probability; the weights ``1 / t`` at masked positions
    alone; a new key, a new draw."""
    key = jax.random.PRNGKey(11)
    noise = bd.draw(key, 4, 4096, 4)
    levels = np.asarray(noise.t).reshape(4, 1024, 4)
    assert (levels == levels[..., :1]).all()          # one level a block
    assert 0.001 <= levels.min() and levels.max() < 1.0
    assert abs(float(levels.mean()) - 0.5) < 0.02
    assert abs(float(noise.masked.mean()) - 0.5) < 0.02
    # the estimator is unbiased: masked / t has mean one
    assert abs(float((noise.masked / noise.t).mean()) - 1.0) < 0.2
    k1, k2 = jax.random.split(key)
    again, other = bd.draw(k1, 4, 4096, 4), bd.draw(k2, 4, 4096, 4)
    np.testing.assert_array_equal(again.masked, bd.draw(k1, 4, 4096, 4).masked)
    assert float((again.masked != other.masked).mean()) > 0.3
    tokens = jnp.full((4, 4096), 5)
    got, pair, positions = bd.noised_inputs(k1, tokens, 4, MASK_TOKEN)
    np.testing.assert_array_equal(got.masked, again.masked)
    np.testing.assert_array_equal(
        pair[:, :4096] == MASK_TOKEN, again.masked)
    np.testing.assert_array_equal(pair[:, 4096:], tokens)
    with pytest.raises(ValueError, match="does not divide"):
        bd.draw(key, 1, 10, 4)


def test_the_loss_is_the_masked_cross_entropy_weighted_by_one_over_t():
    logp = -jnp.arange(8, dtype=jnp.float32).reshape(1, 8)
    noise = bd.Noise(jnp.array([[1, 0, 1, 1, 0, 0, 0, 1]], bool),
                     jnp.array([[.5, .5, .5, .5, .25, .25, .25, .25]]))
    want = (0 / .5 + 2 / .5 + 3 / .5 + 7 / .25) / 8
    assert float(bd.loss(logp, noise)) == pytest.approx(want)
    assert bd.visible_pairs(8192, 4) == 8192 * 4 + 8192 ** 2 == 67_141_632


def test_the_mask_tokens_row_adds_its_gradient_up_in_float32():
    """The mask token's row of the table is looked up once a masked
    position, thousands of times a step: gathered from the float32 table
    and cast after, its gradient is a float32 sum.  (Cast first, as the
    module does it, 2048 bfloat16 terms lose a tenth of their norm and
    more.)"""
    small = dict(SMALL, num_layers=1, max_len=8192)
    tokens = jnp.full((1, 4096), MASK_TOKEN).at[:, 2048:].set(5)
    variables = jax.jit(gpt(NAME, **small).init)(
        jax.random.PRNGKey(0), tokens)

    def row(dtype):
        model = gpt(NAME, **{**small, "dtype": dtype})
        grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
            model.apply({"params": p}, tokens).astype(jnp.float32)))))(
                variables["params"])
        return grads["wte"]["embedding"][MASK_TOKEN]

    half, full = row(jnp.bfloat16), row(jnp.float32)
    assert float(jnp.linalg.norm(half - full)
                 / jnp.linalg.norm(full)) < 0.03
    summed = lambda dtype: jnp.zeros((1, 64), dtype).at[
        jnp.zeros((2048,), jnp.int32)].add(jnp.full((2048, 64), 0.37, dtype))
    assert float(summed(jnp.bfloat16)[0, 0]) < 0.9 * float(
        summed(jnp.float32)[0, 0])


def test_visible_pairs_count_the_dense_mask():
    from horovod_tpu.parallel.ring_attention import block_diffusion_mask

    for length, block in ((32, 4), (64, 32), (16, 1), (8, 8)):
        assert bd.visible_pairs(length, block) == int(
            block_diffusion_mask(2 * length, block).sum())


PUBLISHED = dict(
    vocab_size=151936, num_layers=48, emb_dim=2048, max_len=32768,
    num_heads=32, kv_heads=4, head_dim=128, qk_norm=True,
    pos_embedding="rope", rope_theta=1e6, block_diffusion=4,
    mlp="silu_gated", norm="rmsnorm", norm_eps=1e-6, use_bias=False,
    tie_embeddings=False, routed_experts=128, routed_top_k=8,
    routed_width=768, routed_scores="softmax_chosen",
    routed_activation="silu", routed_router_input="ffn_input",
    shared_experts=0, dense_layers_first=0, routed_scaling=1.0,
    layer_types=None, attention_window=None,
    remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.routed_balance_coef > 0
    assert {cfg.ffn_type(i) for i in range(48)} == {"routed"}
    assert cfg.rotates("attention") and cfg.rope_dim == 128


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def test_the_named_size_counts_30532122624_parameters():
    shapes = jax.eval_shape(lambda: gpt(NAME).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    outside = sum(_count(p["block0"][k]) for k in (
        "qkv", "proj", "q_norm", "k_norm", "ln1", "ln2", "router"))
    assert outside == 19_140_864
    assert _count(p["block0"]) == 623_120_640
    assert _count(p) == 30_532_122_624
    assert "moe_state" not in shapes


def test_the_cut_counts_645623296_parameters():
    """The benchmark's cut from the named size: depth 48 -> 6, 16 of 128
    experts held, an eighth of the vocabulary; every width as published
    (ISSUE 55 has the sum)."""
    model = gpt(NAME, num_layers=6, routed_held=16, vocab_size=18992)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    for i in range(6):
        assert _count(p[f"block{i}"]) == 94_638_336
    assert _count(p["wte"]) == _count(p["head"]) == 38_895_616
    assert _count(p) == 645_623_296
    # per expert layer: rows of 16 held experts, rows dropped, the load of
    # all 128, the overflow counter and the balance loss
    assert _count(shapes["moe_stats"]) == 6 * (16 + 1 + 128 + 1 + 1)


PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]


@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_the_mask_by_name(path):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's
    causal block from raw weights: each refuses the block-diffusion mask
    by name, before anything is traced."""
    from test_glm_moe_mla import _refusals

    cfg = replace(gpt("nano").cfg, block_diffusion=4)
    with pytest.raises(ValueError, match="block_diffusion"):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


@pytest.mark.parametrize("override", [
    {"block_diffusion": 3}, {"block_diffusion": 0},
    {"attention_window": 8}, {"attention_impl": "ring", "sp_axis": "sp"},
    {"differential_attention": True},
    {"layer_types": ("attention", "mamba"), "ssm_heads": 4},
])
def test_configuration_refuses_what_the_mask_cannot_mean(override):
    with pytest.raises(ValueError, match="block_diffusion"):
        small_model(**override)


def test_the_default_is_the_causal_mask():
    assert TransformerConfig().block_diffusion is None
    for size, named in GPT_CONFIGS.items():
        assert (named.block_diffusion is None) == (size != NAME), size


def test_the_attention_call_traces_under_its_scope_and_counts_itself():
    """The scope ``attn_block_diffusion`` inside ``attn`` around the flash
    call, and the gauges the readers read."""
    from horovod_tpu.obs.registry import get_registry

    model = small_model(attention_impl="flash")
    variables = init()
    pair, positions = bd.paired(TOKENS, NOISE, MASK_TOKEN)
    text = jax.jit(lambda v: model.apply(v, pair, positions=positions)
                   ).lower(variables).as_text(debug_info=True)
    assert "block0/attn/attn_block_diffusion/flash_fwd" in text
    registry = get_registry()
    gauge = lambda name, **labels: registry.gauge(name, **labels).value
    assert (gauge("bd.block"), gauge("bd.rows")) == (BLOCK, 2 * SEQ)
    rows = 2 * 8        # batch x heads
    assert gauge("bd.visible_pairs", layer_type="attention") == \
        rows * bd.visible_pairs(SEQ, BLOCK)
    live = gauge("flash.tiles_live", layer_type="attention")
    assert gauge("bd.live_tile_pairs", layer_type="attention") == \
        live * 16 * 8
    assert live == gauge("flash.tiles_grid", layer_type="attention") == \
        rows * 2 * 2 * (2 + 2)      # r n (n + 2) at r = 2, n = 2
    assert bd.publish_masked(jnp.int32(17)) == 17.0 == gauge(
        "bd.masked_tokens")
