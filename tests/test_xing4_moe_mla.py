"""Xing4.0-29B-A4B's mechanisms on the training path (``model_type:
xing4_0``): a residual stream of four copies mixed by manifold-constrained
hyper-connections around latent attention whose rotary channels turn at
YaRN's blended frequencies, a dense feed-forward in the leading layer and
routed experts behind a sigmoid router with a selection bias beside one
shared expert in the others; an untied head.  The program
(``models/transformer.py``, ``models/hyper_connections.py``,
``ops/rope.py``, ``parallel/moe.py``) against the benchmark's own plain
reference (``benchmark/configs/xing4.0-29b-a4b.reference.py``) on seeded
weights; each seeded departure told; the eight shares of the experts
adding up to the uncut layer; the published values of the named size and
the counts of the model and of its cut.
All on the CPU at small sizes: hidden 64, 4 heads with keys of 16 + 8
over values of 16, ranks 24 and 16, YaRN from an original context of 16,
a dense width of 96, 16 experts of width 32, 4 a token, 32 tokens, a
dense layer and an expert layer.
"""

import functools
import importlib.util
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import GPT_CONFIGS, Block, gpt
from horovod_tpu.ops.rope import rope_tables, yarn_mscale

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "xing4.0-29b-a4b"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("xing4_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

YARN = dict(type="yarn", factor=64, original_max_position_embeddings=16,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
SMALL = dict(
    num_layers=2, layer_types=("mla",) * 2, dense_layers_first=1,
    vocab_size=256, emb_dim=64, num_heads=4, num_kv_heads=4, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mlp_width=96, routed_experts=16, routed_held=4, routed_first_held=4,
    routed_top_k=4, routed_width=32, max_len=128, mtp_modules=0,
    rope_scaling=YARN, attention_scale=24 ** -0.5 * yarn_mscale(64, 1) ** 2,
    attention_impl="reference",
    # several tiles a row
    flash_block_q=16, flash_block_k=8, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=YARN,
    num_hidden_layers=2, first_k_dense_replace=1, n_routed_experts=4,
    first_held_expert=4, num_experts_per_tok=4, routed_scaling_factor=2,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)
BATCH = {"tokens": TOKENS}


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread at this width, and the norms' weights (the
    hyper-connections' among them) away from 1."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if "scale" in name:
            return leaf + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf

    return {"params": jax.tree_util.tree_map_with_path(
                moved, variables["params"]),
            "moe_state": variables["moe_state"]}


def program_logprob(model, variables, tokens):
    logits = model.apply(variables, tokens[:, :-1])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def grads_of(loss, variables):
    return jax.grad(lambda p: loss({**variables, "params": p}))(
        variables["params"])


@functools.cache
def sound():
    """The seeded variables and what the plain reference gives for them,
    computed once."""
    variables = init(small_model())

    def loss(p):  # one trace of the reference gives both
        logp = ref.logprob(CONFIG, {**variables, "params": p}, BATCH)
        return -logp.mean(), logp

    with jax.default_matmul_precision("highest"):
        grads, logp = jax.jit(jax.grad(loss, has_aux=True))(
            variables["params"])
    return variables, logp, grads


def test_model_matches_plain_reference():
    """The loss, every label's log-probability and every leaf of the
    gradient (the gains, biases and projections of both sub-layers'
    hyper-connections among them, none of them zero), through the flash
    kernels (the Pallas interpreter, keys of 24 over values of 16) with
    every block recomputed from its four-stream input.  (The reference
    attention schedule and kept blocks run the same cell's tiny size in
    ``benchmark/tests/test_xing4_cpu.py``.)"""
    model = small_model(attention_impl="flash", remat=True)
    variables, want_logp, want_grads = sound()
    with jax.default_matmul_precision("highest"):
        got_logp, got_grads = jax.jit(lambda v: (
            program_logprob(model, v, TOKENS),
            grads_of(lambda t: -program_logprob(model, t, TOKENS).mean(),
                     v)))(variables)
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_logp.mean(), want_logp.mean(), atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    names = {jax.tree_util.keystr(path) for path in flat_want}
    for half in ("hc_attn", "hc_mlp"):
        for leaf in ("scale", "phi", "b", "alpha"):
            assert f"['block1']['{half}_{leaf}']" in names
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_departures_are_eight():
    assert len(ref.DEPARTURES) == 8 and "streams_averaged" in ref.DEPARTURES


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart):
    """Seven of the eight move the loss ten times further and more than
    the program stands from the sound reference.  ``streams_averaged`` cannot:
    the final RMS norm divides the factor 4 out again but for its eps of
    1e-6 under a mean square of order 1, and the test holds that too, so
    that nobody takes the departure for a check."""
    variables, want_logp, _ = sound()
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.loss(CONFIG, v, BATCH, depart))(
            variables)
    # (test_model_matches_plain_reference holds the program to the sound
    # reference's loss within 1e-5)
    apart = abs(float(-want_logp.mean() - departed))
    if depart == "streams_averaged":
        assert apart < 1e-5
    else:
        assert apart > 1e-4


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of sixteen (eight each of
    sixty-four at the real size), four a token, beside one shared expert.  Every share computes the same two
    hyper-connections, the same latent attention, the same router
    decision and the same shared expert, and its own experts' part of
    the routed sum, which the write-back carries into all four streams:
    the routed parts of all eight, with the rest counted ONCE, are the
    whole layer as the uncut reference gives it."""
    seq = 16
    cfg = small_model(routed_held=16, routed_first_held=0).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, seq, 4 * 64))
    positions = jnp.arange(seq)
    tabs = rope_tables(positions, cfg.rope_dim, cfg.rope_theta,
                       dict(cfg.rope_scaling))

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), "mla", "routed")

    variables = jax.jit(block(0, 16).init)(jax.random.PRNGKey(4), x,
                                           positions, tabs)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    bias = variables["moe_state"]["bias"]
    assert bias.shape == (16,) and "shared_fc1" in p and "hc_mlp_phi" in p

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply(
            {"params": mine, "moe_state": {"bias": bias}}, x, positions,
            tabs)

    config = {**CONFIG, "n_routed_experts": 16, "first_held_expert": 0}

    @jax.jit  # one program: op by op the nine applies take a third of a minute
    def sides():
        # the streams, both connections, attention, the shared expert
        alike = share(0, fc2_scale=0.0)
        total = alike + sum(share(first) - alike
                            for first in range(0, 16, 2))
        return (total, share(2),
                ref.block(config, p, bias, x.reshape(2, seq, 4, 64)))

    with jax.default_matmul_precision("highest"):
        total, one, uncut = sides()
    np.testing.assert_allclose(total, uncut.reshape(total.shape), atol=1e-4)
    # and one share alone is NOT the layer: it leaves out 14 experts
    assert float(jnp.abs(one - uncut.reshape(one.shape)).max()) > 1e-2


PUBLISHED = dict(
    vocab_size=131072, num_layers=40, emb_dim=3584, num_heads=32,
    kv_heads=32, ffn_width=9216, q_lora_rank=768, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10000.0, norm_eps=1e-6, routed_experts=64, held_experts=64,
    routed_top_k=4, routed_width=1024, routed_scaling=2.0, shared_experts=1,
    dense_layers_first=2, mtp_modules=1, max_len=262144,
    tie_embeddings=False, use_bias=False, norm="rmsnorm", mlp="silu_gated",
    pos_embedding="rope", routed_scores="sigmoid",
    routed_router_input="ffn_input", hc_mult=4, hc_sinkhorn_iters=20,
    hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0),
    remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert set(cfg.layer_types) == {"mla"} and cfg.rotates("mla")
    assert dict(cfg.rope_scaling) == dict(
        type="yarn", factor=64, original_max_position_embeddings=4096,
        beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
    # 192 ** -0.5 times (0.1 ln 64 + 1) ** 2
    assert cfg.attention_scale == pytest.approx(0.14468, abs=1e-5)
    assert [cfg.ffn_type(i) for i in range(3)] == ["dense", "dense",
                                                   "routed"]
    # no other named size has more than one stream or scales its rotation
    for size, named in GPT_CONFIGS.items():
        if size != NAME:
            assert named.hc_mult == 1 and named.rope_scaling is None, size
    with pytest.raises(ValueError, match="hc_mult=4 with mtp_modules=1"):
        jax.eval_shape(lambda: gpt(NAME).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def _shapes(**overrides):
    model = gpt(NAME, mtp_modules=0, attention_impl="reference", **overrides)
    return jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))


def test_the_whole_model_counts_30277368230_parameters():
    """Forty layers, the embedding, the head and the final norm as the
    program builds them (the prediction module off: the program has no
    wiring for it on four streams), and the module counted by hand as
    the configuration file states it: one expert layer's block with its
    two hyper-connections, the ``2 C x C`` projection, three norms."""
    # the two leading dense layers and ONE of the 38 expert layers built,
    # the other 37 counted from it
    p = _shapes(num_layers=3, layer_types=("mla",) * 3)["params"]
    connection = 14336 * 24 + 24 + 3 + 14336
    assert sum(_count(p["block0"][f"hc_attn_{k}"]) for k in (
        "scale", "phi", "b", "alpha")) == connection == 358_427
    assert sum(_count(p["block0"][k]) for k in (
        "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
        "proj")) == 28_411_136
    assert _count(p["block0"]) == _count(p["block1"]) == 128_225_590
    assert _count(p["block2"]) == 745_017_654 == (
        28_411_136 + 7168 + 2 * connection + 229_376 + 65 * 11_010_048)
    assert _count(p["wte"]) == _count(p["head"]) == 131072 * 3584
    assert set(p) == {"wte", "block0", "block1", "block2", "lnf", "head"}
    whole = _count(p) + 37 * _count(p["block2"])
    assert whole == 29_506_649_712
    module = 745_017_654 + 2 * 3584 * 3584 + 3 * 3584
    assert whole + module == 30_277_368_230


def test_the_cut_counts_759489550_parameters():
    """The benchmark's cut from the named size: depth 40 -> 5 (published
    layer 0 and four expert layers), 8 of 64 experts held, an eighth of
    the vocabulary, no prediction module; every width as published
    (ISSUE 57 has the sum)."""
    shapes = _shapes(num_layers=5, layer_types=("mla",) * 5,
                     dense_layers_first=1, routed_held=8, vocab_size=16384)
    p = shapes["params"]
    assert _count(p["block0"]["fc1"]) + _count(
        p["block0"]["fc2"]) == 99_090_432
    assert _count(p["block1"]["experts_fc1"]) + _count(
        p["block1"]["experts_fc2"]) == 8 * 11_010_048
    assert [_count(p[f"block{i}"]) for i in range(5)] == [
        128_225_590] + [128_454_966] * 4
    assert _count(p["wte"]) == _count(p["head"]) == 58_720_256
    assert _count(p) == 759_489_550
    # 12 B a parameter of step arguments: 8.49 GiB
    assert round(_count(p) * 12 / 2 ** 30, 2) == 8.49
    assert _count(shapes["hc_stats"]) == 10
    assert _count(shapes["moe_stats"]) == 4 * (8 + 1 + 64 + 1)


def test_the_configuration_file_states_the_cut_and_both_counts():
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           NAME + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert [config[k] for k in config["reduced"]] == [5, 1, 8, 16384, 0]
    assert [config["published"][k] for k in config["reduced"]] == [
        40, 2, 64, 131072, 1]
    assert config["published"]["parameters"] == 30_277_368_230
    assert config["published"]["parameters_here"] == 759_489_550
    assert "759 489 550" in config["deployment"]
    assert "30 277 368 230" in config["deployment"]
    assert config["hidden_size"] == 3584 and config["hc_mult"] == 4
