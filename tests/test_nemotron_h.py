"""NVIDIA-Nemotron-3-Nano-30B-A3B's mechanisms on the training path
(``model_type: nemotron_h``): layers of ONE half each (a Mamba-2 mixer
whose B, C and gated norm come in groups, grouped-query attention that
rotates nothing, or routed experts without a gate matrix, ``W_down
relu(W_up x)^2``, beside a shared expert of its own width), one norm a
layer, an untied head.  The program (``models/transformer.py``,
``ops/ssd.py``, ``parallel/moe.py``) against the benchmark's own plain
reference (``benchmark/configs/nvidia-nemotron-3-nano-30b-a3b-bf16.
reference.py``) on seeded weights in float32; every departure of the
reference told from it; the four shares of the experts adding up to the
uncut layer; the ungated grouped feed-forward's hand-written backward;
the norm by group; a block of one half; the counts of the model and of
its cut; the paths that refuse the new layer type and settings.
All on the CPU at small sizes: hidden 64, 8 Mamba heads of 8 in 4 groups
at a chunk of 16, 4 query heads over 2 key/value heads of 16, 16 experts
of width 32, 3 a token, a shared expert of 64, 32 tokens, the three
kinds of layer in the published order ``MEM*E``.
"""

import functools
import importlib.util
import os
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import scopes
from horovod_tpu.models.transformer import (FEED_FORWARD, GPT_CONFIGS,
                                            LAYER_TYPES, Block,
                                            TransformerConfig, gpt,
                                            mamba_mixer)
from horovod_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "nvidia-nemotron-3-nano-30b-a3b-bf16"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("nemotron_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

PATTERN = "MEM*E"
KINDS = tuple({"M": "mamba", "*": "full_attention", "E": FEED_FORWARD}[c]
              for c in PATTERN)
SMALL = dict(
    num_layers=5, layer_types=KINDS, vocab_size=256, emb_dim=64,
    num_heads=4, num_kv_heads=2, head_size=16, ssm_heads=8, ssm_head_dim=8,
    ssm_state=16, ssm_groups=4, ssm_chunk=16, mlp_width=48,
    routed_experts=16, routed_held=4, routed_first_held=8, routed_top_k=3,
    routed_width=32, shared_width=64, max_len=128,
    attention_impl="reference", dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, mamba_num_heads=8, mamba_head_dim=8, n_groups=4,
    ssm_state_size=16, conv_kernel=4, layer_norm_epsilon=1e-5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000, n_routed_experts=4, first_held_expert=8,
    num_experts_per_tok=3, routed_scaling_factor=2.5,
    moe_intermediate_size=32, hybrid_override_pattern=PATTERN,
    num_hidden_layers=5)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)
BATCH = {"tokens": TOKENS}
# float32 against float32 at this size: rounding alone
F32_LIMITS = dict(loss_abs=1e-5, logprob_abs=1e-4, grad_rel=1e-4)


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread at this width, and the norms' weights, the filter's
    bias and the skip away from their constant starts."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if any(part in name for part in ("scale", "ssm_norm", "conv_bias",
                                         "'D'")):
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


def program_loss(model, variables, tokens):
    return -program_logprob(model, variables, tokens).mean()


def grads_of(loss, variables):
    return jax.grad(lambda p: loss({**variables, "params": p}))(
        variables["params"])


def norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(leaf))
                              for leaf in jax.tree.leaves(tree))))


@functools.cache
def sound():
    """The seeded variables, and every label's log-probability and the
    loss's gradient as the program gives them, computed once."""
    model = small_model()
    variables = init(model)
    return (variables, *jax.jit(lambda v: (
        program_logprob(model, v, TOKENS),
        grads_of(lambda t: program_loss(model, t, TOKENS), v)))(variables))


def test_float32_program_is_the_reference_to_rounding():
    variables, logp, grads = sound()
    with jax.default_matmul_precision("highest"):
        want_logp, want_grads = jax.jit(lambda v: (
            ref.logprob(CONFIG, v, BATCH),
            grads_of(lambda t: ref.loss(CONFIG, t, BATCH), v)))(variables)
    assert abs(float(logp.mean() - want_logp.mean())) < F32_LIMITS["loss_abs"]
    assert float(jnp.abs(logp - want_logp).max()) < F32_LIMITS["logprob_abs"]
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    # every leaf on its own: a leaf whose gradient is wrong is a small
    # part of the whole tree's norm
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(want_grads)):
        assert norm(got - want) <= F32_LIMITS["grad_rel"] * max(
            norm(want), 1e-3), jax.tree_util.keystr(path)


def test_the_departures_are_the_ones_the_issue_names():
    assert set(ref.DEPARTURES) >= {
        "experts_gated", "relu_not_squared", "norm_one_group",
        "norm_before_gate", "groups_one", "shared_expert_dropped",
        "shared_width_routed", "bias_in_weights", "scaling_dropped",
        "attention_rotated", "conv_bias_dropped", "skip_D_dropped",
        "second_half_added"}


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_every_departure_moves_the_log_probabilities(depart):
    """Forward only (the benchmark's own test takes each departure's
    gradient, ``benchmark/tests/test_nemotron3n_cpu.py``): a reference
    that leaves the equations gives other log-probabilities than the
    program, ten times past the float32 limit or more (the scan's state
    held in bfloat16 the least: 0.004 against rounding's 3e-6)."""
    variables, logp, _ = sound()
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.logprob(CONFIG, v, BATCH, depart))(
            variables)
    assert float(jnp.abs(logp - departed).max()) > 10 * F32_LIMITS[
        "logprob_abs"]


def test_the_reference_blocks_its_tokens_without_changing_the_result(
        monkeypatch):
    """At 16 384 tokens the reference computes a Mamba layer's filter and
    norm, the feed-forwards, the attention rows and the head in blocks of
    tokens (the filter's blocks with the three tokens before them): the
    same numbers as in one piece."""
    variables, logp, _ = sound()
    for name, size in (("TOKEN_BLOCK", 8), ("TOKEN_RUN", 4),
                       ("ROW_BLOCK", 8), ("HEAD_BLOCK", 16)):
        monkeypatch.setattr(ref, name, size)
    with jax.default_matmul_precision("highest"):
        blocked = jax.jit(lambda v: ref.logprob(CONFIG, v, BATCH))(variables)
    assert float(jnp.abs(logp - blocked).max()) < F32_LIMITS["logprob_abs"]


# ---- the counts of ISSUE 61's Motivation -------------------------------

def _count(tree):
    return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))


@functools.cache
def _shapes(pattern, held, vocab):
    kinds = tuple({"M": "mamba", "*": "full_attention",
                   "E": FEED_FORWARD}[c] for c in pattern)
    model = gpt(NAME, num_layers=len(kinds), layer_types=kinds,
                routed_held=held, vocab_size=vocab,
                attention_impl="reference")
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, 128), jnp.int32))["params"]


def test_the_cut_counts_666962944_parameters():
    tree = _shapes("MEMEM*EME", 8, 16384)
    assert _count(tree["block0"]) == 38_744_896
    assert _count(tree["block5"]) == 23_399_040
    assert _count(tree["block1"]) == 100_125_312
    assert _count(tree) == 666_962_944
    # 12 B a parameter of step arguments: 7.45 GiB
    assert round(12 * _count(tree) / 2 ** 30, 2) == 7.45


def test_the_named_size_counts_31_58_billion_parameters():
    """From one layer of each kind with all 128 experts and the whole
    vocabulary, times the published 23 : 6 : 23."""
    tree = _shapes("M*E", 128, 131072)
    kinds = GPT_CONFIGS[NAME].layer_types
    assert _count(tree["block2"]) == 1_297_468_032
    whole = (kinds.count("mamba") * _count(tree["block0"])
             + kinds.count("full_attention") * _count(tree["block1"])
             + kinds.count(FEED_FORWARD) * _count(tree["block2"])
             + _count(tree["wte"]) + _count(tree["head"])
             + _count(tree["lnf"]))
    assert whole == 31_577_937_344
    assert (kinds.count("mamba"), kinds.count("full_attention"),
            kinds.count(FEED_FORWARD), len(kinds)) == (23, 6, 23, 52)


PUBLISHED = dict(
    vocab_size=131072, num_layers=52, emb_dim=2688, num_heads=32,
    kv_heads=2, head_dim=128, ssm_heads=64, ssm_head_dim=64, ssm_state=128,
    ssm_groups=8, ssm_conv=4, ssm_chunk=128, ssm_inner=4096, ffn_width=1856,
    norm_eps=1e-5, routed_experts=128, held_experts=128, routed_top_k=6,
    routed_width=1856, routed_scaling=2.5, shared_experts=1,
    shared_ffn_width=3712, dense_layers_first=0, mtp_modules=0,
    max_len=262144, tie_embeddings=False, use_bias=False, norm="rmsnorm",
    mlp="relu2", pos_embedding="none", routed_gated=False,
    routed_activation="relu2", routed_scores="sigmoid",
    routed_router_input="ffn_input", one_half=True,
    remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert "".join({"mamba": "M", "full_attention": "*",
                    FEED_FORWARD: "E"}[kind] for kind in cfg.layer_types) == (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    assert [cfg.ffn_type(i) for i in range(3)] == ["none", "routed", "none"]


# ---- a layer of one half ------------------------------------------------

def test_a_block_of_one_half_has_no_leaf_of_the_other():
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))
    mamba = {"ln1", "in_proj", "conv_kernel", "conv_bias", "dt_bias",
             "A_log", "D", "ssm_norm", "out_proj"}
    experts = {"ln1", "router", "experts_fc1", "experts_fc2", "shared_fc1",
               "shared_fc2"}
    params = tree["params"]
    assert set(params["block0"]) == mamba == set(params["block2"])
    assert set(params["block1"]) == experts == set(params["block4"])
    assert set(params["block3"]) == {"ln1", "qkv", "proj"}
    assert "wpe" not in params and "head" in params
    # no gate matrix: the first matrix is [held, d, ff]
    assert params["block1"]["experts_fc1"].shape == (4, 64, 32)
    assert params["block1"]["experts_fc2"].shape == (4, 32, 64)
    assert params["block1"]["shared_fc1"]["kernel"].shape == (64, 64)
    assert params["block3"]["qkv"]["kernel"].shape == (64, 64 + 2 * 32)
    assert params["block0"]["in_proj"]["kernel"].shape == (
        64, 2 * 64 + 2 * 4 * 16 + 8)
    # the selection bias and the counters live where the experts do
    assert set(tree["moe_state"]) == {"block1", "block4"}
    assert set(tree["moe_stats"]) == {"block1", "block4"}


def test_a_dense_feed_forward_layer_holds_a_norm_and_two_matrices():
    tree = jax.eval_shape(lambda: small_model(dense_layers_first=2).init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    assert set(tree["block1"]) == {"ln1", "fc1", "fc2"}
    assert tree["block1"]["fc1"]["kernel"].shape == (64, 48)
    assert "router" in tree["block4"]


def test_a_one_half_layer_traces_under_the_scope_its_half_always_had():
    """A step traced names a Mamba layer's only half ``ssm`` with
    ``ssd_scan`` and ``ssm_norm`` inside, the attention layer's ``attn``,
    an expert layer's ``mlp`` with its ``moe_*`` scopes inside; no layer
    carries the scope of the half it lacks; the gauges hold the scan's
    groups, its chunk and what a layer keeps, and the tile fill of the
    ungated first matmul."""
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.ops.ssd import kept_mib

    assert scopes.SSM_NORM in scopes.SCOPES
    model = small_model()
    variables = sound()[0]
    text = jax.jit(jax.grad(lambda p: program_loss(
        model, {**variables, "params": p}, TOKENS))).lower(
            variables["params"]).as_text(debug_info=True)
    for inner in ("ssd_scan", "ssm_norm"):
        names = set(re.findall(rf'"([^"]*/{inner}/[^"]*)"', text))
        assert any(f"jvp(GPT)/block0/ssm/{inner}/" in name
                   and "transpose(" not in name for name in names), inner
        assert any(f"transpose(jvp(GPT))/block0/ssm/{inner}/" in name
                   for name in names), inner
        assert all(f"/ssm/{inner}/" in name for name in names), inner
    assert "block0/ssm/in_proj" in text and "block0/ssm/out_proj" in text
    assert "block0/mlp" not in text and "block0/attn" not in text
    assert "block3/attn/qkv" in text and "block3/mlp" not in text
    assert "block3/ssm" not in text
    for inner in ("moe_route", "moe_shared"):
        assert f"block1/mlp/{inner}/" in text, inner
    # behind the layer's inner jit, whose body is lowered once a shape
    assert '"moe_dispatch/moe_rows_in/' in text
    assert '"moe_experts/moe_gate/' in text
    assert "block1/attn" not in text and "block1/ssm" not in text
    registry = get_registry()
    assert registry.gauge("ssd.groups").value == 4
    assert registry.gauge("ssd.chunk").value == 16
    assert registry.gauge("ssd.kept_mib").value == kept_mib(
        2, SEQ, 8, 8, 16, 16, 4)
    assert registry.gauge(
        "moe.gmm_tile_fill", layer="block1").value == moe.ffn_tile_fill(
            64, 32, jnp.float32, gated=False)


def test_the_scan_keeps_y_and_a_state_a_chunk():
    from horovod_tpu.ops.ssd import kept_mib

    # the cell's layer: y 16384 x 4096 in bfloat16 and 128 states of
    # 64 x 64 x 128 float32
    assert kept_mib(1, 16384, 64, 64, 128, 128, 2) == 128 + 256
    # granite's, at 8192 tokens and a chunk of 256
    assert kept_mib(1, 8192, 64, 64, 128, 256, 2) == 64 + 64


def test_a_rematerialised_model_of_half_layers_keeps_its_kernels_outputs():
    from horovod_tpu.obs.registry import get_registry

    model = small_model(remat=True)
    variables = sound()[0]
    grads = jax.jit(jax.grad(lambda p: program_loss(
        model, {**variables, "params": p}, TOKENS)))(variables["params"])
    registry = get_registry()
    assert registry.gauge("remat.kept_values", name="ssd_out").value == 2
    assert registry.gauge("remat.kept_values", name="ssd_states").value == 2
    np.testing.assert_allclose(norm(grads), norm(sound()[2]), rtol=1e-5)


# ---- the Mamba-2 mixer's norm by group ----------------------------------

def _mixer_args(cfg, key=5):
    keys = jax.random.split(jax.random.PRNGKey(key), 6)
    inner, heads = cfg.ssm_inner, cfg.ssm_heads
    conv = inner + 2 * cfg.ssm_groups * cfg.ssm_state
    w_in = jax.random.normal(keys[0], (64, inner + conv + heads)) * 0.1
    w_out = jax.random.normal(keys[1], (inner, 64)) * 0.1
    return dict(
        in_proj=lambda h: h @ w_in,
        conv_kernel=jax.random.normal(keys[2], (4, conv)) * 0.5,
        conv_bias=jax.random.normal(keys[3], (conv,)) * 0.1,
        dt_bias=jnp.zeros((heads,)), a_log=jnp.zeros((heads,)),
        d_skip=jnp.ones((heads,)),
        norm_scale=1.0 + 0.3 * jax.random.normal(keys[4], (inner,)),
        out_proj=lambda h: h @ w_out)


def test_one_group_norms_as_before_the_groups_to_the_bit():
    """``ssm_groups=1`` is the mathematics of the mixer before PR 61,
    written out here as it stood: one mean square over all inner
    channels."""
    from horovod_tpu.models.transformer import causal_depthwise_conv
    from horovod_tpu.ops.ssd import ssd_scan

    cfg = replace(small_model().cfg, ssm_groups=1)
    args = _mixer_args(cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 64))

    def before(h):
        b, s, _ = h.shape
        inner, heads, bc = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_state
        fused = args["in_proj"](h)
        z = fused[..., :inner]
        xbc = jax.nn.silu(causal_depthwise_conv(
            fused[..., inner:2 * inner + 2 * bc], args["conv_kernel"],
            args["conv_bias"])).astype(fused.dtype)
        x = xbc[..., :inner].reshape(b, s, heads, cfg.ssm_head_dim)
        B = xbc[..., inner:inner + bc].reshape(b, s, 1, bc)
        C = xbc[..., inner + bc:].reshape(b, s, 1, bc)
        dt = jax.nn.softplus(fused[..., 2 * inner + 2 * bc:].astype(
            jnp.float32) + args["dt_bias"])
        y = ssd_scan(x, dt, -jnp.exp(args["a_log"]), B, C, args["d_skip"],
                     cfg.ssm_chunk)
        gated = y.reshape(b, s, inner).astype(jnp.float32) \
            * jax.nn.silu(z.astype(jnp.float32))
        normed = gated * jax.lax.rsqrt(jnp.mean(
            jnp.square(gated), axis=-1, keepdims=True) + cfg.norm_eps)
        return args["out_proj"](normed * args["norm_scale"])

    got = jax.jit(lambda h: mamba_mixer(cfg, h, **args))(h)
    np.testing.assert_array_equal(got, jax.jit(before)(h))


def test_each_group_is_normed_by_its_own_mean_square():
    """Scaling one group's gate scales nothing that leaves the norm: each
    group's channels are divided by their own root mean square."""
    cfg = small_model().cfg
    args = _mixer_args(cfg)
    h = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 64))
    inner, group = cfg.ssm_inner, cfg.ssm_inner // cfg.ssm_groups

    def with_gate_scaled(factor):
        scale = jnp.ones((inner,)).at[:group].set(factor)
        in_proj = lambda h: args["in_proj"](h).at[..., :inner].multiply(
            scale)
        # read the norm's output: out_proj the identity
        return mamba_mixer(cfg, h, **{**args, "in_proj": in_proj,
                                      "out_proj": lambda t: t})

    one, three = with_gate_scaled(1.0), with_gate_scaled(3.0)
    # the other groups do not see the first one's size
    np.testing.assert_allclose(one[..., group:], three[..., group:],
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(one[..., :group] - three[..., :group]).max()) > 1e-2


# ---- experts without a gate matrix --------------------------------------

def _plain_ffn(xs, up, down, sizes, activation):
    """``grouped_ffn`` as plain ``jax.numpy``: each row through its own
    expert's two matrices; the rows past the held groups come out
    zero."""
    expert = jnp.repeat(jnp.arange(len(sizes)), sizes,
                        total_repeat_length=xs.shape[0])
    held = expert < up.shape[0]
    index = jnp.minimum(expert, up.shape[0] - 1)
    h = moe.ACTIVATIONS[activation](jnp.einsum("rd,rdf->rf", xs, up[index]))
    return jnp.where(held[:, None],
                     jnp.einsum("rf,rfd->rd", h, down[index]), 0.0)


@pytest.mark.parametrize("activation", ["relu2", "silu"])
def test_the_ungated_grouped_ffns_backward_is_the_plain_forms(activation):
    """The hand-written backward (``_ffn_bwd``: from ``xs`` and ``h = xs
    W_up``, ``d relu(h)^2 = 2 relu(h)``) against ``jax.grad`` of the
    plain form, a first matrix ``[held, d, ff]``."""
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    sizes = jnp.array([5, 0, 9, 10], jnp.int32)   # 3 held, 10 rows elsewhere
    xs = jax.random.normal(keys[0], (24, 16))
    up = jax.random.normal(keys[1], (3, 16, 8)) * 0.3
    down = jax.random.normal(keys[2], (3, 8, 16)) * 0.3
    cot = jax.random.normal(keys[3], (24, 16))

    def program(xs, up, down):
        return jnp.sum(cot * moe.grouped_ffn(
            xs, up, down, sizes, dtype=jnp.float32, interpret=True,
            activation=activation))

    def plain(xs, up, down):
        return jnp.sum(cot * _plain_ffn(xs, up, down, sizes, activation))

    np.testing.assert_allclose(program(xs, up, down), plain(xs, up, down),
                               rtol=1e-5)
    for got, want in zip(jax.grad(program, (0, 1, 2))(xs, up, down),
                         jax.grad(plain, (0, 1, 2))(xs, up, down)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_tile_fill_follows_the_first_matmuls_real_width():
    # the cell's expert: 2688 -> 1856 -> 2688, no gate
    assert moe.ffn_calls(2688, 1856, gated=False) == (
        (2688, 1856, False), (1856, 2688, False), (2688, 1856, False),
        (1856, 2688, False), (1856, 2688, True), (2688, 1856, True))
    assert moe.ffn_calls(2688, 1856)[0] == (2688, 3712, False)
    fill = moe.ffn_tile_fill(2688, 1856, jnp.bfloat16, gated=False)
    assert 0.5 < fill <= 1.0


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Four chips hold two experts each of eight, three a token, beside
    the shared expert.  Every share computes the same norm, the same
    router decision and the same shared expert, and its own experts'
    part of the routed sum: the routed parts of all four, with the rest
    counted ONCE, are the whole layer as the uncut reference gives it."""
    cfg = small_model(routed_experts=8, routed_held=8,
                      routed_first_held=0).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), FEED_FORWARD, "routed")

    variables = jax.jit(block(0, 8).init)(jax.random.PRNGKey(4), x,
                                          positions)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    bias = variables["moe_state"]["bias"]
    assert bias.shape == (8,) and float(jnp.abs(bias).max()) > 0
    assert set(p) == {"ln1", "router", "experts_fc1", "experts_fc2",
                      "shared_fc1", "shared_fc2"}

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply(
            {"params": mine, "moe_state": {"bias": bias}}, x, positions)

    config = {**CONFIG, "n_routed_experts": 8, "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)  # the stream and the shared one
        total = alike + sum(share(first) - alike
                            for first in range(0, 8, 2))
        uncut = x + ref._experts(config, p, bias, ref._rms_norm(
            x, p["ln1"]["scale"], 1e-5), None)
        one = share(2)
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    # and one share alone is NOT the layer: it leaves out six experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


# ---- who refuses what ----------------------------------------------------

PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]


@pytest.mark.parametrize("setting", ["feed_forward", "routed_gated",
                                     "shared_width", "mlp"])
@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_the_half_layer_and_its_settings_by_name(path, setting):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's
    two-half block from raw weights: each refuses a layer of one half and
    each new setting by name, before anything is traced."""
    from test_glm_moe_mla import _refusals

    nano = gpt("nano").cfg
    cfg = {"feed_forward": replace(nano, layer_types=(
               "attention", FEED_FORWARD, "attention")),
           "routed_gated": replace(nano, routed_gated=False),
           "shared_width": replace(nano, shared_width=64),
           "mlp": replace(nano, mlp="relu2")}[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


def test_the_named_size_is_refused_off_the_training_path():
    from horovod_tpu.models.transformer import require_gpt2_block

    with pytest.raises(ValueError, match="feed_forward"):
        require_gpt2_block(GPT_CONFIGS[NAME], "decode")


@pytest.mark.parametrize("override,message", [
    ({"mtp_modules": 1}, "one-half layers"),
    ({"hc_mult": 2}, "one-half layers"),
    ({"mlp": "silu_gated"}, "routed experts are ungated: mlp must be 'relu2'"),
    ({"routed_gated": True}, "routed experts are silu-gated"),
    ({"shared_width": 0}, "shared_width=0 must be positive"),
    ({"ssm_groups": 3}, "multiple of ssm_groups=3"),
    ({"routed_activation": "gelu"}, "routed_activation must be one of"),
    ({"layer_types": ("mamba",) * 4 + ("ffn",)}, "layer_types must name"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_the_defaults_are_the_parents():
    """No other named size has a layer of one half, an ungated expert or
    a shared expert of its own width; two halves a block is the
    default."""
    cfg = TransformerConfig()
    assert (cfg.one_half, cfg.routed_gated, cfg.shared_width) == (
        False, True, None)
    assert FEED_FORWARD in LAYER_TYPES
    for size, named in GPT_CONFIGS.items():
        if size == NAME:
            continue
        assert not named.one_half and named.routed_gated, size
        assert named.shared_width is None and named.mlp != "relu2", size
        assert all(named.ffn_type(i) != "none"
                   for i in range(named.num_layers)), size
    granite = GPT_CONFIGS["granite-4.0-h-micro"]
    assert granite.ssm_groups == 1 and granite.ssm_chunk == 256
