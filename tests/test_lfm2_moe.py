"""LFM2-24B-A2B's mechanisms on the training path (``model_type:
lfm2_moe``): the gated short convolution (two gates around a causal
depthwise filter of three taps, no bias) three to one with grouped-query
attention layers that norm each head of q and k and rotate, a dense
feed-forward whose width is no multiple of the stream's, routed experts
behind a sigmoid router whose selection bias moves the choice alone.
The program (``models/transformer.py``, ``parallel/moe.py``) against the
benchmark's own plain reference
(``benchmark/configs/lfm2-24b-a2b.reference.py``) on seeded weights; the
eight shares of the experts adding up to the uncut layer; the published
values of the named size and the counts of the model and of its cut; the
flash kernels' plan for the cell's call; the paths that refuse the new
layer and settings.
All on the CPU at small sizes: hidden 64, 8 query heads over 2 key/value
heads of 8, a dense width of 184, 8 experts of width 32, 2 a token, 32
tokens, the cell's five layers.
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
from horovod_tpu.models.transformer import (GPT_CONFIGS, MIXER_SCOPES, Block,
                                            TransformerConfig,
                                            causal_depthwise_conv, gpt,
                                            short_conv_filter_bytes,
                                            short_conv_mixer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-24b-a2b"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# the cell's cut: published layer 0 (conv, dense) and layers 2-5
KINDS = ("conv", "full_attention", "conv", "conv", "conv")
SMALL = dict(
    num_layers=5, layer_types=KINDS, dense_layers_first=1, vocab_size=256,
    emb_dim=64, num_heads=8, num_kv_heads=2, mlp_width=184,
    routed_experts=8, routed_held=2, routed_first_held=4, routed_top_k=2,
    routed_width=32, max_len=64, attention_impl="reference",
    # several tiles a row
    flash_block_q=16, flash_block_k=8, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
    norm_eps=1e-5, rope_parameters={"rope_theta": 1e6},
    layer_types=list(KINDS), num_dense_layers=1, num_experts=2,
    first_held_expert=4, num_experts_per_tok=2, routed_scaling_factor=1.0)
SEQ = 32
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)
BATCH = {"tokens": TOKENS}


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread at this width, and the norms' weights away from
    1."""
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


def program_loss(model, variables, tokens):
    return -program_logprob(model, variables, tokens).mean()


def grads_of(loss, variables):
    return jax.grad(lambda p: loss({**variables, "params": p}))(
        variables["params"])


@functools.cache
def sound():
    """The seeded variables (the same for every attention schedule, remat
    setting and compute dtype: they change no parameter) and what the
    plain reference gives for them, computed once."""
    variables = init(small_model())
    with jax.default_matmul_precision("highest"):
        return (variables, *jax.jit(lambda v: (
            ref.logprob(CONFIG, v, BATCH),
            grads_of(lambda t: ref.loss(CONFIG, t, BATCH), v)))(variables))


def program(model, variables):
    """The labels' log-probabilities and the gradient of their mean, from
    one trace."""
    return jax.jit(lambda v: (
        program_logprob(model, v, TOKENS),
        grads_of(lambda t: program_loss(model, t, TOKENS), v)))(variables)


@pytest.mark.parametrize("remat", [False, True], ids=["kept", "remat"])
@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_model_matches_plain_reference(attention, remat):
    """The loss, every label's log-probability and every leaf of the
    gradient, with the reference attention and through the flash kernels
    (the Pallas interpreter, four query heads a key/value head), every
    block kept and every block recomputed from its input."""
    model = small_model(attention_impl=attention, remat=remat)
    variables, want_logp, want_grads = sound()
    with jax.default_matmul_precision("highest"):
        got_logp, got_grads = program(model, variables)
    np.testing.assert_allclose(got_logp, want_logp, atol=2e-4)
    np.testing.assert_allclose(got_logp.mean(), want_logp.mean(), atol=1e-5)
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got_grads))
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert flat_got.keys() == flat_want.keys()
    for path, want_leaf in flat_want.items():
        scale = float(jnp.abs(want_leaf).max())
        assert scale > 0, f"{path}: the reference's gradient is zero"
        np.testing.assert_allclose(
            flat_got[path], want_leaf, atol=2e-4 * scale + 1e-7,
            err_msg=jax.tree_util.keystr(path))


# bfloat16 against the float32 reference at this size: the loss, the
# largest difference of one label's log-probability, the norm of the
# gradients' difference over the reference's.  Readings on this machine
# over three seeds of ``init``: 1e-6 to 4.4e-3, 0.07 to 0.12 and 0.048 to
# 0.062 (eight bits of mantissa through five layers at hidden 64, and a
# choice of experts that flips under rounding); each limit stands two and
# a half to four times over the largest.  The departures a limit has to
# tell read, in the gradient: ``filter_identity`` 1.36 to 1.53,
# ``head_norms_dropped`` 0.79 to 0.87, ``weights_unnormalised`` 0.49 to
# 0.57.  (``bias_in_weights`` reads 0.05 here, inside bfloat16's own
# noise: the float32 cases above and the chip's limits hold it.)
BF16_LIMITS = dict(loss_abs=0.02, logprob_abs=0.4, grad_rel=0.15)


def test_bfloat16_stays_within_stated_limits_of_the_reference():
    model = small_model(dtype=jnp.bfloat16)
    variables, want_logp, want_grads = sound()
    got_logp, got_grads = program(model, variables)
    norm = lambda tree: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree.leaves(tree))))
    apart = lambda want: norm(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b, got_grads, want)) / norm(want)
    assert abs(float(got_logp.mean() - want_logp.mean())) \
        <= BF16_LIMITS["loss_abs"]
    assert float(jnp.abs(got_logp - want_logp).max()) \
        <= BF16_LIMITS["logprob_abs"]
    assert apart(want_grads) <= BF16_LIMITS["grad_rel"]
    # and the limits are no formality: they tell a departure
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: grads_of(lambda t: ref.loss(
            CONFIG, t, BATCH, "weights_unnormalised"), v))(variables)
    assert apart(departed) > 2 * BF16_LIMITS["grad_rel"]


@functools.cache
def sound_program_loss():
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda v: program_loss(small_model(), v, TOKENS))(
            sound()[0])


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_comparison_fails_on_a_seeded_departure(depart):
    variables, want_logp, _ = sound()
    got = sound_program_loss()
    with jax.default_matmul_precision("highest"):
        departed = jax.jit(lambda v: ref.loss(CONFIG, v, BATCH, depart))(
            variables)
    assert abs(got + want_logp.mean()) < 1e-5
    assert abs(got - departed) > 1e-4


def _mixer(taps=3, width=16):
    """A conv mixer on plain matrices: the callable, its three weights
    and an input of 12 tokens."""
    k = jax.random.split(jax.random.PRNGKey(2), 4)
    w_in = jax.random.normal(k[0], (width, 3 * width)) * 0.3
    kernel = jax.random.normal(k[1], (taps, width))
    w_out = jax.random.normal(k[2], (width, width)) * 0.3
    cfg = replace(GPT_CONFIGS["nano"], dtype=jnp.float32)
    apply = lambda h: short_conv_mixer(
        cfg, h, in_proj=lambda t: t @ w_in, conv_kernel=kernel,
        out_proj=lambda t: t @ w_out)
    return apply, (w_in, kernel, w_out), jax.random.normal(
        k[3], (1, 12, width))


def test_the_conv_mixer_is_causal_and_reads_exactly_three_taps():
    """An input at ``t`` moves no output before ``t`` and none after
    ``t + 2``: the filter reads the current token and the two before it,
    and the gates and projections are a token's own."""
    apply, _, h = _mixer()
    with jax.default_matmul_precision("highest"):
        jac = jax.jacobian(lambda h: apply(h)[0])(h)[:, :, 0]
    # [t_out, channel_out, t_in, channel_in] -> which t_in move t_out
    moves = np.asarray(jnp.abs(jac).max(axis=(1, 3)) > 1e-9)
    t_out, t_in = np.indices(moves.shape)
    np.testing.assert_array_equal(
        moves, (t_in <= t_out) & (t_out - t_in <= 2))


def test_the_conv_mixer_is_the_gated_filter_written_out():
    apply, (w_in, taps, w_out), h = _mixer()
    with jax.default_matmul_precision("highest"):
        fused = h @ w_in
        b, c, u = fused[..., :16], fused[..., 16:32], fused[..., 32:]
        g = b * u
        before = lambda by: jnp.pad(g, ((0, 0), (by, 0), (0, 0)))[:, :12]
        want = (c * (taps[2] * g + taps[1] * before(1)
                     + taps[0] * before(2))) @ w_out
        np.testing.assert_allclose(apply(h), want, atol=1e-5)
        # a fourth tap is another filter
        assert float(jnp.abs(_mixer(taps=4)[0](h) - want).max()) > 1e-3


def test_the_shared_filter_keeps_mambas_bias_and_takes_none():
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 5))
    kernel = jax.random.normal(jax.random.PRNGKey(4), (4, 5))
    bias = jnp.arange(5.0)
    np.testing.assert_allclose(
        causal_depthwise_conv(x, kernel, bias),
        causal_depthwise_conv(x, kernel) + bias, atol=1e-6)
    assert causal_depthwise_conv(x.astype(jnp.bfloat16),
                                 kernel).dtype == jnp.float32


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold two experts each of sixteen, four a token.
    Every share computes the same convolution and the same router
    decision, and its own experts' part of the routed sum: the routed
    parts of all eight, with the rest counted ONCE, are the whole layer
    as the uncut reference gives it."""
    cfg = small_model(routed_experts=16, routed_held=16,
                      routed_first_held=0, routed_top_k=4).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), "conv", "routed")

    variables = jax.jit(block(0, 16).init)(jax.random.PRNGKey(4), x,
                                           positions)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    bias = variables["moe_state"]["bias"]
    assert bias.shape == (16,) and float(jnp.abs(bias).max()) > 0

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply(
            {"params": mine, "moe_state": {"bias": bias}}, x, positions)

    config = {**CONFIG, "num_experts": 16, "first_held_expert": 0,
              "num_experts_per_tok": 4}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)     # the stream and the conv
        total = alike + sum(share(first) - alike
                            for first in range(0, 16, 2))
        uncut = ref._block(config, p, bias, x, "conv", False)
        one = share(2)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
    # and one share alone is NOT the layer: it leaves out 14 experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


PUBLISHED = dict(
    vocab_size=65536, num_layers=40, emb_dim=2048, num_heads=32,
    kv_heads=8, head_dim=64, ffn_width=11776, conv_taps=3,
    attention_window=None, attention_scale=None, rope_theta=1e6,
    norm_eps=1e-5, routed_experts=64, held_experts=64, routed_top_k=4,
    routed_width=1536, routed_scaling=1.0, shared_experts=0,
    dense_layers_first=2, mtp_modules=0, max_len=128000,
    tie_embeddings=True, use_bias=False, norm="rmsnorm", mlp="silu_gated",
    pos_embedding="rope", rope_layer_types=("full_attention",),
    qk_norm=True, attention_gate=False, post_norms=False,
    routed_router_input="ffn_input", routed_scores="sigmoid",
    routed_activation="silu", routed_balance_coef=0.0,
    remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    assert cfg.layer_types == tuple(
        "full_attention" if i % 4 == 2 else "conv" for i in range(40))
    assert cfg.layer_types.count("full_attention") == 10
    assert [cfg.ffn_type(i) for i in range(3)] == ["dense", "dense",
                                                   "routed"]
    assert cfg.rotates("full_attention") and cfg.window_of(
        "full_attention") is None


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def test_the_named_size_counts_23843659008_parameters():
    shapes = jax.eval_shape(lambda: gpt(NAME).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    assert "head" not in p                      # the table is tied
    assert _count(p["block0"]) == _count(p["block1"]) == 89_139_200
    assert _count(p["block2"]) == 614_600_832   # attention, 64 experts
    assert _count(p["block3"]) == 620_898_304   # conv, 64 experts
    assert _count(p) == 23_843_659_008
    assert _count(shapes["moe_state"]) == 38 * 64


def test_the_cut_counts_469284992_parameters():
    """The benchmark's cut from the named size: depth 40 -> 5 (published
    layer 0 and layers 2-5, one whole period), one leading dense layer,
    8 of 64 experts held, an eighth of the vocabulary; every width as
    published (ISSUE 48 has the sum)."""
    model = gpt(NAME, num_layers=5, layer_types=KINDS, dense_layers_first=1,
                routed_held=8, vocab_size=8192)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    p = shapes["params"]
    conv = sum(_count(p["block0"][k]) for k in (
        "in_proj", "conv_kernel", "out_proj", "ln1", "ln2"))
    attention = sum(_count(p["block1"][k]) for k in (
        "qkv", "proj", "q_norm", "k_norm", "ln1", "ln2"))
    assert (conv, attention) == (16_787_456, 10_489_984)
    assert _count(p["block0"]["fc1"]) + _count(
        p["block0"]["fc2"]) == 72_351_744
    assert _count(p["block1"]["router"]) == 131_072
    assert _count(p["block1"]["experts_fc1"]) + _count(
        p["block1"]["experts_fc2"]) == 8 * 9_437_184
    assert [_count(p[f"block{i}"]) for i in range(5)] == [
        89_139_200, 86_118_528, 92_416_000, 92_416_000, 92_416_000]
    assert _count(p["wte"]) == 16_777_216 and "head" not in p
    assert _count(p) == 469_284_992
    # per expert layer: rows of 8 held experts, rows dropped, the load of
    # all 64 and the overflow counter
    assert _count(shapes["moe_stats"]) == 4 * (8 + 1 + 64 + 1)


def test_the_cells_flash_call_streams_forward_and_holds_dq_backward():
    """The cell's call, ``[32 on 8, 32768, 64]`` in bfloat16: a kv row's
    K and V no longer fit VMEM, so the forward streams its tiles (the
    first cell that does); a row's dk and dv resident count far over the
    ceiling and its dq 36.25 MiB, over the 32 MiB it has to fit to be
    taken in its turn and under the 48 MiB ceiling, so the K-outermost
    kernel takes it and states 37 MiB, a whole MiB: one backward kernel.
    Half the length holds the row forward and fits dq in the 32."""
    from flash_oracle import plan_of
    from horovod_tpu.ops.flash_attention import flash_plan

    shape = lambda heads: jax.ShapeDtypeStruct((1, 32768, heads, 64),
                                               jnp.bfloat16)
    plan = flash_plan(shape(32), shape(8), shape(8), causal=True)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (False, 0)
    assert (plan.bwd_form, plan.bwd_vmem_bytes, plan.bwd_kernels) == (
        "dq_resident", 37 * 2 ** 20, 1)
    assert plan.bwd_vmem_bytes == 38_797_312
    assert (plan.heads, plan.kv_heads, plan.window) == (32, 8, None)
    # of a head's 64 x 128 tiles the causal half and its diagonal, which
    # is all the grid walks since PR 49: the K-outermost table, six
    # columns a step, four query heads a group, is 390 KiB of SMEM
    assert (plan.tiles_live, plan.tiles_mask) == (32 * 4160, 32 * 8192)
    assert plan.tiles_grid == plan.tiles_live
    assert len(plan.live_tiles) == 4160
    half = plan_of(16384, 64, 4, 2, rows=8)
    assert (half.fwd_kv_resident, half.bwd_form, half.bwd_vmem_bytes) == (
        True, "dq_resident", 32 * 2 ** 20)


def test_a_conv_block_carries_its_scopes_and_the_gauges_count_it():
    """A step traced names a conv block's mixer half ``short_conv`` and
    the elementwise chain inside it ``short_conv_filter`` (forward and
    backward), an attention block's ``attn``; the gauges hold the conv
    layers and the bytes their chains move a step."""
    from horovod_tpu.obs.registry import get_registry

    assert scopes.SHORT_CONV in scopes.SCOPES
    assert scopes.SHORT_CONV_FILTER in scopes.SCOPES
    assert MIXER_SCOPES["conv"] == scopes.SHORT_CONV
    model = small_model()
    variables = init(model)
    text = jax.jit(jax.grad(lambda p: program_loss(
        model, {**variables, "params": p}, TOKENS))).lower(
            variables["params"]).as_text(debug_info=True)
    chains = set(re.findall(r'"([^"]*/short_conv_filter/[^"]*)"', text))
    assert any("jvp(GPT)/block0/short_conv/short_conv_filter/" in name
               and "transpose(" not in name for name in chains)
    assert any("transpose(jvp(GPT))/block0/short_conv/short_conv_filter/"
               in name for name in chains)
    assert all("/short_conv/short_conv_filter/" in name for name in chains)
    assert "block0/short_conv/in_proj" in text
    assert "block1/attn/" in text and "block1/short_conv" not in text
    assert "block0/attn" not in text and "block2/mlp/moe_route/" in text
    registry = get_registry()
    assert registry.gauge("short_conv.layers").value == 4
    assert registry.gauge("short_conv.filter_bytes").value == \
        4 * short_conv_filter_bytes(2, SEQ, 64, 4) == 4 * 11 * 2 * SEQ * 64 * 4


def test_a_block_makes_the_conv_modules_only_where_asked():
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    assert set(tree["block0"]) == {"ln1", "in_proj", "conv_kernel",
                                   "out_proj", "ln2", "fc1", "fc2"}
    assert set(tree["block1"]) == {"ln1", "qkv", "q_norm", "k_norm", "proj",
                                   "ln2", "router", "experts_fc1",
                                   "experts_fc2"}
    assert set(tree["block2"]) == {"ln1", "in_proj", "conv_kernel",
                                   "out_proj", "ln2", "router",
                                   "experts_fc1", "experts_fc2"}
    assert tree["block0"]["in_proj"]["kernel"].shape == (64, 192)
    assert tree["block0"]["conv_kernel"].shape == (3, 64)
    assert tree["block0"]["fc1"]["kernel"].shape == (64, 2 * 184)
    assert tree["block0"]["fc2"]["kernel"].shape == (184, 64)


PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]


@pytest.mark.parametrize("setting", ["conv", "mlp_width", "conv_taps"])
@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_the_conv_layer_and_the_new_settings_by_name(
        path, setting):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's block
    from raw weights and keep no convolution state: each refuses the
    ``conv`` layer, a stated dense width and the filter's taps by name,
    before anything is traced."""
    from test_glm_moe_mla import _refusals

    nano = gpt("nano").cfg
    cfg = {"conv": replace(nano, layer_types=("attention", "conv",
                                              "attention")),
           "mlp_width": replace(nano, mlp_width=184),
           "conv_taps": replace(nano, conv_taps=4)}[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


@pytest.mark.parametrize("override,message", [
    ({"mlp_width": 0}, "mlp_width=0 must be positive"),
    ({"conv_taps": 0}, "conv_taps=0"),
    ({"layer_types": ("conv",) * 4 + ("convolution",)},
     "layer_types must name"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_the_defaults_are_the_parents():
    """The dense width defaults to ``mlp_ratio * emb_dim`` and no named
    size states another but this one, since PR 57 Xing4.0's (9216 on a
    stream of 3584), since PR 61 Nemotron-3-Nano's (1856 on 2688) and
    since PR 64 Qwen3-Next's (5120 on 2048); no other named size has a
    conv layer."""
    cfg = TransformerConfig()
    assert (cfg.mlp_width, cfg.conv_taps) == (None, 3)
    assert cfg.ffn_width == cfg.mlp_ratio * cfg.emb_dim
    for size, named in GPT_CONFIGS.items():
        if size == NAME:
            continue
        assert "conv" not in (named.layer_types or ()), size
        if size == "xing4.0-29b-a4b":
            assert named.ffn_width == named.mlp_width == 9216
            continue
        if size == "nvidia-nemotron-3-nano-30b-a3b-bf16":
            assert named.ffn_width == named.mlp_width == 1856
            continue
        if size == "qwen3-next-80b-a3b-instruct":
            # published, and used by no layer: every layer routes
            assert named.ffn_width == named.mlp_width == 5120
            continue
        assert named.mlp_width is None, size
        assert named.ffn_width == named.mlp_ratio * named.emb_dim, size
