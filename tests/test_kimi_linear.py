"""Kimi-Linear-48B-A3B-Instruct's mechanisms on the training path
(``model_type: kimi_linear``): Kimi Delta Attention (three causal
four-tap filters with silu, unit-length q and k, a decay per channel of
the key, the gated delta rule of ``ops/kda.py``, a gated norm a head)
three to one with latent attention that has no query rank, rotates
nothing and reads keys wider than its values; a dense feed-forward in the
leading layer and routed experts behind a sigmoid router with a selection
bias beside one shared expert in the others; an untied head.  The program
(``models/transformer.py``, ``ops/kda.py``, ``parallel/moe.py``) against
the benchmark's own plain reference
(``benchmark/configs/kimi-linear-48b-a3b-instruct.reference.py``) on
seeded weights (the float32 comparison and the departures it tells are
in ``tests/test_kimi_linear_reference.py``, the bfloat16 limits here);
the thirty-two shares of the experts adding up to the
uncut layer; the published values of the named size and the counts of the
model and of its cut; the flash kernels' plan for the cell's call; the
paths that refuse the new layer and settings.
All on the CPU at small sizes: hidden 64, 4 KDA heads of 16 at a chunk of
16, 4 latent heads with keys of 16 + 8 over values of 16, a dense width
of 192, 16 experts of width 32, 4 a token, 64 tokens, the cell's five
layers.
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
from horovod_tpu.models.transformer import (GPT_CONFIGS, LAYER_TYPES,
                                            MIXER_SCOPES, Block,
                                            TransformerConfig, gpt,
                                            kda_mixer, mla_mixer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "kimi-linear-48b-a3b-instruct"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("kimi_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# the cell's cut: the first five published layers
KINDS = ("kda", "kda", "kda", "mla", "kda")
SMALL = dict(
    num_layers=5, layer_types=KINDS, dense_layers_first=1, vocab_size=256,
    emb_dim=64, num_heads=4, num_kv_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kda_heads=4,
    kda_head_dim=16, kda_chunk=16, kda_states_every=2, mlp_ratio=3,
    routed_experts=16, routed_held=4, routed_first_held=8, routed_top_k=4,
    routed_width=32, max_len=128, attention_impl="reference",
    # several tiles a row
    flash_block_q=16, flash_block_k=8, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rms_norm_eps=1e-5, rope_theta=10000,
    linear_attn_config={"num_heads": 4, "head_dim": 16,
                        "short_conv_kernel_size": 4,
                        "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]},
    num_hidden_layers=5, first_k_dense_replace=1, num_experts=4,
    first_held_expert=8, num_experts_per_token=4,
    routed_scaling_factor=2.446)
SEQ = 64
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
        if "scale" in name or "o_norm" in name:
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


def program_sides(model, variables):
    """Every label's log-probability and the loss's gradient, one
    compiled program (op by op the rule's scans take minutes)."""
    return jax.jit(lambda v: (
        program_logprob(model, v, TOKENS),
        grads_of(lambda t: program_loss(model, t, TOKENS), v)))(variables)


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


# bfloat16 against the float32 reference at this size: the loss, the
# largest difference of one label's log-probability, the norm of the
# gradients' difference over the reference's.  At hidden 64 with four of
# sixteen experts a token, a choice that flips under rounding is a large
# part of a token's output, so the limits are wide; they still tell a
# departure (the decays dropped reads far over them).
BF16_LIMITS = dict(loss_abs=0.05, logprob_abs=1.0, grad_rel=0.35)


def test_bfloat16_stays_within_stated_limits_of_the_reference():
    model = small_model(dtype=jnp.bfloat16)
    variables, want_logp, want_grads = sound()
    got_logp, got_grads = program_sides(model, variables)
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
            CONFIG, t, BATCH, "qk_l2norm_dropped"), v))(variables)
    assert apart(departed) > 2 * BF16_LIMITS["grad_rel"]


def _kda_layer(dtype=jnp.float32, strong=False):
    """A KDA mixer on plain matrices: the callable and an input of 48
    tokens (three chunks of 16)."""
    width, heads, hd = 32, 2, 16
    inner = heads * hd
    k = jax.random.split(jax.random.PRNGKey(2), 12)
    mat = lambda key, shape: jax.random.normal(key, shape) * shape[0] ** -0.5
    w = dict(qkv=mat(k[0], (width, 3 * inner)), f_a=mat(k[1], (width, hd)),
             f_b=mat(k[2], (hd, inner)), b=mat(k[3], (width, heads)),
             g_a=mat(k[4], (width, hd)), g_b=mat(k[5], (hd, inner)),
             o=mat(k[6], (inner, width)))
    cfg = replace(GPT_CONFIGS["nano"], dtype=dtype, kda_heads=heads,
                  kda_head_dim=hd, kda_chunk=16, norm_eps=1e-5)
    apply = lambda h: kda_mixer(
        cfg, h, qkv=lambda t: t @ w["qkv"],
        conv_kernel=jax.random.normal(k[7], (4, 3 * inner)) * 0.5,
        f_a=lambda t: t @ w["f_a"], f_b=lambda t: t @ w["f_b"],
        dt_bias=jnp.full((inner,), 6.0 if strong else -2.0),
        a_log=jnp.log(jnp.asarray([16.0, 9.0] if strong else [1.0, 4.0])),
        b_proj=lambda t: t @ w["b"], g_a=lambda t: t @ w["g_a"],
        g_b=lambda t: t @ w["g_b"],
        norm_scale=1.0 + 0.1 * jax.random.normal(k[8], (hd,)),
        o_proj=lambda t: t @ w["o"])
    return apply, jax.random.normal(k[9], (1, 48, width))


def test_the_kda_mixer_is_causal_and_reaches_across_chunks():
    """An input at ``t`` moves no output before ``t``; through the state
    it moves outputs chunks later (token 2 moves token 47, three chunks
    on), and through the filter the three tokens after it."""
    apply, h = _kda_layer()
    run = jax.jit(apply)
    with jax.default_matmul_precision("highest"):
        base = run(h)
        for t in (2, 17, 40):
            moved = jnp.abs(run(h.at[:, t].add(0.5)) - base)[0].max(axis=-1)
            assert float(moved[:t].max()) == 0.0, t
            assert float(moved[t]) > 1e-4, t
            assert float(moved[47]) > 1e-7, t


def test_the_kda_mixer_stays_finite_under_the_models_strongest_decays():
    """``A`` at 16 and a softplus of six: ``g`` about -96 a token and
    channel, ``G`` -1500 over the chunk of 16: the state is wiped before
    every token, the output is each token's own ``beta (k . q) v`` and
    every gradient is finite."""
    apply, h = _kda_layer(strong=True)
    with jax.default_matmul_precision("highest"):
        out, grad = jax.jit(jax.value_and_grad(
            lambda h: jnp.sum(apply(h) ** 2)))(h)
        run = jax.jit(apply)
        moved = jnp.abs(run(h.at[:, 5].add(0.5)) - run(h))[0].max(axis=-1)
    assert bool(jnp.isfinite(out)) and bool(jnp.isfinite(grad).all())
    assert float(jnp.abs(grad).max()) > 0
    # only the filter's four taps connect tokens
    assert float(moved[:5].max()) == 0.0 and float(moved[5]) > 1e-4
    assert float(moved[9:].max()) < 1e-12


def _mla_layer(cfg_edits):
    cfg = small_model(**cfg_edits).cfg
    k = jax.random.split(jax.random.PRNGKey(5), 8)
    heads, keys = cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    mat = lambda key, shape: jax.random.normal(key, shape) * shape[0] ** -0.5
    w = dict(q=mat(k[0], (64, heads * keys)),
             kv_a=mat(k[1], (64, cfg.kv_lora_rank + cfg.qk_rope_head_dim)),
             kv_b=mat(k[2], (cfg.kv_lora_rank,
                             heads * (cfg.qk_nope_head_dim
                                      + cfg.v_head_dim))),
             proj=mat(k[3], (heads * cfg.v_head_dim, 64)))
    apply = lambda h: mla_mixer(
        cfg, h, jnp.arange(h.shape[1]), None, q_b=lambda t: t @ w["q"],
        kv_a=lambda t: t @ w["kv_a"], kv_a_norm=lambda t: t,
        kv_b=lambda t: t @ w["kv_b"], proj=lambda t: t @ w["proj"])
    return apply, jax.random.normal(k[4], (2, SEQ, 64))


def test_flash_takes_keys_wider_than_values_and_agrees_with_reference():
    """Keys of 16 + 8 over values of 16, no query rank, nothing rotated:
    the flash path (the Pallas interpreter) and the reference path give
    the same layer, forward and backward.  ``mla_mixer`` refused this
    before PR 51 (a check older than PR 42's unequal widths)."""
    plain, h = _mla_layer(dict(attention_impl="reference"))
    flash, _ = _mla_layer(dict(attention_impl="flash"))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(flash(h), plain(h), atol=2e-5)
        grad = lambda f: jax.grad(lambda t: jnp.sum(f(t) ** 2))(h)
        np.testing.assert_allclose(grad(flash), grad(plain), atol=2e-4)


def test_latent_attention_without_positions_forgets_the_order_of_its_past():
    """No rotation, no table: swapping two earlier tokens leaves a later
    token's output as it was (the order lives in the KDA layers)."""
    apply, h = _mla_layer(dict(attention_impl="reference"))
    swapped = h.at[:, 3].set(h[:, 9]).at[:, 9].set(h[:, 3])
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(apply(swapped)[:, 20:], apply(h)[:, 20:],
                                   atol=1e-5)
        assert float(jnp.abs(apply(swapped) - apply(h))[:, 5].max()) > 1e-3


def test_glms_latent_path_traces_what_it_traced():
    """A query rank and a rotation: ``mla_mixer`` traces the operations
    it traced before PR 51 (the count of the small GLM block's equations
    is the parent's), and the named GLM size still makes ``q_a``,
    ``q_a_norm`` and ``q_b``."""
    from test_glm_moe_mla import small_model as glm_small

    model = glm_small()
    tokens = jnp.zeros((2, 16), jnp.int32)
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b",
            "proj"} <= set(variables["params"]["block0"])
    forward = jax.make_jaxpr(lambda v: model.apply(v, tokens))(variables)
    backward = jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {**variables, "params": p}, tokens).sum()))(variables["params"])
    assert GPT_CONFIGS["glm-4.7-flash"].rotates("mla")
    assert (len(forward.jaxpr.eqns),
            len(backward.jaxpr.eqns)) == GLM_SMALL_EQUATIONS


# jax.make_jaxpr of tests/test_glm_moe_mla.py's small model applied to a
# [2, 16] batch, forward and differentiated, counted on the parent commit
# (PR 50's tree, from git archive) and on this one: the same, (494, 1112),
# until PR 58 gave the model's three expert layers a router that counts by
# comparison, selects the chosen scores and sorts twice (``mla_mixer`` and
# its callers untouched there: four equations fewer forward, two backward)
GLM_SMALL_EQUATIONS = (490, 1110)


def test_the_thirty_two_shares_add_up_to_the_uncut_layer():
    """Thirty-two chips hold two experts each of sixty-four, four a
    token, beside one shared expert.  Every share computes the same KDA
    mixer, the same router decision and the same shared expert, and its
    own experts' part of the routed sum: the routed parts of all
    thirty-two, with the rest counted ONCE, are the whole layer as the
    uncut reference gives it."""
    cfg = small_model(routed_experts=64, routed_held=64,
                      routed_first_held=0, routed_top_k=4).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), "kda", "routed")

    variables = jax.jit(block(0, 64).init)(jax.random.PRNGKey(4), x,
                                           positions)
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    bias = variables["moe_state"]["bias"]
    assert bias.shape == (64,) and float(jnp.abs(bias).max()) > 0
    assert "shared_fc1" in p

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 2],
                "experts_fc2": p["experts_fc2"][first:first + 2]
                * fc2_scale}
        return block(first, 2).apply(
            {"params": mine, "moe_state": {"bias": bias}}, x, positions)

    config = {**CONFIG, "num_experts": 64, "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)  # the stream, KDA, the shared one
        total = alike + sum(share(first) - alike
                            for first in range(0, 64, 2))
        uncut = ref._block(config, p, bias, x, "kda", False)
        one = share(2)
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    # and one share alone is NOT the layer: it leaves out 62 experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


PUBLISHED = dict(
    vocab_size=163840, num_layers=27, emb_dim=2304, num_heads=32,
    kv_heads=32, head_dim=72, ffn_width=9216, q_lora_rank=0,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, kda_heads=32, kda_head_dim=128, kda_conv=4,
    kda_chunk=64, kda_states_every=4, kda_inner=4096,
    attention_window=None, attention_scale=None, norm_eps=1e-5,
    routed_experts=256, held_experts=256, routed_top_k=8,
    routed_width=1024, routed_scaling=2.446, shared_experts=1,
    dense_layers_first=1, mtp_modules=0, max_len=1048576,
    tie_embeddings=False, use_bias=False, norm="rmsnorm", mlp="silu_gated",
    pos_embedding="none", rope_layer_types=None, qk_norm=False,
    attention_gate=False, post_norms=False,
    routed_router_input="ffn_input", routed_scores="sigmoid",
    routed_activation="silu", routed_balance_coef=0.0,
    remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    # linear_attn_config counts from 1: full attention at 4, 8, ..., 24
    # and 27, KDA everywhere else
    latent = [i for i, kind in enumerate(cfg.layer_types) if kind == "mla"]
    assert [i + 1 for i in latent] == [4, 8, 12, 16, 20, 24, 27]
    assert set(cfg.layer_types) == {"kda", "mla"}
    assert cfg.layer_types[:5] == KINDS
    assert [cfg.ffn_type(i) for i in range(3)] == ["dense", "routed",
                                                   "routed"]
    assert not cfg.rotates("mla") and not cfg.rotates("kda")
    assert "kda" in LAYER_TYPES


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def test_the_named_size_counts_49122675072_parameters():
    shapes = jax.eval_shape(lambda: gpt(NAME).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    p = shapes["params"]
    kda_mixer_leaves = ("qkv", "conv_kernel", "f_a", "f_b", "dt_bias",
                        "A_log", "b_proj", "g_a", "g_b", "o_norm", "o_proj")
    assert sum(_count(p["block0"][k]) for k in kda_mixer_leaves) \
        == 39_514_272
    assert sum(_count(p["block3"][k]) for k in (
        "q_b", "kv_a", "kv_a_norm", "kv_b", "proj")) == 29_114_880
    assert _count(p["block0"]) == 103_219_872   # KDA, the dense 9216
    routed = 589_824 + 257 * 7_077_888          # router, 256 + 1 experts
    assert _count(p["block1"]) == 39_514_272 + routed + 4608
    assert _count(p["block3"]) == 29_114_880 + routed + 4608
    assert _count(p["wte"]) == _count(p["head"]) == 163840 * 2304
    assert _count(p) == 49_122_675_072
    assert _count(shapes["moe_state"]) == 26 * 256


def test_the_cut_counts_602433408_parameters():
    """The benchmark's cut from the named size: depth 27 -> 5 (the first
    five published layers: KDA with the dense feed-forward, KDA, KDA,
    latent, KDA), 8 of 256 experts held, an eighth of the vocabulary;
    every width as published (ISSUE 51 has the sum)."""
    model = gpt(NAME, num_layers=5, layer_types=KINDS, routed_held=8,
                vocab_size=20480)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    p = shapes["params"]
    assert _count(p["block0"]["fc1"]) + _count(
        p["block0"]["fc2"]) == 63_700_992
    assert _count(p["block1"]["router"]) == 589_824
    assert _count(p["block1"]["experts_fc1"]) + _count(
        p["block1"]["experts_fc2"]) == 8 * 7_077_888
    assert _count(p["block1"]["shared_fc1"]) + _count(
        p["block1"]["shared_fc2"]) == 7_077_888
    assert [_count(p[f"block{i}"]) for i in range(5)] == [
        103_219_872, 103_809_696, 103_809_696, 93_410_304, 103_809_696]
    assert _count(p["wte"]) == _count(p["head"]) == 47_185_920
    assert _count(p["lnf"]) == 2304
    assert _count(p) == 602_433_408
    # 12 B a parameter of step arguments: 6.73 GiB
    assert round(_count(p) * 12 / 2 ** 30, 2) == 6.73
    # per expert layer: rows of 8 held experts, rows dropped, the load of
    # all 256 and the overflow counter
    assert _count(shapes["moe_stats"]) == 4 * (8 + 1 + 256 + 1)


def test_the_cells_flash_call_holds_kv_forward_and_dq_backward():
    """The cell's call, ``[32, 16384, 192]`` on values of 128 in
    bfloat16: the first head size that is no multiple of 128 and no 64
    (192 channels occupy 256 lanes).  Forward a kv row's K and V stay
    resident, stating 27 MiB; backward the K-outermost kernel with a
    row's dq resident at the 32 MiB, one kernel; of a head's 32 x 64
    tiles the causal half and its diagonal, the table walked."""
    from horovod_tpu.ops.flash_attention import flash_plan

    shape = lambda width: jax.ShapeDtypeStruct((1, 16384, 32, width),
                                               jnp.bfloat16)
    plan = flash_plan(shape(192), shape(192), shape(128), causal=True)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (True, 28_311_552)
    assert (plan.bwd_form, plan.bwd_vmem_bytes, plan.bwd_kernels) == (
        "dq_resident", 32 * 2 ** 20, 1)
    assert (plan.heads, plan.kv_heads, plan.window) == (32, 32, None)
    assert (plan.block_q, plan.block_k) == (512, 256)
    assert (plan.tiles_live, plan.tiles_mask) == (33_792, 65_536)
    assert plan.tiles_grid == plan.tiles_live
    assert len(plan.live_tiles) == 33_792 // 32


def test_a_kda_block_carries_its_scopes_and_the_gauges_count_it():
    """A step traced names a KDA block's mixer half ``kda``, the
    float32 chain inside it ``kda_prep`` and the rule ``kda_scan``
    (forward and backward), the latent block's ``attn`` with
    ``mla_proj`` inside; the gauges hold the KDA layers, those whose
    chain took the kernels (all at 64 tokens, none at 40), the chunk
    and what a layer keeps."""
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.ops.kda import kept_mib

    assert {scopes.KDA, scopes.KDA_PREP, scopes.KDA_SCAN} <= set(
        scopes.SCOPES)
    assert MIXER_SCOPES["kda"] == scopes.KDA
    model = small_model()
    variables = sound()[0]
    text = jax.jit(jax.grad(lambda p: program_loss(
        model, {**variables, "params": p}, TOKENS))).lower(
            variables["params"]).as_text(debug_info=True)
    for inner in ("kda_prep", "kda_scan"):
        names = set(re.findall(rf'"([^"]*/{inner}/[^"]*)"', text))
        assert any(f"jvp(GPT)/block0/kda/{inner}/" in name
                   and "transpose(" not in name for name in names), inner
        assert any(f"transpose(jvp(GPT))/block0/kda/{inner}/" in name
                   for name in names), inner
        assert all(f"/kda/{inner}/" in name for name in names), inner
    assert "block0/kda/qkv" in text and "block0/kda/o_proj" in text
    assert "block3/attn/mla_proj/" in text and "block3/kda" not in text
    assert "block0/attn" not in text and "block1/mlp/moe_route/" in text
    # the chain is the two kernels of ops/kda_prep.py behind their inner
    # jit, whose body (interpreted here) is lowered once for all layers
    assert '/jvp(GPT)/block0/kda/kda_prep/jit(_forward)"' in text
    assert ('/transpose(jvp(GPT))/block0/kda/kda_prep/jit(_backward)"'
            in text)
    assert '"kda_prep_fwd/' in text and '"kda_prep_bwd/' in text
    registry = get_registry()
    assert registry.gauge("kda.layers").value == 4
    assert registry.gauge("kda.prep_kernel_layers").value == 4
    assert registry.gauge("kda.kernel_layers").value == 4
    assert '/jvp(GPT)/block0/kda/kda_scan/jit(_kernel_forward)"' in text
    assert ('/transpose(jvp(GPT))/block0/kda/kda_scan/'
            'jit(_kernel_backward)"' in text)
    assert '"kda_fwd/' in text and '"kda_bwd/' in text
    assert registry.gauge("kda.chunk").value == 16
    assert registry.gauge("kda.kept_mib").value == kept_mib(
        2, SEQ, 4, 16, 16, 16, 2, 4)
    # 40 tokens are no whole 16-row tiles: the chain as XLA compiles it
    short = str(jax.make_jaxpr(lambda p: program_loss(
        small_model(kda_chunk=8), {**variables, "params": p},
        TOKENS[:, :41]))(variables["params"]))
    assert "kda_prep_fwd" not in short and "logistic" in short
    assert registry.gauge("kda.layers").value == 4
    assert registry.gauge("kda.prep_kernel_layers").value == 0


def test_a_rematerialised_kda_block_keeps_the_rules_outputs_by_name():
    """Under remat the block keeps its input and the rule's ``o`` and
    states: the gauges count four of each."""
    from horovod_tpu.obs.registry import get_registry

    model = small_model(remat=True)
    variables = sound()[0]
    jax.make_jaxpr(jax.grad(lambda p: program_loss(
        model, {**variables, "params": p}, TOKENS)))(variables["params"])
    registry = get_registry()
    assert registry.gauge("remat.kept_values", name="kda_out").value == 4
    assert registry.gauge("remat.kept_values",
                          name="kda_states").value == 4


def test_a_block_makes_the_kda_modules_only_where_asked():
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    kda_leaves = {"ln1", "qkv", "conv_kernel", "f_a", "f_b", "dt_bias",
                  "A_log", "b_proj", "g_a", "g_b", "o_norm", "o_proj", "ln2"}
    routed = {"router", "experts_fc1", "experts_fc2", "shared_fc1",
              "shared_fc2"}
    assert set(tree["block0"]) == kda_leaves | {"fc1", "fc2"}
    assert set(tree["block1"]) == kda_leaves | routed
    # no query rank: one matrix, no q_a and no norm of it
    assert set(tree["block3"]) == {"ln1", "q_b", "kv_a", "kv_a_norm",
                                   "kv_b", "proj", "ln2"} | routed
    assert "wpe" not in tree and "head" in tree
    assert tree["block0"]["qkv"]["kernel"].shape == (64, 3 * 64)
    assert tree["block0"]["conv_kernel"].shape == (4, 3 * 64)
    assert tree["block0"]["f_a"]["kernel"].shape == (64, 16)
    assert tree["block0"]["f_b"]["kernel"].shape == (16, 64)
    assert tree["block0"]["b_proj"]["kernel"].shape == (64, 4)
    assert tree["block0"]["A_log"].shape == (4,)
    assert tree["block0"]["dt_bias"].shape == (64,)
    assert tree["block0"]["o_norm"].shape == (16,)
    assert tree["block3"]["q_b"]["kernel"].shape == (64, 4 * 24)
    assert tree["block3"]["kv_a"]["kernel"].shape == (64, 32 + 8)
    assert tree["block3"]["kv_b"]["kernel"].shape == (32, 4 * 32)


def test_a_log_is_drawn_between_one_and_sixteen():
    a = jnp.concatenate([jnp.exp(block["A_log"]) for block in
                         sound()[0]["params"].values() if "A_log" in block])
    assert a.shape == (16,)
    assert bool((a >= 1.0).all()) and bool((a <= 16.0).all())
    assert float(a.max() - a.min()) > 5.0


def test_a_sequence_the_chunk_does_not_divide_is_refused_by_name():
    """The model hands ``kda_chunk`` to the rule as it is set, and says so
    in its gauge: a sequence the chunk does not divide is the rule's to
    refuse, and no smaller chunk is taken silently."""
    from horovod_tpu.obs.registry import get_registry

    model = small_model()
    variables = sound()[0]
    run = jax.jit(model.apply)
    with pytest.raises(ValueError,
                       match="kda: seq=24 is not a multiple of chunk=16"):
        run(variables, TOKENS[:, :24])
    with jax.default_matmul_precision("highest"):
        short = run(variables, TOKENS[:, :32])
        whole = run(variables, TOKENS[:, :SEQ])
    assert get_registry().gauge("kda.chunk").value == 16
    # causal: the first 32 tokens' logits do not depend on the rest
    np.testing.assert_allclose(short, whole[:, :32], atol=2e-4)


PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]


@pytest.mark.parametrize("setting", ["kda", "kda_heads", "kda_head_dim",
                                     "kda_conv", "kda_chunk",
                                     "kda_states_every"])
@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_the_kda_layer_and_its_settings_by_name(path, setting):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's block
    from raw weights and keep no recurrent state: each refuses the
    ``kda`` layer and each of its settings by name, before anything is
    traced."""
    from test_glm_moe_mla import _refusals

    nano = gpt("nano").cfg
    cfg = {"kda": replace(nano, kda_heads=2, kda_head_dim=16,
                          layer_types=("attention", "kda", "attention")),
           "kda_heads": replace(nano, kda_heads=2),
           "kda_head_dim": replace(nano, kda_head_dim=64),
           "kda_conv": replace(nano, kda_conv=3),
           "kda_chunk": replace(nano, kda_chunk=32),
           "kda_states_every": replace(nano, kda_states_every=8)}[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


@pytest.mark.parametrize("override,message", [
    ({"kda_heads": 0}, "a 'kda' layer needs positive kda_heads=0"),
    ({"kda_chunk": 48}, "kda_chunk=48 a power of two"),
    ({"kda_states_every": 0}, "kda_states_every=0"),
    ({"kv_lora_rank": 0}, "needs positive kv_lora_rank"),
    ({"q_lora_rank": -1}, "q_lora_rank may be 0"),
    ({"pos_embedding": "learned"}, "pos_embedding must be 'rope' or 'none'"),
    ({"layer_types": ("kda",) * 4 + ("delta",)}, "layer_types must name"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_the_defaults_are_the_parents():
    """No other named size has a KDA layer, and the two latent sizes
    differ as published: GLM with a query rank and a rotation, this one
    with neither."""
    cfg = TransformerConfig()
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_chunk,
            cfg.kda_states_every) == (0, 128, 4, 64, 4)
    for size, named in GPT_CONFIGS.items():
        if size == NAME:
            continue
        assert "kda" not in (named.layer_types or ()), size
        assert named.kda_heads == 0, size
    glm = GPT_CONFIGS["glm-4.7-flash"]
    assert glm.q_lora_rank == 768 and glm.rotates("mla")
    assert glm.qk_nope_head_dim + glm.qk_rope_head_dim == glm.v_head_dim


def test_the_reference_blocks_its_heads_without_changing_the_result(
        monkeypatch):
    """At the real size the reference computes a KDA layer and a latent
    layer eight heads at a time; here two of the four, against all at
    once."""
    variables, want_logp, _ = sound()
    monkeypatch.setattr(ref, "KDA_HEADS", 2)
    monkeypatch.setattr(ref, "MLA_HEADS", 2)
    with jax.default_matmul_precision("highest"):
        blocked = jax.jit(lambda v: ref.logprob(CONFIG, v, BATCH))(variables)
    np.testing.assert_allclose(blocked, want_logp, atol=1e-4)
