"""Qwen3-Next-80B-A3B-Instruct's mechanisms on the training path
(``model_type: qwen3_next``): Gated DeltaNet (one causal four-tap filter
over q, k and v with silu, unit-length q and k, a decay that is one
number a head, fewer key heads than value heads, the gated delta rule
through ``ops/kda.py:gated_delta_rule``, a head norm gated by
``silu(z)``) three to one with gated attention (the gate out of the
query projection, ``1 + w`` head norms, a quarter of a head rotated);
every stream norm ``1 + w``; routed experts behind a softmax router that
renormalises its chosen weights, beside a shared expert behind a sigmoid
gate; a load-balance loss; an untied head.  The program
(``models/transformer.py``, ``ops/kda.py``, ``ops/rope.py``,
``parallel/moe.py``) against the benchmark's own plain reference
(``benchmark/configs/qwen3-next-80b-a3b-instruct.reference.py``) on
seeded weights in float32: loss, log-probabilities, every gradient leaf,
and every departure of the reference told from it; the rule's new entry
against the recurrence token by token, kernels interpreted and the XLA
form, at decays of sixty a token; the sixteen shares of the experts
adding up to the uncut layer; the published values of the named size and
the counts of the model and of its cut; the scopes and gauges; the paths
that refuse the new layer and settings.
All on the CPU at small sizes that keep every ratio: hidden 64, 2 key
heads under 4 value heads of 16 at a chunk of 16, 4 attention heads over
2 key/value heads of 16 with a quarter rotated, 16 experts of width 32,
4 a token, 4 held, 64 tokens, the cell's four layers.
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
                                            TransformerConfig, gpt)
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops.kda import gated_delta_rule, kept_mib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "qwen3-next-80b-a3b-instruct"


def _load_reference():
    path = os.path.join(ROOT, "benchmark", "configs", NAME + ".reference.py")
    spec = importlib.util.spec_from_file_location("qwen3_next_reference",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()

# the cell's cut: the first four published layers, one whole period
KINDS = ("gdn", "gdn", "gdn", "full_attention")
SMALL = dict(
    num_layers=4, layer_types=KINDS, vocab_size=256, emb_dim=64,
    num_heads=4, num_kv_heads=2, head_size=16, gdn_key_heads=2,
    gdn_value_heads=4, gdn_key_head_dim=16, gdn_value_head_dim=16,
    kda_chunk=16, kda_states_every=2, mlp_width=192,
    routed_experts=16, routed_held=4, routed_first_held=8, routed_top_k=4,
    routed_width=32, routed_balance_coef=0.01,
    max_len=128, attention_impl="reference",
    # several tiles a row
    flash_block_q=16, flash_block_k=8, dtype=jnp.float32)
CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=10000000,
    rms_norm_eps=1e-6, full_attention_interval=4,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, num_hidden_layers=4, num_experts=4,
    first_held_expert=8, num_experts_per_tok=4, balance_loss_coef=0.01)
SEQ = 64
TOKENS = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)
BATCH = {"tokens": TOKENS}


def small_model(**overrides):
    return gpt(NAME, **{**SMALL, **overrides})


def init(model, key=1):
    """Seeded variables; the router ten times its initial size so that
    the scores spread at this width, the token table at the cell's
    scale, milder decays, and every norm's weight off its
    starting value (``w`` off zero: a ``1 + w`` norm with ``w = 0`` would
    hide a plain scale's fault behind a zero)."""
    variables = jax.jit(model.init)(jax.random.PRNGKey(key),
                                    TOKENS[:, :SEQ])

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if "router" in name:
            return leaf * 10.0
        if "wte" in name:
            # a standard normal table, as the cell's builder draws it
            return leaf * leaf.shape[-1] ** 0.5
        if "dt_bias" in name:
            # decays of 0.1 A a token and not the family's 1.3 A, under
            # which all but a head in sixteen forget within two tokens:
            # the state has to carry the sequence for a fault in it to
            # weigh
            return leaf - 3.0
        if "scale" in name or "o_norm" in name:
            return leaf + 0.3 * jax.random.normal(
                jax.random.PRNGKey(len(name)), leaf.shape)
        return leaf

    return {"params": jax.tree_util.tree_map_with_path(
        moved, variables["params"])}


def program_sides(model, variables, tokens=TOKENS):
    """The loss (with the balance term), every label's log-probability
    and the loss's gradient, one compiled program."""
    coef = model.cfg.routed_balance_coef

    def sides(v):
        def loss_of(p):
            logits, sown = model.apply({"params": p}, tokens[:, :-1],
                                       mutable=["losses"])
            logp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), tokens[:, 1:, None],
                axis=-1)[..., 0]
            balance = coef * sum(jax.tree.leaves(sown["losses"]))
            return -logp.mean() + balance, logp

        (loss, logp), grads = jax.value_and_grad(loss_of, has_aux=True)(
            v["params"])
        return loss, logp, grads

    return jax.jit(sides)(variables)


def reference_sides(variables, depart=None, config=CONFIG):
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda v: (
            ref.loss(config, v, BATCH, depart),
            ref.logprob(config, v, BATCH, depart),
            jax.grad(lambda p: ref.loss(config, {"params": p}, BATCH,
                                        depart))(v["params"])))(variables)


@functools.cache
def sound():
    """The seeded variables and what the program gives for them in
    float32, computed once."""
    variables = init(small_model())
    with jax.default_matmul_precision("highest"):
        return (variables, *program_sides(small_model(), variables))


def _norm(tree):
    return float(jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(tree))))


def _apart(got, want):
    return _norm(jax.tree.map(lambda a, b: a - b, got, want)) / _norm(want)


# float32 on both sides: what rounding alone leaves between the chunked
# rule and the recurrence, the sorted experts and every expert on every
# token
FLOAT32_LIMITS = dict(loss_abs=2e-5, logprob_abs=2e-4, grad_rel=1e-3)


def test_the_program_is_the_reference_in_float32():
    variables, loss, logp, grads = sound()
    want_loss, want_logp, want_grads = reference_sides(variables)
    assert abs(float(loss - want_loss)) <= FLOAT32_LIMITS["loss_abs"]
    assert float(jnp.abs(logp - want_logp).max()) \
        <= FLOAT32_LIMITS["logprob_abs"]
    assert _apart(grads, want_grads) <= FLOAT32_LIMITS["grad_rel"]
    # every leaf, each against its own size
    flat = jax.tree_util.tree_leaves_with_path(grads)
    for (path, got), want in zip(flat, jax.tree.leaves(want_grads)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(want).max()) > 0, name
        assert _apart(got, want) <= 5e-3, name


@pytest.mark.parametrize("depart", ref.DEPARTURES)
def test_every_departure_moves_the_loss_or_a_gradient(depart):
    """Each fault the reference can seed reads past the float32 limits
    in the loss, a log-probability or the gradient."""
    variables, loss, logp, grads = sound()
    got_loss, got_logp, got_grads = reference_sides(variables, depart)
    apart = _apart(grads, got_grads)
    told = (abs(float(loss - got_loss)) > 10 * FLOAT32_LIMITS["loss_abs"]
            or float(jnp.abs(logp - got_logp).max())
            > 10 * FLOAT32_LIMITS["logprob_abs"]
            or not apart <= 10 * FLOAT32_LIMITS["grad_rel"])
    assert told, (depart, float(loss - got_loss), apart)


def test_the_departures_named_by_the_issue_are_all_there():
    assert set(ref.DEPARTURES) >= {
        "decay_dropped", "decay_per_key_head", "beta_one", "conv_sees_next",
        "conv_per_stream", "qk_l2norm_dropped", "out_gate_sigmoid",
        "out_norm_unit_offset", "key_heads_tiled", "attn_gate_dropped",
        "rotary_full", "norm_plain_scale", "shared_gate_dropped",
        "softmax_before_topk_not_renormalised"}


def test_bfloat16_stays_within_stated_limits_of_the_reference():
    """bfloat16 compute against the float32 reference at this size; a
    flipped choice of experts is a large part of a token's output at
    hidden 64, so the limits are wide, and they still tell a
    departure."""
    variables = sound()[0]
    model = small_model(dtype=jnp.bfloat16)
    loss, logp, grads = program_sides(model, variables)
    want_loss, want_logp, want_grads = reference_sides(variables)
    assert abs(float(loss - want_loss)) <= 0.05
    assert float(jnp.abs(logp - want_logp).max()) <= 2.0
    assert _apart(jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                  want_grads) <= 0.35
    departed = reference_sides(variables, "decay_dropped")[2]
    assert _apart(jax.tree.map(lambda g: g.astype(jnp.float32), grads),
                  departed) > 0.7


# ---- the rule's entry


def recurrence(q, k, v, g, beta):
    """``S_t = (I - b k k^T) exp(g) S + b k v^T``, ``o = S^T q``, a token
    at a time, a value head at a time; value head ``i`` reads key head
    ``i // (heads // key heads)``."""
    rep = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(t, rep, axis=2) for t in (q, k))
    dk, dv = q.shape[-1], v.shape[-1]

    def head(q, k, v, g, beta):
        def token(S, at):
            q_t, k_t, v_t, g_t, b_t = at
            S = jnp.exp(g_t) * S
            S = S + b_t * jnp.outer(k_t, v_t - S.T @ k_t)
            return S, S.T @ q_t

        return jax.lax.scan(token, jnp.zeros((dk, dv)),
                            (q, k, v, g, beta))[1]

    per_head = jax.vmap(head, in_axes=1, out_axes=1)
    return jax.vmap(per_head)(q, k, v, g, beta)


def rule_inputs(seq, strength, seed=0, batch=2, key_heads=2, heads=4,
                dk=16, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, seq, key_heads, dk))) \
        * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, seq, key_heads, dk)))
    v = jax.random.normal(ks[2], (batch, seq, heads, dv))
    g = -strength * jax.nn.softplus(
        jax.random.normal(ks[3], (batch, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, seq, heads)))
    return q, k, v, g, beta


@pytest.fixture(params=["xla", "kernels"])
def form(request, monkeypatch):
    """Which form of the rule the entry runs: ``plan`` decides, from the
    shape alone; off the TPU it gives the kernels every shape."""
    if request.param == "xla":
        monkeypatch.setattr(kda_ops, "plan", lambda *shape: None)
    return request.param


@pytest.mark.parametrize("strength", [0.5, 60.0])
def test_the_entry_is_the_recurrence_forward_and_backward(form, strength):
    """``o`` and every gradient against the token-by-token rule, at mild
    decays and at sixty a token (``exp(-G)`` over a chunk would
    overflow), with two value heads to a key head."""
    args = rule_inputs(64, strength)
    weights = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 4, 8))
    run = lambda f: jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(f(*a) * weights), argnums=(0, 1, 2, 3, 4)))(*args)
    with jax.default_matmul_precision("highest"):
        want, want_grads = run(recurrence)
        got, got_grads = run(functools.partial(
            gated_delta_rule, chunk=16, states_every=2))
        np.testing.assert_allclose(
            gated_delta_rule(*args, chunk=16, states_every=2),
            recurrence(*args), atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g, w in zip("q k v g beta".split(), got_grads, want_grads):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=3e-5 * max(
            1.0, float(jnp.abs(w).max())), err_msg=name)


def test_the_entry_with_equal_head_counts_is_the_scalar_rule(form):
    args = rule_inputs(32, 1.0, key_heads=3, heads=3)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            gated_delta_rule(*args, chunk=8), recurrence(*args), atol=2e-5)


def test_the_entry_traces_under_its_scope_and_runs_the_kernels():
    args = rule_inputs(32, 1.0)
    text = jax.jit(functools.partial(gated_delta_rule, chunk=16)).lower(
        *args).as_text(debug_info=True)
    assert '/gdn_scan/jit(_kernel_forward)"' in text and '"kda_fwd/' in text
    assert "kda_scan" not in text
    # what spreads the decay and the key heads has a scope of its own
    # inside the rule's, the kernels stand outside it
    assert '/gdn_scan/gdn_spread/' in text
    assert "gdn_spread/jit(_kernel" not in text


@pytest.mark.parametrize("edit,message", [
    (dict(seq=24), "seq=24 must be a multiple of chunk=16"),
    (dict(key_heads=3), "do not agree"),
])
def test_the_entry_refuses_what_it_cannot_compute(edit, message):
    q, k, v, g, beta = rule_inputs(edit.get("seq", 32), 1.0,
                                   key_heads=edit.get("key_heads", 2))
    with pytest.raises(ValueError, match=message):
        gated_delta_rule(q, k, v, g, beta, chunk=16)


def test_the_entry_refuses_a_decay_per_channel():
    q, k, v, g, beta = rule_inputs(32, 1.0)
    with pytest.raises(ValueError, match="do not agree"):
        gated_delta_rule(q, k, v, jnp.broadcast_to(
            g[..., None], (*g.shape, 16)), beta, chunk=16)


# ---- the attention layer's three settings


def test_a_part_rotated_head_turns_its_first_channels_only():
    from horovod_tpu.ops.rope import apply_rope_tables, rope_tables

    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 2, 16))
    cos, sin = rope_tables(jnp.arange(8), 4, 1e7)
    out = apply_rope_tables(x, cos, sin)
    np.testing.assert_array_equal(out[..., 4:], x[..., 4:])
    np.testing.assert_allclose(
        out[..., :4], apply_rope_tables(x[..., :4], cos, sin), atol=0)
    assert float(jnp.abs(out[:, 1:, :, :4] - x[:, 1:, :, :4]).max()) > 1e-3
    # whole heads as before
    full = rope_tables(jnp.arange(8), 16, 1e7)
    assert apply_rope_tables(x, *full).shape == x.shape
    assert small_model().cfg.rope_dim == 4
    assert GPT_CONFIGS[NAME].rope_dim == 64


def test_the_unit_offset_norm_starts_as_the_plain_one():
    from horovod_tpu.models.transformer import UnitOffsetRMSNorm

    x = jax.random.normal(jax.random.PRNGKey(4), (3, 16))
    norm = UnitOffsetRMSNorm(epsilon=1e-6)
    variables = norm.init(jax.random.PRNGKey(0), x)
    assert float(jnp.abs(variables["params"]["scale"]).max()) == 0.0
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.apply(variables, x), want, rtol=1e-6)
    moved = {"params": {"scale": jnp.full((16,), 0.5)}}
    np.testing.assert_allclose(norm.apply(moved, x), 1.5 * want, rtol=1e-6)


def test_the_attention_layer_gets_the_chain_and_the_gauges_say_so():
    """A gate out of the query projection, ``1 + w`` head norms and a
    part-rotated head: none is the prep kernels', so ``plan`` is never
    asked and the layer counts as one that has a chain and no kernel."""
    from horovod_tpu.models.transformer import _attn_prep_plan
    from horovod_tpu.obs.registry import get_registry

    cfg = replace(GPT_CONFIGS[NAME], attention_impl="flash")
    assert _attn_prep_plan(cfg, 16384, 16, 2, norm="rmsnorm", rotates=True,
                           plain=True) is None
    for edit in (dict(attention_gate=False, norm_unit_offset=False),
                 dict(partial_rotary_factor=1.0, norm_unit_offset=False),
                 dict(attention_gate=False, partial_rotary_factor=1.0)):
        assert _attn_prep_plan(
            replace(cfg, **edit), 16384, 16, 2, norm="rmsnorm",
            rotates=True, plain=True) is None, edit
    assert _attn_prep_plan(
        replace(cfg, attention_gate=False, norm_unit_offset=False,
                partial_rotary_factor=1.0), 16384, 16, 2, norm="rmsnorm",
        rotates=True, plain=True) is not None
    model = small_model(attention_impl="flash")
    jax.eval_shape(lambda v: model.apply(v, TOKENS[:, :SEQ],
                                         mutable=["losses"]), sound()[0])
    registry = get_registry()
    assert registry.gauge("attn_prep.layers").value == 1
    assert registry.gauge("attn_prep.kernel_layers").value == 0


def test_flash_and_reference_schedules_agree_on_the_gated_layer():
    variables = sound()[0]
    tokens = TOKENS[:, :SEQ]
    with jax.default_matmul_precision("highest"):
        run = lambda impl: jax.jit(lambda v: small_model(
            attention_impl=impl).apply(v, tokens, mutable=["losses"])[0])(
                variables)
        np.testing.assert_allclose(run("flash"), run("reference"),
                                   atol=2e-4)


# ---- the share test


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold four experts each of sixty-four, four a token,
    beside one shared expert behind its gate.  Every share computes the
    same DeltaNet mixer, the same router decision and the same gated
    shared expert, and its own experts' part of the routed sum: the
    routed parts of all sixteen, with the rest counted ONCE, are the
    whole layer as the uncut reference gives it."""
    cfg = small_model(routed_experts=64, routed_held=64,
                      routed_first_held=0, routed_top_k=4).cfg
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.arange(SEQ)

    def block(first, held):
        return Block(replace(cfg, routed_first_held=first,
                             routed_held=held), "gdn", "routed")

    variables = jax.jit(lambda: block(0, 64).init(
        jax.random.PRNGKey(4), x, positions))()
    p = dict(variables["params"])
    p["router"] = p["router"] * 10.0
    assert {"shared_fc1", "shared_gate"} <= set(p)
    assert "moe_state" not in variables

    def share(first, fc2_scale=1.0):
        mine = {**p, "experts_fc1": p["experts_fc1"][first:first + 4],
                "experts_fc2": p["experts_fc2"][first:first + 4]
                * fc2_scale}
        return block(first, 4).apply({"params": mine}, x, positions,
                                     mutable=["losses"])[0]

    config = {**CONFIG, "num_experts": 64, "first_held_expert": 0}
    with jax.default_matmul_precision("highest"):
        alike = share(0, fc2_scale=0.0)  # the stream, the mixer, shared
        total = alike + sum(share(first) - alike
                            for first in range(0, 64, 4))
        uncut, _ = ref._block(config, p, x, "gdn")
        one = share(4)
    np.testing.assert_allclose(total, uncut, atol=1e-4)
    # and one share alone is NOT the layer: it leaves out 60 experts
    assert float(jnp.abs(one - uncut).max()) > 1e-2


# ---- the named size


PUBLISHED = dict(
    vocab_size=151936, num_layers=48, emb_dim=2048, num_heads=16,
    kv_heads=2, head_dim=256, ffn_width=5120, rope_theta=1e7,
    partial_rotary_factor=0.25, rope_dim=64, gdn_key_heads=16,
    gdn_value_heads=32, gdn_key_head_dim=128, gdn_value_head_dim=128,
    gdn_conv=4, kda_chunk=64, kda_states_every=4, gdn_key_inner=2048,
    gdn_value_inner=4096, attention_window=None, attention_scale=None,
    norm_eps=1e-6, norm_unit_offset=True, routed_experts=512,
    held_experts=512, routed_top_k=10, routed_width=512, routed_scaling=1.0,
    shared_experts=1, shared_ffn_width=512, shared_expert_gate=True,
    dense_layers_first=0, mtp_modules=0, max_len=262144,
    tie_embeddings=False, use_bias=False, norm="rmsnorm", mlp="silu_gated",
    pos_embedding="rope", rope_layer_types=None, qk_norm=True,
    attention_gate="query", query_gate=True,
    post_norms=False, routed_router_input="ffn_input",
    routed_scores="softmax_chosen", routed_activation="silu",
    routed_balance_coef=0.001, remat_policy="nothing_saveable")


def test_named_configuration_holds_the_published_values():
    cfg = GPT_CONFIGS[NAME]
    for key, value in PUBLISHED.items():
        assert getattr(cfg, key) == value, key
    # full_attention_interval 4: layer i attends where (i + 1) % 4 == 0
    attends = [i for i, kind in enumerate(cfg.layer_types)
               if kind == "full_attention"]
    assert attends == list(range(3, 48, 4))
    assert set(cfg.layer_types) == {"gdn", "full_attention"}
    assert cfg.layer_types[:4] == KINDS
    assert {cfg.ffn_type(i) for i in range(48)} == {"routed"}
    assert cfg.rotates("full_attention")
    assert "gdn" in LAYER_TYPES


def _count(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


GDN_LEAVES = ("in_proj", "ba_proj", "conv_kernel", "dt_bias", "A_log",
              "o_norm", "out_proj")
ATTENTION_LEAVES = ("qkv", "q_norm", "k_norm", "proj")
EXPERT_LEAVES = ("router", "shared_fc1", "shared_fc2", "shared_gate")


def test_the_named_size_counts_79674391296_parameters():
    shapes = jax.eval_shape(lambda: gpt(NAME).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    p = shapes["params"]
    assert sum(_count(p["block0"][k]) for k in GDN_LEAVES) == 33_718_464
    assert sum(_count(p["block3"][k])
               for k in ATTENTION_LEAVES) == 27_263_488
    assert sum(_count(p["block0"][k]) for k in EXPERT_LEAVES) == 4_196_352
    experts = 512 * 3 * 2048 * 512
    assert _count(p["block0"]) == 33_718_464 + 4_196_352 + 4096 + experts
    assert _count(p["block3"]) == 27_263_488 + 4_196_352 + 4096 + experts
    assert _count(p["wte"]) == _count(p["head"]) == 151936 * 2048
    assert _count(p) == 79_674_391_296
    assert "moe_state" not in shapes and "mtp" not in p


def test_the_cut_counts_625667136_parameters():
    """The benchmark's cut from the named size: depth 48 -> 4 (published
    layers 0-3: three DeltaNet layers and the attention layer), 32 of 512
    experts held, an eighth of the vocabulary; every width as published
    (ISSUE 64 has the sum)."""
    model = gpt(NAME, num_layers=4, layer_types=KINDS, routed_held=32,
                vocab_size=18992)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32)))
    p = shapes["params"]
    assert _count(p["block0"]["in_proj"]) == 25_165_824
    assert _count(p["block0"]["ba_proj"]) == 131_072
    assert _count(p["block0"]["conv_kernel"]) == 32_768
    assert _count(p["block0"]["out_proj"]) == 8_388_608
    assert _count(p["block3"]["qkv"]) == 2048 * (8192 + 1024)
    assert _count(p["block1"]["experts_fc1"]) + _count(
        p["block1"]["experts_fc2"]) == 100_663_296
    assert [_count(p[f"block{i}"]) for i in range(4)] == [
        138_582_208, 138_582_208, 138_582_208, 132_127_232]
    assert _count(p["wte"]) + _count(p["head"]) == 77_791_232
    assert _count(p["lnf"]) == 2048
    assert _count(p) == 625_667_136
    # 12 B a parameter of step arguments: 6.99 GiB
    assert round(_count(p) * 12 / 2 ** 30, 2) == 6.99
    # per expert layer: rows of 32 held experts, rows dropped, the load
    # of all 512, the overflow counter and the balance loss
    assert _count(shapes["moe_stats"]) == 4 * (32 + 1 + 512 + 1 + 1)


def test_the_cells_flash_call_plans_its_backward():
    """The cell's call, 16 query heads over 2 key/value heads of 256 at
    16 384 keys in bfloat16, a shape no other cell has.  Neither a kv
    row's K and V forward nor either one-kernel backward fits VMEM at
    heads of 256 over 16 384 keys with eight query heads to a row: the
    forward fetches its tiles and the backward is the two passes, which
    the gauge ``flash.bwd_kernels`` publishes as 2."""
    from horovod_tpu.ops.flash_attention import flash_plan

    shape = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 256),
                                               jnp.bfloat16)
    plan = flash_plan(shape(16), shape(2), shape(2), causal=True)
    assert (plan.heads, plan.kv_heads, plan.window) == (16, 2, None)
    assert (plan.bwd_form, plan.bwd_kernels) == ("two_passes", 2)
    assert not plan.fwd_kv_resident
    assert (plan.block_q, plan.block_k) == (512, 256)
    assert (plan.tiles_live, plan.tiles_mask) == (16_896, 32_768)
    assert plan.tiles_grid == plan.tiles_live


# ---- scopes, gauges, the tree


def test_a_gdn_block_carries_its_scopes_and_the_gauges_count_it():
    """A step traced names a DeltaNet block's mixer half ``gdn``, the
    float32 chain inside it ``gdn_prep`` and the rule ``gdn_scan``
    (forward and backward), the attention block's gate product
    ``attn_gate`` and the shared expert with its gate ``moe_shared``;
    the gauges hold the layers, those whose rule took the kernels, the
    chunk and what a layer keeps."""
    from horovod_tpu.obs.registry import get_registry

    assert {scopes.GDN, scopes.GDN_PREP, scopes.GDN_SCAN,
            scopes.GDN_SPREAD} <= set(scopes.SCOPES)
    assert MIXER_SCOPES["gdn"] == scopes.GDN
    model = small_model()
    variables = sound()[0]
    text = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, TOKENS[:, :SEQ], mutable=["losses"])[0].sum())).lower(
            variables["params"]).as_text(debug_info=True)
    for inner in ("gdn_prep", "gdn_scan"):
        names = set(re.findall(rf'"([^"]*/{inner}/[^"]*)"', text))
        assert any(f"jvp(GPT)/block0/gdn/{inner}/" in name
                   and "transpose(" not in name for name in names), inner
        assert any(f"transpose(jvp(GPT))/block0/gdn/{inner}/" in name
                   for name in names), inner
        assert all(f"/gdn/{inner}/" in name for name in names), inner
    assert "block0/gdn/in_proj" in text and "block0/gdn/out_proj" in text
    assert "block0/gdn/ba_proj" in text
    assert "block3/attn/attn_gate/" in text and "block3/gdn" not in text
    assert "block3/attn/attn_prep/" in text
    assert "block0/attn" not in text and "block1/mlp/moe_route/" in text
    assert "block0/mlp/moe_shared/shared_gate" in text
    assert '/jvp(GPT)/block0/gdn/gdn_scan/jit(_kernel_forward)"' in text
    assert ('/transpose(jvp(GPT))/block0/gdn/gdn_scan/'
            'jit(_kernel_backward)"' in text)
    assert '"kda_fwd/' in text and '"kda_bwd/' in text
    assert "kda_scan" not in text and "kda_prep" not in text
    # the spreading, forward and the sums of its gradients, inside the
    # rule's scope and nowhere else
    spread = set(re.findall(r'"([^"]*/gdn_spread/[^"]*)"', text))
    assert any("transpose(" in name for name in spread)
    assert any("transpose(" not in name for name in spread)
    assert all("/gdn/gdn_scan/gdn_spread/" in name for name in spread)
    registry = get_registry()
    assert registry.gauge("gdn.layers").value == 3
    assert registry.gauge("gdn.kernel_layers").value == 3
    assert registry.gauge("gdn.chunk").value == 16
    assert registry.gauge("gdn.kept_mib").value == kept_mib(
        2, SEQ, 4, 16, 16, 16, 2, 4)


def test_a_rematerialised_gdn_block_keeps_the_rules_outputs_by_name():
    """Under remat the block keeps its input and the rule's ``o`` and
    states under the rule's own names: three of each, and the attention
    layer's two."""
    from horovod_tpu.obs.registry import get_registry

    model = small_model(remat=True, attention_impl="flash")
    variables = sound()[0]
    jax.make_jaxpr(jax.grad(lambda p: model.apply(
        {"params": p}, TOKENS[:, :SEQ], mutable=["losses"])[0].sum()))(
            variables["params"])
    registry = get_registry()
    assert registry.gauge("remat.kept_values", name="kda_out").value == 3
    assert registry.gauge("remat.kept_values", name="kda_states").value == 3
    assert registry.gauge("remat.kept_values", name="flash_out").value == 1


def test_a_block_makes_the_gdn_modules_only_where_asked():
    tree = jax.eval_shape(lambda: small_model().init(
        jax.random.PRNGKey(0), TOKENS[:, :SEQ]))["params"]
    routed = {"ln2", "router", "experts_fc1", "experts_fc2", "shared_fc1",
              "shared_fc2", "shared_gate"}
    assert set(tree["block0"]) == {"ln1", *GDN_LEAVES} | routed
    assert set(tree["block3"]) == {"ln1", *ATTENTION_LEAVES} | routed
    assert "wpe" not in tree and "head" in tree
    assert tree["block0"]["in_proj"]["kernel"].shape == (64, 32 + 32 + 128)
    assert tree["block0"]["ba_proj"]["kernel"].shape == (64, 8)
    assert tree["block0"]["conv_kernel"].shape == (4, 32 + 32 + 64)
    assert tree["block0"]["A_log"].shape == (4,)
    assert tree["block0"]["dt_bias"].shape == (4,)
    assert tree["block0"]["o_norm"].shape == (16,)
    assert tree["block0"]["shared_gate"]["kernel"].shape == (64, 1)
    # [gate ; q ; k ; v]
    assert tree["block3"]["qkv"]["kernel"].shape == (64, 64 + 64 + 32 + 32)
    assert tree["block3"]["q_norm"]["scale"].shape == (16,)


def test_the_norms_start_at_zero_and_the_decay_as_the_family_draws_it():
    params = jax.jit(small_model().init)(
        jax.random.PRNGKey(1), TOKENS[:, :SEQ])["params"]
    for name in ("ln1", "ln2"):
        assert float(jnp.abs(params["block0"][name]["scale"]).max()) == 0.0
    assert float(jnp.abs(params["lnf"]["scale"]).max()) == 0.0
    assert float(jnp.abs(params["block3"]["q_norm"]["scale"]).max()) == 0.0
    np.testing.assert_array_equal(params["block0"]["o_norm"], 1.0)
    np.testing.assert_array_equal(params["block0"]["dt_bias"], 1.0)
    a = jnp.concatenate([jnp.exp(params[f"block{i}"]["A_log"])
                         for i in range(3)])
    assert bool((a > 0.0).all()) and bool((a <= 16.0).all())
    assert float(a.max() - a.min()) > 5.0


def test_the_rule_runs_at_the_chunk_the_delta_rules_share():
    """``kda_chunk`` and ``kda_states_every`` are the one rule's: a
    DeltaNet layer runs at them too, to the same loss whatever they are,
    and its gauge says which chunk it took."""
    from horovod_tpu.obs.registry import get_registry

    variables = sound()[0]
    loss = lambda **kw: jax.jit(lambda v: small_model(**kw).apply(
        v, TOKENS[:, :SEQ], mutable=["losses"])[0].sum())(variables)
    want = loss()
    np.testing.assert_allclose(loss(kda_chunk=8, kda_states_every=4), want,
                               rtol=1e-5)
    assert get_registry().gauge("gdn.chunk").value == 8


def test_a_sequence_the_chunk_does_not_divide_is_refused_by_name():
    model = small_model()
    with pytest.raises(ValueError, match="gated_delta_rule: seq=24 must be "
                                         "a multiple of chunk=16"):
        jax.eval_shape(lambda v: model.apply(
            v, TOKENS[:, :24], mutable=["losses"]), sound()[0])


PATHS = ["decode_step", "generate", "init_cache", "init_paged_pool",
         "pp_gpt_apply", "prefill", "raw_block_forward", "slot_engine",
         "stack_pp_params", "stack_tp_params", "tp_gpt_apply"]
SETTINGS = ["gdn", "gdn_key_heads", "gdn_value_heads", "gdn_key_head_dim",
            "gdn_value_head_dim", "gdn_conv", "attention_gate",
            "partial_rotary_factor", "norm_unit_offset",
            "shared_expert_gate"]


@pytest.mark.parametrize("setting", SETTINGS)
@pytest.mark.parametrize("path", PATHS)
def test_paths_refuse_the_gdn_layer_and_the_new_settings_by_name(path,
                                                                 setting):
    """Decode, serve, tensor and pipeline parallelism build GPT-2's block
    from raw weights and keep no recurrent state: each refuses the
    ``gdn`` layer and each new setting by name, before anything is
    traced."""
    from test_glm_moe_mla import _refusals

    nano = gpt("nano").cfg
    cfg = {"gdn": replace(nano, gdn_key_heads=2, gdn_value_heads=4,
                          layer_types=("attention", "gdn", "attention")),
           "gdn_key_heads": replace(nano, gdn_key_heads=2),
           "gdn_value_heads": replace(nano, gdn_value_heads=2),
           "gdn_key_head_dim": replace(nano, gdn_key_head_dim=64),
           "gdn_value_head_dim": replace(nano, gdn_value_head_dim=64),
           "gdn_conv": replace(nano, gdn_conv=3),
           "attention_gate": replace(nano, attention_gate="query"),
           "partial_rotary_factor": replace(
               nano, pos_embedding="rope", partial_rotary_factor=0.5),
           "norm_unit_offset": replace(nano, norm="rmsnorm",
                                       norm_unit_offset=True),
           "shared_expert_gate": replace(nano, shared_expert_gate=True),
           }[setting]
    with pytest.raises(ValueError, match=setting):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


def test_every_refusing_path_is_a_case_above():
    from test_glm_moe_mla import _refusals

    assert PATHS == sorted(_refusals())


@pytest.mark.parametrize("override,message", [
    ({"gdn_key_heads": 0}, "a 'gdn' layer needs gdn_value_heads=4"),
    ({"gdn_key_heads": 3}, "a positive multiple of gdn_key_heads=3"),
    ({"kda_chunk": 48}, "a 'gdn' layer needs .* kda_chunk=48 a power of two"),
    ({"kda_states_every": 0}, "a 'gdn' layer needs .* kda_states_every=0"),
    ({"partial_rotary_factor": 0.3}, "an even number of channels"),
    ({"partial_rotary_factor": 1.5}, "at most the head"),
    ({"norm": "layernorm"}, "norm must be 'rmsnorm'"),
    ({"attention_gate": "key"}, "attention_gate must be one of"),
    ({"differential_attention": True}, "attention_gate='query'"),
    ({"layer_types": ("gdn",) * 3 + ("delta",)}, "layer_types must name"),
])
def test_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        small_model(**override)


def test_the_defaults_are_the_parents():
    """No other named size has a DeltaNet layer or any of the new
    settings."""
    cfg = TransformerConfig()
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_head_dim,
            cfg.gdn_value_head_dim, cfg.gdn_conv) == (0, 0, 128, 128, 4)
    assert (cfg.attention_gate, cfg.partial_rotary_factor,
            cfg.norm_unit_offset, cfg.shared_expert_gate) == (
                False, 1.0, False, False)
    for size, named in GPT_CONFIGS.items():
        if size == NAME:
            continue
        assert "gdn" not in (named.layer_types or ()), size
        assert named.gdn_value_heads == 0 and not named.query_gate, size
        assert named.rope_dim in (named.head_dim,
                                  named.qk_rope_head_dim), size
        assert not named.norm_unit_offset, size
        assert not named.shared_expert_gate, size
    assert GPT_CONFIGS["trinity-mini"].attention_gate is True


def test_the_reference_blocks_its_heads_without_changing_the_result(
        monkeypatch):
    """At the real size the reference computes a DeltaNet layer four key
    heads at a time; here one of the two, against both at once."""
    variables = sound()[0]
    want = reference_sides(variables)[1]
    monkeypatch.setattr(ref, "GDN_KEY_HEADS", 1)
    with jax.default_matmul_precision("highest"):
        blocked = jax.jit(lambda v: ref.logprob(CONFIG, v, BATCH))(variables)
    np.testing.assert_allclose(blocked, want, atol=1e-4)
