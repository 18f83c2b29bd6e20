"""Manifold-constrained hyper-connections
(``models/hyper_connections.py``) and what came with them: Sinkhorn's
iteration against a NumPy loop, the read-out and the write-back against
``einsum`` spellings, ``block_math`` over several streams on stub
callables and, with one stream, to the bit what the parent commit's
wiring gives at a tiny size of every named size; YaRN's frequencies in
``ops/rope.py`` against the closed form; the settings refused by name on
the raw-weights paths.  All on the CPU at tiny sizes.
"""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import scopes
from horovod_tpu.models import hyper_connections as hc
from horovod_tpu.models.transformer import (GPT_CONFIGS, MIXER_SCOPES,
                                            TransformerConfig, act_store,
                                            block_math, gpt,
                                            require_gpt2_block)
from horovod_tpu.ops import rope

N, TOKENS, WIDTH = 4, (2, 24), 16
KEYS = jax.random.split(jax.random.PRNGKey(0), 8)


def _numpy_sinkhorn(logits, iters, eps):
    """The same rounds on one matrix at a time, float64."""
    out = np.empty_like(logits, dtype=np.float64)
    for index in np.ndindex(*logits.shape[2:]):
        m = np.exp(logits[(slice(None), slice(None), *index)].astype(
            np.float64))
        for _ in range(iters):
            m = m / (m.sum(axis=0, keepdims=True) + eps)
            m = m / (m.sum(axis=1, keepdims=True) + eps)
        out[(slice(None), slice(None), *index)] = m
    return out


def test_sinkhorn_is_doubly_stochastic_and_equals_a_numpy_loop():
    logits = jax.random.normal(KEYS[0], (N, N, *TOKENS))
    got = hc.sinkhorn(logits, 20, 1e-6)
    np.testing.assert_allclose(got.sum(axis=0), 1.0, atol=1e-4)
    np.testing.assert_allclose(got.sum(axis=1), 1.0, atol=1e-4)
    np.testing.assert_allclose(
        got, _numpy_sinkhorn(np.asarray(logits), 20, 1e-6), atol=1e-6)
    assert float(hc.stochastic_err(got)) < 1e-4
    # one round is not twenty: the columns are still off
    assert float(hc.stochastic_err(hc.sinkhorn(logits, 1, 1e-6))) > 1e-2
    # rows are divided last
    once = hc.sinkhorn(logits, 1, 1e-6)
    np.testing.assert_allclose(once.sum(axis=1), 1.0, atol=1e-5)


def _connection(key=KEYS[1], alpha=(1.0, 1.0, 1.0)):
    k = jax.random.split(key, 3)
    wide, outs = N * WIDTH, N * N + 2 * N
    return dict(
        scale=1.0 + 0.3 * jax.random.normal(k[0], (wide,)),
        phi=jax.random.normal(k[1], (wide, outs)) * wide ** -0.5,
        b=jax.random.normal(k[2], (outs,)).at[2 * N:].add(
            2.0 * jnp.eye(N).reshape(-1)),
        alpha=jnp.asarray(alpha), norm_eps=1e-6, iters=20, eps=1e-6,
        clamp=(-30.0, 30.0))


def test_coefficients_follow_the_equations():
    """The norm over all ``n C`` channels, one projection, the sigmoids,
    the clamped exponential and Sinkhorn, spelt per token."""
    x = jax.random.normal(KEYS[2], (*TOKENS, N * WIDTH))
    p = _connection(alpha=(0.7, 1.3, 0.9))
    pre, post, res = hc.coefficients(x, N, **p)
    assert pre.shape == post.shape == (N, *TOKENS)
    assert res.shape == (N, N, *TOKENS)
    r = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * p["scale"]
    raw = jnp.einsum("bsc,ck->bsk", r, p["phi"], precision="highest")
    b = p["b"]
    np.testing.assert_allclose(
        jnp.moveaxis(pre, 0, -1),
        jax.nn.sigmoid(0.7 * raw[..., :N] + b[:N]), atol=1e-5)
    np.testing.assert_allclose(
        jnp.moveaxis(post, 0, -1),
        2 * jax.nn.sigmoid(1.3 * raw[..., N:2 * N] + b[N:2 * N]), atol=1e-5)
    logits = (0.9 * raw[..., 2 * N:] + b[2 * N:]).reshape(*TOKENS, N, N)
    want = _numpy_sinkhorn(
        np.asarray(jnp.moveaxis(logits, (2, 3), (0, 1))), 20, 1e-6)
    np.testing.assert_allclose(res, want, atol=1e-5)
    # the clamp holds the exponential finite where a gain runs away
    _, _, wild = hc.coefficients(x, N, **{**p, "alpha": jnp.asarray(
        [1.0, 1.0, 1e4])})
    assert bool(jnp.isfinite(wild).all())
    assert pre.dtype == post.dtype == res.dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_read_out_and_write_back_are_the_einsums(dtype):
    x = jax.random.normal(KEYS[3], (*TOKENS, N * WIDTH)).astype(dtype)
    y = jax.random.normal(KEYS[4], (*TOKENS, WIDTH)).astype(dtype)
    pre, post, res = hc.coefficients(x, N, **_connection())
    streams = x.astype(jnp.float32).reshape(*TOKENS, N, WIDTH)
    u = hc.read_out(x, pre)
    new = hc.write_back(x, y, post, res)
    assert u.dtype == new.dtype == dtype
    assert u.shape == (*TOKENS, WIDTH) and new.shape == x.shape
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        u.astype(jnp.float32),
        jnp.einsum("jbs,bsjc->bsc", pre, streams), atol=tol)
    want = (jnp.einsum("ijbs,bsjc->bsic", res, streams)
            + jnp.einsum("ibs,bsc->bsic", post, y.astype(jnp.float32)))
    np.testing.assert_allclose(new.astype(jnp.float32).reshape(want.shape),
                               want, atol=tol)


STUBS = dict(ln1=lambda h: h + 1.0, ln2=lambda h: h - 2.0,
             post_attn_norm=lambda d: d * 3.0, post_mlp_norm=lambda d: d * 5.0)


@pytest.mark.parametrize("hands_on", [False, True])
def test_block_math_over_four_streams_on_stub_callables(hands_on):
    """Each half ``coefficients -> read-out -> norm -> branch ->
    write-back``: the branch sees ``[b, s, C]``, its output joins every
    stream times ``H_post`` (through the post-norm and the multiplier),
    and the three stages trace under their own scopes outside the
    halves'."""
    cfg = TransformerConfig(residual_multiplier=0.25, hc_mult=N)
    x = jax.random.normal(KEYS[5], (*TOKENS, N * WIDTH))
    first, second = _connection(KEYS[6]), _connection(KEYS[7])
    seen = []

    def mixer(h):
        seen.append(h.shape)
        return (h * 7.0, h - 11.0) if hands_on else h * 7.0

    def block(x):
        return block_math(
            cfg, x, mixer=mixer, mlp=lambda h: h * 13.0,
            hand_on="kv" if hands_on else None, layer_type="mla",
            connections=(lambda x: hc.coefficients(x, N, **first),
                         lambda x: hc.coefficients(x, N, **second)),
            **STUBS)

    out = block(x)
    if hands_on:
        out, handed = out

    def half(x, p, norm, branch, post):
        pre, post_w, res = hc.coefficients(x, N, **p)
        s = x.reshape(*TOKENS, N, WIDTH)
        u = jnp.einsum("jbs,bsjc->bsc", pre, s)
        y = 0.25 * post(branch(norm(u)))
        return (jnp.einsum("ijbs,bsjc->bsic", res, s)
                + jnp.einsum("ibs,bsc->bsic", post_w, y)).reshape(x.shape), u

    mixed, u = half(x, first, STUBS["ln1"], lambda h: h * 7.0,
                    STUBS["post_attn_norm"])
    want, _ = half(mixed, second, STUBS["ln2"], lambda h: h * 13.0,
                   STUBS["post_mlp_norm"])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert seen == [(*TOKENS, WIDTH)]
    if hands_on:
        np.testing.assert_allclose(handed, (u + 1.0) - 11.0, atol=1e-5)
    text = jax.jit(block).lower(x).as_text(debug_info=True)
    for scope in (scopes.HC_COEFF, scopes.HC_READ, scopes.HC_WRITE,
                  scopes.ATTN, scopes.MLP):
        assert f"/{scope}/" in text, scope
    assert "attn/hc_" not in text and "mlp/hc_" not in text
    assert {scopes.HC_COEFF, scopes.HC_READ, scopes.HC_WRITE} <= set(
        scopes.SCOPES)


def _parents_block_math(cfg, x, *, ln1, mixer, ln2, mlp, layer_type=None,
                        post_attn_norm=None, post_mlp_norm=None,
                        hand_on=None, route=None):
    """``block_math`` as the parent commit (PR 56) had it, line for
    line."""
    def add(x, delta, post=None):
        if post is not None:
            delta = post(delta).astype(x.dtype)
        if cfg.residual_multiplier == 1.0:
            return x + delta
        return x + cfg.residual_multiplier * delta

    handed = None
    scope = MIXER_SCOPES.get(layer_type, scopes.ATTN)
    decided = () if route is None else (route(x),)
    with jax.named_scope(scope):
        delta = mixer(ln1(x))
        if hand_on is not None:
            delta, handed = delta
        x = add(x, act_store(delta, cfg), post_attn_norm)
    with jax.named_scope(scopes.MLP):
        x = add(x, act_store(mlp(ln2(x), *decided), cfg), post_mlp_norm)
    return x if hand_on is None else (x, handed)


@pytest.mark.parametrize("size", sorted(GPT_CONFIGS))
def test_one_stream_is_the_parents_wiring_to_the_bit(size):
    """Every named size's block settings (its multiplier, its post-norms,
    its dtype, its activation store) at a tiny width, on seeded matrices:
    with ``hc_mult=1`` today's ``block_math`` returns the parent's bits,
    handed-on value and router's decision included."""
    named = GPT_CONFIGS[size]
    cfg = replace(TransformerConfig(), dtype=named.dtype,
                  residual_multiplier=named.residual_multiplier,
                  act_store_dtype=named.act_store_dtype,
                  post_norms=named.post_norms)
    assert cfg.hc_mult == 1
    k = jax.random.split(jax.random.PRNGKey(len(size)), 4)
    x = jax.random.normal(k[0], (2, 8, WIDTH)).astype(cfg.dtype)
    w1, w2, w3 = (jax.random.normal(key, (WIDTH, WIDTH)).astype(cfg.dtype)
                  * 0.3 for key in k[1:])
    norm = lambda h: (h.astype(jnp.float32) * jax.lax.rsqrt(jnp.mean(
        jnp.square(h.astype(jnp.float32)), -1, keepdims=True) + 1e-6))
    layer_type = (named.layer_types or ("attention",))[0]
    hands = named.shared_kv_layer is not None
    routes = named.routed_router_input == "layer_input"
    call = dict(
        ln1=norm, ln2=norm, layer_type=layer_type,
        mixer=lambda h: ((h.astype(cfg.dtype) @ w1, h @ w3) if hands
                         else h.astype(cfg.dtype) @ w1),
        mlp=lambda h, *decided: jax.nn.silu(h.astype(cfg.dtype) @ w2) * (
            decided[0] if decided else 1.0),
        hand_on="kv" if hands else None,
        route=(lambda x: x.astype(jnp.float32).mean().astype(cfg.dtype))
        if routes else None)
    if named.post_norms:
        call.update(post_attn_norm=norm, post_mlp_norm=norm)
    got = jax.jit(lambda x: block_math(cfg, x, **call))(x)
    want = jax.jit(lambda x: _parents_block_math(cfg, x, **call))(x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


XING_YARN = dict(type="yarn", factor=64, beta_fast=32, beta_slow=1,
                 mscale=1, mscale_all_dim=1,
                 original_max_position_embeddings=4096)


def test_yarn_frequencies_against_the_closed_form():
    """The published record over 64 rotary channels: channels 0..10 keep
    their frequency, 23..31 have it divided by 64, a line between;
    ``m = 0.1 ln 64 + 1`` and the softmax takes its square."""
    assert rope.yarn_ramp(64, 10000.0, XING_YARN) == (10, 23)
    assert rope.yarn_mscale(64, 1) == pytest.approx(1.4158883)
    assert rope.yarn_mscale(64, 1) ** 2 == pytest.approx(2.0048, abs=1e-4)
    assert rope.yarn_mscale(1.0, 1) == 1.0
    freqs, magnitude = rope.scaled_frequencies(64, 10000.0, XING_YARN)
    assert magnitude == 1.0
    i = np.arange(32)
    plain = 10000.0 ** (-2.0 * i / 64)
    rho = np.clip((i - 10) / 13, 0.0, 1.0)
    np.testing.assert_allclose(
        freqs, (1 - rho) * plain + rho * plain / 64, rtol=1e-6)
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)
    positions = jnp.arange(8192)
    cos, sin = rope.rope_tables(positions, 64, 10000.0, XING_YARN)
    np.testing.assert_allclose(
        cos, np.cos(np.asarray(positions, np.float32)[:, None]
                    * np.asarray(freqs)[None]), atol=1e-6)
    # past the original context the blend matters: the last channel has
    # turned 64 times less far
    plain_cos, _ = rope.rope_tables(positions, 64, 10000.0)
    assert float(jnp.abs(cos - plain_cos)[4096:].max()) > 0.5
    # a factor on cos and sin where the two mscales differ
    scaled, _ = rope.rope_tables(positions, 64, 10000.0,
                                 {**XING_YARN, "mscale_all_dim": 0})
    np.testing.assert_allclose(scaled, cos * rope.yarn_mscale(64, 1),
                               rtol=1e-6)
    with pytest.raises(ValueError, match="'linear' is not implemented"):
        rope.rope_tables(positions, 64, 10000.0, {"type": "linear"})


def test_rope_tables_without_a_scaling_are_bitwise_what_they_were():
    positions = jnp.arange(300) * 7
    for head_dim, theta in ((64, 10000.0), (128, 1e6), (8, 1.5e6)):
        half = head_dim // 2
        freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
        cos, sin = rope.rope_tables(positions, head_dim, theta)
        np.testing.assert_array_equal(cos, jnp.cos(ang))
        np.testing.assert_array_equal(sin, jnp.sin(ang))
        again = rope.rope_tables(positions, head_dim, theta, None)
        np.testing.assert_array_equal(again[0], cos)


NEW_SETTINGS = {"hc_mult": 4, "hc_sinkhorn_iters": 10, "hc_eps": 1e-5,
                "hc_res_clamp": (-10.0, 10.0), "rope_scaling": XING_YARN}


@pytest.mark.parametrize("setting", sorted(NEW_SETTINGS))
def test_the_raw_weights_paths_refuse_the_new_settings_by_name(setting):
    cfg = replace(gpt("nano").cfg, pos_embedding="rope",
                  **{setting: NEW_SETTINGS[setting]})
    with pytest.raises(ValueError, match=f"{setting}="):
        require_gpt2_block(cfg, "decode")


@pytest.mark.parametrize("path", ["generate", "slot_engine", "tp_gpt_apply",
                                  "pp_gpt_apply", "raw_block_forward"])
def test_decode_serve_tp_and_pp_refuse_several_streams(path):
    from test_glm_moe_mla import _refusals

    cfg = replace(gpt("nano").cfg, hc_mult=4)
    with pytest.raises(ValueError, match="hc_mult=4"):
        _refusals()[path](cfg, jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("override,message", [
    ({"hc_mult": 0}, "hc_mult=0 >= 1"),
    ({"hc_mult": 2, "hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters=0"),
    ({"hc_mult": 2, "hc_res_clamp": (3.0, -3.0)}, "hc_res_clamp="),
    ({"hc_mult": 2, "mlp": "silu_gated", "routed_experts": 4,
      "routed_top_k": 2, "routed_width": 8,
      "routed_router_input": "layer_input"}, "would read hc_mult streams"),
    ({"rope_scaling": XING_YARN}, "pos_embedding must be 'rope'"),
])
def test_the_configuration_refuses_what_it_cannot_mean(override, message):
    with pytest.raises(ValueError, match=message):
        replace(gpt("nano").cfg, **override)


def test_a_model_of_several_streams_and_the_gauges_it_sets():
    """Two streams through the nano model: the streams start as the
    embedding, every block carries two connections' parameters and one
    ``hc_stats`` entry each, and the gauges count them; a prediction
    module on such a stream is refused by name."""
    from horovod_tpu.obs.registry import MetricsRegistry, get_registry

    model = gpt("nano", hc_mult=2, hc_sinkhorn_iters=7,
                attention_impl="reference", dtype=jnp.float32)
    tokens = jnp.arange(16).reshape(1, 16) % 7
    variables = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)
    assert set(variables) == {"params", "hc_stats"}
    block = variables["params"]["block0"]
    assert block["hc_attn_phi"].shape == (2 * 128, 2 * 2 + 2 * 2)
    assert block["hc_mlp_scale"].shape == (256,)
    assert block["hc_attn_alpha"].tolist() == [1.0, 1.0, 1.0]
    logits, new = jax.jit(lambda v: model.apply(
        v, tokens, mutable=["hc_stats"]))(variables)
    assert logits.shape == (1, 16, 1024)
    assert get_registry().gauge("hc.streams").value == 2
    assert get_registry().gauge("hc.sinkhorn_iters").value == 7
    assert get_registry().gauge("hc.sublayers").value == 6
    registry = MetricsRegistry()
    published = hc.publish_stats(new["hc_stats"], registry)
    errors = jax.tree.leaves(new["hc_stats"])
    assert len(errors) == 6
    assert published == {"stochastic_err": max(map(float, errors))}
    assert registry.gauge("hc.stochastic_err").value < 0.05
    assert hc.publish_stats({}, registry) == {}
    # the gradient reaches every leaf of both connections
    grads = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, tokens).astype(jnp.float32).var()))(
            variables["params"])
    for name, leaf in grads["block2"].items():
        if name.startswith("hc_"):
            assert float(jnp.abs(leaf).max()) > 0, name
    with pytest.raises(ValueError, match="hc_mult=2 with mtp_modules=1"):
        gpt("nano", hc_mult=2, mtp_modules=1).init(
            jax.random.PRNGKey(0), tokens)
