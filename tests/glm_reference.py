"""Plain float32 reference of GLM-4.7-Flash's equations (``glm4_moe_lite``:
latent attention, routed experts that drop nothing beside a shared
expert, one multi-token-prediction module), for the CPU tests:
straightforward ``jax.numpy``, attention as plain masked softmax, every
held expert applied to EVERY token and masked by its weight (no sort, no
grouped matmul: two algorithms are compared), no kernel.  It reads the
program's parameter tree (``params`` and the selection bias under
``moe_state``) and a ``config`` dict with the published key names;
``n_routed_experts`` counts the experts held, from ``first_held_expert``
on, and the router's own width says how many are scored.  The benchmark
keeps its own copy (benchmark/configs/glm-4.7-flash.reference.py),
blocked so that it fits at 8192 tokens.

``depart`` seeds one fault, so that a test can show the comparison with
the program fails when either side departs from the equations:
``bias_in_weights`` (the selection bias enters the weights),
``rope_per_head_key`` (the rotary key is not rotated), ``mtp_after_norm``
(the prediction module reads the stream after the final norm),
``concat_swapped`` (``[h ; emb]`` instead of ``[emb ; h]``).
"""

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _gated(x, gate_up, down):
    """``W_down(silu(x W_gate) * (x W_up))``, gate and up side by side."""
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (_silu(gate) * up) @ down


def _rope(x, theta):
    """Split halves: channel ``i`` pairs with ``i + half``.  ``x``
    [b, s, heads, dim], positions 0 .. s-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def latent_attention(config, blk, h, depart=None):
    b, s, _ = h.shape
    heads, latent = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, eps = config["v_head_dim"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    cq = _rms_norm(h @ blk["q_a"]["kernel"], blk["q_a_norm"]["scale"], eps)
    q = (cq @ blk["q_b"]["kernel"]).reshape(b, s, heads, nope + rope)
    kv = h @ blk["kv_a"]["kernel"]
    ckv = _rms_norm(kv[..., :latent], blk["kv_a_norm"]["scale"], eps)
    k_v = (ckv @ blk["kv_b"]["kernel"]).reshape(b, s, heads, nope + vd)
    k_rope = kv[:, :, None, latent:]
    if depart != "rope_per_head_key":
        k_rope = _rope(k_rope, theta)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [k_v[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, k_v[..., nope:])
    return out.reshape(b, s, heads * vd) @ blk["proj"]["kernel"]


def routing_weights(config, blk, bias, h, depart=None):
    """``[tokens, experts scored]``: a chosen expert's weight, 0 for the
    rest."""
    scores = jax.nn.sigmoid(h @ blk["router"])
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -config["num_experts_per_tok"]]
    chosen = biased >= kth[..., None]
    picked = jnp.where(chosen, biased if depart == "bias_in_weights"
                       else scores, 0.0)
    return (picked / (picked.sum(-1, keepdims=True) + 1e-20)
            * config["routed_scaling_factor"])


def expert_layer(config, blk, bias, h, depart=None):
    """The held experts' weighted outputs and the shared expert's."""
    weights = routing_weights(config, blk, bias, h, depart)
    first = config.get("first_held_expert", 0)
    y = _gated(h, blk["shared_fc1"]["kernel"], blk["shared_fc2"]["kernel"])
    for e in range(config["n_routed_experts"]):
        y = y + weights[..., first + e, None] * _gated(
            h, blk["experts_fc1"][e], blk["experts_fc2"][e])
    return y


def block(config, blk, bias, x, depart=None):
    eps = config["rms_norm_eps"]
    x = x + latent_attention(
        config, blk, _rms_norm(x, blk["ln1"]["scale"], eps), depart)
    h = _rms_norm(x, blk["ln2"]["scale"], eps)
    if "router" in blk:
        return x + expert_layer(config, blk, bias, h, depart)
    return x + _gated(h, blk["fc1"]["kernel"], blk["fc2"]["kernel"])


def forward(config, variables, tokens, next_tokens, depart=None):
    """``(logits, mtp_logits)`` [b, s, vocab] each, float32."""
    p = variables["params"]
    biases = variables["moe_state"]
    eps = config["rms_norm_eps"]
    emb = p["wte"]["embedding"]
    x = emb[tokens]
    for i in range(config["num_hidden_layers"]):
        name = f"block{i}"
        x = block(config, p[name], biases.get(name, {}).get("bias"), x,
                  depart)
    normed = _rms_norm(x, p["lnf"]["scale"], eps)
    logits = normed @ p["head"]["kernel"]
    m = p["mtp"]
    e = _rms_norm(emb[next_tokens], m["enorm"]["scale"], eps)
    h = _rms_norm(normed if depart == "mtp_after_norm" else x,
                  m["hnorm"]["scale"], eps)
    pair = [h, e] if depart == "concat_swapped" else [e, h]
    y = jnp.concatenate(pair, axis=-1) @ m["eh_proj"]["kernel"]
    y = block(config, m["block"], biases["mtp"]["block"]["bias"], y, depart)
    y = _rms_norm(y, m["norm"]["scale"], eps)
    return logits, y @ p["head"]["kernel"]


def _cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def losses(config, variables, tokens, depart=None):
    """``(CE_main, CE_mtp)`` of rows that hold ``seq + 2`` tokens: means
    over the ``seq`` positions; position ``i`` predicts ``t_{i+1}`` and,
    through the prediction module, ``t_{i+2}``."""
    logits, mtp_logits = forward(config, variables, tokens[:, :-2],
                                 tokens[:, 1:-1], depart)
    return (_cross_entropy(logits, tokens[:, 1:-1]).mean(),
            _cross_entropy(mtp_logits, tokens[:, 2:]).mean())


def loss(config, variables, tokens, depart=None):
    main, mtp = losses(config, variables, tokens, depart)
    return main + config["mtp_loss_weight"] * mtp
