"""Plain float32 reference of the hybrid Mamba-2 / grouped-query-attention
model (granite-4.0-h-micro's equations), for the CPU tests: straightforward
``jax.numpy``, the Mamba layer as the token-by-token recurrence

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,   y_t = S_t C_t + D x_t

attention as plain masked softmax, no kernel.  It reads the program's
parameter tree and a ``config`` dict with the published key names.  The
benchmark keeps its own copy (benchmark/configs/granite-4.0-h-micro.
reference.py), blocked so that it fits at 8192 tokens.

``depart`` seeds one fault, so that a test can show the comparison with
the program fails when either side departs from the equations:
``norm_then_gate`` (the mixer's RMS norm before the gate), ``wrong_kv_heads``
(query head i reads K/V head i % kv_heads, not i // group), ``conv_shift``
(the conv reads inputs t-4 .. t-1 instead of t-3 .. t).
"""

import jax
import jax.numpy as jnp


def ssd_recurrence(x, dt, A, B, C, D):
    """The scan ``ops/ssd.py`` computes in chunks, one token at a time.
    ``x`` [b, s, h, p], ``dt`` [b, s, h], ``A``, ``D`` [h], ``B``, ``C``
    [b, s, g, n]."""
    h, g = x.shape[2], B.shape[2]
    B = jnp.repeat(B, h // g, axis=2)
    C = jnp.repeat(C, h // g, axis=2)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp                 # [b,h,p] [b,h] [b,h,n]
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    first = jnp.zeros(x.shape[:1] + x.shape[2:] + B.shape[-1:], x.dtype)
    _, y = jax.lax.scan(token, first, jax.tree.map(
        lambda t: jnp.moveaxis(t, 1, 0), (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1) + D[:, None] * x


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _mamba(config, blk, h, depart):
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    inner, taps = heads * p, config["mamba_d_conv"]
    batch, seq, _ = h.shape
    z, xbc, dt = jnp.split(h @ blk["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * groups * n], axis=-1)
    shift = 1 if depart == "conv_shift" else 0
    padded = jnp.pad(xbc, ((0, 0), (taps - 1 + shift, 0), (0, 0)))
    xbc = _silu(sum(padded[:, k:k + seq] * blk["conv_kernel"][k]
                    for k in range(taps)) + blk["conv_bias"])
    x, B, C = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(batch, seq, heads, p)
    y = ssd_recurrence(
        x, jnp.logaddexp(dt + blk["dt_bias"], 0.0), -jnp.exp(blk["A_log"]),
        B.reshape(batch, seq, groups, n), C.reshape(batch, seq, groups, n),
        blk["D"]).reshape(batch, seq, inner)
    eps = config["rms_norm_eps"]
    if depart == "norm_then_gate":
        y = _rms_norm(y, blk["ssm_norm"], eps) * _silu(z)
    else:
        y = _rms_norm(y * _silu(z), blk["ssm_norm"], eps)
    return y @ blk["out_proj"]["kernel"]


def _attention(config, blk, h, depart):
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    batch, seq, width = h.shape
    d = width // heads
    q, k, v = jnp.split(h @ blk["qkv"]["kernel"],
                        [heads * d, (heads + kv_heads) * d], axis=-1)
    q = q.reshape(batch, seq, heads, d)
    k, v = (t.reshape(batch, seq, kv_heads, d) for t in (k, v))
    if depart == "wrong_kv_heads":
        k, v = (jnp.tile(t, (1, 1, heads // kv_heads, 1)) for t in (k, v))
    else:
        k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    scores = config["attention_multiplier"] * jnp.einsum(
        "bqhd,bkhd->bhqk", q, k)
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores,
                       -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(batch, seq, width) @ blk["proj"]["kernel"]


def logits(config, params, tokens, depart=None):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    with jax.default_matmul_precision("highest"):
        table = p["wte"]["embedding"]
        x = config["embedding_multiplier"] * table[tokens]
        for i, kind in enumerate(config["layer_types"]):
            blk = p[f"block{i}"]
            mixer = _mamba if kind == "mamba" else _attention
            x = x + r * mixer(config, blk,
                              _rms_norm(x, blk["ln1"]["scale"], eps), depart)
            gate, up = jnp.split(
                _rms_norm(x, blk["ln2"]["scale"], eps) @ blk["fc1"]["kernel"],
                2, axis=-1)
            x = x + r * ((_silu(gate) * up) @ blk["fc2"]["kernel"])
        x = _rms_norm(x, p["lnf"]["scale"], eps)
        return (x @ table.T) / config["logits_scaling"]


def loss(config, params, tokens, depart=None):
    lg = logits(config, params, tokens[:, :-1], depart)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
