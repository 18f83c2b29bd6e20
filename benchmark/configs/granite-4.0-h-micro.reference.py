"""Plain reference for ``granite-4.0-h-micro``: the hybrid Mamba-2 /
grouped-query-attention forward pass and its next-token loss in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel, no cache.  It reads the program's parameter tree (``wte``,
``block<i>/{ln1, ln2, fc1, fc2}`` with ``{in_proj, conv_kernel,
conv_bias, dt_bias, A_log, D, ssm_norm, out_proj}`` in a Mamba layer and
``{qkv, proj}`` in an attention layer, ``lnf``) and nothing else of the
program; the sizes come from the configuration file's published keys.

The Mamba layer is the recurrence itself, one token at a time::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,   y_t = S_t C_t + D x_t

and not the chunked form the program computes (``ops/ssd.py``): the
comparison is of two algorithms.  So that it fits at 8192 tokens, the
scan over tokens is nested (an outer scan over blocks of ``TOKEN_BLOCK``
tokens keeps one state a block, the inner scan is recomputed in the
backward pass), attention is computed ``ROW_BLOCK`` query rows at a time
(32 heads x 8192 x 8192 scores are never whole), and every layer is
recomputed in the backward pass.  The layers are a Python loop and not a
scan over stacked parameters, unlike GPT-2's reference: two kinds of
layer do not stack, and a stacked copy of nine Mamba layers' float32
parameters and of their gradients (2.7 GB each) does not fit beside the
parameters and two gradients the checks hold.

No departure from the published equations; the initialisation and the
optimizer's missing decay mask are stated under ``assumed`` in the
configuration file.
"""

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 64
ROW_BLOCK = 512


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _recurrence(x, dt, a, B, C):
    """``x`` [seq, heads, p], ``dt`` [seq, heads], ``a`` [heads]
    (negative), ``B``, ``C`` [seq, heads, n] -> ``y`` [seq, heads, p],
    the state starting at zero."""
    seq, heads, p = x.shape
    block = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else 1

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def tokens(state, inp):
        return jax.lax.scan(token, state, inp)

    blocked = jax.tree.map(
        lambda t: t.reshape(seq // block, block, *t.shape[1:]),
        (x, dt, B, C))
    _, y = jax.lax.scan(tokens, jnp.zeros((heads, p, B.shape[-1])), blocked)
    return y.reshape(seq, heads, p)


def _mamba(config, blk, h):
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    groups, n = config["mamba_n_groups"], config["mamba_d_state"]
    inner, taps = heads * p, config["mamba_d_conv"]
    batch, seq, _ = h.shape
    fused = h @ blk["in_proj"]["kernel"]
    z, xbc, dt = jnp.split(fused, [inner, 2 * inner + 2 * groups * n],
                           axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = _silu(sum(padded[:, k:k + seq] * blk["conv_kernel"][k]
                    for k in range(taps)) + blk["conv_bias"])
    x, B, C = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(batch, seq, heads, p)
    # each group's B and C serve heads // groups consecutive heads
    B = jnp.repeat(B.reshape(batch, seq, groups, n), heads // groups, axis=2)
    C = jnp.repeat(C.reshape(batch, seq, groups, n), heads // groups, axis=2)
    dt = jnp.logaddexp(dt + blk["dt_bias"], 0.0)          # softplus
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0))(
        x, dt, -jnp.exp(blk["A_log"]), B, C)
    y = y + blk["D"][:, None] * x
    gated = y.reshape(batch, seq, inner) * _silu(z)        # gate, then norm
    return _rms_norm(gated, blk["ssm_norm"],
                     config["rms_norm_eps"]) @ blk["out_proj"]["kernel"]


def _attention(config, blk, h):
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    batch, seq, width = h.shape
    d = width // heads
    fused = h @ blk["qkv"]["kernel"]
    q, k, v = jnp.split(fused, [heads * d, (heads + kv_heads) * d], axis=-1)
    q = q.reshape(batch, seq, heads, d)
    # query head i reads key/value head i // (heads // kv_heads)
    k = jnp.repeat(k.reshape(batch, seq, kv_heads, d), heads // kv_heads, 2)
    v = jnp.repeat(v.reshape(batch, seq, kv_heads, d), heads // kv_heads, 2)
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def row_block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = config["attention_multiplier"] * jnp.einsum(
            "bqhd,bkhd->bhqk", q_rows, k)
        seen = (jnp.arange(seq)[None, :]
                <= start + jnp.arange(rows)[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, width)
    return out @ blk["proj"]["kernel"]


def _ffn(blk, h):
    gate, up = jnp.split(h @ blk["fc1"]["kernel"], 2, axis=-1)
    return (_silu(gate) * up) @ blk["fc2"]["kernel"]


def logits(config, params, tokens):
    """``tokens`` int [batch, seq] -> float32 logits [batch, seq, vocab]."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params["params"])
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]

    def layer(kind):
        mixer = _mamba if kind == "mamba" else _attention

        @jax.checkpoint
        def apply(blk, x):
            x = x + r * mixer(config, blk,
                              _rms_norm(x, blk["ln1"]["scale"], eps))
            return x + r * _ffn(blk, _rms_norm(x, blk["ln2"]["scale"], eps))

        return apply

    with jax.default_matmul_precision("highest"):
        table = p["wte"]["embedding"]
        x = config["embedding_multiplier"] * table[tokens]
        for i, kind in enumerate(config["layer_types"]):
            x = layer(kind)(p[f"block{i}"], x)
        x = _rms_norm(x, p["lnf"]["scale"], eps)
        return (x @ table.T) / config["logits_scaling"]


def logprob(config, params, batch):
    """Log-probability of each next token of ``batch`` int [n, seq + 1]:
    float32 [n, seq]."""
    tokens = batch["tokens"]
    lg = logits(config, params, tokens[:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    return jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]


def loss(config, params, batch):
    """Mean next-token cross-entropy."""
    return -logprob(config, params, batch).mean()
