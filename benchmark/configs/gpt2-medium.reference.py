"""Plain reference for ``gpt2-medium``: the GPT-2 forward pass and its
next-token loss in straightforward ``jax.numpy``, float32, full-precision
matmuls, no kernel, no cache.  It reads the program's parameter tree
(``wte``, ``wpe``, ``block<i>/{ln1,qkv,proj,ln2,fc1,fc2}``, ``lnf``,
``head``) and nothing else of the program.

Departures from the published model, both forced by the program and
stated in the configuration file: the output head is its own matrix
(not ``wte`` transposed), and LayerNorm's epsilon is 1e-6.
"""

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    y = x @ p["kernel"].astype(jnp.float32)
    return y + p["bias"] if "bias" in p else y


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def logits(config, params, tokens):
    """``tokens`` int [batch, seq] -> float32 logits [batch, seq, vocab].

    The blocks are applied by ``lax.scan`` over their stacked parameters
    and not by a Python loop.  Unrolled 24 times, this forward pass and
    its gradient compiled in 52 s into an 85 MB executable (my chip run,
    PR 27), which every run of every cell would have fetched and which
    alone was over two fifths of the chip tool's compile cache.  Under
    the scan the backward pass would keep every block's two
    [batch, heads, seq, seq] float32 attention arrays at once (14.8 GB for
    two sequences), so each block is recomputed in the backward pass
    (``jax.checkpoint``): the same arithmetic, a block's worth at a time."""
    p = params["params"]
    heads = config["n_head"]
    b, s = tokens.shape
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, blk):
        h = _dense(_layer_norm(x, blk["ln1"]), blk["qkv"])
        q, k, v = jnp.split(h, 3, axis=-1)
        q, k, v = (t.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        att = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(q.shape[-1])
        att = jnp.where(causal, att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1) @ v
        att = att.transpose(0, 2, 1, 3).reshape(b, s, -1)
        x = x + _dense(att, blk["proj"])
        h = _gelu_new(_dense(_layer_norm(x, blk["ln2"]), blk["fc1"]))
        return x + _dense(h, blk["fc2"]), None

    with jax.default_matmul_precision("highest"):
        x = p["wte"]["embedding"].astype(jnp.float32)[tokens]
        x = x + p["wpe"].astype(jnp.float32)[:s][None]
        blocks = jax.tree.map(
            lambda *leaves: jnp.stack(leaves),
            *(p[f"block{i}"] for i in range(config["n_layer"])))
        x, _ = jax.lax.scan(jax.checkpoint(block), x, blocks)
        return _dense(_layer_norm(x, p["lnf"]), p["head"])


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
