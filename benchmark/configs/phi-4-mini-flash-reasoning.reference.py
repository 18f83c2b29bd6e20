"""Plain reference for ``phi-4-mini-flash-reasoning`` (``model_type:
phi4flash``, the SambaY decoder-hybrid-decoder of arXiv:2507.06607):
Mamba-1 layers, sliding-window and full differential attention, and a
cross-decoder whose gated memory units read one scan memory and whose
cross-attention layers read one layer's keys and values, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel.  It reads the program's variables (``params``: ``wte``,
``block<i>/{ln1, ln2, fc1, fc2}`` with ``{in_proj, conv_kernel,
conv_bias, x_proj, dt_proj, A_log, D, out_proj}`` in a Mamba layer,
``{qkv | q, proj, lambda_q1, lambda_k1, lambda_q2, lambda_k2, subln}`` in
an attention layer and ``{in_proj, out_proj}`` in a gated memory unit,
``lnf``) and nothing else of the program; the sizes come from the
configuration file's keys.

The layers, stream ``x`` [T, hidden], ``LN`` a LayerNorm with scale and
bias, ``i`` the PUBLISHED layer index (``first_layer_index`` + the
layer's place in ``layer_types``), every layer ``x = x + mixer(LN1(x));
x = x + W_down(silu(W_gate m) * (W_up m)), m = LN2(x)``::

    x0 = wte[tokens]                                  no positions
    selective_scan layer, a = LN1(x):
      [u ; z] = a W_in
      u  = silu(conv(u))             causal depthwise, zeros before the sequence
      [r ; B ; C] = u W_x
      dt = softplus(r W_dt + b_dt)
      h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]
      y_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] u_t[c]        A = -exp(A_log)
      out = (y * silu(z)) W_out
      layer ``memory_layer`` hands on M = y                   BEFORE the gate
    attention layer, a = LN1(x):
      sliding / full:  [q ; k ; v] = a W_qkv + b;  layer ``shared_kv_layer``
                       hands on its (k, v)
      cross_attention: q = a W_q + b;  (k, v) = the handed-on pair
      q1, q2 = q[:, 0::2], q[:, 1::2];  k1, k2 = k[:, 0::2], k[:, 1::2]
      V = [v[:, 0::2] ; v[:, 1::2]]          query pair p reads K/V pair p // 2
      P_j = softmax(q_j k_j^T / sqrt(head) + mask)   s <= t, and in a sliding
                                                     layer t - s < window
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,  lam0 = 0.8 - 0.6 exp(-0.3 i)
      o   = (1 - lam0) RMSNorm(P_1 V - lam P_2 V)    over the pair's 2 x head
      out = o W_o + b
    gmu layer, a = LN1(x):   out = (silu(a W_in) * M) W_out
    logits = LN_f(x) wte^T

The scan is the token-by-token recurrence (``lax.scan``), never the
program's blocked kernel; attention is a dense masked softmax.  So that
it fits at 8192 tokens beside the parameters and two gradients the
checks hold, the recurrence is recomputed ``TOKEN_BLOCK`` tokens at a
time in the backward pass, attention is computed ``ROW_BLOCK`` query rows
at a time, the log-probabilities ``HEAD_BLOCK`` positions at a time, and
every layer is recomputed in the backward pass.

What the source's config.json does not spell out is stated under
``assumed`` in the configuration file.  ``depart`` seeds one fault, so
that a test (and ``benchmark/tools/probe_departures.py`` on the chip)
can show that the comparison with the program fails when either side
leaves the equations: ``lambda_zero`` (plain attention in differential
clothing), ``memory_after_gate``, ``kv_of_window_layer`` (the cross
layers read the keys and values of the last sliding layer before
``shared_kv_layer``), ``window_lifted`` (that same sliding layer sees
every earlier key), ``window_in_full_layer``.  The benchmark never
passes it.
"""

import math

import jax
import jax.numpy as jnp

DEPARTURES = ("lambda_zero", "memory_after_gate", "kv_of_window_layer",
              "window_lifted", "window_in_full_layer")
TOKEN_BLOCK = 64
ROW_BLOCK = 256
HEAD_BLOCK = 1024


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _recurrence(u, dt, a, B, C):
    """``u``, ``dt`` [seq, channels], ``a`` [channels, n] (negative),
    ``B``, ``C`` [seq, n] -> ``sum_n C_t[n] h_t[c, n]`` [seq, channels],
    the state starting at zero."""
    seq = u.shape[0]
    block = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else 1

    def token(h, inp):
        u_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * u_t)[:, None] * b_t
        return h, jnp.sum(h * c_t, axis=-1)

    @jax.checkpoint
    def tokens(h, inp):
        return jax.lax.scan(token, h, inp)

    blocked = jax.tree.map(
        lambda t: t.reshape(seq // block, block, *t.shape[1:]),
        (u, dt, B, C))
    _, y = jax.lax.scan(tokens, jnp.zeros(a.shape), blocked)
    return y.reshape(u.shape)


def _selective_scan_mixer(config, blk, a, depart):
    """The Mamba-1 mixer on the normed stream ``a``; returns the branch
    and the memory it would hand on."""
    n, taps = config["mamba_d_state"], config["mamba_d_conv"]
    rank = config["mamba_dt_rank"]
    seq = a.shape[1]
    u, z = jnp.split(a @ blk["in_proj"]["kernel"], 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = _silu(sum(padded[:, k:k + seq] * blk["conv_kernel"][k]
                  for k in range(taps)) + blk["conv_bias"])
    r, B, C = jnp.split(u @ blk["x_proj"]["kernel"], [rank, rank + n],
                        axis=-1)
    dt = jnp.logaddexp(r @ blk["dt_proj"]["kernel"]
                       + blk["dt_proj"]["bias"], 0.0)          # softplus
    y = jax.vmap(_recurrence, in_axes=(0, 0, None, 0, 0))(
        u, dt, -jnp.exp(blk["A_log"]), B, C) + blk["D"] * u
    gated = y * _silu(z)
    memory = gated if depart == "memory_after_gate" else y
    return gated @ blk["out_proj"]["kernel"], memory


def _attention(config, blk, a, index, window, shared_kv, depart):
    """Differential attention on the normed stream ``a``; ``index`` is
    the published layer index, ``window`` the keys a query sees (None:
    all earlier ones).  Returns the branch and the layer's (k, v)."""
    batch, seq, _ = a.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd = config["hidden_size"] // heads
    if shared_kv is None:
        fused = a @ blk["qkv"]["kernel"] + blk["qkv"]["bias"]
        q, k, v = jnp.split(fused, [heads * hd, (heads + kv_heads) * hd],
                            axis=-1)
        k = k.reshape(batch, seq, kv_heads, hd)
        v = v.reshape(batch, seq, kv_heads, hd)
    else:
        q = a @ blk["q"]["kernel"] + blk["q"]["bias"]
        k, v = shared_kv
    q = q.reshape(batch, seq, heads, hd)
    pairs, kv_pairs = heads // 2, kv_heads // 2
    group = pairs // kv_pairs              # query pair p reads pair p // group
    # [batch, seq, 2 (which map), kv pair, query pairs of it, hd]
    q = jnp.stack([q[:, :, 0::2], q[:, :, 1::2]], axis=2).reshape(
        batch, seq, 2, kv_pairs, group, hd)
    k2 = jnp.stack([k[:, :, 0::2], k[:, :, 1::2]], axis=2)
    wide = jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], axis=-1)
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def row_block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqjngd,bkjnd->bjngqk", q_rows, k2) / jnp.sqrt(
            1.0 * hd)
        t = start + jnp.arange(rows)[:, None]
        s = jnp.arange(seq)[None, :]
        seen = s <= t
        if window is not None:
            seen = seen & (t - s < window)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bjngqk,bkne->bjqnge", maps, wide)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    # [blocks, batch, 2, rows, kv pairs, group, 2 hd] -> [batch, 2, seq, ..]
    out = jnp.moveaxis(out, 0, 2).reshape(batch, 2, seq, pairs, 2 * hd)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(blk["lambda_q1"] * blk["lambda_k1"]))
           - jnp.exp(jnp.sum(blk["lambda_q2"] * blk["lambda_k2"])) + lam0)
    if depart == "lambda_zero":
        lam = 0.0
    o = out[:, 0] - lam * out[:, 1]
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                     + config["layer_norm_eps"]) * blk["subln"]["scale"]
    o = ((1.0 - lam0) * o).reshape(batch, seq, heads * hd)
    return o @ blk["proj"]["kernel"] + blk["proj"]["bias"], (k, v)


def _ffn(blk, m):
    gate, up = jnp.split(m @ blk["fc1"]["kernel"], 2, axis=-1)
    return (_silu(gate) * up) @ blk["fc2"]["kernel"]


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), variables["params"])
    eps = config["layer_norm_eps"]
    kinds = list(config["layer_types"])
    kv_layer, memory_layer = (config["shared_kv_layer"],
                              config["memory_layer"])
    # the last sliding layer before the one whose keys and values are
    # shared: where two of the departures strike
    last_sliding = max((i for i in range(kv_layer)
                        if kinds[i] == "sliding_attention"), default=None)

    def layer(i, kind):
        index = config["first_layer_index"] + i
        window = config["sliding_window"] if (
            kind == "sliding_attention"
            or (kind == "full_attention"
                and depart == "window_in_full_layer")) else None
        if depart == "window_lifted" and i == last_sliding:
            window = None

        @jax.checkpoint
        def apply(blk, x, memory, shared_kv):
            a = _layer_norm(x, blk["ln1"], eps)
            handed = None
            if kind == "selective_scan":
                branch, handed = _selective_scan_mixer(config, blk, a, depart)
            elif kind == "gmu":
                branch = (_silu(a @ blk["in_proj"]["kernel"]) * memory
                          ) @ blk["out_proj"]["kernel"]
            else:
                branch, handed = _attention(
                    config, blk, a, index, window,
                    shared_kv if kind == "cross_attention" else None, depart)
            x = x + branch
            return x + _ffn(blk, _layer_norm(x, blk["ln2"], eps)), handed

        return apply

    x = p["wte"]["embedding"][tokens]
    memory = shared_kv = None
    for i, kind in enumerate(kinds):
        x, handed = layer(i, kind)(p[f"block{i}"], x, memory, shared_kv)
        if i == memory_layer:
            memory = handed
        if i == (last_sliding if depart == "kv_of_window_layer"
                 else kv_layer):
            shared_kv = handed
    return _layer_norm(x, p["lnf"], eps), p["wte"]["embedding"]


def _picked(stream, table, labels):
    """Log-probability of ``labels`` [batch, seq] under
    ``log_softmax(stream @ table^T)``, ``HEAD_BLOCK`` positions at a
    time."""
    batch, seq, width = stream.shape
    rows = HEAD_BLOCK if seq % HEAD_BLOCK == 0 else seq

    @jax.checkpoint
    def positions(args):
        h, lab = args
        logp = jax.nn.log_softmax(h @ table.T, axis=-1)
        return jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]

    blocked = (
        jnp.moveaxis(stream.reshape(batch, seq // rows, rows, width), 1, 0),
        jnp.moveaxis(labels.reshape(batch, seq // rows, rows), 1, 0))
    return jnp.moveaxis(jax.lax.map(positions, blocked), 0, 1).reshape(
        batch, seq)


def logprob(config, variables, batch, depart=None):
    """Log-probability of each label of ``batch`` int [n, seq + 1]:
    float32 [n, seq], position ``i``'s next token."""
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        stream, table = _stream(config, variables, tokens[:, :-1], depart)
        return _picked(stream, table, tokens[:, 1:])


def loss(config, variables, batch, depart=None):
    """Mean cross-entropy over the ``seq`` positions."""
    return -logprob(config, variables, batch, depart).mean()
