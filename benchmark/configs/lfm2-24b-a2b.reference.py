"""Plain reference for ``lfm2-24b-a2b`` (``model_type: lfm2_moe``): gated
short convolutions and grouped-query attention layers, a dense gated
feed-forward in the leading layers and, in the others, the routed experts
this chip holds behind a sigmoid router with a selection bias, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel.  It reads the program's variables (``params``: ``wte``,
``block<i>/{ln1, ln2}`` and, by the layer's kind, ``in_proj, conv_kernel,
out_proj`` or ``qkv, q_norm, k_norm, proj``, and ``fc1, fc2`` or ``router,
experts_fc1, experts_fc2``, ``lnf``; ``moe_state``: ``block<i>/bias``)
and nothing else of the program; the sizes come from the configuration
file's published keys.

The block, stream ``x`` [T, hidden], every norm an RMSNorm with a learned
scale and ``norm_eps`` (ISSUE 48's equations)::

    h  = x + op(ln1(x));   y = h + ffn(ln2(h))

    op, layer type conv, on n = ln1(x):
        [B ; C ; u] = n W_in             three thirds of 3 hidden columns
        g_t = B_t * u_t
        c_t = sum over j < conv_L_cache of w[L-1-j] * g_(t-j)
                                         a causal depthwise filter, zeros
                                         before the sequence, no bias
        op  = (C * c) W_out

    op, layer type full_attention, on n = ln1(x):
        q, k, v = split(n Wqkv) as [T, 32, 64], [T, 8, 64], [T, 8, 64]
        q, k = rms_norm over each head's 64 channels (one scale each)
        q, k = rope(q, k)                all 64 channels, theta 1e6
        o   = softmax(q k^T / 8 + causal mask) v   head h reads kv head
                                                   h // 4
        op  = o Wo

    ffn, the first num_dense_layers layers:  W_down(silu(W_gate n) *
                                             (W_up n))
    ffn, the others, on n = ln2(h):
        s   = sigmoid(n W_r)             [T, 64]
        idx = the 4 largest of s + bias  the bias moves the CHOICE only
        w   = s[idx] / (sum of s[idx] + 1e-6) * routed_scaling_factor
        ffn = sum over e in idx and held of
              w_e W_down,e (silu(W_gate,e n) * (W_up,e n))

    logits = lnf(x) wte^T                the table is tied

The expert layer is not the program's algorithm (top-k, rows sorted by
expert, a grouped matmul, the rows put back): EVERY held expert is
applied to EVERY token and its output multiplied by the token's weight
for it, which is zero where the token did not choose it.  The same share
of the experts as the program's (``num_experts`` held from
``first_held_expert`` on, of the router's own width), so what the experts
held elsewhere would have added is left out on both sides.  The filter is
three shifted products.  So that it fits at 32 768 tokens beside the
parameters and two gradients the checks hold (32 heads x 32 768^2 float32
scores are 137 GB whole, a ``[32768, 23552]`` float32 array 2.9 GiB),
attention is computed ``ROW_BLOCK`` query rows at a time, the dense
feed-forward ``TOKEN_BLOCK`` tokens at a time, the log-probabilities
``HEAD_BLOCK`` positions at a time, and every layer, and within it every
expert, is recomputed in the backward pass.

What the source's config.json does not spell out (the order of
``in_proj``'s thirds, the norms' places, the rotation's pairing, the tied
table) is stated under ``assumed`` in the configuration file.  ``depart``
seeds one fault (``DEPARTURES``), so that a test or
``benchmark/tools/probe_departures.py`` can show that the comparison with
the program fails when either side leaves the equations:
``filter_identity`` (only the current tap), ``filter_sees_next`` (the
filter moved one token ahead: not causal), ``gate_b_dropped``,
``gate_c_dropped``, ``head_norms_dropped``, ``rope_dropped``,
``bias_in_weights`` (the selection bias entering the weights),
``weights_unnormalised``.  The benchmark never passes it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("filter_identity", "filter_sees_next", "gate_b_dropped",
              "gate_c_dropped", "head_norms_dropped", "rope_dropped",
              "bias_in_weights", "weights_unnormalised")
ROW_BLOCK = 128
TOKEN_BLOCK = 4096
HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """Split halves (channel ``i`` turns with ``i + half``), positions
    0 .. seq-1; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _delayed(g, by):
    """``g`` [batch, seq, channels] ``by`` tokens later (earlier where
    ``by`` is negative), zeros where the sequence has none."""
    seq = g.shape[1]
    if by >= 0:
        return jnp.pad(g, ((0, 0), (by, 0), (0, 0)))[:, :seq]
    return jnp.pad(g, ((0, 0), (0, -by), (0, 0)))[:, -by:]


def _short_conv(blk, n, depart):
    """The gated short convolution on the normed stream ``n``."""
    hidden = n.shape[-1]
    fused = n @ blk["in_proj"]["kernel"]
    gate_b, gate_c, u = (fused[..., :hidden], fused[..., hidden:2 * hidden],
                         fused[..., 2 * hidden:])
    g = u if depart == "gate_b_dropped" else gate_b * u
    taps = blk["conv_kernel"]            # [L, hidden]; the last is now
    last = taps.shape[0] - 1
    ahead = 1 if depart == "filter_sees_next" else 0
    reach = 1 if depart == "filter_identity" else taps.shape[0]
    c = sum(taps[last - j] * _delayed(g, j - ahead) for j in range(reach))
    z = c if depart == "gate_c_dropped" else gate_c * c
    return z @ blk["out_proj"]["kernel"]


def _attention(config, blk, n, depart):
    """Grouped-query attention on the normed stream ``n`` [batch, seq,
    hidden]: causal, every earlier key."""
    batch, seq, hidden = n.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd = hidden // heads
    q_dim, kv_dim = heads * hd, kv_heads * hd
    eps = config["norm_eps"]
    fused = n @ blk["qkv"]["kernel"]
    q = fused[..., :q_dim].reshape(batch, seq, heads, hd)
    k = fused[..., q_dim:q_dim + kv_dim].reshape(batch, seq, kv_heads, hd)
    v = fused[..., q_dim + kv_dim:].reshape(batch, seq, kv_heads, hd)
    if depart != "head_norms_dropped":
        q = _rms_norm(q, blk["q_norm"]["scale"], eps)
        k = _rms_norm(k, blk["k_norm"]["scale"], eps)
    if depart != "rope_dropped":
        theta = config["rope_parameters"]["rope_theta"]
        q, k = _rope(q, theta), _rope(k, theta)
    # query head h reads key/value head h // group
    group = heads // kv_heads
    q = q.reshape(batch, seq, kv_heads, group, hd)
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def row_block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", q_rows, k) / jnp.sqrt(hd)
        i = start + jnp.arange(rows)[:, None]
        j = jnp.arange(seq)[None, :]
        scores = jnp.where(j <= i, scores, -jnp.inf)
        return jnp.einsum("bngqk,bknd->bqngd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, q_dim)
    return out @ blk["proj"]["kernel"]


def _dense(blk, n):
    """The silu-gated feed-forward, ``TOKEN_BLOCK`` tokens at a time."""
    batch, seq, hidden = n.shape
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq

    @jax.checkpoint
    def tokens(part):
        gate, up = jnp.split(part @ blk["fc1"]["kernel"], 2, axis=-1)
        return (_silu(gate) * up) @ blk["fc2"]["kernel"]

    blocked = jnp.moveaxis(n.reshape(batch, seq // rows, rows, hidden), 1, 0)
    return jnp.moveaxis(jax.lax.map(tokens, blocked), 0, 1).reshape(
        batch, seq, hidden)


def _weights(config, blk, bias, n, depart):
    """Each token's weight for each of ALL experts, zero where it did
    not choose the expert."""
    top_k = config["num_experts_per_tok"]
    scores = 1.0 / (1.0 + jnp.exp(-(n @ blk["router"])))
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    picked = biased >= kth[..., None]
    chosen = jnp.where(
        picked, biased if depart == "bias_in_weights" else scores, 0.0)
    if depart != "weights_unnormalised":
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    return chosen * config["routed_scaling_factor"]


def _experts(config, blk, weights, n):
    first, held = config["first_held_expert"], config["num_experts"]

    @jax.checkpoint
    def add_expert(y, expert):
        weight, gate_up, down = expert
        gate, up = jnp.split(n @ gate_up, 2, axis=-1)
        return y + weight[..., None] * ((_silu(gate) * up) @ down), None

    # one held expert after the other, each over every token
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(n),
        (jnp.moveaxis(weights[..., first:first + held], -1, 0),
         blk["experts_fc1"], blk["experts_fc2"]))
    return y


def _block(config, blk, bias, x, kind, dense, depart=None):
    """One block: ``bias`` is the layer's selection bias (``None`` in a
    dense layer)."""
    eps = config["norm_eps"]
    n = _rms_norm(x, blk["ln1"]["scale"], eps)
    if kind == "conv":
        x = x + _short_conv(blk, n, depart)
    elif kind == "full_attention":
        x = x + _attention(config, blk, n, depart)
    else:
        raise ValueError(f"layer_types names {kind!r}: this reference "
                         f"knows 'conv' and 'full_attention'")
    n = _rms_norm(x, blk["ln2"]["scale"], eps)
    if dense:
        return x + _dense(blk, n)
    return x + _experts(config, blk,
                        _weights(config, blk, bias, n, depart), n)


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = variables["params"]
    x = p["wte"]["embedding"][tokens]
    for i, kind in enumerate(config["layer_types"]):
        dense = i < config["num_dense_layers"]
        bias = (None if dense
                else variables["moe_state"][f"block{i}"]["bias"])
        # every layer recomputed in the backward pass
        block = jax.checkpoint(
            lambda blk, bias, x, kind=kind, dense=dense: _block(
                config, blk, bias, x, kind, dense, depart))
        x = block(p[f"block{i}"], bias, x)
    return _rms_norm(x, p["lnf"]["scale"], config["norm_eps"])


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
        stream = _stream(config, variables, tokens[:, :-1], depart)
        return _picked(stream, variables["params"]["wte"]["embedding"],
                       tokens[:, 1:])


def loss(config, variables, batch, depart=None):
    """Mean cross-entropy over the ``seq`` positions."""
    return -logprob(config, variables, batch, depart).mean()
