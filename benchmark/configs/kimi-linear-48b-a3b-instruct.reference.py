"""Plain reference for ``kimi-linear-48b-a3b-instruct`` (``model_type:
kimi_linear``, arXiv:2510.26692): Kimi Delta Attention layers three to
one with latent attention layers that see no positions, a dense gated
feed-forward in the leading layer and, in the others, the routed experts
this chip holds behind a sigmoid router with a selection bias beside one
shared expert, an untied head, in straightforward ``jax.numpy``,
float32, full-precision matmuls, no kernel.  It reads the program's
variables (``params``: ``wte``, ``block<i>/{ln1, ln2}`` and, by the
layer's kind, ``qkv, conv_kernel, f_a, f_b, dt_bias, A_log, b_proj, g_a,
g_b, o_norm, o_proj`` or ``q_b, kv_a, kv_a_norm, kv_b, proj``, and ``fc1,
fc2`` or ``router, experts_fc1, experts_fc2, shared_fc1, shared_fc2``,
``lnf``, ``head``; ``moe_state``: ``block<i>/bias``) and nothing else of
the program; the sizes come from the configuration file's published
keys.

The block, stream ``x`` [T, hidden], every norm an RMSNorm with a learned
scale and ``rms_norm_eps`` (ISSUE 51's equations)::

    h  = x + op(ln1(x));   y = h + ffn(ln2(h))

    op, a KDA layer, on n = ln1(x), a head of 32 with d_k = d_v = 128:
        [q ; k ; v] = silu(conv4(n W_qkv))   conv4 a causal depthwise
                                             filter of 4 taps, zeros
                                             before the sequence, no bias
        q, k = each head's 128 channels / sqrt(sum of squares + 1e-6);
        q   = q * 128^-1/2
        g_t = -exp(A_log_h) * softplus(n W_fa W_fb + dt_bias)   in R^128
        b_t = sigmoid(n W_b)                                    a head
        S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + b_t k_t v_t^T
        o_t = S_t^T q_t                      S_0 = 0, S in R^(128 x 128)
        op  = (rms_norm_128(o) * o_norm * sigmoid(n W_ga W_gb)) W_o

    op, a latent-attention layer, on n = ln1(x):
        q   = n W_q                          [T, 32, 192], no query rank
        [c ; k_s] = n W_kva                  512 + 64
        [k_nope ; v] = rms_norm(c) W_kvb     [T, 32, 128 + 128]
        k   = [k_nope ; k_s]                 k_s shared by the heads,
                                             NOTHING rotated
        o   = softmax(q k^T / sqrt(192) + causal mask) v
        op  = o W_o

    ffn, the first first_k_dense_replace layers:
        W_down(silu(W_gate n) * (W_up n))
    ffn, the others, on n = ln2(h):
        s   = sigmoid(n W_r)                 [T, 256]
        idx = the 8 largest of s + bias      the bias moves the CHOICE only
        w   = s[idx] / (sum of s[idx] + 1e-20) * routed_scaling_factor
        ffn = sum over e in idx and held of
              w_e W_down,e (silu(W_gate,e n) * (W_up,e n))
              + shared(n)                    one gated expert of 1024

    logits = lnf(x) W_head

KDA is the recurrence itself, token by token (two nested ``lax.scan``s
over ``TOKEN_RUN`` tokens each, the outer one's body recomputed in the
backward pass, so that the gradient at 16 384 tokens keeps 128 + 128
states a layer and not 16 384), never the chunk algebra of
``horovod_tpu/ops/kda.py``.  The expert layer is not the program's
algorithm either (top-k, rows sorted by expert, a grouped matmul, the
rows put back): EVERY held expert is applied to EVERY token and its
output multiplied by the token's weight for it, which is zero where the
token did not choose it.  The same share of the experts as the program's
(``num_experts`` held from ``first_held_expert`` on, of the router's own
width), so what the experts held elsewhere would have added is left out
on both sides.  The filters are four shifted products.  So that it fits
at 16 384 tokens beside the parameters and two gradients the checks hold,
a KDA layer is computed ``KDA_HEADS`` heads at a time (the heads are
independent between the projections and ``W_o``: ``[16384, 12288]``
float32 of q, k and v alone is 0.75 GiB, and a dozen such arrays live in
a layer's backward pass), attention ``MLA_HEADS`` heads and
``ROW_BLOCK`` query rows at a time, the feed-forwards (dense, routed and shared) ``TOKEN_BLOCK``
tokens at a time, the log-probabilities ``HEAD_BLOCK`` positions at a
time, and every layer, and within it every such block, is recomputed in
the backward pass.

What the source's config.json does not spell out is stated under
``assumed`` in the configuration file.  ``depart`` seeds one fault
(``DEPARTURES``), so that a test or
``benchmark/tools/probe_departures.py`` can show that the comparison with
the program fails when either side leaves the equations:
``decay_dropped`` (``exp(g) = 1``), ``decay_per_head`` (a head's 128
decays replaced by their mean: a gated delta rule), ``erase_dropped`` (no
``- b k k^T`` term: gated linear attention), ``beta_one``,
``qk_l2norm_dropped``, ``conv_sees_next`` (the filter moved one token
ahead: not causal), ``out_gate_dropped``, ``mla_rotated`` (a rotary
table, theta ``rope_theta``, on the 64 shared channels),
``shared_expert_dropped``, ``bias_in_weights``, ``weights_unnormalised``,
``chunk_state_dropped`` (the LAST KDA layer's state zeroed before every
``STATE_DROP``-th token, what a chunked rule that loses its chunks'
starting state computes: every pair of tokens inside a chunk still
agrees, so a short-range check would miss it), ``state_bfloat16`` (every
KDA layer's decays ``exp(g)`` and its state after every token rounded to
bfloat16: the recurrence in the precision below the float32 the
configuration states for it).  The benchmark never passes it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("decay_dropped", "decay_per_head", "erase_dropped",
              "beta_one", "qk_l2norm_dropped", "conv_sees_next",
              "out_gate_dropped", "mla_rotated", "shared_expert_dropped",
              "bias_in_weights", "weights_unnormalised",
              "chunk_state_dropped", "state_bfloat16")
STATE_DROP = 64
ROW_BLOCK = 128
TOKEN_BLOCK = 4096
HEAD_BLOCK = 1024
TOKEN_RUN = 128
KDA_HEADS = 8
MLA_HEADS = 8


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


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
    """``g`` [batch, seq, ...] ``by`` tokens later (earlier where ``by``
    is negative), zeros where the sequence has none."""
    seq = g.shape[1]
    rest = ((0, 0),) * (g.ndim - 2)
    if by >= 0:
        return jnp.pad(g, ((0, 0), (by, 0), *rest))[:, :seq]
    return jnp.pad(g, ((0, 0), (0, -by), *rest))[:, -by:]


def _delta_rule(q, k, v, g, beta, depart, drop_every=None):
    """The recurrence, a token at a time: ``q``, ``k``, ``g`` [batch,
    seq, heads, d_k], ``v`` [.., d_v], ``beta`` [batch, seq, heads] ->
    ``o`` [batch, seq, heads, d_v].  ``drop_every`` (a departure's)
    zeroes the state before every token whose index it divides."""
    batch, seq, heads, dk = q.shape
    run = TOKEN_RUN if seq % TOKEN_RUN == 0 else seq
    keep = jnp.ones((seq,), jnp.float32)
    if drop_every:
        keep = (jnp.arange(seq) % drop_every != 0).astype(jnp.float32)

    held = lambda t: t  # what the state and the decays are held in
    if depart == "state_bfloat16":
        # not a cast there and back, which XLA may take for excess
        # precision and drop
        held = lambda t: jax.lax.reduce_precision(t, exponent_bits=8,
                                                  mantissa_bits=7)

    def token(S, at):
        q_t, k_t, v_t, g_t, b_t, keep_t = at  # [batch, heads, ...]
        S = held(jnp.exp(g_t))[..., None] * S * keep_t
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        write = v_t if depart == "erase_dropped" else v_t - seen
        S = held(S + b_t[..., None, None] * k_t[..., None]
                 * write[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def tokens(S, part):
        return jax.lax.scan(token, S, part)

    # [runs, run, batch, heads, ...]
    runs = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        seq // run, run, *t.shape[:1], *t.shape[2:])
    _, o = jax.lax.scan(
        tokens, jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        (*(runs(t) for t in (q, k, v, g, beta)),
         keep.reshape(seq // run, run)))
    return jnp.moveaxis(o.reshape(seq, batch, heads, -1), 0, 1)


def _kda(config, blk, n, depart, last_kda=False):
    """Kimi Delta Attention on the normed stream ``n`` [batch, seq,
    hidden]; ``last_kda``: the model's last such layer.  The heads are
    independent between the projections and ``W_o``: ``KDA_HEADS`` of
    them at a time, each block recomputed in the backward pass, their
    parts of ``o W_o`` added up."""
    linear = config["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    held = KDA_HEADS if heads % KDA_HEADS == 0 else heads
    # a weight's head axis as [blocks, .., held, ..], the blocks first
    blocks = lambda w, axis: jnp.moveaxis(w.reshape(
        *w.shape[:axis], heads // held, held, *w.shape[axis + 1:]), axis, 0)
    hidden = n.shape[-1]
    taps = blk["conv_kernel"]            # [4, 3 inner]; the last is now
    last = taps.shape[0] - 1
    ahead = 1 if depart == "conv_sees_next" else 0
    low_decay = n @ blk["f_a"]["kernel"]
    low_gate = n @ blk["g_a"]["kernel"]
    dropped = depart == "chunk_state_dropped" and last_kda

    @jax.checkpoint
    def add_heads(y, w):
        fused = jnp.einsum("bsd,dthc->bsthc", n, w["qkv"])
        mixed = _silu(sum(w["taps"][last - j] * _delayed(fused, j - ahead)
                          for j in range(last + 1)))
        q, k, v = mixed[:, :, 0], mixed[:, :, 1], mixed[:, :, 2]
        if depart != "qk_l2norm_dropped":
            q, k = (t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6) for t in (q, k))
        q = q * hd ** -0.5
        g = -jnp.exp(w["A_log"])[:, None] * _softplus(jnp.einsum(
            "bsr,rhc->bshc", low_decay, w["f_b"]) + w["dt_bias"])
        if depart == "decay_dropped":
            g = jnp.zeros_like(g)
        elif depart == "decay_per_head":
            g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
        beta = _sigmoid(n @ w["b_proj"])
        if depart == "beta_one":
            beta = jnp.ones_like(beta)
        o = _delta_rule(q, k, v, g, beta, depart,
                        STATE_DROP if dropped else None)
        o = _rms_norm(o, blk["o_norm"], config["rms_norm_eps"])
        if depart != "out_gate_dropped":
            o = o * _sigmoid(jnp.einsum("bsr,rhc->bshc", low_gate,
                                        w["g_b"]))
        return y + jnp.einsum("bshc,hcd->bsd", o, w["o_proj"]), None

    y, _ = jax.lax.scan(add_heads, jnp.zeros_like(n), {
        "qkv": blocks(blk["qkv"]["kernel"].reshape(hidden, 3, heads, hd), 2),
        "taps": blocks(taps.reshape(last + 1, 3, heads, hd), 2),
        "f_b": blocks(blk["f_b"]["kernel"].reshape(hd, heads, hd), 1),
        "dt_bias": blocks(blk["dt_bias"].reshape(heads, hd), 0),
        "A_log": blocks(blk["A_log"], 0),
        "b_proj": blocks(blk["b_proj"]["kernel"], 1),
        "g_b": blocks(blk["g_b"]["kernel"].reshape(hd, heads, hd), 1),
        "o_proj": blocks(blk["o_proj"]["kernel"].reshape(heads, hd, hidden),
                         0)})
    return y


def _latent_attention(config, blk, n, depart):
    """Latent attention without positions on the normed stream ``n``
    [batch, seq, hidden]: causal, every earlier key.  ``MLA_HEADS`` heads
    at a time (they share the latent and the one 64-channel key, nothing
    else), each block recomputed in the backward pass, their parts of
    ``o W_o`` added up."""
    batch, seq, hidden = n.shape
    heads, latent = config["num_attention_heads"], config["kv_lora_rank"]
    nope, shared, vd = (config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"], config["v_head_dim"])
    held = MLA_HEADS if heads % MLA_HEADS == 0 else heads
    blocks = lambda w, axis: jnp.moveaxis(w.reshape(
        *w.shape[:axis], heads // held, held, *w.shape[axis + 1:]), axis, 0)
    kv = n @ blk["kv_a"]["kernel"]
    normed = _rms_norm(kv[..., :latent], blk["kv_a_norm"]["scale"],
                       config["rms_norm_eps"])
    k_shared = kv[..., None, latent:]
    if depart == "mla_rotated":
        k_shared = _rope(k_shared, config["rope_theta"])
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def add_heads(y, w):
        q = jnp.einsum("bsd,dhc->bshc", n, w["q_b"])
        if depart == "mla_rotated":
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], config["rope_theta"])],
                axis=-1)
        made = jnp.einsum("bsl,lhc->bshc", normed, w["kv_b"])
        k = jnp.concatenate(
            [made[..., :nope],
             jnp.broadcast_to(k_shared, (batch, seq, held, shared))],
            axis=-1)
        v = made[..., nope:]

        @jax.checkpoint
        def row_block(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q_rows, k)
                      / jnp.sqrt(nope + shared))
            i = start + jnp.arange(rows)[:, None]
            j = jnp.arange(seq)[None, :]
            scores = jnp.where(j <= i, scores, -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v)

        out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
        out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, held, vd)
        return y + jnp.einsum("bshc,hcd->bsd", out, w["proj"]), None

    y, _ = jax.lax.scan(add_heads, jnp.zeros_like(n), {
        "q_b": blocks(blk["q_b"]["kernel"].reshape(
            hidden, heads, nope + shared), 1),
        "kv_b": blocks(blk["kv_b"]["kernel"].reshape(
            latent, heads, nope + vd), 1),
        "proj": blocks(blk["proj"]["kernel"].reshape(heads, vd, hidden), 0)})
    return y


def _gated(n, gate_up, down):
    gate, up = jnp.split(n @ gate_up, 2, axis=-1)
    return (_silu(gate) * up) @ down


def _dense(blk, n):
    """The silu-gated feed-forward, ``TOKEN_BLOCK`` tokens at a time."""
    batch, seq, hidden = n.shape
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq
    tokens = jax.checkpoint(lambda part: _gated(
        part, blk["fc1"]["kernel"], blk["fc2"]["kernel"]))
    blocked = jnp.moveaxis(n.reshape(batch, seq // rows, rows, hidden), 1, 0)
    return jnp.moveaxis(jax.lax.map(tokens, blocked), 0, 1).reshape(
        batch, seq, hidden)


def _weights(config, blk, bias, n, depart):
    """Each token's weight for each of ALL experts, zero where it did
    not choose the expert."""
    top_k = config["num_experts_per_token"]
    scores = _sigmoid(n @ blk["router"])
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    picked = biased >= kth[..., None]
    chosen = jnp.where(
        picked, biased if depart == "bias_in_weights" else scores, 0.0)
    if depart != "weights_unnormalised":
        chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return chosen * config["routed_scaling_factor"]


def _experts(config, blk, weights, n, shared):
    """Every held expert on every token, weighted by the token's choice,
    and the shared expert beside them where ``shared``;
    ``TOKEN_BLOCK`` tokens at a time, each block recomputed in the
    backward pass."""
    first, held = config["first_held_expert"], config["num_experts"]
    batch, seq, hidden = n.shape
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq

    @jax.checkpoint
    def tokens(part):
        rows_n, rows_w = part

        # one held expert after the other, each over every token (a scan
        # and no Python loop: one expert's program, not ``held`` copies)
        def add_expert(y, expert):
            fc1, fc2, weight = expert
            return y + weight[..., None] * _gated(rows_n, fc1, fc2), None

        y, _ = jax.lax.scan(
            add_expert, jnp.zeros_like(rows_n),
            (blk["experts_fc1"], blk["experts_fc2"],
             jnp.moveaxis(rows_w, -1, 0)))
        if shared:
            y = y + _gated(rows_n, blk["shared_fc1"]["kernel"],
                           blk["shared_fc2"]["kernel"])
        return y

    blocked = lambda t: jnp.moveaxis(
        t.reshape(batch, seq // rows, rows, t.shape[-1]), 1, 0)
    out = jax.lax.map(tokens, (blocked(n),
                               blocked(weights[..., first:first + held])))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, hidden)


def _block(config, blk, bias, x, kind, dense, depart=None, last_kda=False):
    """One block: ``bias`` is the layer's selection bias (``None`` in a
    dense layer)."""
    eps = config["rms_norm_eps"]
    n = _rms_norm(x, blk["ln1"]["scale"], eps)
    if kind == "kda":
        x = x + _kda(config, blk, n, depart, last_kda)
    else:
        x = x + _latent_attention(config, blk, n, depart)
    n = _rms_norm(x, blk["ln2"]["scale"], eps)
    if dense:
        return x + _dense(blk, n)
    return x + _experts(config, blk, _weights(config, blk, bias, n, depart),
                        n, depart != "shared_expert_dropped")


def layer_kinds(config):
    """``"kda"`` or ``"mla"`` a layer, from the configuration's two
    lists, which count layers from 1."""
    linear = config["linear_attn_config"]
    kinds = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if (i in linear["kda_layers"]) == (i in linear["full_attn_layers"]):
            raise ValueError(
                f"linear_attn_config names layer {i} in both of its lists "
                f"or in neither")
        kinds.append("kda" if i in linear["kda_layers"] else "mla")
    return kinds


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = variables["params"]
    x = p["wte"]["embedding"][tokens]
    kinds = layer_kinds(config)
    last_kda = max(i for i, kind in enumerate(kinds) if kind == "kda")
    for i, kind in enumerate(kinds):
        dense = i < config["first_k_dense_replace"]
        bias = (None if dense
                else variables["moe_state"][f"block{i}"]["bias"])
        # every layer recomputed in the backward pass
        block = jax.checkpoint(
            lambda blk, bias, x, kind=kind, dense=dense,
            last=i == last_kda: _block(
                config, blk, bias, x, kind, dense, depart, last))
        x = block(p[f"block{i}"], bias, x)
    return _rms_norm(x, p["lnf"]["scale"], config["rms_norm_eps"])


def _picked(stream, head, labels):
    """Log-probability of ``labels`` [batch, seq] under
    ``log_softmax(stream @ head)``, ``HEAD_BLOCK`` positions at a
    time."""
    batch, seq, width = stream.shape
    rows = HEAD_BLOCK if seq % HEAD_BLOCK == 0 else seq

    @jax.checkpoint
    def positions(args):
        h, lab = args
        logp = jax.nn.log_softmax(h @ head, axis=-1)
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
        return _picked(stream, variables["params"]["head"]["kernel"],
                       tokens[:, 1:])


def loss(config, variables, batch, depart=None):
    """Mean cross-entropy over the ``seq`` positions."""
    return -logprob(config, variables, batch, depart).mean()
