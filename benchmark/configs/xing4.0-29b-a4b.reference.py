"""Plain reference for ``xing4.0-29b-a4b`` (``model_type: xing4_0``): a
residual stream of ``hc_mult`` copies mixed by manifold-constrained
hyper-connections around latent attention with YaRN-scaled rotary
channels, a dense feed-forward in the leading layer and, in the others,
the routed experts this chip holds beside the shared expert, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel, no call into ``horovod_tpu``.  It reads the program's variables
(``params``: ``wte``, ``block<i>/{hc_attn_*, ln1, q_a, q_a_norm, q_b,
kv_a, kv_a_norm, kv_b, proj, hc_mlp_*, ln2}`` with ``{fc1, fc2}`` in a
dense layer and ``{router, experts_fc1, experts_fc2, shared_fc1,
shared_fc2}`` in an expert layer, each ``hc_*`` being ``_scale`` [n C],
``_phi`` [n C, n^2 + 2 n], ``_b`` [n^2 + 2 n] and ``_alpha`` [3];
``lnf``, ``head``; ``moe_state``: each expert layer's selection bias) and
nothing else of the program; the sizes come from the configuration
file's published keys.

The model, ``X`` [T, n, C] the streams (``n = hc_mult``), every norm an
RMSNorm with a learned weight and ``rms_norm_eps``::

    X[t, j] = wte[token_t]                                  for every j
    one sub-layer (a layer has two: F = attention, then F = feed-forward,
    each with its own g, phi, b, alpha and its own norm ln):
      r        = RMSNorm_g(vec(X[t]))                 over all n C channels
      [p; q; R] = r phi                               n, n and n^2 numbers
      H_pre    = sigmoid(alpha_0 p + b[:n])
      H_post   = 2 sigmoid(alpha_1 q + b[n:2n])
      M        = exp(clip(alpha_2 mat(R) + mat(b[2n:]), clamp_min, clamp_max))
      hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps)
                                M <- M / (rowsum(M) + hc_eps)
      u        = sum_j H_pre[j] X[t, j]
      y        = F(ln(u))
      X'[t, i] = sum_j M[i, j] X[t, j] + H_post[i] y
    attention:  c_q = norm(a W_qa);  [q_n ; q_r] = c_q W_qb  (heads of 128 + 64)
                [c_kv ; k_r] = a W_kva;  [k_n ; v] = norm(c_kv) W_kvb
                q = [q_n ; rope(q_r)],  k = [k_n ; rope(k_r)]   k_r one for all heads
                o = softmax_causal(s q k^T) v W_o,  s = (128 + 64)^-1/2 m^2,
                m = 0.1 mscale_all_dim ln(factor) + 1
    rope:       split halves, frequencies f_i = theta^(-2i/64) blended by YaRN:
                (1 - rho_i) f_i + rho_i f_i / factor,
                rho_i = clip((i - low) / (high - low), 0, 1),
                low = floor(64 ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
                high = ceil(64 ln(L0 / (beta_slow 2 pi)) / (2 ln theta));
                cos and sin times m(factor, mscale) / m(factor, mscale_all_dim)
    experts:    s = sigmoid(a W_r);  the num_experts_per_tok largest of s + bias;
                w_e = routed_scaling_factor s_e / sum_chosen s;
                f = shared(a) + sum over e chosen and held of w_e expert_e(a)
    h_t = sum_j X[t, j];  logits = lnf(h) W_head

The expert layer is not the program's algorithm (scores, top-k, rows
sorted by expert, a grouped matmul, the rows put back): EVERY held expert
is applied to EVERY token and its output multiplied by the token's weight
for it, which is zero where the token did not choose it.  The same share
of the experts as the program's (``n_routed_experts`` held from
``first_held_expert`` on, of the router's own width), so what the experts
held elsewhere would have added is left out on both sides.  So that it
fits at 8192 tokens beside the parameters and two gradients the checks
hold, attention is computed ``MLA_HEADS`` heads and ``ROW_BLOCK`` query
rows at a time, the feed-forwards and the hyper-connections' read-out and
write-back ``TOKEN_BLOCK`` tokens at a time, the log-probabilities
``HEAD_BLOCK`` positions at a time, and every layer, and within it every
such block, is recomputed in the backward pass: what is kept is one
float32 copy of the four streams a layer, 470 MB each.

What the source's config.json does not spell out is stated under
``assumed`` in the configuration file.  ``depart`` seeds one fault
(``DEPARTURES``), so that a test or ``benchmark/tools/probe_departures.py``
can show that the comparison with the program fails when either side
leaves the equations: ``res_identity`` (``M`` the identity: the streams
never mix), ``sinkhorn_once`` (one round for ``hc_sinkhorn_iters``),
``post_unscaled`` (``H_post`` without its factor 2), ``streams_averaged``
(``h`` the streams' mean, not their sum: the final norm divides it out
again but for its eps, so this one is NOT expected to be told),
``pre_after_norm`` (``u = sum_j H_pre[j] ln(X[j])``: the read-out behind
the branch's norm), ``rope_unscaled`` (plain frequencies, no YaRN),
``scale_without_mscale`` (``s`` without ``m^2``), ``bias_in_weights``
(the selection bias in the weights).  The benchmark never passes it.
"""

import math

import jax
import jax.numpy as jnp

DEPARTURES = ("res_identity", "sinkhorn_once", "post_unscaled",
              "streams_averaged", "pre_after_norm", "rope_unscaled",
              "scale_without_mscale", "bias_in_weights")
ROW_BLOCK = 128
TOKEN_BLOCK = 512
HEAD_BLOCK = 1024
MLA_HEADS = 8


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(config, depart=None):
    """The rotary channels' frequencies [rope / 2] and the factor on cos
    and sin."""
    rope, theta = config["qk_rope_head_dim"], config["rope_theta"]
    half = rope // 2
    i = jnp.arange(half, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / rope)
    scaling = config.get("rope_scaling")
    if not scaling or depart == "rope_unscaled":
        return plain, 1.0
    original = scaling["original_max_position_embeddings"]
    channel = lambda rotations: (
        rope * math.log(original / (rotations * 2 * math.pi))
        / (2 * math.log(theta)))
    low = max(math.floor(channel(scaling["beta_fast"])), 0)
    high = min(math.ceil(channel(scaling["beta_slow"])), rope - 1)
    rho = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((1.0 - rho) * plain + rho * plain / scaling["factor"],
            _mscale(scaling["factor"], scaling["mscale"])
            / _mscale(scaling["factor"], scaling["mscale_all_dim"]))


def softmax_scale(config, depart=None):
    scale = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5
    scaling = config.get("rope_scaling")
    if scaling and depart != "scale_without_mscale":
        scale *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
    return scale


def _rope(x, freqs, magnitude):
    """Split halves (channel ``i`` turns with ``i + half``), positions
    0 .. seq-1; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :] * magnitude
    sin = jnp.sin(ang)[:, None, :] * magnitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(logits, iters, eps):
    """``logits`` [..., n, n] -> ``exp(logits)`` after ``iters`` rounds:
    columns divided by their sums, then rows by theirs."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
    return m


def connection(config, blk, name, x, depart=None):
    """One sub-layer's three maps from the streams ``x`` [batch, seq, n,
    hidden]: ``(H_pre [.., n], H_post [.., n], H_res [.., n, n])``."""
    batch, seq, n, hidden = x.shape
    r = _rms_norm(x.reshape(batch, seq, n * hidden), blk[name + "_scale"],
                  config["rms_norm_eps"])
    raw = r @ blk[name + "_phi"]
    b, alpha = blk[name + "_b"], blk[name + "_alpha"]
    pre = _sigmoid(alpha[0] * raw[..., :n] + b[:n])
    post = _sigmoid(alpha[1] * raw[..., n:2 * n] + b[n:2 * n])
    if depart != "post_unscaled":
        post = 2.0 * post
    if depart == "res_identity":
        return pre, post, jnp.broadcast_to(jnp.eye(n), (batch, seq, n, n))
    logits = jnp.clip(
        (alpha[2] * raw[..., 2 * n:] + b[2 * n:]).reshape(batch, seq, n, n),
        config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"])
    iters = 1 if depart == "sinkhorn_once" else config["hc_sinkhorn_iters"]
    return pre, post, sinkhorn(logits, iters, config["hc_eps"])


def _token_blocks(fn, *arrays):
    """``fn`` over ``TOKEN_BLOCK`` tokens of each ``[batch, seq, ...]``
    array at a time, each block recomputed in the backward pass."""
    batch, seq = arrays[0].shape[:2]
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq
    blocked = lambda t: jnp.moveaxis(
        t.reshape(batch, seq // rows, rows, *t.shape[2:]), 1, 0)
    out = jax.lax.map(jax.checkpoint(lambda part: fn(*part)),
                      tuple(blocked(t) for t in arrays))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, *out.shape[3:])


def _mixed(weights, x):
    """``sum_j weights[..., j] x[:, :, j]``: ``weights`` [batch, seq, n]
    on the streams ``x`` [batch, seq, n, hidden], one stream after the
    other (a product and a sum an element, no matmul)."""
    return sum(weights[..., j, None] * x[:, :, j]
               for j in range(x.shape[2]))


def _sublayer(config, blk, name, ln, x, branch, depart):
    """``coefficients -> read-out -> norm -> branch -> write-back`` on
    the streams ``x`` [batch, seq, n, hidden].  The maps are a token's
    own, so the read-out and the write-back go ``TOKEN_BLOCK`` tokens at
    a time and each makes the maps for its tokens; the branch between
    them sees the whole sequence."""
    eps = config["rms_norm_eps"]

    def read(rows):
        pre, _, _ = connection(config, blk, name, rows, depart)
        if depart == "pre_after_norm":
            return _mixed(pre, _rms_norm(rows, ln, eps))
        return _rms_norm(_mixed(pre, rows), ln, eps)

    def write(rows, y):
        _, post, res = connection(config, blk, name, rows, depart)
        return jnp.stack([_mixed(res[:, :, i], rows) + post[..., i, None] * y
                          for i in range(rows.shape[2])], axis=2)

    return _token_blocks(write, x, branch(_token_blocks(read, x)))


def _latent_attention(config, blk, n, depart):
    """Latent attention on the normed stream ``n`` [batch, seq, hidden]:
    causal, every earlier key.  ``MLA_HEADS`` heads at a time (they share
    the two latents and the one rotary key, nothing else), each block
    recomputed in the backward pass, their parts of ``o W_o`` added
    up."""
    batch, seq, hidden = n.shape
    heads, latent = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"],
                      config["qk_rope_head_dim"], config["v_head_dim"])
    eps, q_rank = config["rms_norm_eps"], config["q_lora_rank"]
    freqs, magnitude = yarn_frequencies(config, depart)
    scale = softmax_scale(config, depart)
    held = MLA_HEADS if heads % MLA_HEADS == 0 else heads
    blocks = lambda w, axis: jnp.moveaxis(w.reshape(
        *w.shape[:axis], heads // held, held, *w.shape[axis + 1:]), axis, 0)
    c_q = _rms_norm(n @ blk["q_a"]["kernel"], blk["q_a_norm"]["scale"], eps)
    kv = n @ blk["kv_a"]["kernel"]
    c_kv = _rms_norm(kv[..., :latent], blk["kv_a_norm"]["scale"], eps)
    # one rotary key for all heads
    k_rope = _rope(kv[:, :, None, latent:], freqs, magnitude)
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def add_heads(y, w):
        q = jnp.einsum("bsr,rhc->bshc", c_q, w["q_b"])
        q = jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], freqs, magnitude)], axis=-1)
        made = jnp.einsum("bsl,lhc->bshc", c_kv, w["kv_b"])
        k = jnp.concatenate(
            [made[..., :nope],
             jnp.broadcast_to(k_rope, (batch, seq, held, rope))], axis=-1)
        v = made[..., nope:]

        @jax.checkpoint
        def row_block(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) * scale
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
            q_rank, heads, nope + rope), 1),
        "kv_b": blocks(blk["kv_b"]["kernel"].reshape(
            latent, heads, nope + vd), 1),
        "proj": blocks(blk["proj"]["kernel"].reshape(heads, vd, hidden), 0)})
    return y


def _gated(n, gate_up, down):
    gate, up = jnp.split(n @ gate_up, 2, axis=-1)
    return (_silu(gate) * up) @ down


def routing_weights(config, blk, bias, n, depart=None):
    """Each token's weight for each of ALL experts, zero where it did
    not choose the expert."""
    top_k = config["num_experts_per_tok"]
    scores = _sigmoid(n @ blk["router"])
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -top_k]
    picked = biased >= kth[..., None]
    chosen = jnp.where(
        picked, biased if depart == "bias_in_weights" else scores, 0.0)
    chosen = chosen / (chosen.sum(-1, keepdims=True) + 1e-20)
    return chosen * config["routed_scaling_factor"]


def _experts(config, blk, weights, n):
    """Every held expert on every token, weighted by the token's choice,
    and the shared expert beside them."""
    first, held = config["first_held_expert"], config["n_routed_experts"]

    def tokens(rows_n, rows_w):
        # one held expert after the other, each over every token (a scan
        # and no Python loop: one expert's program, not ``held`` copies)
        def add_expert(y, expert):
            fc1, fc2, weight = expert
            return y + weight[..., None] * _gated(rows_n, fc1, fc2), None

        y, _ = jax.lax.scan(
            add_expert,
            _gated(rows_n, blk["shared_fc1"]["kernel"],
                   blk["shared_fc2"]["kernel"]),
            (blk["experts_fc1"], blk["experts_fc2"],
             jnp.moveaxis(rows_w, -1, 0)))
        return y

    return _token_blocks(tokens, n, weights[..., first:first + held])


def block(config, blk, bias, x, depart=None):
    """One layer on the streams ``x`` [batch, seq, n, hidden]: ``bias``
    is its selection bias (``None`` in a dense layer)."""
    x = _sublayer(config, blk, "hc_attn", blk["ln1"]["scale"], x,
                  lambda n: _latent_attention(config, blk, n, depart),
                  depart)

    def feed_forward(n):
        if bias is None:
            return _token_blocks(lambda rows: _gated(
                rows, blk["fc1"]["kernel"], blk["fc2"]["kernel"]), n)
        return _experts(config, blk,
                        routing_weights(config, blk, bias, n, depart), n)

    return _sublayer(config, blk, "hc_mlp", blk["ln2"]["scale"], x,
                     feed_forward, depart)


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = variables["params"]
    emb = p["wte"]["embedding"][tokens]
    x = jnp.broadcast_to(emb[:, :, None, :],
                         (*emb.shape[:2], config["hc_mult"], emb.shape[-1]))
    for i in range(config["num_hidden_layers"]):
        dense = i < config["first_k_dense_replace"]
        bias = (None if dense
                else variables["moe_state"][f"block{i}"]["bias"])
        # every layer recomputed in the backward pass
        x = jax.checkpoint(lambda blk, bias, x: block(
            config, blk, bias, x, depart))(p[f"block{i}"], bias, x)
    h = x.mean(axis=2) if depart == "streams_averaged" else x.sum(axis=2)
    return _rms_norm(h, p["lnf"]["scale"], config["rms_norm_eps"])


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
