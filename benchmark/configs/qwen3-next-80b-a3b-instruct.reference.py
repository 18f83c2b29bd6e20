"""Plain reference for ``qwen3-next-80b-a3b-instruct`` (``model_type:
qwen3_next``; the rule: Gated DeltaNet, arXiv:2412.06464): Gated DeltaNet
layers three to one with gated attention layers, in every layer the
routed experts this chip holds behind a softmax router that renormalises
its ten chosen weights, beside a shared expert behind a sigmoid gate of
its own, an untied head, in straightforward ``jax.numpy``, float32,
full-precision matmuls, no kernel.  It reads the program's variables
(``params``: ``wte``, ``block<i>/{ln1, ln2, router, experts_fc1,
experts_fc2, shared_fc1, shared_fc2, shared_gate}`` and, by the layer's
kind, ``in_proj, ba_proj, conv_kernel, dt_bias, A_log, o_norm, out_proj``
or ``qkv, q_norm, k_norm, proj``, ``lnf``, ``head``) and nothing else of
the program; the sizes come from the configuration file's published keys.

The block, stream ``x`` [T, hidden]; ``norm(x, w) = x / sqrt(mean x^2 +
rms_norm_eps) * (1 + w)`` for the stream's norms and the two head norms
(ISSUE 64's equations)::

    h  = x + op(norm(x, ln1));   y = h + ffn(norm(h, ln2))

    op, a Gated DeltaNet layer, on n = norm(x, ln1); 16 key heads of 128
    under 32 value heads of 128, value head i reading key head i // 2:
        [q ; k ; v ; z] = n W_in             2048 + 2048 + 4096 + 4096
        [b ; a] = n W_ba                     32 + 32
        [q ; k ; v] = silu(conv4([q ; k ; v]))   ONE causal depthwise
                                             filter of 4 taps over the
                                             8192 channels, zeros before
                                             the sequence, no bias
        q, k = each head's 128 channels / sqrt(sum of squares + 1e-6);
        q   = q * 128^-1/2
        g_t = -exp(A_log_h) * softplus(a_t + dt_bias_h)     ONE number a
        b_t = sigmoid(b_t)                                  value head
        S_t = (I - b_t k_t k_t^T) exp(g_t) S_(t-1) + b_t k_t v_t^T
        o_t = S_t^T q_t                      S_0 = 0, S in R^(128 x 128)
        op  = (o / sqrt(mean o^2 + eps) * o_norm * silu(z)) W_out
                                             o_norm a plain scale of 128

    op, a gated attention layer, on n = norm(x, ln1); 16 heads over 2
    key/value heads of 256:
        [gate ; q ; k ; v] = n W_qkv         4096 + 4096 + 512 + 512 (the
                                             program's column order: the
                                             same parameters as a q_proj
                                             whose heads are [q ; gate])
        q, k = norm over each head's 256 channels (q_norm, k_norm)
        q, k = the FIRST 64 channels of a head rotated (split halves
               among the 64, theta rope_theta), the other 192 as they are
        o   = softmax(q k^T / sqrt(256) + causal mask) v
        op  = (o * sigmoid(gate)) W_o

    ffn, on n = norm(h, ln2):
        r   = n W_r                          [T, 512]
        idx = the 10 largest of r
        w   = softmax(r)[idx] / sum of softmax(r)[idx]
        ffn = sum over e in idx and held of
              w_e W_down,e (silu(W_gate,e n) * (W_up,e n))
              + sigmoid(n w_s) * shared(n)   one gated expert of 512

    logits = norm(x, lnf) W_head
    loss   = mean cross-entropy
           + balance_loss_coef * sum over layers of 512 sum_e f_e P_e
             (f_e the share of the 10 T slots that chose expert e, P_e
             the mean of softmax(r)_e over the T tokens)

The rule is the recurrence itself, token by token (two nested
``lax.scan``s over ``TOKEN_RUN`` tokens each, the outer one's body
recomputed in the backward pass), never the chunk algebra of
``horovod_tpu/ops/kda.py``.  The expert layer is not the program's
algorithm either: EVERY held expert is applied to EVERY token and its
output multiplied by the token's weight for it, which is zero where the
token did not choose it; the same share of the experts as the program's
(``num_experts`` held from ``first_held_expert`` on, of the router's own
width).  The filter is four shifted products.  So that it fits at
16 384 tokens beside the parameters and two gradients the checks hold, a
DeltaNet layer is computed ``GDN_KEY_HEADS`` key heads (and their value
heads) at a time, attention one key/value head's query heads and
``ROW_BLOCK`` query rows at a time, the experts ``TOKEN_BLOCK`` tokens
at a time, the log-probabilities ``HEAD_BLOCK`` positions at a time, and
every layer, and within it every such block, is recomputed in the
backward pass.

What the source's config.json does not spell out is stated under
``assumed`` in the configuration file.  ``depart`` seeds one fault
(``DEPARTURES``), so that a test or
``benchmark/tools/probe_departures.py`` can show that the comparison with
the program fails when either side leaves the equations:
``decay_dropped`` (``exp(g) = 1``), ``decay_per_key_head`` (the two value
heads of a key head share the mean of their log-decays: ``g`` from 16
heads), ``beta_one``, ``conv_sees_next`` (the filter moved one token
ahead: not causal), ``conv_per_stream`` (q, k and v filtered as one
sequence laid end to end, so that a stream's first tokens read the
stream before's last where the filter has zeros: a filter that mixes
streams), ``qk_l2norm_dropped``, ``out_gate_sigmoid`` (``sigmoid(z)`` for
``silu(z)``), ``out_norm_unit_offset`` (``1 + o_norm`` for ``o_norm``),
``key_heads_tiled`` (value head ``i`` reads key head ``i % 16``),
``attn_gate_dropped``, ``rotary_full`` (all 256 channels rotated),
``norm_plain_scale`` (``w`` for ``1 + w`` in every such norm),
``shared_gate_dropped``, ``softmax_before_topk_not_renormalised`` (the
chosen weights are the full softmax's values as they stand),
``state_bfloat16`` (every DeltaNet layer's decay ``exp(g)`` and its state
after every token rounded to bfloat16: the recurrence in the precision
below the float32 the configuration states for it).  The benchmark never
passes it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("decay_dropped", "decay_per_key_head", "beta_one",
              "conv_sees_next", "conv_per_stream", "qk_l2norm_dropped",
              "out_gate_sigmoid", "out_norm_unit_offset", "key_heads_tiled",
              "attn_gate_dropped", "rotary_full", "norm_plain_scale",
              "shared_gate_dropped", "softmax_before_topk_not_renormalised",
              "state_bfloat16")
ROW_BLOCK = 128
TOKEN_BLOCK = 4096
HEAD_BLOCK = 1024
TOKEN_RUN = 128
GDN_KEY_HEADS = 4


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps, depart):
    """The family's norm: the scale is ``1 + w``."""
    return _rms(x, eps) * (w if depart == "norm_plain_scale" else 1.0 + w)


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _rope(x, theta, rotary):
    """The first ``rotary`` channels of ``x`` [batch, seq, heads, dim]
    turned, split halves among themselves (channel ``i`` with ``i +
    rotary / 2``), positions 0 .. seq-1; the others as they are."""
    half = rotary // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], -1)


def _delayed(g, by, before=None):
    """``g`` [batch, seq, ...] ``by`` tokens later (earlier where ``by``
    is negative), zeros where the sequence has none, or ``before``'s
    last tokens ahead of the first (a departure's)."""
    seq = g.shape[1]
    rest = ((0, 0),) * (g.ndim - 2)
    if by < 0:
        return jnp.pad(g, ((0, 0), (0, -by), *rest))[:, -by:]
    if before is None or by == 0:
        return jnp.pad(g, ((0, 0), (by, 0), *rest))[:, :seq]
    return jnp.concatenate([before[:, seq - by:], g], axis=1)[:, :seq]


def _delta_rule(q, k, v, g, beta, depart):
    """The recurrence, a token at a time: ``q``, ``k`` [batch, seq,
    heads, d_k], ``v`` [.., d_v], ``g`` and ``beta`` [batch, seq, heads]
    -> ``o`` [batch, seq, heads, d_v]."""
    batch, seq, heads, dk = q.shape
    run = TOKEN_RUN if seq % TOKEN_RUN == 0 else seq
    held = lambda t: t  # what the state and the decay are held in
    if depart == "state_bfloat16":
        # not a cast there and back, which XLA may take for excess
        # precision and drop
        held = lambda t: jax.lax.reduce_precision(t, exponent_bits=8,
                                                  mantissa_bits=7)

    def token(S, at):
        q_t, k_t, v_t, g_t, b_t = at  # [batch, heads, ...]
        S = held(jnp.exp(g_t))[..., None, None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, k_t)
        S = held(S + b_t[..., None, None] * k_t[..., None]
                 * (v_t - seen)[..., None, :])
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    @jax.checkpoint
    def tokens(S, part):
        return jax.lax.scan(token, S, part)

    # [runs, run, batch, heads, ...]
    runs = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        seq // run, run, *t.shape[:1], *t.shape[2:])
    _, o = jax.lax.scan(
        tokens, jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        tuple(runs(t) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(seq, batch, heads, -1), 0, 1)


def _gated_delta_net(config, blk, n, depart):
    """Gated DeltaNet on the normed stream ``n`` [batch, seq, hidden].
    A key head and the value heads it serves are independent of the
    others between the projections and ``W_out``: ``GDN_KEY_HEADS`` key
    heads at a time, each block recomputed in the backward pass, their
    parts of ``o W_out`` added up."""
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    rep, hidden = hv // hk, n.shape[-1]
    held = GDN_KEY_HEADS if hk % GDN_KEY_HEADS == 0 else hk
    tiled = depart == "key_heads_tiled"

    def by_key_head(w, axis):
        """An axis of the ``hv`` value heads as ``[hk, rep]``: the value
        heads each key head serves."""
        if not tiled:
            return w.reshape(*w.shape[:axis], hk, rep, *w.shape[axis + 1:])
        w = w.reshape(*w.shape[:axis], rep, hk, *w.shape[axis + 1:])
        return jnp.swapaxes(w, axis, axis + 1)

    # a weight's key-head axis as [blocks, .., held, ..], the blocks first
    blocks = lambda w, axis: jnp.moveaxis(w.reshape(
        *w.shape[:axis], hk // held, held, *w.shape[axis + 1:]), axis, 0)
    kq, kv = hk * dk, hv * dv
    w_in, w_ba = blk["in_proj"]["kernel"], blk["ba_proj"]["kernel"]
    taps = blk["conv_kernel"]            # [4, q + k + v]; the last is now
    last = taps.shape[0] - 1
    ahead = 1 if depart == "conv_sees_next" else 0
    mixes = depart == "conv_per_stream"

    def filtered(x, w, before=None):
        """silu of the filter over ``x`` [batch, seq, ...channels]."""
        return _silu(sum(
            w[last - j] * _delayed(x, j - ahead, before if mixes else None)
            for j in range(last + 1)))

    @jax.checkpoint
    def add_heads(y, w):
        q_in = jnp.einsum("bsd,dhc->bshc", n, w["q"])
        k_in = jnp.einsum("bsd,dhc->bshc", n, w["k"])
        v_in = jnp.einsum("bsd,dhrc->bshrc", n, w["v"])
        q = filtered(q_in, w["q_taps"], jnp.zeros_like(q_in))
        k = filtered(k_in, w["k_taps"], q_in)
        # the departure's v reads k's last tokens channel for channel
        v = filtered(v_in, w["v_taps"],
                     jnp.broadcast_to(k_in[..., None, :dv], v_in.shape)
                     if dk >= dv else jnp.zeros_like(v_in))
        if depart != "qk_l2norm_dropped":
            q, k = (t / jnp.sqrt(jnp.sum(t * t, axis=-1, keepdims=True)
                                 + 1e-6) for t in (q, k))
        q = q * dk ** -0.5
        g = -jnp.exp(w["A_log"]) * _softplus(
            jnp.einsum("bsd,dhr->bshr", n, w["a"]) + w["dt_bias"])
        if depart == "decay_dropped":
            g = jnp.zeros_like(g)
        elif depart == "decay_per_key_head":
            g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
        beta = _sigmoid(jnp.einsum("bsd,dhr->bshr", n, w["b"]))
        if depart == "beta_one":
            beta = jnp.ones_like(beta)
        # the rule a value head: its key head's q and k, repeated
        b, s = n.shape[:2]
        flat = lambda t: t.reshape(b, s, held * rep, *t.shape[4:])
        spread = lambda t: flat(jnp.broadcast_to(
            t[:, :, :, None], (b, s, held, rep, dk)))
        o = _delta_rule(spread(q), spread(k), flat(v), flat(g), flat(beta),
                        depart)
        scale = blk["o_norm"] + (1.0 if depart == "out_norm_unit_offset"
                                 else 0.0)
        o = _rms(o, config["rms_norm_eps"]) * scale
        z = flat(jnp.einsum("bsd,dhrc->bshrc", n, w["z"]))
        o = o * (_sigmoid(z) if depart == "out_gate_sigmoid" else _silu(z))
        return y + jnp.einsum("bshc,hcd->bsd", o, w["out"]), None

    # columns (or taps) of the value heads' channels as [.., hk, rep, dv]
    v_cols = lambda w: by_key_head(w.reshape(*w.shape[:-1], hv, dv),
                                   w.ndim - 1)
    y, _ = jax.lax.scan(add_heads, jnp.zeros_like(n), {
        "q": blocks(w_in[:, :kq].reshape(hidden, hk, dk), 1),
        "k": blocks(w_in[:, kq:2 * kq].reshape(hidden, hk, dk), 1),
        "v": blocks(v_cols(w_in[:, 2 * kq:2 * kq + kv]), 1),
        "z": blocks(v_cols(w_in[:, 2 * kq + kv:]), 1),
        "b": blocks(by_key_head(w_ba[:, :hv], 1), 1),
        "a": blocks(by_key_head(w_ba[:, hv:], 1), 1),
        "q_taps": blocks(taps[:, :kq].reshape(last + 1, hk, dk), 1),
        "k_taps": blocks(taps[:, kq:2 * kq].reshape(last + 1, hk, dk), 1),
        "v_taps": blocks(v_cols(taps[:, 2 * kq:]), 1),
        "A_log": blocks(by_key_head(blk["A_log"], 0), 0),
        "dt_bias": blocks(by_key_head(blk["dt_bias"], 0), 0),
        "out": blocks(by_key_head(blk["out_proj"]["kernel"].reshape(
            hv, dv, hidden), 0), 0).reshape(
                hk // held, held * rep, dv, hidden)})
    return y


def _gated_attention(config, blk, n, depart):
    """Gated attention on the normed stream ``n`` [batch, seq, hidden]:
    causal, every earlier key.  One key/value head's query heads at a
    time, ``ROW_BLOCK`` query rows at a time, each block recomputed in
    the backward pass, their parts of ``o W_o`` added up."""
    batch, seq, hidden = n.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    group, q_dim = heads // kv_heads, heads * hd
    rotary = hd if depart == "rotary_full" else int(
        hd * config["partial_rotary_factor"])
    theta = config["rope_theta"]
    w = blk["qkv"]["kernel"]
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq
    # a weight's query-head axis as [kv heads, group, ..], kv heads first
    grouped = lambda t, axis: jnp.moveaxis(t.reshape(
        *t.shape[:axis], kv_heads, group, *t.shape[axis + 1:]), axis, 0)

    @jax.checkpoint
    def add_group(y, part):
        q = jnp.einsum("bsd,dhc->bshc", n, part["q"])
        k = jnp.einsum("bsd,dc->bsc", n, part["k"])[:, :, None]
        v = jnp.einsum("bsd,dc->bsc", n, part["v"])
        q = _rope(_norm(q, blk["q_norm"]["scale"], eps, depart), theta,
                  rotary)
        k = _rope(_norm(k, blk["k_norm"]["scale"], eps, depart), theta,
                  rotary)[:, :, 0]

        @jax.checkpoint
        def row_block(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
            scores = jnp.einsum("bqhd,bkd->bhqk", q_rows, k) / jnp.sqrt(hd)
            i = start + jnp.arange(rows)[:, None]
            j = jnp.arange(seq)[None, :]
            scores = jnp.where(j <= i, scores, -jnp.inf)
            return jnp.einsum("bhqk,bkd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v)

        out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
        out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, group, hd)
        if depart != "attn_gate_dropped":
            out = out * _sigmoid(jnp.einsum("bsd,dhc->bshc", n,
                                            part["gate"]))
        return y + jnp.einsum("bshc,hcd->bsd", out, part["proj"]), None

    y, _ = jax.lax.scan(add_group, jnp.zeros_like(n), {
        "gate": grouped(w[:, :q_dim].reshape(hidden, heads, hd), 1),
        "q": grouped(w[:, q_dim:2 * q_dim].reshape(hidden, heads, hd), 1),
        "k": jnp.moveaxis(w[:, 2 * q_dim:2 * q_dim + kv_heads * hd].reshape(
            hidden, kv_heads, hd), 1, 0),
        "v": jnp.moveaxis(w[:, 2 * q_dim + kv_heads * hd:].reshape(
            hidden, kv_heads, hd), 1, 0),
        "proj": grouped(blk["proj"]["kernel"].reshape(heads, hd, hidden),
                        0)})
    return y


def _gated(n, gate_up, down):
    gate, up = jnp.split(n @ gate_up, 2, axis=-1)
    return (_silu(gate) * up) @ down


def _route(config, blk, n, depart):
    """Each token's weight for each of ALL experts (zero where it did
    not choose the expert) and the layer's balance loss."""
    top_k = config["num_experts_per_tok"]
    r = n @ blk["router"]
    kth = jnp.sort(r, axis=-1)[..., -top_k]
    picked = r >= kth[..., None]
    full = jax.nn.softmax(r, axis=-1)
    weights = jnp.where(picked, full, 0.0)
    if depart != "softmax_before_topk_not_renormalised":
        weights = weights / weights.sum(-1, keepdims=True)
    experts = r.shape[-1]
    tokens = r.size // experts
    share = picked.reshape(tokens, experts).sum(0) / (tokens * top_k)
    balance = experts * jnp.sum(share * full.reshape(tokens, experts).mean(0))
    return weights, balance


def _experts(config, blk, weights, n, depart):
    """Every held expert on every token, weighted by the token's choice,
    and the gated shared expert beside them; ``TOKEN_BLOCK`` tokens at a
    time, each block recomputed in the backward pass."""
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
        shared = _gated(rows_n, blk["shared_fc1"]["kernel"],
                        blk["shared_fc2"]["kernel"])
        if depart != "shared_gate_dropped":
            shared = shared * _sigmoid(rows_n @ blk["shared_gate"]["kernel"])
        return y + shared

    blocked = lambda t: jnp.moveaxis(
        t.reshape(batch, seq // rows, rows, t.shape[-1]), 1, 0)
    out = jax.lax.map(tokens, (blocked(n),
                               blocked(weights[..., first:first + held])))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, hidden)


def layer_kinds(config):
    """``"gdn"`` or ``"full_attention"`` a layer: layer ``i`` attends
    where ``(i + 1) % full_attention_interval == 0``."""
    return ["full_attention"
            if (i + 1) % config["full_attention_interval"] == 0 else "gdn"
            for i in range(config["num_hidden_layers"])]


def _block(config, blk, x, kind, depart=None):
    """One block; returns the stream and the layer's balance loss."""
    eps = config["rms_norm_eps"]
    n = _norm(x, blk["ln1"]["scale"], eps, depart)
    mixer = _gated_delta_net if kind == "gdn" else _gated_attention
    x = x + mixer(config, blk, n, depart)
    n = _norm(x, blk["ln2"]["scale"], eps, depart)
    weights, balance = _route(config, blk, n, depart)
    return x + _experts(config, blk, weights, n, depart), balance


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden], and the layers' balance losses summed."""
    p = variables["params"]
    x = p["wte"]["embedding"][tokens]
    balance = 0.0
    for i, kind in enumerate(layer_kinds(config)):
        # every layer recomputed in the backward pass
        block = jax.checkpoint(
            lambda blk, x, kind=kind: _block(config, blk, x, kind, depart))
        x, layer_balance = block(p[f"block{i}"], x)
        balance = balance + layer_balance
    return _norm(x, p["lnf"]["scale"], config["rms_norm_eps"],
                 depart), balance


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


def _logprob_and_balance(config, variables, batch, depart):
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        stream, balance = _stream(config, variables, tokens[:, :-1], depart)
        return _picked(stream, variables["params"]["head"]["kernel"],
                       tokens[:, 1:]), balance


def logprob(config, variables, batch, depart=None):
    """Log-probability of each label of ``batch`` int [n, seq + 1]:
    float32 [n, seq], position ``i``'s next token."""
    return _logprob_and_balance(config, variables, batch, depart)[0]


def loss(config, variables, batch, depart=None):
    """Mean cross-entropy over the ``seq`` positions, plus the balance
    loss: ``balance_loss_coef`` times the layers' sum."""
    picked, balance = _logprob_and_balance(config, variables, batch, depart)
    return -picked.mean() + config["balance_loss_coef"] * balance
