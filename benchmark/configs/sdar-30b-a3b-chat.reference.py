"""Plain reference for ``sdar-30b-a3b-chat`` (``model_type: sdar_moe``)
on the training path: block-diffusion training (BD3-LMs,
arXiv:2503.09573, which SDAR, arXiv:2510.06303, follows) of a
grouped-query transformer whose every layer holds routed experts, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel.  It reads the program's variables (``params``: ``wte``,
``block<i>/{ln1, qkv, q_norm, k_norm, proj, ln2, router, experts_fc1,
experts_fc2}``, ``lnf``, ``head``) and nothing else of the program; the
sizes come from the configuration file's published keys and its
``block_length`` and ``mask_token_id``.

The batch holds the clean tokens ``x_0`` int [n, L], which positions are
masked (bool [n, L]) and each position's noise level ``t`` (its block's,
float32 [n, L]): the reference draws nothing.  Rows ``i, j`` of the
``2L``-row sequence ``[x_t ; x_0]``, ``B`` the block length, every norm
an RMSNorm with a learned scale::

    x_t,i = MASK where masked, else x_0,i
    pos(i) = i mod L
    a   = ln1(x)
    q, k, v = split(a Wqkv) as [2L, 32, 128], [2L, 4, 128], [2L, 4, 128]
    q   = rope(q_norm(q));  k = rope(k_norm(k))   over each head's 128
          channels, at pos(i)
    o   = softmax(q k^T / sqrt(128) + M) v        head h reads kv head h // 8
    M(i, j): (i <  L and j <  L and i // B == j // B)            or
             (i <  L and j >= L and (j - L) // B <  i // B)      or
             (i >= L and j >= L and (j - L) // B <= (i - L) // B)
    h   = x + o Wo
    u   = ln2(h)
    r   = u W_r    [2L, 128];  idx = the 8 largest;  w = softmax(r[idx])
    y   = h + sum over e in idx and held of
              w_e W_down,e (silu(W_gate,e u) * (W_up,e u))
    logits_i = lnf(y_i) W_head            for the noised rows, i < L
    loss = (1 / L) sum over masked i of (1 / t_i) (-log softmax(logits_i)[x_0,i])
           + balance_loss_coef * sum over layers of 128 sum_e f_e P_e

``f_e`` is the share of the ``8 * 2L`` slots that chose expert ``e`` (a
count: no gradient), ``P_e`` the mean over the ``2L`` rows of the full
128-way softmax of ``r``; 1.0 a layer at an even load.

The expert layer is not the program's algorithm (top-k, rows sorted by
expert, a grouped matmul, the rows put back): EVERY held expert is
applied to EVERY row and its output multiplied by the row's weight for
it, which is zero where the row did not choose it.  The same share of
the experts as the program's (``num_experts`` held from
``first_held_expert`` on, of the router's own width), so what the
experts held elsewhere would have added is left out on both sides.  The
mask is built dense from the equation above, ``ROW_BLOCK`` query rows at
a time (32 heads x 16 384^2 float32 scores are 34 GB whole; 64 rows, and
not the siblings' 512, because the checks' reference side has to fit the
chip beside the variables and two gradients: the configuration file's
``depth_rule`` has the readings); the
log-probabilities ``HEAD_BLOCK`` positions at a time; every layer, and
within it every expert, is recomputed in the backward pass.

``logprob`` is each position's log-probability of its own token, zero
where the position is not masked (an unmasked position's token is in
its own row's input).

What the source's config.json does not spell out (the block length, the
noise, the layout and the mask, the labels, the mask token, the balance
loss, the rotation's pairing) is stated under ``assumed`` in the
configuration file.  ``depart`` seeds one fault (``DEPARTURES``), so
that a test or ``benchmark/tools/probe_departures.py`` can show that the
comparison with the program fails when either side leaves the
equations: ``causal_mask`` (a causal mask over the ``2L`` rows),
``positions_not_repeated`` (``0 .. 2L-1``), ``shifted_labels`` (position
``i`` is asked for token ``i + 1``), ``weight_dropped`` (no ``1 / t``),
``loss_over_every_row`` (the unmasked positions weigh ``1 / t`` too),
``blind_to_own_block`` (a noised row sees the clean past alone; in the
first block, nothing, and attention adds nothing there), ``block_8``
(the mask in blocks of 8).  The benchmark never passes it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("causal_mask", "positions_not_repeated", "shifted_labels",
              "weight_dropped", "loss_over_every_row",
              "blind_to_own_block", "block_8")
ROW_BLOCK = 64
HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, positions, theta):
    """Split halves (channel ``i`` turns with ``i + half``) at
    ``positions`` [seq]; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _seen(i, j, half, block, depart):
    """``M(i, j)`` for row indices ``i`` [rows, 1] and ``j`` [1, 2L]."""
    if depart == "causal_mask":
        return j <= i
    if depart == "block_8":
        block = 8
    noised_q, noised_k = i < half, j < half
    qb = jnp.where(noised_q, i, i - half) // block
    kb = jnp.where(noised_k, j, j - half) // block
    own = noised_q & noised_k & (qb == kb)
    if depart == "blind_to_own_block":
        own = jnp.zeros_like(own)
    return (own | (noised_q & ~noised_k & (kb < qb))
            | (~noised_q & ~noised_k & (kb <= qb)))


def _attention(config, blk, a, depart):
    """The attention branch on the normed stream ``a`` [batch, 2L,
    hidden]."""
    batch, seq, _ = a.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    half, block = seq // 2, config["block_length"]
    q_dim, kv_dim = heads * hd, kv_heads * hd
    fused = a @ blk["qkv"]["kernel"]
    q = fused[..., :q_dim].reshape(batch, seq, heads, hd)
    k = fused[..., q_dim:q_dim + kv_dim].reshape(batch, seq, kv_heads, hd)
    v = fused[..., q_dim + kv_dim:].reshape(batch, seq, kv_heads, hd)
    at = jnp.arange(seq)
    positions = at if depart == "positions_not_repeated" else at % half
    q = _rope(_rms_norm(q, blk["q_norm"]["scale"], eps), positions,
              config["rope_theta"])
    k = _rope(_rms_norm(k, blk["k_norm"]["scale"], eps), positions,
              config["rope_theta"])
    # query head h reads key/value head h // group
    group = heads // kv_heads
    q = q.reshape(batch, seq, kv_heads, group, hd)
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def row_block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqngd,bknd->bngqk", q_rows, k) / jnp.sqrt(hd)
        seen = _seen(start + jnp.arange(rows)[:, None],
                     jnp.arange(seq)[None, :], half, block, depart)
        # a row that sees nothing (one departure makes some) adds nothing
        top = jnp.max(jnp.where(seen, scores, -jnp.inf), -1, keepdims=True)
        p = jnp.where(seen, jnp.exp(scores - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        return jnp.einsum("bngqk,bknd->bqngd", p, v)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, q_dim)
    return out @ blk["proj"]["kernel"]


def _route(config, blk, u):
    """Each row's weight for each of ALL experts (zero where not chosen)
    and the layer's balance loss."""
    top_k = config["num_experts_per_tok"]
    r = u @ blk["router"]
    kth = jnp.sort(r, axis=-1)[..., -top_k]
    picked = r >= kth[..., None]
    weights = jax.nn.softmax(jnp.where(picked, r, -jnp.inf), axis=-1)
    experts = r.shape[-1]
    rows = r.size // experts
    share = picked.reshape(rows, experts).sum(0) / (rows * top_k)
    full = jax.nn.softmax(r, axis=-1).reshape(rows, experts)
    return weights, experts * jnp.sum(share * full.mean(0))


def _experts(config, blk, weights, u):
    first, held = config["first_held_expert"], config["num_experts"]

    @jax.checkpoint
    def applied(expert):
        weight, gate_up, down = expert
        gate, up = jnp.split(u @ gate_up, 2, axis=-1)
        return weight[..., None] * ((_silu(gate) * up) @ down)

    # one held expert after the other, each over every row; the sum's
    # gradient needs no partial sum, so the backward keeps none
    y, _ = jax.lax.scan(
        lambda y, expert: (y + applied(expert), None), jnp.zeros_like(u),
        (jnp.moveaxis(weights[..., first:first + held], -1, 0),
         blk["experts_fc1"], blk["experts_fc2"]))
    return y


def _block(config, blk, x, depart):
    eps = config["rms_norm_eps"]
    x = x + _attention(config, blk, _rms_norm(x, blk["ln1"]["scale"], eps),
                       depart)
    u = _rms_norm(x, blk["ln2"]["scale"], eps)
    weights, balance = _route(config, blk, u)
    return x + _experts(config, blk, weights, u), balance


def _picked(stream, head, labels):
    """Log-probability of ``labels`` [batch, L] under
    ``log_softmax(stream @ head)``, ``HEAD_BLOCK`` positions at a time."""
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
    """Each position's log-probability of its label under the noised
    row's logits, float32 [n, L], and the layers' balance losses summed."""
    p = variables["params"]
    tokens, masked = batch["tokens"], batch["masked"]
    half = tokens.shape[1]
    pair = jnp.concatenate(
        [jnp.where(masked, config["mask_token_id"], tokens), tokens], axis=1)
    with jax.default_matmul_precision("highest"):
        x = p["wte"]["embedding"][pair]
        balance = 0.0
        for i in range(config["num_hidden_layers"]):
            # every layer recomputed in the backward pass
            block = jax.checkpoint(
                lambda blk, x: _block(config, blk, x, depart))
            x, layer_balance = block(p[f"block{i}"], x)
            balance = balance + layer_balance
        stream = _rms_norm(x[:, :half], p["lnf"]["scale"],
                           config["rms_norm_eps"])
        labels = tokens
        if depart == "shifted_labels":
            labels = jnp.roll(tokens, -1, axis=1)
        return _picked(stream, p["head"]["kernel"], labels), balance


def _weights(batch, depart):
    masked, t = batch["masked"], batch["t"]
    counted = jnp.ones_like(t) if depart == "loss_over_every_row" else masked
    return counted if depart == "weight_dropped" else counted / t


def logprob(config, variables, batch, depart=None):
    """The masked positions' log-probability of their own token, float32
    [n, L]; zero where a position is not masked."""
    picked, _ = _logprob_and_balance(config, variables, batch, depart)
    return jnp.where(batch["masked"], picked, 0.0)


def loss(config, variables, batch, depart=None):
    """The masked positions' cross-entropy weighted by ``1 / t``, over
    ``L``, plus the balance loss: ``balance_loss_coef`` times the layers'
    sum."""
    picked, balance = _logprob_and_balance(config, variables, batch, depart)
    weighted = -(_weights(batch, depart) * picked).sum(-1).mean()
    return (weighted / picked.shape[-1]
            + config["balance_loss_coef"] * balance)
