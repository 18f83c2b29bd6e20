"""Plain reference for ``smallthinker-21ba3b-instruct`` (``model_name:
smallthinker_21b_instruct``): full attention layers without positions
and sliding-window layers with rotary positions, grouped heads, and in
every layer the routed experts this chip holds behind a router that
reads the LAYER'S INPUT, in straightforward ``jax.numpy``, float32,
full-precision matmuls, no kernel.  It reads the program's variables
(``params``: ``wte``, ``block<i>/{ln1, qkv, proj, ln2, router,
experts_fc1, experts_fc2}``, ``lnf``, ``head``) and nothing else of the
program; the sizes come from the configuration file's published keys.

The layer, stream ``x`` [T, hidden], both norms an RMSNorm with a learned
scale (ISSUE 43's four equations)::

    r   = x W_r                    [T, 64], from the layer's INPUT,
                                   before any norm
    idx = the 6 largest of r;  w = softmax(r[idx])  over the six alone
    h   = ln1(x)
    q, k, v = split(h Wqkv) as [T, 28, 128], [T, 4, 128], [T, 4, 128]
    q, k = rope(q, k)              where rope_layout says 1, else not
    o   = softmax(q k^T / sqrt(128) + mask) v    head h reads kv head h // 7
          mask: j <= i, and where sliding_window_layout says 1
          i - j < sliding_window_size
    x'  = x + o Wo
    u   = ln2(x')
    x'' = x' + sum over e in idx and held of
               w_e W_down,e (relu(W_gate,e u) * (W_up,e u))
    logits = lnf(x) W_head
    loss = mean cross-entropy
           + balance_loss_coef * sum over layers of 64 sum_e f_e P_e

``f_e`` is the share of the ``6 T`` slots that chose expert ``e`` (a
count: no gradient), ``P_e`` the mean over tokens of the full 64-way
softmax of ``r``; 1.0 a layer at an even load.

The expert layer is not the program's algorithm (top-k, rows sorted by
expert, a grouped matmul, the rows put back): EVERY held expert is
applied to EVERY token and its output multiplied by the token's weight
for it, which is zero where the token did not choose it.  The same share
of the experts as the program's (``moe_num_primary_experts`` held from
``first_held_expert`` on, of the router's own width), so what the experts
held elsewhere would have added is left out on both sides.  The window
is an explicit mask over all the keys.  So that it fits at 16 384 tokens
beside the parameters and two gradients the checks hold (28 heads x
16 384^2 float32 scores are 30 GB whole), attention is computed
``ROW_BLOCK`` query rows at a time, the log-probabilities ``HEAD_BLOCK``
positions at a time, and every layer, and within it every expert, is
recomputed in the backward pass.

What the source's config.json does not spell out (what the router reads,
the balance loss, the rotation's pairing) is stated under ``assumed`` in
the configuration file.  ``depart`` seeds one fault (``DEPARTURES``), so
that a test or ``benchmark/tools/probe_departures.py`` can show that the
comparison with the program fails when either side leaves the equations:
``router_after_attention`` (the router reads ``u``, the usual place: only
the choice and its weights change), ``silu_gate``, ``softmax_over_all``
(the full softmax's values for the chosen, not renormalised),
``rope_in_full_layer``, ``window_ignored``.  The benchmark never passes
it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("router_after_attention", "silu_gate", "softmax_over_all",
              "rope_in_full_layer", "window_ignored")
ROW_BLOCK = 512
HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Split halves (channel ``i`` turns with ``i + half``), positions
    0 .. seq-1; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(config, blk, h, rotates, windowed):
    """The attention branch on the normed stream ``h`` [batch, seq,
    hidden]."""
    batch, seq, _ = h.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd = config["head_dim"]
    q_dim, kv_dim = heads * hd, kv_heads * hd
    fused = h @ blk["qkv"]["kernel"]
    q = fused[..., :q_dim].reshape(batch, seq, heads, hd)
    k = fused[..., q_dim:q_dim + kv_dim].reshape(batch, seq, kv_heads, hd)
    v = fused[..., q_dim + kv_dim:].reshape(batch, seq, kv_heads, hd)
    if rotates:
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    window = config["sliding_window_size"] if windowed else None
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
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bngqk,bknd->bqngd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, q_dim)
    return out @ blk["proj"]["kernel"]


def _route(config, blk, x, depart):
    """From the tensor the router reads: each token's weight for each of
    ALL experts (zero where not chosen) and the layer's balance loss."""
    top_k = config["moe_num_active_primary_experts"]
    r = x @ blk["router"]
    kth = jnp.sort(r, axis=-1)[..., -top_k]
    picked = r >= kth[..., None]
    full = jax.nn.softmax(r, axis=-1)
    if depart == "softmax_over_all":
        weights = jnp.where(picked, full, 0.0)
    else:
        weights = jax.nn.softmax(jnp.where(picked, r, -jnp.inf), axis=-1)
    experts = r.shape[-1]
    tokens = r.size // experts
    share = picked.reshape(tokens, experts).sum(0) / (tokens * top_k)
    balance = experts * jnp.sum(share * full.reshape(tokens, experts).mean(0))
    return weights, balance


def _experts(config, blk, weights, u, depart):
    first, held = (config["first_held_expert"],
                   config["moe_num_primary_experts"])

    def act(gate):
        if depart == "silu_gate":
            return gate / (1.0 + jnp.exp(-gate))
        return jnp.maximum(gate, 0.0)

    @jax.checkpoint
    def add_expert(y, expert):
        weight, gate_up, down = expert
        gate, up = jnp.split(u @ gate_up, 2, axis=-1)
        return y + weight[..., None] * ((act(gate) * up) @ down), None

    # one held expert after the other, each over every token
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.moveaxis(weights[..., first:first + held], -1, 0),
         blk["experts_fc1"], blk["experts_fc2"]))
    return y


def _block(config, blk, x, rotates, windowed, depart=None):
    eps = config["rms_norm_eps"]
    late = depart == "router_after_attention"
    if not late:
        weights, balance = _route(config, blk, x, depart)
    x = x + _attention(
        config, blk, _rms_norm(x, blk["ln1"]["scale"], eps),
        rotates or depart == "rope_in_full_layer",
        windowed and depart != "window_ignored")
    u = _rms_norm(x, blk["ln2"]["scale"], eps)
    if late:
        weights, balance = _route(config, blk, u, depart)
    return x + _experts(config, blk, weights, u, depart), balance


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden], and the layers' balance losses summed."""
    p = variables["params"]
    x = p["wte"]["embedding"][tokens]
    balance = 0.0
    for i, (rotates, windowed) in enumerate(zip(
            config["rope_layout"], config["sliding_window_layout"])):
        # every layer recomputed in the backward pass
        block = jax.checkpoint(
            lambda blk, x, rotates=rotates, windowed=windowed: _block(
                config, blk, x, bool(rotates), bool(windowed), depart))
        x, layer_balance = block(p[f"block{i}"], x)
        balance = balance + layer_balance
    return _rms_norm(x, p["lnf"]["scale"], config["rms_norm_eps"]), balance


def _picked(stream, head, labels):
    """Log-probability of ``labels`` [batch, seq] under
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
