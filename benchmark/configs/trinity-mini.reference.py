"""Plain reference for ``trinity-mini`` (``model_type: afmoe``): sliding-
window and full attention layers mixed, rotary positions in the window
layers only, a norm over each head of q and of k, a sigmoid output gate,
four norms a block, a leading dense layer, then the routed experts this
chip holds beside the shared expert, in straightforward ``jax.numpy``,
float32, full-precision matmuls, no kernel.  It reads the program's
variables (``params``: ``wte``, ``block<i>/{ln1, qkv, q_norm, k_norm,
gate, proj, post_attn_norm, ln2, post_mlp_norm}`` with ``{fc1, fc2}`` in
a dense layer and ``{router, experts_fc1, experts_fc2, shared_fc1,
shared_fc2}`` in an expert layer, ``lnf``, ``head``; ``moe_state``: each
expert layer's selection bias) and nothing else of the program; the
sizes come from the configuration file's published keys.

The layer, stream ``x`` [T, hidden], every norm an RMSNorm with a
learned scale::

    x0  = sqrt(hidden) * wte[tokens]
    a   = ln1(x)
    q, k, v = split(a Wqkv) as [T, 32, 128], [T, 4, 128], [T, 4, 128]
    g   = a Wg                                              [T, 4096]
    q   = q_norm(q);  k = k_norm(k)        over each head's 128 channels
    q, k = rope(q, k)   in a sliding_attention layer only
    o   = softmax(q k^T / sqrt(128) + mask) v    head h reads kv head h // 8
          mask: j <= i, and in a sliding_attention layer i - j < window
    x   = x + post_attn_norm((o * sigmoid(g)) Wo)
    m   = ln2(x)
    f   = dense(m)  before num_dense_layers,  else
          shared(m) + sum over e in chosen(m) and held of w_e expert_e(m)
    x   = x + post_mlp_norm(f)
    logits = lnf(x) W_head

The expert layer is not the program's algorithm (scores, top-k, rows
sorted by expert, a grouped matmul, the rows put back): EVERY held expert
is applied to EVERY token and its output multiplied by the token's weight
for it, which is zero where the token did not choose it.  The same share
of the experts as the program's (``num_experts`` held from
``first_held_expert`` on, of the router's own width), so what the experts
held elsewhere would have added is left out on both sides.  The window is
an explicit mask over all the keys.  So that it fits at 8192 tokens
beside the parameters and two gradients the checks hold, attention is
computed ``ROW_BLOCK`` query rows at a time, the log-probabilities
``HEAD_BLOCK`` positions at a time (8192 x 25024 logits are never
whole), and every layer, and within it every expert, is recomputed in
the backward pass.

What the source's config.json does not spell out (the gate, the head
norms, the four norms, rotary in the window layers only, the embedding
multiplier, the rotation's pairing) is stated under ``assumed`` in the
configuration file.  ``depart`` seeds one fault, so that a test can show
that the comparison with the program fails when either side leaves the
equations: ``window_ignored``, ``window_off_by_one`` (a window layer
sees one key more), ``gate_dropped``, ``rope_in_full_layer``,
``post_norm_dropped`` (the attention branch's), ``multiplier_dropped``.
The benchmark never passes it.
"""

import jax
import jax.numpy as jnp

ROW_BLOCK = 512
HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _gated(x, gate_up, down):
    gate, up = jnp.split(x @ gate_up, 2, axis=-1)
    return (_silu(gate) * up) @ down


def _rope(x, theta):
    """Split halves (channel ``i`` turns with ``i + half``), positions
    0 .. seq-1; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(config, blk, a, kind, depart):
    """The attention branch on the normed stream ``a`` [batch, seq,
    hidden], before its post-norm; ``kind`` is the layer's type."""
    batch, seq, _ = a.shape
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    q_dim, kv_dim = heads * hd, kv_heads * hd
    fused = a @ blk["qkv"]["kernel"]
    q = fused[..., :q_dim].reshape(batch, seq, heads, hd)
    k = fused[..., q_dim:q_dim + kv_dim].reshape(batch, seq, kv_heads, hd)
    v = fused[..., q_dim + kv_dim:].reshape(batch, seq, kv_heads, hd)
    q = _rms_norm(q, blk["q_norm"]["scale"], eps)
    k = _rms_norm(k, blk["k_norm"]["scale"], eps)
    sliding = kind == "sliding_attention"
    if sliding or depart == "rope_in_full_layer":
        q, k = _rope(q, config["rope_theta"]), _rope(k, config["rope_theta"])
    window = None
    if sliding and depart != "window_ignored":
        window = config["sliding_window"] + (depart == "window_off_by_one")
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
    if depart != "gate_dropped":
        out = out * _sigmoid(a @ blk["gate"]["kernel"])
    return out @ blk["proj"]["kernel"]


def _experts(config, blk, bias, m):
    scores = _sigmoid(m @ blk["router"])
    # the bias chooses; the weights are the scores'
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -config["num_experts_per_tok"]]
    picked = jnp.where(biased >= kth[..., None], scores, 0.0)
    weights = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
               * config["route_scale"])
    first, held = config["first_held_expert"], config["num_experts"]

    @jax.checkpoint
    def add_expert(y, expert):
        weight, gate_up, down = expert
        return y + weight[..., None] * _gated(m, gate_up, down), None

    # one held expert after the other, each over every token
    y, _ = jax.lax.scan(
        add_expert,
        _gated(m, blk["shared_fc1"]["kernel"], blk["shared_fc2"]["kernel"]),
        (jnp.moveaxis(weights[..., first:first + held], -1, 0),
         blk["experts_fc1"], blk["experts_fc2"]))
    return y


def _block(config, blk, bias, x, kind, depart=None):
    eps = config["rms_norm_eps"]
    branch = _attention(config, blk, _rms_norm(x, blk["ln1"]["scale"], eps),
                        kind, depart)
    if depart != "post_norm_dropped":
        branch = _rms_norm(branch, blk["post_attn_norm"]["scale"], eps)
    x = x + branch
    m = _rms_norm(x, blk["ln2"]["scale"], eps)
    if "router" in blk:
        f = _experts(config, blk, bias, m)
    else:
        f = _gated(m, blk["fc1"]["kernel"], blk["fc2"]["kernel"])
    return x + _rms_norm(f, blk["post_mlp_norm"]["scale"], eps)


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = variables["params"]
    biases = variables.get("moe_state", {})
    x = p["wte"]["embedding"][tokens]
    if depart != "multiplier_dropped":
        x = x * jnp.sqrt(1.0 * config["hidden_size"])
    for i, kind in enumerate(config["layer_types"]):
        name = f"block{i}"
        # every layer recomputed in the backward pass
        block = jax.checkpoint(
            lambda blk, bias, x, kind=kind: _block(config, blk, bias, x,
                                                   kind, depart))
        x = block(p[name], biases.get(name, {}).get("bias"), x)
    return _rms_norm(x, p["lnf"]["scale"], config["rms_norm_eps"])


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
