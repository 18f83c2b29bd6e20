"""Plain reference for ``glm-4.7-flash``: latent attention, the routed
experts this chip holds beside the shared expert, the dense first layer,
the multi-token-prediction module and both losses through one head, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel.  It reads the program's variables (``params``: ``wte``,
``block<i>/{ln1, q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, proj, ln2}``
with ``{fc1, fc2}`` in the dense layer and ``{router, experts_fc1,
experts_fc2, shared_fc1, shared_fc2}`` in an expert layer, ``lnf``,
``head``, ``mtp/{enorm, hnorm, eh_proj, block, norm}``; ``moe_state``:
each expert layer's selection bias) and nothing else of the program; the
sizes come from the configuration file's published keys.

The expert layer is not the program's algorithm (scores, top-k, rows
sorted by expert, a grouped matmul, the rows put back): EVERY held expert
is applied to EVERY token and its output multiplied by the token's weight
for it, which is zero where the token did not choose it.  The same share
of the experts as the program's (``n_routed_experts`` held from
``first_held_expert`` on, of the router's own width), so what the experts
held elsewhere would have added is left out on both sides.  So that it
fits at 8192 tokens beside the parameters and two gradients the checks
hold, attention is computed ``ROW_BLOCK`` query rows at a time, the
log-probabilities ``HEAD_BLOCK`` positions at a time (8192 x 19360
logits are never whole), and every layer, and within it every expert, is
recomputed in the backward pass.

No departure from the equations of ISSUE 32; what the source's
config.json does not fix (the rotation's pairing, the loss weight, the
order of the concatenation, the stream the module reads, the bias's
values) is stated under ``assumed`` in the configuration file.
"""

import jax
import jax.numpy as jnp

ROW_BLOCK = 512
HEAD_BLOCK = 1024


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


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


def _latent_attention(config, blk, h):
    batch, seq, _ = h.shape
    heads, latent = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, eps = config["v_head_dim"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    cq = _rms_norm(h @ blk["q_a"]["kernel"], blk["q_a_norm"]["scale"], eps)
    q = (cq @ blk["q_b"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    kv = h @ blk["kv_a"]["kernel"]
    ckv = _rms_norm(kv[..., :latent], blk["kv_a_norm"]["scale"], eps)
    k_v = (ckv @ blk["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + vd)
    # one rotary key for all heads
    k_rope = _rope(kv[:, :, None, latent:], theta)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate([k_v[..., :nope], jnp.broadcast_to(
        k_rope, (batch, seq, heads, rope))], -1)
    v = k_v[..., nope:]
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def row_block(start):
        q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) \
            / jnp.sqrt(nope + rope)
        seen = (jnp.arange(seq)[None, :]
                <= start + jnp.arange(rows)[:, None])
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
    out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, heads * vd)
    return out @ blk["proj"]["kernel"]


def _experts(config, blk, bias, h):
    scores = jax.nn.sigmoid(h @ blk["router"])
    # the bias chooses; the weights are the scores'
    biased = scores + bias
    kth = jnp.sort(biased, axis=-1)[..., -config["num_experts_per_tok"]]
    picked = jnp.where(biased >= kth[..., None], scores, 0.0)
    weights = (picked / (picked.sum(-1, keepdims=True) + 1e-20)
               * config["routed_scaling_factor"])
    first, held = config["first_held_expert"], config["n_routed_experts"]

    @jax.checkpoint
    def add_expert(y, expert):
        weight, gate_up, down = expert
        return y + weight[..., None] * _gated(h, gate_up, down), None

    # one held expert after the other, each over every token
    y, _ = jax.lax.scan(
        add_expert,
        _gated(h, blk["shared_fc1"]["kernel"], blk["shared_fc2"]["kernel"]),
        (jnp.moveaxis(weights[..., first:first + held], -1, 0),
         blk["experts_fc1"], blk["experts_fc2"]))
    return y


def _block(config, blk, bias, x):
    eps = config["rms_norm_eps"]
    x = x + _latent_attention(config, blk,
                              _rms_norm(x, blk["ln1"]["scale"], eps))
    h = _rms_norm(x, blk["ln2"]["scale"], eps)
    if "router" in blk:
        return x + _experts(config, blk, bias, h)
    return x + _gated(h, blk["fc1"]["kernel"], blk["fc2"]["kernel"])


def _streams(config, variables, tokens, next_tokens):
    """``tokens``, ``next_tokens`` int [batch, seq] -> the two normed
    streams the head reads, float32 [batch, seq, hidden]: the model's
    for the next token and the prediction module's for the one after."""
    p = variables["params"]
    biases = variables["moe_state"]
    eps = config["rms_norm_eps"]
    # every layer recomputed in the backward pass
    block = jax.checkpoint(
        lambda blk, bias, x: _block(config, blk, bias, x))
    table = p["wte"]["embedding"]
    x = table[tokens]
    for i in range(config["num_hidden_layers"]):
        name = f"block{i}"
        x = block(p[name], biases.get(name, {}).get("bias"), x)
    m = p["mtp"]
    pair = jnp.concatenate(
        [_rms_norm(table[next_tokens], m["enorm"]["scale"], eps),
         _rms_norm(x, m["hnorm"]["scale"], eps)], axis=-1)
    y = block(m["block"], biases["mtp"]["block"]["bias"],
              pair @ m["eh_proj"]["kernel"])
    return (_rms_norm(x, p["lnf"]["scale"], eps),
            _rms_norm(y, m["norm"]["scale"], eps))


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


def logprob(config, variables, batch):
    """Log-probability of each label of ``batch`` int [n, seq + 2]:
    float32 [n, 2 seq], position ``i``'s next token first, then its
    token after next."""
    tokens = batch["tokens"]
    with jax.default_matmul_precision("highest"):
        main, mtp = _streams(config, variables, tokens[:, :-2],
                             tokens[:, 1:-1])
        head = variables["params"]["head"]["kernel"]
        return jnp.concatenate([_picked(main, head, tokens[:, 1:-1]),
                                _picked(mtp, head, tokens[:, 2:])], axis=-1)


def loss(config, variables, batch):
    """``CE_main + mtp_loss_weight * CE_mtp``, each a mean over the
    ``seq`` positions."""
    main, mtp = jnp.split(-logprob(config, variables, batch), 2, axis=-1)
    return main.mean() + config["mtp_loss_weight"] * mtp.mean()
