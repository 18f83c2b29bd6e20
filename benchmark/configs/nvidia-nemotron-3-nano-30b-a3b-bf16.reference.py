"""Plain reference for ``nvidia-nemotron-3-nano-30b-a3b-bf16``
(``model_type: nemotron_h``; arXiv:2504.03624, arXiv:2512.20848): layers
of ONE half each, named by ``hybrid_override_pattern``, in
straightforward ``jax.numpy``, float32, full-precision matmuls, no
kernel.  It reads the program's variables (``params``: ``wte``,
``block<i>/ln1`` and, by the layer's kind, ``in_proj, conv_kernel,
conv_bias, dt_bias, A_log, D, ssm_norm, out_proj`` or ``qkv, proj`` or
``router, experts_fc1, experts_fc2, shared_fc1, shared_fc2``, ``lnf``,
``head``; ``moe_state``: ``block<i>/bias``) and nothing else of the
program; the sizes come from the configuration file's published keys.

Layer ``i`` of kind ``c = hybrid_override_pattern[i]``, stream ``x`` [T,
hidden], one RMSNorm a layer with a learned scale and
``layer_norm_epsilon`` (ISSUE 61's equations)::

    x = x + f_c(ln1(x))

    f_M, Mamba-2, on n = ln1(x), 64 heads of 64, state 128, 8 groups:
        [z ; xBC ; dt] = n W_in              4096 + (4096 + 2 x 8 x 128) + 64
        xBC = silu(conv4(xBC) + b)           causal depthwise filter of 4
                                             taps, zeros before the sequence
        x, B, C = xBC                        head h reads group h // 8
        dt  = softplus(dt + dt_bias);  A = -exp(A_log)         a head
        S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
        g   = y * silu(z)                    the gate BEFORE the norm
        f_M = rms_norm_by_group(g) * ssm_norm  W_out
                                             each group's 512 channels
                                             with their own mean square

    f_*, attention, on n = ln1(x), 32 query heads over 2 key/value heads
    of 128, NOTHING rotated:
        o   = softmax(q k^T / sqrt(128) + causal mask) v
                                             query head h reads key/value
                                             head h // 16
        f_* = o W_o

    f_E, experts, on n = ln1(x):
        s   = sigmoid(n W_r)                 [T, 128]
        idx = the 6 largest of s + bias      the bias moves the CHOICE only
        w   = s[idx] / (sum of s[idx] + 1e-20) * routed_scaling_factor
        f_E = sum over e in idx and held of w_e W_down,e relu(W_up,e n)^2
              + W_down,s relu(W_up,s n)^2    the shared expert, 3712 wide;
                                             NO gate matrix anywhere

    logits = lnf(x) W_head

The Mamba layer is the recurrence itself, one token at a time (two
nested ``lax.scan``s over ``TOKEN_RUN`` tokens each, the outer one's
body recomputed in the backward pass), never the chunked form of
``horovod_tpu/ops/ssd.py``; the filter is four shifted products; the
grouped norm a reshape to ``[.., 8, 512]``.  The expert layer is not the
program's algorithm either (top-k, rows sorted by expert, a grouped
matmul, the rows put back): EVERY held expert is applied to EVERY token
and its output multiplied by the token's weight for it, zero where the
token did not choose it.  The same share of the experts as the program's
(``n_routed_experts`` held from ``first_held_expert`` on, of the router's
own width), so what the experts held elsewhere would have added is left
out on both sides.  So that it fits at 16 384 tokens beside the
parameters and two gradients the checks hold, attention is computed
``QUERY_HEADS`` query heads and ``ROW_BLOCK`` query rows at a time, a
Mamba layer's filter (with the three tokens before a block) and gated
norm and the feed-forwards ``TOKEN_BLOCK`` tokens at a time, the
log-probabilities ``HEAD_BLOCK`` positions at a time, and every layer,
and within it every such block, is recomputed in the backward pass.

What the source's config.json does not spell out is stated under
``assumed`` in the configuration file.  ``depart`` seeds one fault
(``DEPARTURES``), so that a test or
``benchmark/tools/probe_departures.py`` can show that the comparison with
the program fails when either side leaves the equations:
``experts_gated`` (a silu gate from a third matrix, each expert's
``W_up`` with its columns moved round by one), ``relu_not_squared``,
``norm_one_group`` (one mean square over all 4096 channels),
``norm_before_gate``, ``groups_one`` (every head reads group 0's B and
C), ``shared_expert_dropped``, ``shared_width_routed`` (the shared
expert cut to a routed expert's 1856 columns), ``bias_in_weights``,
``scaling_dropped``, ``attention_rotated`` (a rotary table, theta
``rope_theta``, over a head's 128 channels), ``conv_bias_dropped``,
``skip_D_dropped``, ``second_half_added`` (after every mixer a
feed-forward half as a two-half block would have: the next expert
layer's shared expert behind the mixer layer's own norm),
``state_bfloat16`` (the decays ``exp(dt A)`` and the state after every
token rounded to bfloat16: the recurrence in the precision below the
float32 the configuration states for it).  The benchmark never passes
it.
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("experts_gated", "relu_not_squared", "norm_one_group",
              "norm_before_gate", "groups_one", "shared_expert_dropped",
              "shared_width_routed", "bias_in_weights", "scaling_dropped",
              "attention_rotated", "conv_bias_dropped", "skip_D_dropped",
              "second_half_added", "state_bfloat16")
KINDS = {"M": "mamba", "*": "attention", "E": "experts"}
ROW_BLOCK = 128
QUERY_HEADS = 8
TOKEN_BLOCK = 2048
HEAD_BLOCK = 1024
TOKEN_RUN = 128


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """Split halves (channel ``i`` turns with ``i + half``), positions
    0 .. seq-1; ``x`` [batch, seq, heads, dim]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _recurrence(x, dt, a, B, C, group_of, depart):
    """``x`` [seq, heads, p], ``dt`` [seq, heads], ``a`` [heads]
    (negative), ``B``, ``C`` [seq, groups, n], head ``h`` reading group
    ``group_of[h]`` -> ``y`` [seq, heads, p], the state starting at
    zero."""
    seq, heads, p = x.shape
    run = TOKEN_RUN if seq % TOKEN_RUN == 0 else seq
    held = lambda t: t  # what the state and the decays are held in
    if depart == "state_bfloat16":
        # not a cast there and back, which XLA may take for excess
        # precision and drop
        held = lambda t: jax.lax.reduce_precision(t, exponent_bits=8,
                                                  mantissa_bits=7)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = held(held(jnp.exp(dt_t * a))[:, None, None] * state
                     + (dt_t[:, None] * x_t.reshape(heads, p))[:, :, None]
                     * b_t[group_of][:, None, :])
        y_t = jnp.sum(state * c_t[group_of][:, None, :], axis=-1)
        return state, y_t.reshape(heads * p)

    @jax.checkpoint
    def tokens(state, inp):
        return jax.lax.scan(token, state, inp)

    # a token's heads side by side (on the chip 64 channels alone would
    # be padded to a tile's 128)
    runs = jax.tree.map(
        lambda t: t.reshape(seq // run, run, *t.shape[1:]),
        (x.reshape(seq, heads * p), dt, B, C))
    _, y = jax.lax.scan(tokens, jnp.zeros((heads, p, B.shape[-1])), runs)
    return y.reshape(seq, heads, p)


def _mamba(config, blk, n, depart):
    """The Mamba-2 mixer on the normed stream ``n`` [batch, seq,
    hidden].  What comes before the recurrence and what comes after it
    are computed ``TOKEN_BLOCK`` tokens at a time, each block recomputed
    in the backward pass (a dozen ``[16384, 6144]`` float32 arrays of the
    filter's products alone are 4.5 GiB)."""
    heads, p = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner, taps = heads * p, config["conv_kernel"]
    eps = config["layer_norm_epsilon"]
    batch, seq, _ = n.shape
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq
    # zeros before the sequence: in_proj has no bias, so the filter's
    # input there is zero as the equations say
    padded = jnp.pad(n, ((0, 0), (taps - 1, 0), (0, 0)))

    @jax.checkpoint
    def before(start):
        """A block's gate, filtered x, B and C, and dt, from its tokens
        and the ``taps - 1`` before them."""
        part = jax.lax.dynamic_slice_in_dim(padded, start, rows + taps - 1,
                                            axis=1)
        z, xbc, dt = jnp.split(
            part @ blk["in_proj"]["kernel"],
            [inner, 2 * inner + 2 * groups * state], axis=-1)
        xbc = sum(xbc[:, k:k + rows] * blk["conv_kernel"][k]
                  for k in range(taps))
        if depart != "conv_bias_dropped":
            xbc = xbc + blk["conv_bias"]
        x, B, C = jnp.split(_silu(xbc), [inner, inner + groups * state],
                            axis=-1)
        return (z[:, taps - 1:], x, B, C, jnp.logaddexp(
            dt[:, taps - 1:] + blk["dt_bias"], 0.0))  # softplus

    z, x, B, C, dt = (
        jnp.moveaxis(t, 0, 1).reshape(batch, seq, t.shape[-1])
        for t in jax.lax.map(before, jnp.arange(0, seq, rows)))
    # head h reads group h // (heads // groups)
    group_of = jnp.arange(heads) // (heads // groups)
    if depart == "groups_one":
        group_of = jnp.zeros_like(group_of)
    y = jax.vmap(lambda *t: _recurrence(*t, group_of, depart),
                 in_axes=(0, 0, None, 0, 0))(
        x.reshape(batch, seq, heads, p), dt, -jnp.exp(blk["A_log"]),
        B.reshape(batch, seq, groups, state),
        C.reshape(batch, seq, groups, state))

    def after(y, x, z):
        """The skip, the gate, THEN the norm by group, and out_proj."""
        tokens = y.shape[:2]
        if depart != "skip_D_dropped":
            y = y + jnp.repeat(blk["D"], p) * x
        gate = _silu(z)
        by_group = lambda t: t.reshape(
            *tokens, 1 if depart == "norm_one_group" else groups, -1)
        norm = lambda t: _rms_norm(by_group(t), 1.0, eps).reshape(
            *tokens, inner) * blk["ssm_norm"]
        out = (norm(y) * gate if depart == "norm_before_gate"
               else norm(y * gate))
        return out @ blk["out_proj"]["kernel"]

    return _blocked(after, y.reshape(batch, seq, inner), x, z)


def _attention(config, blk, n, depart):
    """Grouped-query attention without positions on the normed stream
    ``n`` [batch, seq, hidden]: causal, every earlier key.  The query
    heads are independent between the projections and ``W_o``:
    ``QUERY_HEADS`` of them at a time (all of one key/value head's
    group) and ``ROW_BLOCK`` query rows at a time, each block recomputed
    in the backward pass, their parts of ``o W_o`` added up."""
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["head_dim"]
    batch, seq, hidden = n.shape
    group = heads // kv_heads        # query heads a key/value head
    held = QUERY_HEADS if group % QUERY_HEADS == 0 else group
    kernel = blk["qkv"]["kernel"]
    k, v = (t.reshape(batch, seq, kv_heads, d) for t in jnp.split(
        n @ kernel[:, heads * d:], 2, axis=-1))
    if depart == "attention_rotated":
        k = _rope(k, config["rope_theta"])
    rows = ROW_BLOCK if seq % ROW_BLOCK == 0 else seq

    @jax.checkpoint
    def add_heads(y, w):
        q = jnp.einsum("bsd,dhc->bshc", n, w["q"])
        if depart == "attention_rotated":
            q = _rope(q, config["rope_theta"])
        # query head h reads key/value head h // group
        mine = lambda t: jax.lax.dynamic_index_in_dim(
            t, w["first"] // group, axis=2, keepdims=False)
        k_h, v_h = mine(k), mine(v)

        @jax.checkpoint
        def row_block(start):
            q_rows = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
            scores = jnp.einsum("bqhd,bkd->bhqk", q_rows, k_h) / jnp.sqrt(d)
            seen = (jnp.arange(seq)[None, :]
                    <= start + jnp.arange(rows)[:, None])
            scores = jnp.where(seen, scores, -jnp.inf)
            return jnp.einsum("bhqk,bkd->bqhd",
                              jax.nn.softmax(scores, axis=-1), v_h)

        out = jax.lax.map(row_block, jnp.arange(0, seq, rows))
        out = jnp.moveaxis(out, 0, 1).reshape(batch, seq, held, d)
        return y + jnp.einsum("bshc,hcd->bsd", out, w["proj"]), None

    y, _ = jax.lax.scan(add_heads, jnp.zeros_like(n), {
        "q": jnp.moveaxis(kernel[:, :heads * d].reshape(
            hidden, heads // held, held, d), 1, 0),
        "proj": blk["proj"]["kernel"].reshape(
            heads // held, held, d, hidden),
        "first": jnp.arange(0, heads, held)})
    return y


def _ungated(n, up, down, depart=None):
    """``W_down relu(W_up n)^2``."""
    h = jnp.maximum(n @ up, 0.0)
    if depart == "experts_gated":
        # a third matrix: W_up with its columns moved round by one
        h = _silu(n @ jnp.roll(up, 1, axis=-1)) * (n @ up)
    elif depart != "relu_not_squared":
        h = h * h
    return h @ down


def _weights(config, blk, bias, n, depart):
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
    if depart == "scaling_dropped":
        return chosen
    return chosen * config["routed_scaling_factor"]


def _shared(config, blk, n, depart):
    """The shared expert, every token, weight 1."""
    up, down = blk["shared_fc1"]["kernel"], blk["shared_fc2"]["kernel"]
    if depart == "shared_width_routed":
        wide = config["moe_intermediate_size"]
        up, down = up[:, :wide], down[:wide]
    return _ungated(n, up, down,
                    depart if depart == "relu_not_squared" else None)


def _blocked(apply, *streams):
    """``apply`` on ``TOKEN_BLOCK`` tokens of every ``[batch, seq, .]``
    stream at a time, each block recomputed in the backward pass."""
    batch, seq = streams[0].shape[:2]
    rows = TOKEN_BLOCK if seq % TOKEN_BLOCK == 0 else seq
    blocked = lambda t: jnp.moveaxis(
        t.reshape(batch, seq // rows, rows, t.shape[-1]), 1, 0)
    out = jax.lax.map(lambda part: jax.checkpoint(apply)(*part),
                      tuple(blocked(t) for t in streams))
    return jnp.moveaxis(out, 0, 1).reshape(batch, seq, out.shape[-1])


def _experts(config, blk, bias, n, depart):
    """Every held expert on every token, weighted by the token's choice,
    and the shared expert beside them."""
    first, held = config["first_held_expert"], config["n_routed_experts"]
    weights = _weights(config, blk, bias, n, depart)[..., first:first + held]

    def tokens(rows_n, rows_w):
        # one held expert after the other, each over every token (a scan
        # and no Python loop: one expert's program, not ``held`` copies)
        def add_expert(y, expert):
            up, down, weight = expert
            return y + weight[..., None] * _ungated(rows_n, up, down,
                                                    depart), None

        y, _ = jax.lax.scan(
            add_expert, jnp.zeros_like(rows_n),
            (blk["experts_fc1"], blk["experts_fc2"],
             jnp.moveaxis(rows_w, -1, 0)))
        if depart != "shared_expert_dropped":
            y = y + _shared(config, blk, rows_n, depart)
        return y

    return _blocked(tokens, n, weights)


def layer_kinds(config):
    """``"mamba"``, ``"attention"`` or ``"experts"`` a layer, from
    ``hybrid_override_pattern``."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            KINDS):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has to name each of the "
            f"{config['num_hidden_layers']} layers M, * or E")
    return [KINDS[c] for c in pattern]


def _stream(config, variables, tokens, depart):
    """``tokens`` int [batch, seq] -> the normed stream the head reads,
    float32 [batch, seq, hidden]."""
    p = variables["params"]
    eps = config["layer_norm_epsilon"]
    x = p["wte"]["embedding"][tokens]
    kinds = layer_kinds(config)
    for i, kind in enumerate(kinds):
        blk = p[f"block{i}"]

        def layer(blk, bias, x, kind=kind):
            n = _rms_norm(x, blk["ln1"]["scale"], eps)
            if kind == "experts":
                return x + _experts(config, blk, bias, n, depart)
            mixer = _mamba if kind == "mamba" else _attention
            return x + mixer(config, blk, n, depart)

        bias = (variables["moe_state"][f"block{i}"]["bias"]
                if kind == "experts" else None)
        # every layer recomputed in the backward pass
        x = jax.checkpoint(layer)(blk, bias, x)
        later = [j for j in range(i + 1, len(kinds)) if kinds[j] == "experts"]
        if depart == "second_half_added" and kind != "experts" and later:
            x = x + _blocked(
                lambda rows, shared=p[f"block{later[0]}"]: _shared(
                    config, shared, rows, None),
                _rms_norm(x, blk["ln1"]["scale"], eps))
    return _rms_norm(x, p["lnf"]["scale"], eps)


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
