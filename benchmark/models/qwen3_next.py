"""The Qwen3-Next-80B-A3B-Instruct training step (family ``qwen3_next``:
Gated DeltaNet layers, a delta rule whose decay is one number a head
with 16 key heads under 32 value heads, three to one with gated
attention layers whose output gate comes out of the query projection and
whose heads are rotated in their first quarter, every norm ``1 + w``, in
every layer routed experts that drop nothing behind a softmax router
that renormalises its ten chosen weights, beside a shared expert behind
a sigmoid gate of its own, a load-balance loss, an untied head), written
as a user of horovod_tpu writes it: ``hvd.init`` -> model from the zoo
-> ``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state, as
``benchmark/models/smallthinker.py`` builds SmallThinker.  The zoo's
named configuration holds the published values; this builder overrides
only the cut the configuration file states (depth, the layers' types,
the experts held, the vocabulary) and the balance loss's coefficient,
which the source does not publish; and it draws the token table at the
scale the configuration file states (``embedding_init_std``, SDAR's
lesson: ``benchmark/configs/sdar-30b-a3b-chat.json``).

There is no selection bias, so no ``moe_state`` and no ``rebalanced``
call: the step's loss is the cross-entropy plus ``balance_loss_coef``
times the sum of the expert layers' load-balance losses (collection
``losses``, sown by the model).  The state the step carries is three
trees: the variables the mathematics reads (``params``), the optimizer's
state, ``moe_stats`` (each expert layer's rows per held expert, rows
dropped, slots per routed expert and balance loss, of the last step),
which ``variables`` reads from the final carry into
``ran["moe_counters"]``; beside them it leaves what the model counted
while the step was traced: ``ran["flash_tiles"]`` and
``ran["flash_bwd_kernels"]`` of the attention layer's call (gauges
``flash.tiles_live`` / ``flash.tiles_grid`` / ``flash.bwd_kernels``: 1
is the one-kernel backward, 2 the two passes) and ``ran["gdn"]`` (gauges
``gdn.layers``, ``gdn.kernel_layers``, ``gdn.chunk``, ``gdn.kept_mib``).
"""

from __future__ import annotations

from benchmark.harness import kda_flops, moe_flops, window_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "intermediate_size": "ffn_width",
    "partial_rotary_factor": "partial_rotary_factor",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "linear_num_key_heads": "gdn_key_heads",
    "linear_num_value_heads": "gdn_value_heads",
    "linear_key_head_dim": "gdn_key_head_dim",
    "linear_value_head_dim": "gdn_value_head_dim",
    "linear_conv_kernel_dim": "gdn_conv",
    "moe_intermediate_size": "routed_width",
    "shared_expert_intermediate_size": "shared_ffn_width",
    "num_experts_per_tok": "routed_top_k",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "held_experts", "first_held_expert": "routed_first_held",
    "balance_loss_coef": "routed_balance_coef",
}


def layer_types(config: dict) -> list:
    """The program's layer types: layer ``i`` attends where ``(i + 1) %
    full_attention_interval == 0``; every layer is an expert layer
    (``decoder_sparse_step`` 1, no ``mlp_only_layers``), which is all the
    program builds."""
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]:
        raise ValueError(
            "the program builds an expert layer in every layer: "
            f"decoder_sparse_step={config['decoder_sparse_step']} and "
            f"mlp_only_layers={config['mlp_only_layers']} say otherwise")
    return ["full_attention"
            if (i + 1) % config["full_attention_interval"] == 0 else "gdn"
            for i in range(config["num_hidden_layers"])]


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; a DeltaNet layer's
    two projections and ``out_proj``, and the chunk rule as
    ``harness/kda_flops.py`` counts the algorithm at the value heads'
    count; an attention layer's doubled query projection, keys, values
    and ``o``), attention over the keys a token sees on average (the
    causal half), a routed expert counted at the share of a token it is
    expected to see (``experts a token x held / routed``), the shared
    expert, its gate and the router whole, the head (the lookup is no
    matmul); backward twice the forward; recomputation not counted."""
    c = {**config, **ran}
    d, heads, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    key_inner = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    value_heads = c["linear_num_value_heads"]
    value_inner = value_heads * c["linear_value_head_dim"]
    seq = ran["seq_len"]
    mixer = {
        "gdn": 2 * (d * (2 * key_inner + 2 * value_inner)
                    + d * 2 * value_heads + value_inner * d)
        + 2 * kda_flops.kda_forward_macs_per_token(
            value_heads, c["linear_key_head_dim"],
            c["linear_value_head_dim"],
            (ran.get("gdn") or {}).get("chunk") or 64),
        "full_attention": 2 * (d * (2 * q_dim + 2 * kv_dim) + q_dim * d)
        # QK^T and PV over the keys a query sees on average
        + 2 * 2 * q_dim * window_flops.visible_pairs(seq) / seq,
    }
    expected = c["num_experts_per_tok"] * c["num_experts"] / ran[
        "router_width"]
    routed = (2 * d * ran["router_width"] + 2 * d
              + expected * 2 * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_intermediate_size"])
              + 2 * moe_flops.expert_forward_macs_per_row(
                  d, c["shared_expert_intermediate_size"]))
    forward = 2 * d * c["vocab_size"]
    for kind in layer_types(c):
        forward += mixer[kind] + routed
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Damaged copies the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing (its gated shared expert still
    does).  ``state_forgets``: the decay of the LAST DeltaNet layer's
    every head driven to zero (``dt_bias`` at 30 and ``A_log`` at 5:
    ``g`` about -4450 a token), so that layer's state is wiped before
    every token and carries nothing from chunk to chunk or token to
    token.  What no damage of the variables can make is seeded into the
    reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    kinds = layer_types({**config, **ran})
    last_gdn = max(i for i, kind in enumerate(kinds) if kind == "gdn")

    def state_forgets(variables):
        params = dict(variables["params"])
        block = params[f"block{last_gdn}"]
        params[f"block{last_gdn}"] = {
            **block, "dt_bias": block["dt_bias"] * 0.0 + 30.0,
            "A_log": block["A_log"] * 0.0 + 5.0}
        return {**variables, "params": params}

    return {"experts_silent": lambda v: zeroed(
                v, [("params", f"block{len(kinds) - 1}", "experts_fc2")]),
            "state_forgets": state_forgets}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import GPT_CONFIGS, gpt

    size = config["program"]["size"]
    if size not in GPT_CONFIGS:
        raise SystemExit(
            f"benchmark: this tree's horovod_tpu has no configuration "
            f"{size!r} (the Gated DeltaNet layer came with it)")
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.parallel.moe import publish_stats

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=tuple(layer_types(config)),
                     routed_held=config["num_experts"],
                     routed_first_held=config["first_held_expert"],
                     vocab_size=config["vocab_size"],
                     routed_balance_coef=config["balance_loss_coef"],
                     remat=bool(params.get("remat", False)))
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        overrides.update(params["overrides"])
    model = gpt(size, attention_impl=params.get("attention", "flash"),
                **overrides)
    # The same variables without a kernel: initialising through it keeps
    # the Pallas calls out of the init program.
    init_model = gpt(size, attention_impl="reference", **overrides)
    cfg = model.cfg
    ran = {key: getattr(cfg, attr) for key, attr in PUBLISHED.items()}
    if not params.get("overrides"):
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")
        published = config["published"]["num_experts"]
        if cfg.routed_experts != published:
            raise ValueError(
                f"the router scores {cfg.routed_experts} experts, the "
                f"configuration file publishes {published}")
    coef = cfg.routed_balance_coef

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    emb_std = config["embedding_init_std"]

    def scaled_embedding(p):
        """The token table at standard deviation ``embedding_init_std`` a
        channel (flax draws it at ``hidden ** -0.5``), so that a row of
        the stream is its own token's.  The configuration file's
        ``assumed`` says why."""
        table = p["wte"]["embedding"]
        return {**p, "wte": {
            "embedding": table * (emb_std * table.shape[-1] ** 0.5)}}

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        # one chunk of the rule: the shortest sequence a gdn layer takes
        made = init_model.init(
            k_params, jnp.zeros((1, cfg.kda_chunk), jnp.int32))
        made = {**made, "params": scaled_embedding(made["params"])}
        # rows of seq + 1 tokens: position i predicts token i + 1
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)
        return ({"params": made["params"]}, tx.init(made["params"]),
                made["moe_stats"], tokens)

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), replicated(mesh),
        sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def token_losses(logits, toks):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:])

    def balance(sown):
        """The expert layers' load-balance losses, summed, times the
        coefficient (each chip's own tokens; the gradient all-reduce
        averages the chips')."""
        return coef * sum(jax.tree.leaves(sown["losses"]))

    def local_step(variables, opt_state, stats, toks):
        def loss_of(p):
            logits, new = model.apply(
                {"params": p, "moe_stats": stats}, toks[:, :-1],
                mutable=["moe_stats", "losses"])
            return (token_losses(logits, toks).mean() + balance(new),
                    new["moe_stats"])

        p = variables["params"]
        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(p)
        updates, opt_state = tx.update(grads, opt_state, p)
        # out_specs P() presents the loss as replicated, so it has to be
        # the global mean.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        # under the scope ``DistributedOptimizer`` gives the update
        # itself, so that ``optimizer_ms`` finds both (gpt2.py says why)
        with jax.named_scope(OPTIMIZER_SCOPE):
            p = optax.apply_updates(p, updates)
        return {"params": p}, opt_state, stats, loss

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))

    def program_loss(variables, b):
        """The step's loss again, keeping each label's term."""
        toks = b["tokens"]
        logits, sown = model.apply(variables, toks[:, :-1],
                                   mutable=["losses"])
        losses = token_losses(logits, toks)
        return losses.mean() + balance(sown), -losses

    def sample(n):
        """``n`` fresh sequences, not the batch the window trained on."""
        return {"tokens": jax.random.randint(
            jax.random.fold_in(seed_key(seed), FRESH), (n, seq + 1), 0,
            cfg.vocab_size, jnp.int32)}

    # under the names the readers that are there read their sizes by
    ran.update(seq_len=seq, global_batch=batch,
               layer_types=list(cfg.layer_types),
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and what
        the model counted when the step was traced (the runner frees what
        this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        gauge = lambda name, **labels: registry.gauge(name, **labels).value
        kind = "full_attention"
        ran["flash_tiles"] = {kind: {
            name: gauge(f"flash.tiles_{name}", layer_type=kind)
            for name in ("live", "grid")}}
        ran["flash_bwd_kernels"] = {
            kind: gauge("flash.bwd_kernels", layer_type=kind)}
        ran["gdn"] = {name: gauge(f"gdn.{name}") for name in (
            "layers", "kernel_layers", "chunk", "kept_mib")}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
