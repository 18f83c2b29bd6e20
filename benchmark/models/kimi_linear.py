"""The Kimi-Linear-48B-A3B-Instruct training step (family ``kimi_linear``:
Kimi Delta Attention layers three to one with latent attention layers
that see no positions, a dense gated feed-forward in the leading layer
and routed experts that drop nothing behind a sigmoid router with a
selection bias beside one shared expert in the others, an untied head),
written as a user of horovod_tpu writes it: ``hvd.init`` -> model from
the zoo -> ``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit``
step over ``hvd.mesh("flat")`` with donated state, as
``benchmark/models/lfm2_moe.py`` builds LFM2.  The zoo's named
configuration holds the published values; this builder overrides only
the cut the configuration file states (depth, the layers' types, the
experts held, the vocabulary).

The state the step carries is three trees: the variables the mathematics
reads (``params`` and ``moe_state``, each expert layer's selection bias:
no gradient, no AdamW moments; after every step the aux-free balancing
update moves it by ``bias_update_rate`` against the load,
``parallel/moe.py:rebalanced``), the optimizer's state, ``moe_stats``
(each expert layer's rows per held expert, rows dropped and slots per
routed expert, of the last step), which ``variables`` reads from the
final carry into ``ran["moe_counters"]``; beside them it leaves what the
model counted while the step was traced: ``ran["flash_tiles"]``,
``ran["flash_bwd_kernels"]`` and ``ran["flash_fwd_kv_resident"]`` of the
latent layers' call (gauges ``flash.tiles_live`` / ``flash.tiles_grid`` /
``flash.bwd_kernels``; the third from the call's own ``FlashPlan``), and
``ran["kda"]`` (gauges ``kda.layers``, ``kda.chunk`` and
``kda.kept_mib``).
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
    "intermediate_size": "ffn_width", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta",
    "moe_intermediate_size": "routed_width",
    "num_experts_per_token": "routed_top_k",
    "routed_scaling_factor": "routed_scaling",
    "num_shared_experts": "shared_experts",
    "first_k_dense_replace": "dense_layers_first",
    "num_nextn_predict_layers": "mtp_modules",
    "tie_word_embeddings": "tie_embeddings",
    "model_max_length": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "held_experts", "first_held_expert": "routed_first_held",
}
# the same for the keys of ``linear_attn_config``
PUBLISHED_LINEAR = {"num_heads": "kda_heads", "head_dim": "kda_head_dim",
                    "short_conv_kernel_size": "kda_conv"}


def layer_types(config: dict) -> list:
    """The program's layer types from ``linear_attn_config``'s two
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


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; a KDA layer's q, k
    and v, its two low-rank gates, beta and the output projection, and
    the chunk rule as ``harness/kda_flops.py`` counts the algorithm; a
    latent layer's four projections), latent attention over the keys a
    token sees on average (the causal half, 192 channels for the scores
    and 128 for the values), the dense feed-forward, a routed expert
    counted at the share of a token it is expected to see (``experts a
    token x held / routed``), the shared expert and the router whole, the
    head (the lookup is no matmul); backward twice the forward;
    recomputation not counted."""
    c = {**config, **ran}
    d, heads = c["hidden_size"], c["num_attention_heads"]
    linear = c["linear_attn_config"]
    k_heads, hd = linear["num_heads"], linear["head_dim"]
    inner = k_heads * hd
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    seq = ran["seq_len"]
    mixer = {
        "kda": 2 * (d * 3 * inner + 2 * (d * hd + hd * inner)
                    + d * k_heads + inner * d)
        + 2 * kda_flops.kda_forward_macs_per_token(
            k_heads, hd, hd, (ran.get("kda") or {}).get("chunk") or 64),
        "mla": 2 * (d * heads * qk + d * (c["kv_lora_rank"]
                                          + c["qk_rope_head_dim"])
                    + c["kv_lora_rank"] * heads
                    * (c["qk_nope_head_dim"] + c["v_head_dim"])
                    + heads * c["v_head_dim"] * d)
        # QK^T and PV over the keys a query sees on average
        + 2 * heads * (qk + c["v_head_dim"])
        * window_flops.visible_pairs(seq) / seq,
    }
    dense = 2 * 3 * d * c["intermediate_size"]
    expected = (c["num_experts_per_token"] * c["num_experts"]
                / ran["router_width"])
    routed = (2 * d * ran["router_width"]
              + (expected + c["num_shared_experts"]) * 2
              * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_intermediate_size"]))
    forward = 2 * d * c["vocab_size"]
    for i, kind in enumerate(layer_types(c)):
        forward += mixer[kind] + (
            dense if i < c["first_k_dense_replace"] else routed)
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Damaged copies the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing (its shared expert still does).
    ``state_forgets``: the decays of the LAST KDA layer's every channel
    driven to zero (``dt_bias`` at 30 and ``A_log`` at 5: ``g`` about
    -4450 a token), so that layer's state is wiped before every token
    and carries nothing from chunk to chunk or token to token: what a
    chunk's starting state zeroed would give, and more, from the
    variables alone.  What no damage of the variables can make is seeded
    into the reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    kinds = layer_types({**config, **ran})
    last_kda = max(i for i, kind in enumerate(kinds) if kind == "kda")

    def state_forgets(variables):
        params = dict(variables["params"])
        block = params[f"block{last_kda}"]
        params[f"block{last_kda}"] = {
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
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.ops.flash_attention import flash_plan
    from horovod_tpu.parallel.moe import publish_stats, rebalanced

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=tuple(layer_types(config)),
                     routed_held=config["num_experts"],
                     routed_first_held=config["first_held_expert"],
                     vocab_size=config["vocab_size"],
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
    # the published null: one matrix, no query rank
    ran["q_lora_rank"] = cfg.q_lora_rank or None
    ran["linear_attn_config"] = {
        **config["linear_attn_config"],
        **{key: getattr(cfg, attr)
           for key, attr in PUBLISHED_LINEAR.items()}}
    if not params.get("overrides"):
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")
        if cfg.routed_experts != config["published"]["num_experts"]:
            raise ValueError(
                f"the router scores {cfg.routed_experts} experts, the "
                f"configuration file publishes "
                f"{config['published']['num_experts']}")
    ran["layer_types"] = list(cfg.layer_types)
    bias_rate = config["bias_update_rate"]

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        # one chunk of the rule: the shortest sequence a kda layer takes
        made = init_model.init(
            k_params, jnp.zeros((1, cfg.kda_chunk), jnp.int32))
        variables = {"params": made["params"],
                     "moe_state": made["moe_state"]}
        # rows of seq + 1 tokens: position i predicts token i + 1
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)
        return (variables, tx.init(made["params"]), made["moe_stats"],
                tokens)

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), replicated(mesh),
        sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def token_losses(logits, toks):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:])

    def local_step(variables, opt_state, stats, toks):
        def loss_of(p):
            logits, new = model.apply(
                {**variables, "params": p, "moe_stats": stats},
                toks[:, :-1], mutable=["moe_stats"])
            return token_losses(logits, toks).mean(), new["moe_stats"]

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
        moe_state = rebalanced(variables["moe_state"], stats, bias_rate,
                               axis_name=hvd.DP_AXIS)
        return ({"params": p, "moe_state": moe_state}, opt_state, stats,
                loss)

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))

    def program_loss(variables, b):
        """The step's loss again, keeping each label's term."""
        toks = b["tokens"]
        losses = token_losses(model.apply(variables, toks[:, :-1]), toks)
        return losses.mean(), -losses

    def sample(n):
        """``n`` fresh sequences, not the batch the window trained on."""
        return {"tokens": jax.random.randint(
            jax.random.fold_in(seed_key(seed), FRESH), (n, seq + 1), 0,
            cfg.vocab_size, jnp.int32)}

    # the names the readers that are there read their sizes by
    ran.update(seq_len=seq, global_batch=batch,
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               num_experts_per_tok=cfg.routed_top_k,
               kda_num_heads=cfg.kda_heads, kda_head_dim=cfg.kda_head_dim,
               attention=cfg.attention_impl)
    if cfg.attention_impl == "flash":
        # the latent layers' one call, as the kernels plan it: the plan's
        # own record says whether the forward holds a kv row resident
        # (no gauge publishes that)
        row = params["per_chip_batch"], seq
        keys = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        shape = lambda width: jax.ShapeDtypeStruct(
            (*row, cfg.num_heads, width), cfg.dtype)
        plan = flash_plan(shape(keys), shape(keys), shape(cfg.v_head_dim),
                          causal=True, block_q=cfg.flash_block_q,
                          block_k=cfg.flash_block_k, window=None)
        ran["flash_fwd_kv_resident"] = {
            "attention": bool(plan.fwd_kv_resident)}

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and what
        the model counted when the step was traced (the runner frees what
        this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        gauge = lambda name, **labels: registry.gauge(name, **labels).value
        # a latent layer's call carries the label of a plain one
        ran["flash_tiles"] = {"attention": {
            name: gauge(f"flash.tiles_{name}", layer_type="attention")
            for name in ("live", "grid")}}
        ran["flash_bwd_kernels"] = {
            "attention": gauge("flash.bwd_kernels", layer_type="attention")}
        ran["kda"] = {name: gauge(f"kda.{name}")
                      for name in ("layers", "chunk", "kept_mib")}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
