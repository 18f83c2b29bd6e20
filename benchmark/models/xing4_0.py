"""The Xing4.0-29B-A4B training step (family ``xing4_0``: a residual
stream of four copies mixed by manifold-constrained hyper-connections
around latent attention whose rotary channels turn at YaRN's blended
frequencies, a dense gated feed-forward in the leading layer and routed
experts that drop nothing behind a sigmoid router with a selection bias
beside one shared expert in the others, an untied head), written as a
user of horovod_tpu writes it: ``hvd.init`` -> model from the zoo ->
``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state, as
``benchmark/models/glm4_moe_lite.py`` builds GLM.  The zoo's named
configuration holds the published values; this builder overrides only
the cut the configuration file states (depth, the leading dense layers,
the experts held, the vocabulary, the prediction module).

The state the step carries is three trees: the variables the mathematics
reads (``params`` and ``moe_state``, each expert layer's selection bias:
no gradient, no AdamW moments; after every step the aux-free balancing
update moves it by ``bias_update_rate`` against the load,
``parallel/moe.py:rebalanced``), the optimizer's state, and the last
step's counters (``moe_stats``: each expert layer's rows per held expert,
rows dropped and slots per routed expert; ``hc_stats``: how far each
sub-layer's mixing map was from doubly stochastic), which ``variables``
reads from the final carry into ``ran["moe_counters"]`` and
``ran["hyper_connections"]``; beside them it leaves what the model
counted while the step was traced (``ran["flash_tiles"]``,
``ran["flash_bwd_kernels"]``, the gauges ``hc.streams``,
``hc.sinkhorn_iters`` and ``hc.sublayers``).
"""

from __future__ import annotations

from benchmark.harness import moe_flops, window_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "intermediate_size": "ffn_width",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "moe_intermediate_size": "routed_width",
    "num_experts_per_tok": "routed_top_k",
    "routed_scaling_factor": "routed_scaling",
    "n_shared_experts": "shared_experts",
    "first_k_dense_replace": "dense_layers_first",
    "num_nextn_predict_layers": "mtp_modules",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "use_bias",
    "max_position_embeddings": "max_len",
    "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "n_routed_experts": "held_experts",
    "first_held_expert": "routed_first_held",
}


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations: latent attention's
    five projections, each sub-layer's hyper-connection projection of
    ``hc_mult x hidden`` onto ``hc_mult^2 + 2 hc_mult`` numbers), latent
    attention over the keys a token sees on average (the causal half,
    192 channels for the scores and 128 for the values), the dense
    feed-forward, a routed expert counted at the share of a token it is
    expected to see (``experts a token x held / routed``), the shared
    expert and the router whole, the head (the lookup is no matmul);
    backward twice the forward; recomputation not counted, nor the
    hyper-connections' elementwise mixing (a dozen operations a channel,
    bound by memory: ``hc_mix_roofline``)."""
    c = {**config, **ran}
    d, heads, n = c["hidden_size"], c["num_attention_heads"], c["hc_mult"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    seq = ran["seq_len"]
    attention = (
        2 * (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
             + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
             + c["kv_lora_rank"] * heads
             * (c["qk_nope_head_dim"] + c["v_head_dim"])
             + heads * c["v_head_dim"] * d)
        + 2 * heads * (qk + c["v_head_dim"])
        * window_flops.visible_pairs(seq) / seq)
    connections = 2 * 2 * n * d * (n * n + 2 * n)
    dense = 2 * 3 * d * c["intermediate_size"]
    expected = (c["num_experts_per_tok"] * c["n_routed_experts"]
                / ran["router_width"])
    routed = (2 * d * ran["router_width"]
              + (expected + c["n_shared_experts"]) * 2
              * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_intermediate_size"]))
    dense_layers = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense_layers
    forward = (c["num_hidden_layers"] * (attention + connections)
               + dense_layers * dense + expert_layers * routed
               + 2 * d * c["vocab_size"])
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Damaged copies the program must fail the checks with.
    ``experts_silent``: the last expert layer's held experts' down
    projections zero, so the routed part of that layer adds nothing (its
    shared expert still does).  ``rotary_key_zero``: every layer's
    columns of ``kv_a`` that make the shared rotary key zero, so no key
    carries a position.  ``mixing_uniform``: every sub-layer's gain
    on the mixing map's logits and that map's bias zero, so that every
    map is the uniform 1/4 (doubly stochastic still: what a
    hyper-connection that ignores its input and its training computes);
    the read and write maps stay as trained.
    What no damage of the variables can make is seeded into the
    reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    c = {**config, **ran}
    latent, n = c["kv_lora_rank"], c["hc_mult"]
    last = f"block{c['num_hidden_layers'] - 1}"

    def rotary_key_zero(variables):
        def walk(tree):
            if "kv_a" in tree:
                kernel = tree["kv_a"]["kernel"]
                return {**tree, "kv_a": {
                    "kernel": kernel.at[:, latent:].set(0.0)}}
            return {k: walk(v) if isinstance(v, dict) else v
                    for k, v in tree.items()}

        return {**variables, "params": walk(variables["params"])}

    def mixing_uniform(variables):
        def walk(tree):
            out = {}
            for key, leaf in tree.items():
                if isinstance(leaf, dict):
                    out[key] = walk(leaf)
                elif key in ("hc_attn_alpha", "hc_mlp_alpha"):
                    out[key] = leaf.at[2].set(0.0)
                elif key in ("hc_attn_b", "hc_mlp_b"):
                    out[key] = leaf.at[2 * n:].set(0.0)
                else:
                    out[key] = leaf
            return out

        return {**variables, "params": walk(variables["params"])}

    return {"experts_silent": lambda v: zeroed(
                v, [("params", last, "experts_fc2")]),
            "rotary_key_zero": rotary_key_zero,
            "mixing_uniform": mixing_uniform}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models import hyper_connections
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.parallel.moe import publish_stats, rebalanced

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=("mla",) * config["num_hidden_layers"],
                     dense_layers_first=config["first_k_dense_replace"],
                     routed_held=config["n_routed_experts"],
                     routed_first_held=config["first_held_expert"],
                     vocab_size=config["vocab_size"],
                     mtp_modules=config["num_nextn_predict_layers"],
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
    ran["rope_scaling"] = dict(cfg.rope_scaling)
    ran["mhc_h_res_clamp_min"], ran["mhc_h_res_clamp_max"] = cfg.hc_res_clamp
    if not params.get("overrides"):
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")
        if cfg.routed_experts != config["published"]["n_routed_experts"]:
            raise ValueError(
                f"the router scores {cfg.routed_experts} experts, the "
                f"configuration file publishes "
                f"{config['published']['n_routed_experts']}")
    ran["layer_types"] = list(cfg.layer_types)
    bias_rate = config["bias_update_rate"]

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        made = init_model.init(k_params, jnp.zeros((1, 8), jnp.int32))
        variables = {"params": made["params"],
                     "moe_state": made["moe_state"]}
        # rows of seq + 1 tokens: position i predicts token i + 1
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)
        stats = {"moe_stats": made["moe_stats"],
                 "hc_stats": made["hc_stats"]}
        return variables, tx.init(made["params"]), stats, tokens

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
                {**variables, "params": p, **stats}, toks[:, :-1],
                mutable=list(stats))
            return token_losses(logits, toks).mean(), dict(new)

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
        moe_state = rebalanced(variables["moe_state"], stats["moe_stats"],
                               bias_rate, axis_name=hvd.DP_AXIS)
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

    ran.update(seq_len=seq, global_batch=batch,
               router_width=cfg.routed_experts,
               stream_itemsize=jnp.dtype(cfg.dtype).itemsize,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the counters of the last step go
        from the carry into ``ran`` on the way, and what the model
        counted when the step was traced (the runner frees what this
        does not return)."""
        ran["moe_counters"] = publish_stats(state[2]["moe_stats"])
        registry = get_registry()
        gauge = lambda name, **labels: registry.gauge(name, **labels).value
        ran["hyper_connections"] = {
            **hyper_connections.publish_stats(state[2]["hc_stats"]),
            **{name: gauge(f"hc.{name}")
               for name in ("streams", "sinkhorn_iters", "sublayers")}}
        # a latent layer's call carries the label of a plain one
        ran["flash_tiles"] = {"attention": {
            name: gauge(f"flash.tiles_{name}", layer_type="attention")
            for name in ("live", "grid")}}
        ran["flash_bwd_kernels"] = {
            "attention": gauge("flash.bwd_kernels", layer_type="attention")}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
