"""The GLM-4.7-Flash training step (family ``glm4_moe_lite``: latent
attention, routed experts that drop nothing beside a shared expert, one
multi-token-prediction module), written as a user of horovod_tpu writes
it: ``hvd.init`` -> model from the zoo -> ``hvd.DistributedOptimizer`` ->
one ``shard_map`` + ``jit`` step over ``hvd.mesh("flat")`` with donated
state, as ``benchmark/models/granite_hybrid.py`` builds granite.  The
zoo's named configuration holds the published values; this builder
overrides only the cut the configuration file states (depth, the experts
held, the vocabulary).

The state the step carries is three trees: the variables the mathematics
reads (``params`` and ``moe_state``, each expert layer's selection bias:
no gradient, no AdamW moments; after every step the aux-free balancing
update the configuration names, ``topk_method: noaux_tc``, moves it by
``bias_update_rate`` against the load, ``parallel/moe.py:rebalanced``),
the optimizer's state, ``moe_stats`` (each expert layer's rows per held
expert, rows dropped and slots per routed expert, of the last step),
which ``variables`` reads from the final carry into
``ran["moe_counters"]``.
"""

from __future__ import annotations

from benchmark.harness import moe_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
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
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "n_routed_experts": "held_experts",
    "first_held_expert": "routed_first_held",
}


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations), latent attention's
    causal half, a routed expert counted at the share of a token it is
    expected to see (``experts a token x held / routed``: the program
    computes the rows routed, not rows x experts held), the shared expert
    and the router whole, the prediction module's projection and block,
    the head twice (the lookups are no matmul); backward twice the
    forward; recomputation not counted."""
    c = {**config, **ran}
    d, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    attention = (
        2 * (d * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
             + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
             + c["kv_lora_rank"] * heads
             * (c["qk_nope_head_dim"] + c["v_head_dim"])
             + heads * c["v_head_dim"] * d)
        # QK^T and PV over (seq_len + 1) / 2 keys on average
        + 2 * heads * (qk + c["v_head_dim"]) * (ran["seq_len"] + 1) / 2)
    dense = 2 * 3 * d * c["intermediate_size"]
    expected = (c["num_experts_per_tok"] * c["n_routed_experts"]
                / ran["router_width"])
    one_expert = 2 * moe_flops.expert_forward_macs_per_row(
        d, c["moe_intermediate_size"])
    routed = (2 * d * ran["router_width"]
              + (expected + c["n_shared_experts"]) * one_expert)
    dense_layers = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense_layers
    modules = c["num_nextn_predict_layers"]
    forward = (dense_layers * (attention + dense)
               + (expert_layers + modules) * (attention + routed)
               + modules * 2 * 2 * d * d
               + (1 + modules) * 2 * d * c["vocab_size"])
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Three damaged copies the program must fail the checks with.
    ``experts_silent``: the last expert layer's held experts' down
    projections zero, so the routed part of that layer adds nothing (its
    shared expert still does).  ``rotary_key_zero``: every layer's columns
    of ``kv_a`` that make the shared rotary key zero, so no key carries a
    position.  ``eh_proj_zero``: the prediction module's projection zero,
    so the module sees neither the stream nor the next token."""
    from benchmark.harness.correct import zeroed

    latent = ({**config, **ran})["kv_lora_rank"]

    def rotary_key_zero(variables):
        def walk(tree):
            if "kv_a" in tree:
                kernel = tree["kv_a"]["kernel"]
                return {**tree, "kv_a": {
                    "kernel": kernel.at[:, latent:].set(0.0)}}
            return {k: walk(v) if isinstance(v, dict) else v
                    for k, v in tree.items()}

        return {**variables, "params": walk(variables["params"])}

    last = f"block{ran['num_hidden_layers'] - 1}"
    return {"experts_silent": lambda v: zeroed(
                v, [("params", last, "experts_fc2")]),
            "rotary_key_zero": rotary_key_zero,
            "eh_proj_zero": lambda v: zeroed(
                v, [("params", "mtp", "eh_proj")])}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.parallel.moe import publish_stats, rebalanced

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=("mla",) * config["num_hidden_layers"],
                     routed_held=config["n_routed_experts"],
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
    ran["intermediate_size"] = cfg.mlp_ratio * cfg.emb_dim
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
    weight = config["mtp_loss_weight"]
    bias_rate = config["bias_update_rate"]

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        made = init_model.init(k_params, jnp.zeros((1, 8), jnp.int32))
        variables = {"params": made["params"],
                     "moe_state": made["moe_state"]}
        # rows of seq + 2 tokens: position i predicts token i + 1 and,
        # through the prediction module, token i + 2
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 2), 0, cfg.vocab_size, jnp.int32)
        return (variables, tx.init(made["params"]), made["moe_stats"],
                tokens)

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), replicated(mesh),
        sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def nll(logits, labels):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)

    def forward(variables, toks, **kwargs):
        return model.apply(variables, toks[:, :-2],
                           next_tokens=toks[:, 1:-1], **kwargs)

    def token_losses(logits, mtp_logits, toks):
        """Each position's two terms, [n, 2 seq]: the next token's, then
        the token after next's."""
        return jnp.concatenate([nll(logits, toks[:, 1:-1]),
                                nll(mtp_logits, toks[:, 2:])], axis=-1)

    def total(both):
        main, mtp = jnp.split(both, 2, axis=-1)
        return main.mean() + weight * mtp.mean()

    def local_step(variables, opt_state, stats, toks):
        def loss_of(p):
            out, new = forward(
                {**variables, "params": p, "moe_stats": stats}, toks,
                mutable=["moe_stats"])
            return total(token_losses(*out, toks)), new["moe_stats"]

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
        both = token_losses(*forward(variables, b["tokens"]), b["tokens"])
        return total(both), -both

    def sample(n):
        """``n`` fresh sequences, not the batch the window trained on."""
        return {"tokens": jax.random.randint(
            jax.random.fold_in(seed_key(seed), FRESH), (n, seq + 2), 0,
            cfg.vocab_size, jnp.int32)}

    ran.update(seq_len=seq, global_batch=batch,
               router_width=cfg.routed_experts,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way (the
        runner frees what this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
