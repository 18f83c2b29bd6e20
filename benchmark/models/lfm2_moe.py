"""The LFM2-24B-A2B training step (family ``lfm2_moe``: gated short
convolutions three to one with grouped-query attention layers, a norm
over each head of q and k, rotary positions in the attention layers, a
dense gated feed-forward in the leading layers and routed experts that
drop nothing behind a sigmoid router with a selection bias in the others,
no shared expert, a tied head), written as a user of horovod_tpu writes
it: ``hvd.init`` -> model from the zoo -> ``hvd.DistributedOptimizer`` ->
one ``shard_map`` + ``jit`` step over ``hvd.mesh("flat")`` with donated
state, as ``benchmark/models/afmoe.py`` builds Trinity.  The zoo's named
configuration holds the published values; this builder overrides only
the cut the configuration file states (depth, the leading dense layers,
the layers' types, the experts held, the vocabulary).

The state the step carries is three trees: the variables the mathematics
reads (``params`` and ``moe_state``, each expert layer's selection bias:
no gradient, no AdamW moments; after every step the aux-free balancing
update moves it by ``bias_update_rate`` against the load,
``parallel/moe.py:rebalanced``), the optimizer's state, ``moe_stats``
(each expert layer's rows per held expert, rows dropped and slots per
routed expert, of the last step), which ``variables`` reads from the
final carry into ``ran["moe_counters"]``; beside them it leaves what the
model counted while the step was traced: ``ran["flash_tiles"]``,
``ran["flash_bwd_kernels"]`` and ``ran["flash_fwd_kv_resident"]`` by layer
type (gauges ``flash.tiles_live`` / ``flash.tiles_grid`` /
``flash.bwd_kernels``; the third from the call's own ``FlashPlan``), and
``ran["short_conv"]`` (gauges ``short_conv.layers`` and
``short_conv.filter_bytes``).
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
    "conv_L_cache": "conv_taps", "norm_eps": "norm_eps",
    "moe_intermediate_size": "routed_width",
    "num_experts_per_tok": "routed_top_k",
    "routed_scaling_factor": "routed_scaling",
    "num_dense_layers": "dense_layers_first",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "held_experts", "first_held_expert": "routed_first_held",
}


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; a conv layer's
    ``in_proj`` and ``out_proj``, the filter's taps are no matmul; an
    attention layer's q, k, v and output projection), attention over the
    keys a token sees on average (the causal half:
    ``harness/window_flops.py:visible_pairs`` over the sequence), the
    dense feed-forward, a routed expert counted at the share of a token
    it is expected to see (``experts a token x held / routed``), the
    router whole, the tied head (the lookup is no matmul); backward twice
    the forward; recomputation not counted."""
    c = {**config, **ran}
    d, heads = c["hidden_size"], c["num_attention_heads"]
    hd = d // heads
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    seq = ran["seq_len"]
    mixer = {
        "conv": 2 * (d * 3 * d + d * d),
        "full_attention": 2 * (d * (q_dim + 2 * kv_dim) + q_dim * d)
        # QK^T and PV over the keys a query sees on average
        + 2 * 2 * q_dim * window_flops.visible_pairs(seq) / seq,
    }
    dense = 2 * 3 * d * c["intermediate_size"]
    expected = c["num_experts_per_tok"] * c["num_experts"] / ran["router_width"]
    routed = (2 * d * ran["router_width"]
              + expected * 2 * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_intermediate_size"]))
    forward = 2 * d * c["vocab_size"]
    for i, kind in enumerate(c["layer_types"]):
        forward += mixer[kind] + (
            dense if i < c["num_dense_layers"] else routed)
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Damaged copies the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing and the layer is its
    convolution's alone (one expert layer of four, as Trinity's probe).
    ``filter_past_zero``: every conv layer's taps but the current one
    zero, so each filter forgets the two tokens before.  What no damage
    of the variables can make (a filter that is not causal, a gate
    dropped, the head norms or the rotation left out, the bias in the
    weights, the weights not normalised) is seeded into the reference:
    its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    kinds = ({**config, **ran})["layer_types"]
    convs = [f"block{i}" for i, kind in enumerate(kinds) if kind == "conv"]

    def filter_past_zero(variables):
        params = dict(variables["params"])
        for b in convs:
            taps = params[b]["conv_kernel"]
            params[b] = {**params[b],
                         "conv_kernel": taps.at[:-1].set(0.0)}
        return {**variables, "params": params}

    return {"experts_silent": lambda v: zeroed(
                v, [("params", f"block{len(kinds) - 1}", "experts_fc2")]),
            "filter_past_zero": filter_past_zero}


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
                     dense_layers_first=config["num_dense_layers"],
                     layer_types=tuple(config["layer_types"]),
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
    ran["layer_types"] = list(cfg.layer_types)
    ran["rope_parameters"] = {**config["rope_parameters"],
                              "rope_theta": cfg.rope_theta}
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
               head_dim=cfg.head_dim,
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               attention=cfg.attention_impl)
    if cfg.attention_impl == "flash":
        # the attention layers' one call, as the kernels plan it: the
        # plan's own record says whether the forward holds a kv row
        # resident (no gauge publishes that)
        row = params["per_chip_batch"], seq
        plan = flash_plan(
            jax.ShapeDtypeStruct((*row, cfg.num_heads, cfg.head_dim),
                                 cfg.dtype),
            *(jax.ShapeDtypeStruct((*row, cfg.kv_heads, cfg.head_dim),
                                   cfg.dtype),) * 2,
            causal=True, block_q=cfg.flash_block_q,
            block_k=cfg.flash_block_k, window=None)
        ran["flash_fwd_kv_resident"] = {
            "full_attention": bool(plan.fwd_kv_resident)}

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and what
        the model counted when the step was traced (the runner frees what
        this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        attends = sorted(set(cfg.layer_types) - {"conv"})
        ran["flash_tiles"] = {
            kind: {name: registry.gauge(f"flash.tiles_{name}",
                                        layer_type=kind).value
                   for name in ("live", "grid")} for kind in attends}
        ran["flash_bwd_kernels"] = {
            kind: registry.gauge("flash.bwd_kernels", layer_type=kind).value
            for kind in attends}
        ran["short_conv"] = {
            "layers": registry.gauge("short_conv.layers").value,
            "filter_bytes": registry.gauge("short_conv.filter_bytes").value}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
