"""The Trinity-Mini training step (family ``afmoe``: sliding-window and
full attention layers mixed, rotary positions in the window layers only,
a norm over each head of q and k, a sigmoid output gate, four norms a
block, routed experts that drop nothing beside a shared expert), written
as a user of horovod_tpu writes it: ``hvd.init`` -> model from the zoo ->
``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state, as
``benchmark/models/glm4_moe_lite.py`` builds GLM.  The zoo's named
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
final carry into ``ran["moe_counters"]``; beside them it leaves under
``ran["flash_tiles"]`` what the attention calls counted while the step
was traced (gauges ``flash.tiles_live`` / ``flash.tiles_grid`` by layer
type).
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
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "sliding_window": "attention_window", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "moe_intermediate_size": "routed_width",
    "num_experts_per_tok": "routed_top_k", "route_scale": "routed_scaling",
    "num_shared_experts": "shared_experts",
    "num_dense_layers": "dense_layers_first",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "held_experts", "first_held_expert": "routed_first_held",
}


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; q, k, v, the gate and
    the output projection), attention over the keys a token sees on
    average (a window layer's band counted as a band:
    ``harness/window_flops.py:visible_pairs`` over the sequence; a full
    layer's causal half), a routed expert counted at the share of a token
    it is expected to see (``experts a token x held / routed``), the
    shared expert and the router whole, the head (the lookup is no
    matmul); backward twice the forward; recomputation not counted."""
    c = {**config, **ran}
    d, heads, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    seq = ran["seq_len"]
    projections = 2 * (d * (q_dim + 2 * kv_dim) + d * q_dim + q_dim * d)

    def scores(kind):
        window = c["sliding_window"] if kind == "sliding_attention" else None
        # QK^T and PV over the keys a query sees on average
        return 2 * 2 * q_dim * window_flops.visible_pairs(seq, window) / seq

    dense = 2 * 3 * d * c["intermediate_size"]
    expected = c["num_experts_per_tok"] * c["num_experts"] / ran["router_width"]
    one_expert = 2 * moe_flops.expert_forward_macs_per_row(
        d, c["moe_intermediate_size"])
    routed = (2 * d * ran["router_width"]
              + (expected + c["num_shared_experts"]) * one_expert)
    forward = 2 * d * c["vocab_size"]
    for i, kind in enumerate(c["layer_types"]):
        forward += projections + scores(kind) + (
            dense if i < c["num_dense_layers"] else routed)
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Three damaged copies the program must fail the checks with.
    ``gate_zero``: every layer's ``gate`` matrix zero, so each gate reads
    0.5 whatever the token.  ``experts_silent``: the last expert layer's
    held experts' down projections zero, so the routed part of that layer
    adds nothing (its shared expert still does).  ``k_norm_zero``: the
    full-attention layers' key-norm scale zero, so their scores are flat
    and every query averages the values it sees."""
    from benchmark.harness.correct import zeroed

    kinds = ({**config, **ran})["layer_types"]
    blocks = [f"block{i}" for i in range(len(kinds))]
    full = [b for b, kind in zip(blocks, kinds) if kind == "full_attention"]
    return {"gate_zero": lambda v: zeroed(
                v, [("params", b, "gate") for b in blocks]),
            "experts_silent": lambda v: zeroed(
                v, [("params", blocks[-1], "experts_fc2")]),
            "k_norm_zero": lambda v: zeroed(
                v, [("params", b, "k_norm") for b in full])}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
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
    ran["intermediate_size"] = cfg.mlp_ratio * cfg.emb_dim
    ran["layer_types"] = list(cfg.layer_types)
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

    # the names the MoE readers that are there read their sizes by
    ran.update(seq_len=seq, global_batch=batch,
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and the
        tiles the attention calls counted when the step was traced (the
        runner frees what this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        ran["flash_tiles"] = {
            kind: {name: registry.gauge(f"flash.tiles_{name}",
                                        layer_type=kind).value
                   for name in ("live", "grid")}
            for kind in sorted(set(cfg.layer_types))}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
