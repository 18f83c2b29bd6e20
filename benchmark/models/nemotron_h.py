"""The NVIDIA-Nemotron-3-Nano-30B-A3B training step (family
``nemotron_h``: layers of ONE half each, ``hybrid_override_pattern``
naming them: a Mamba-2 mixer whose B, C and gated norm come in 8 groups,
grouped-query attention that rotates nothing, or routed experts WITHOUT a
gate matrix, ``W_down relu(W_up x)^2``, behind a sigmoid router with a
selection bias beside a shared expert of its own width; an untied head),
written as a user of horovod_tpu writes it: ``hvd.init`` -> model from
the zoo -> ``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit``
step over ``hvd.mesh("flat")`` with donated state, as
``benchmark/models/kimi_linear.py`` builds Kimi Linear.  The zoo's named
configuration holds the published values; this builder overrides only
the cut the configuration file states (depth with its pattern, the
experts held, the vocabulary).

The state the step carries is three trees: the variables the mathematics
reads (``params`` and ``moe_state``, each expert layer's selection bias:
no gradient, no AdamW moments; after every step the aux-free balancing
update moves it by ``bias_update_rate`` against the load,
``parallel/moe.py:rebalanced``), the optimizer's state, ``moe_stats``
(each expert layer's rows per held expert, rows dropped and slots per
routed expert, of the last step), which ``variables`` reads from the
final carry into ``ran["moe_counters"]``; beside them it leaves what the
model counted while the step was traced: ``ran["flash_tiles"]`` and
``ran["flash_bwd_kernels"]`` of the attention layer's call (gauges
``flash.tiles_live`` / ``flash.tiles_grid`` / ``flash.bwd_kernels``) and
``ran["ssd"]`` (gauges ``ssd.groups``, ``ssd.chunk`` and
``ssd.kept_mib``).
"""

from __future__ import annotations

from benchmark.harness import ssd_flops, ungated_expert_flops, window_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "mamba_num_heads": "ssm_heads", "mamba_head_dim": "ssm_head_dim",
    "ssm_state_size": "ssm_state", "n_groups": "ssm_groups",
    "conv_kernel": "ssm_conv", "chunk_size": "ssm_chunk",
    "layer_norm_epsilon": "norm_eps",
    "intermediate_size": "ffn_width",
    "moe_intermediate_size": "routed_width",
    "moe_shared_expert_intermediate_size": "shared_ffn_width",
    "num_experts_per_tok": "routed_top_k",
    "routed_scaling_factor": "routed_scaling",
    "n_shared_experts": "shared_experts",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "n_routed_experts": "held_experts",
    "first_held_expert": "routed_first_held",
}
# hybrid_override_pattern's letters -> the program's layer types
LAYER_TYPES = {"M": "mamba", "*": "full_attention", "E": "feed_forward"}


def layer_types(config: dict) -> list:
    """The program's layer types from ``hybrid_override_pattern``."""
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            LAYER_TYPES):
        raise ValueError(
            f"hybrid_override_pattern {pattern!r} has to name each of the "
            f"{config['num_hidden_layers']} layers M, * or E")
    return [LAYER_TYPES[letter] for letter in pattern]


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; a Mamba-2 layer's two
    projections and the scan's four products as ``harness/ssd_flops.py``
    counts them; the attention layer's two projections and its scores
    and values over the keys a token sees on average, the causal half;
    an expert layer's router whole, a routed expert at the share of a
    token it is expected to see, ``experts a token x held / routed``, and
    the shared expert whole, two matrices each as
    ``harness/ungated_expert_flops.py`` counts them), the head (the
    lookup is no matmul); backward twice the forward; recomputation not
    counted."""
    c = {**config, **ran}
    d, seq = c["hidden_size"], ran["seq_len"]
    heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    groups, n = c["n_groups"], c["ssm_state_size"]
    inner = heads * p
    q_dim = c["num_attention_heads"] * c["head_dim"]
    kv_dim = c["num_key_value_heads"] * c["head_dim"]
    expected = (c["num_experts_per_tok"] * c["n_routed_experts"]
                / ran["router_width"])
    per_row = ungated_expert_flops.expert_forward_macs_per_row
    layer = {
        "mamba": 2 * d * (2 * inner + 2 * groups * n + heads)
        + 2 * inner * d + 2 * ssd_flops.ssd_forward_macs_per_token(
            heads, p, groups, n, ran["ssd_chunk"]),
        "full_attention": 2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
        # QK^T and PV over the keys a query sees on average
        + 2 * 2 * q_dim * window_flops.visible_pairs(seq) / seq,
        "feed_forward": 2 * d * ran["router_width"]
        + 2 * expected * per_row(d, c["moe_intermediate_size"])
        + 2 * c["n_shared_experts"] * per_row(
            d, c["moe_shared_expert_intermediate_size"]),
    }
    forward = 2 * d * c["vocab_size"] + sum(
        layer[kind] for kind in layer_types(c))
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Damaged copies the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing (its shared expert still does).
    ``state_forgets``: every ``A_log`` raised by 10, so ``exp(dt A)`` is
    under ``exp(-22)`` at the smallest ``dt``: the state forgets within a
    token and ``y_t`` keeps only token ``t``'s own terms.
    ``mamba_identity``: the last Mamba layer's ``out_proj`` zero, so its
    mixer adds nothing.  What no damage of the variables can make is
    seeded into the reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    kinds = layer_types({**config, **ran})
    last = lambda kind: "block%d" % max(
        i for i, k in enumerate(kinds) if k == kind)

    def state_forgets(variables):
        blocks = {
            name: ({**blk, "A_log": blk["A_log"] + 10.0}
                   if "A_log" in blk else blk)
            for name, blk in variables["params"].items()}
        return {**variables, "params": blocks}

    return {"experts_silent": lambda v: zeroed(
                v, [("params", last("feed_forward"), "experts_fc2")]),
            "state_forgets": state_forgets,
            "mamba_identity": lambda v: zeroed(
                v, [("params", last("mamba"), "out_proj")])}


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
                     layer_types=tuple(layer_types(config)),
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
    letters = {kind: letter for letter, kind in LAYER_TYPES.items()}
    ran["hybrid_override_pattern"] = "".join(
        letters[kind] for kind in cfg.layer_types)
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
        # one chunk of tokens: the scan takes whole chunks only
        made = init_model.init(
            k_params, jnp.zeros((1, cfg.ssm_chunk), jnp.int32))
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
               mamba_n_heads=cfg.ssm_heads, mamba_d_head=cfg.ssm_head_dim,
               mamba_n_groups=cfg.ssm_groups, mamba_d_state=cfg.ssm_state,
               ssd_chunk=cfg.ssm_chunk, experts_gated=cfg.routed_gated,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and what
        the model counted when the step was traced (the runner frees what
        this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        gauge = lambda name, **labels: registry.gauge(name, **labels).value
        ran["flash_tiles"] = {"full_attention": {
            name: gauge(f"flash.tiles_{name}", layer_type="full_attention")
            for name in ("live", "grid")}}
        ran["flash_bwd_kernels"] = {"full_attention": gauge(
            "flash.bwd_kernels", layer_type="full_attention")}
        ran["ssd"] = {name: gauge(f"ssd.{name}")
                      for name in ("groups", "chunk", "kept_mib")}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
