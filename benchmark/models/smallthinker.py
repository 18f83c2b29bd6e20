"""The SmallThinker-21BA3B training step (family ``smallthinker``: full
attention layers without positions and sliding-window layers with rotary
positions mixed 1:3, grouped heads 7 to a key/value head, routed experts
that drop nothing behind a router that reads the LAYER'S INPUT ahead of
attention, weights a softmax over the chosen logits with no selection
bias, a ReLU gate, no shared expert, a load-balance loss), written as a
user of horovod_tpu writes it: ``hvd.init`` -> model from the zoo ->
``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state, as ``benchmark/models/afmoe.py``
builds Trinity.  The zoo's named configuration holds the published
values; this builder overrides only the cut the configuration file
states (depth, the layers' types, the experts held, the vocabulary) and
the balance loss's coefficient, which the source does not publish; and
it draws the token table at the scale the configuration file states
(``embedding_init_std``; the source gives no ``initializer_range``): the
router reads the stream un-normed, so the table's scale is the scale of
the router's logits, and at flax's ``hidden ** -0.5`` the routing of one
seed was not the routing of the next (PERF.md section 6).

There is no selection bias, so no ``moe_state`` and no ``rebalanced``
call: the step's loss is the cross-entropy plus ``balance_loss_coef``
times the sum of the expert layers' load-balance losses (collection
``losses``, sown by the model).  The state the step carries is three
trees: the variables the mathematics reads (``params``), the optimizer's
state, ``moe_stats`` (each expert layer's rows per held expert, rows
dropped, slots per routed expert and balance loss, of the last step),
which ``variables`` reads from the final carry into
``ran["moe_counters"]``; beside them it leaves under
``ran["flash_tiles"]`` and ``ran["flash_bwd_kernels"]`` what the attention
calls counted while the step was traced (gauges ``flash.tiles_live`` /
``flash.tiles_grid`` / ``flash.bwd_kernels`` by layer type).
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
    "sliding_window_size": "attention_window", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "moe_ffn_hidden_size": "routed_width",
    "moe_num_active_primary_experts": "routed_top_k",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "moe_num_primary_experts": "held_experts",
    "first_held_expert": "routed_first_held",
    "balance_loss_coef": "routed_balance_coef",
}
# sliding_window_layout / rope_layout: 1 = a window layer that rotates
LAYER_TYPES = {0: "full_attention", 1: "sliding_attention"}


def layer_types(config: dict) -> list:
    """The program's layer types from the two published layouts, which
    have to agree: a layer either sees every earlier key and no positions
    or its last ``sliding_window_size`` keys and rotates."""
    if config["rope_layout"] != config["sliding_window_layout"]:
        raise ValueError(
            "the program rotates exactly the window layers: rope_layout "
            f"{config['rope_layout']} and sliding_window_layout "
            f"{config['sliding_window_layout']} differ")
    return [LAYER_TYPES[flag] for flag in config["sliding_window_layout"]]


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations; q, k, v and the output
    projection), attention over the keys a token sees on average (a
    window layer's band counted as a band:
    ``harness/window_flops.py:visible_pairs`` over the sequence; a full
    layer's causal half), a ReLU-gated routed expert counted at the share
    of a token it is expected to see (``experts a token x held /
    routed``; the ReLU and the product are no matmuls), the router whole,
    the head (the lookup is no matmul); backward twice the forward;
    recomputation not counted."""
    c = {**config, **ran}
    d, heads, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    seq = ran["seq_len"]
    projections = 2 * (d * (q_dim + 2 * kv_dim) + q_dim * d)

    def scores(kind):
        window = (c["sliding_window_size"] if kind == "sliding_attention"
                  else None)
        # QK^T and PV over the keys a query sees on average
        return 2 * 2 * q_dim * window_flops.visible_pairs(seq, window) / seq

    expected = (c["moe_num_active_primary_experts"]
                * c["moe_num_primary_experts"] / ran["router_width"])
    routed = (2 * d * ran["router_width"]
              + expected * 2 * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_ffn_hidden_size"]))
    forward = 2 * d * c["vocab_size"]
    for kind in c["layer_types"]:
        forward += projections + scores(kind) + routed
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """A damaged copy the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing and the layer is attention's
    alone (one layer of four, as Trinity's probe; the configuration file
    has its readings beside the sound ones).  What no damage of the
    variables can make, the router reading another tensor, another gate,
    another weight rule, the window, the positions, is seeded into the
    reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    last = len(({**config, **ran})["layer_types"]) - 1
    return {"experts_silent": lambda v: zeroed(
        v, [("params", f"block{last}", "experts_fc2")])}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.parallel.moe import publish_stats

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=tuple(layer_types(config)),
                     routed_held=config["moe_num_primary_experts"],
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
    flags = [int(kind == "sliding_attention") for kind in cfg.layer_types]
    ran.update(sliding_window_layout=flags, rope_layout=flags)
    if not params.get("overrides"):
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")
        published = config["published"]["moe_num_primary_experts"]
        if cfg.routed_experts != published:
            raise ValueError(
                f"the router scores {cfg.routed_experts} experts, the "
                f"configuration file publishes {published}")
    coef = cfg.routed_balance_coef

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    emb_std = config["embedding_init_std"]

    def scaled_embedding(p):
        """The token table at standard deviation ``embedding_init_std`` a
        channel (flax draws it at ``hidden ** -0.5``): the router reads
        the stream un-normed, so the table's scale is the scale of its
        logits.  The configuration file's ``assumed`` says why."""
        table = p["wte"]["embedding"]
        return {**p, "wte": {
            "embedding": table * (emb_std * table.shape[-1] ** 0.5)}}

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        made = init_model.init(k_params, jnp.zeros((1, 8), jnp.int32))
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

    # under the names the readers that are there read their sizes by,
    # whatever this config.json calls them
    ran.update(seq_len=seq, global_batch=batch,
               layer_types=list(cfg.layer_types),
               sliding_window=cfg.attention_window,
               moe_intermediate_size=cfg.routed_width,
               num_experts_per_tok=cfg.routed_top_k,
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters of
        the last step go from the carry into ``ran`` on the way, and what
        the attention calls counted when the step was traced (the runner
        frees what this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        kinds = sorted(set(cfg.layer_types))
        ran["flash_tiles"] = {
            kind: {name: registry.gauge(f"flash.tiles_{name}",
                                        layer_type=kind).value
                   for name in ("live", "grid")} for kind in kinds}
        ran["flash_bwd_kernels"] = {
            kind: registry.gauge("flash.bwd_kernels", layer_type=kind).value
            for kind in kinds}
        return state[0]

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
