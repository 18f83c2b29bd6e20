"""The SDAR-30B-A3B training step (family ``sdar_moe``: block-diffusion
training of a grouped-query transformer, 32 query heads over 4 key/value
heads of 128 with a norm over each head of q and k and rotary positions,
whose every layer holds routed experts that drop nothing behind a router
on the normed feed-forward input, weights a softmax over the chosen
logits with no selection bias, a silu gate, no shared expert, a
load-balance loss), written as a user of horovod_tpu writes it:
``hvd.init`` -> model from the zoo -> ``hvd.DistributedOptimizer`` -> one
``shard_map`` + ``jit`` step over ``hvd.mesh("flat")`` with donated
state, as ``benchmark/models/smallthinker.py`` builds SmallThinker.  The
zoo's named configuration holds the published values; this builder
overrides only the cut the configuration file states (depth, the experts
held, the vocabulary) and the two sizes the source does not publish, the
block length and the balance loss's coefficient; and it draws the token
table at the scale the configuration file states
(``embedding_init_std``; the source gives no ``initializer_range``): at
flax's ``hidden ** -0.5`` what attention and the experts write into the
stream outweighs the token's own row forty times, every row's router
reads much the same vector, and the rows move between experts together
(PERF.md section 6).

What differs from every other family is the objective
(``horovod_tpu/models/block_diffusion.py``): the batch is ``seq_len``
clean tokens a sequence; each step draws its noise on the device from a
key in the carry (a level a block, the masked positions), lays the
noised copy beside the clean one as ONE sequence of ``2 * seq_len`` rows
at repeated positions, runs the model under the block-diffusion mask,
and takes the masked positions' cross-entropy against their own tokens,
weighted by ``1 / t``, plus ``balance_loss_coef`` times the expert
layers' load-balance losses.  An item is one data token.

The state the step carries is four trees: the variables the mathematics
reads (``params``), the optimizer's state, ``moe_stats`` (each expert
layer's counters of the last step) and ``noise`` (the key, and the last
step's count of masked tokens); ``variables`` reads the last two from the
final carry into ``ran["moe_counters"]`` and ``ran["block_diffusion"]``,
beside what the attention calls counted while the step was traced
(``ran["flash_tiles"]``, ``ran["flash_bwd_kernels"]``).  The check's
sample carries a fixed draw, so that program and reference see the same
``x_t``.
"""

from __future__ import annotations

from benchmark.harness import block_diffusion_flops, moe_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "moe_intermediate_size": "routed_width",
    "num_experts_per_tok": "routed_top_k",
    "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "held_experts",
    "first_held_expert": "routed_first_held",
    "balance_loss_coef": "routed_balance_coef",
    "block_length": "block_diffusion",
}
NOISE_KEY = 0xB10C  # folded into the seed's key for the step's noise


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one DATA token of a training step requires.  Both of
    its rows (the noised and the clean one) go through every block: the
    matmuls of q, k, v and the output projection, the router whole, a
    silu-gated routed expert counted at the share of a row it is
    expected to see (``experts a token x held / routed``); attention
    over the pairs the block-diffusion mask shows
    (``harness/block_diffusion_flops.py:visible_pairs``: both copies'
    rows, QK^T and PV); the head on the noised row alone (the lookup is
    no matmul); backward twice the forward; recomputation not counted."""
    c = {**config, **ran}
    d, heads, hd = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    seq = ran["seq_len"]
    projections = 2 * (d * (q_dim + 2 * kv_dim) + q_dim * d)
    scores = 2 * 2 * q_dim * block_diffusion_flops.visible_pairs(
        seq, c["block_length"]) / seq
    expected = (c["num_experts_per_tok"] * c["num_experts"]
                / ran["router_width"])
    routed = (2 * d * ran["router_width"]
              + expected * 2 * moe_flops.expert_forward_macs_per_row(
                  d, c["moe_intermediate_size"]))
    forward = (2 * d * c["vocab_size"] + c["num_hidden_layers"] * (
        2 * (projections + routed) + scores))
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """A damaged copy the program must fail the checks with.
    ``experts_silent``: the LAST layer's held experts' down projections
    zero, so its routed part adds nothing (one layer of the cut, as
    SmallThinker's probe).  What no damage of the variables can make,
    another mask, other positions, labels or weights, is seeded into the
    reference: its ``DEPARTURES``."""
    from benchmark.harness.correct import zeroed

    last = ({**config, **ran})["num_hidden_layers"] - 1
    return {"experts_silent": lambda v: zeroed(
        v, [("params", f"block{last}", "experts_fc2")])}


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
            f"{size!r} (block-diffusion training came with it)")
    from horovod_tpu.models import block_diffusion as bd
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.parallel.moe import publish_stats

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    overrides = dict(num_layers=config["num_hidden_layers"],
                     routed_held=config["num_experts"],
                     routed_first_held=config["first_held_expert"],
                     vocab_size=config["vocab_size"],
                     routed_balance_coef=config["balance_loss_coef"],
                     block_diffusion=params["block_length"],
                     remat=bool(params.get("remat", False)))
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        overrides.update(params["overrides"])
    model = gpt(size, attention_impl=params.get("attention", "flash"),
                **overrides)
    # The same variables without a kernel: initialising through it keeps
    # the Pallas calls out of the init program.
    init_model = gpt(size, attention_impl="reference", **overrides)
    cfg = model.cfg
    block = cfg.block_diffusion
    # the vocabulary slice's last row is the mask token's; data ids are
    # drawn from the rows before it
    mask_token = cfg.vocab_size - 1
    ran = {key: getattr(cfg, attr) for key, attr in PUBLISHED.items()}
    ran["mask_token_id"] = mask_token
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
    t_min = params["noise"]["t_min"]

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
        made = init_model.init(k_params, jnp.zeros((1, 2 * block), jnp.int32))
        made = {**made, "params": scaled_embedding(made["params"])}
        tokens = jax.random.randint(
            k_tokens, (batch, seq), 0, mask_token, jnp.int32)
        noise = {"key": jax.random.fold_in(key, NOISE_KEY),
                 "masked_tokens": jnp.zeros((), jnp.int32)}
        return ({"params": made["params"]}, tx.init(made["params"]),
                made["moe_stats"], noise, tokens)

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), replicated(mesh),
        replicated(mesh), sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def balance(sown):
        """The expert layers' load-balance losses, summed, times the
        coefficient (each chip's own rows; the gradient all-reduce
        averages the chips')."""
        return coef * sum(jax.tree.leaves(sown["losses"]))

    def local_step(variables, opt_state, stats, noise, toks):
        # a fresh draw a step, each chip its own
        key, drawn = jax.random.split(noise["key"])
        drawn = jax.random.fold_in(drawn, jax.lax.axis_index(hvd.DP_AXIS))
        level, pair, positions = bd.noised_inputs(drawn, toks, block,
                                                  mask_token, t_min)

        def loss_of(p):
            logits, new = model.apply(
                {"params": p, "moe_stats": stats}, pair,
                positions=positions, mutable=["moe_stats", "losses"])
            return (bd.loss(bd.label_logprobs(logits, toks), level)
                    + balance(new), new["moe_stats"])

        p = variables["params"]
        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(p)
        updates, opt_state = tx.update(grads, opt_state, p)
        # out_specs P() presents the loss as replicated, so it has to be
        # the global mean.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        masked = jax.lax.psum(level.masked.sum().astype(jnp.int32),
                              hvd.DP_AXIS)
        # under the scope ``DistributedOptimizer`` gives the update
        # itself, so that ``optimizer_ms`` finds both (gpt2.py says why)
        with jax.named_scope(OPTIMIZER_SCOPE):
            p = optax.apply_updates(p, updates)
        return ({"params": p}, opt_state, stats,
                {"key": key, "masked_tokens": masked}, loss)

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P(), P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2, 3))

    def program_loss(variables, b):
        """The step's loss on a given draw, keeping each masked
        position's log-probability of its own token."""
        toks = b["tokens"]
        level = bd.Noise(b["masked"], b["t"])
        pair, positions = bd.paired(toks, level, mask_token)
        logits, sown = model.apply(variables, pair, positions=positions,
                                   mutable=["losses"])
        picked = bd.label_logprobs(logits, toks)
        return (bd.loss(picked, level) + balance(sown),
                jnp.where(level.masked, picked, 0.0))

    def sample(n):
        """``n`` fresh sequences, not the batch the window trained on,
        and one fixed draw of the noise for them."""
        k_tokens, k_noise = jax.random.split(
            jax.random.fold_in(seed_key(seed), FRESH))
        level = bd.draw(k_noise, n, seq, block, t_min)
        return {"tokens": jax.random.randint(
            k_tokens, (n, seq), 0, mask_token, jnp.int32),
            "masked": level.masked, "t": level.t}

    # under the names the readers that are there read their sizes by,
    # whatever this config.json calls them
    layer_types = [cfg.layer_type(i) for i in range(cfg.num_layers)]
    ran.update(seq_len=seq, global_batch=batch, rows=2 * seq,
               layer_types=layer_types,
               moe_intermediate_size=cfg.routed_width,
               num_experts_per_tok=cfg.routed_top_k,
               router_width=cfg.routed_experts,
               n_routed_experts=cfg.held_experts,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; the expert layers' counters and
        the masked tokens of the last step go from the carry into ``ran``
        on the way, and what the attention calls counted when the step
        was traced (the runner frees what this does not return)."""
        ran["moe_counters"] = publish_stats(state[2])
        registry = get_registry()
        kinds = sorted(set(layer_types))
        by_kind = lambda name: {
            kind: registry.gauge(name, layer_type=kind).value
            for kind in kinds}
        ran["flash_tiles"] = {
            kind: {name: registry.gauge(f"flash.tiles_{name}",
                                        layer_type=kind).value
                   for name in ("live", "grid")} for kind in kinds}
        ran["flash_bwd_kernels"] = by_kind("flash.bwd_kernels")
        ran["block_diffusion"] = {
            "block": registry.gauge("bd.block").value,
            "rows": registry.gauge("bd.rows").value,
            "visible_pairs": by_kind("bd.visible_pairs"),
            "live_tile_pairs": by_kind("bd.live_tile_pairs"),
            "masked_tokens": bd.publish_masked(state[3]["masked_tokens"]),
        }
        return state[0]

    return Built(
        step=step, state=state, carry_len=4,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
