"""The hybrid Mamba-2 / grouped-query-attention training step (family
``granitemoehybrid`` without routed experts), written as a user of
horovod_tpu writes it: ``hvd.init`` -> model from the zoo ->
``hvd.DistributedOptimizer`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state, as ``benchmark/models/gpt2.py``
builds GPT-2.  The zoo's named configuration holds the published values;
this builder overrides only the cut the configuration file states (depth
with its ``layer_types``, the vocabulary) and the sequence length.
"""

from __future__ import annotations

from benchmark.harness import ssd_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "mamba_n_heads": "ssm_heads",
    "mamba_d_head": "ssm_head_dim", "mamba_d_state": "ssm_state",
    "mamba_n_groups": "ssm_groups", "mamba_d_conv": "ssm_conv",
    "mamba_chunk_size": "ssm_chunk", "rms_norm_eps": "norm_eps",
    "attention_multiplier": "attention_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "tie_word_embeddings": "tie_embeddings",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
}


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations), the scan's four
    products (``harness/ssd_flops.py``), the attention layers' causal
    half, the tied head once (the lookup is no matmul); backward twice
    the forward; recomputation not counted."""
    c = {**config, **ran}
    d, width = c["hidden_size"], c["intermediate_size"]
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    groups, n = c["mamba_n_groups"], c["mamba_d_state"]
    inner = heads * p
    ffn = 2 * (d * 2 * width + width * d)
    mamba = (2 * d * (2 * inner + 2 * groups * n + heads)      # in_proj
             + 2 * inner * d                                   # out_proj
             + 2 * ssd_flops.ssd_forward_macs_per_token(
                 heads, p, groups, n, ran["ssd_chunk"]))
    q_dim = d
    kv_dim = q_dim // c["num_attention_heads"] * c["num_key_value_heads"]
    attention = (2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
                 # QK^T and PV over (seq_len + 1) / 2 keys on average
                 + 2 * 2 * q_dim * (ran["seq_len"] + 1) / 2)
    kinds = list(c["layer_types"])
    forward = (kinds.count("mamba") * (mamba + ffn)
               + kinds.count("attention") * (attention + ffn)
               + 2 * d * c["vocab_size"])
    return 3.0 * forward


def fault_probes(config: dict, ran: dict) -> dict:
    """Two damaged copies the program must fail the checks with.
    ``mamba_identity``: the last Mamba layer's ``out_proj`` zero, so its
    mixer adds nothing.  ``state_forgets``: every ``A_log`` raised by 10,
    so ``exp(dt A)`` is under ``exp(-22)`` at the smallest ``dt``: the
    state forgets within a token and ``y_t`` keeps only token ``t``'s own
    terms, which shows the checks see the recurrence and not only
    ``D x``."""
    from benchmark.harness.correct import zeroed

    kinds = list(({**config, **ran})["layer_types"])
    last = f"block{len(kinds) - 1 - kinds[::-1].index('mamba')}"

    def state_forgets(variables):
        blocks = {
            name: ({**blk, "A_log": blk["A_log"] + 10.0}
                   if "A_log" in blk else blk)
            for name, blk in variables["params"].items()}
        return {**variables, "params": blocks}

    return {"mamba_identity": lambda v: zeroed(
                v, [("params", last, "out_proj")]),
            "state_forgets": state_forgets}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=tuple(config["layer_types"]),
                     vocab_size=config["vocab_size"],
                     remat=bool(params.get("remat", False)))
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        overrides.update(params["overrides"])
    model = gpt(size, attention_impl=params.get("attention", "flash"),
                **overrides)
    # The same parameter tree without the kernel: initialising through
    # it keeps the Pallas call out of the init program.
    init_model = gpt(size, attention_impl="reference", **overrides)
    cfg = model.cfg
    ran = {key: getattr(cfg, attr) for key, attr in PUBLISHED.items()}
    ran.update(layer_types=list(cfg.layer_types),
               intermediate_size=cfg.mlp_ratio * cfg.emb_dim)
    if not params.get("overrides"):
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        # one chunk of tokens: the scan takes whole chunks only
        p = init_model.init(k_params,
                            jnp.zeros((1, cfg.ssm_chunk), jnp.int32))
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)
        return p, tx.init(p), tokens

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def token_losses(p, toks):
        logits = model.apply(p, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:])

    def local_step(p, opt_state, toks):
        loss, grads = jax.value_and_grad(
            lambda p: token_losses(p, toks).mean())(p)
        updates, opt_state = tx.update(grads, opt_state, p)
        # out_specs P() presents the loss as replicated, so it has to be
        # the global mean.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        # under the scope ``DistributedOptimizer`` gives the update
        # itself, so that ``optimizer_ms`` finds both (gpt2.py says why)
        with jax.named_scope(OPTIMIZER_SCOPE):
            p = optax.apply_updates(p, updates)
        return p, opt_state, loss

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    def program_loss(p, b):
        """The step's loss again, keeping each token's term."""
        nll = token_losses(p, b["tokens"])
        return nll.mean(), -nll

    def sample(n):
        """``n`` fresh sequences, not the batch the window trained on."""
        return {"tokens": jax.random.randint(
            jax.random.fold_in(seed_key(seed), FRESH), (n, seq + 1), 0,
            cfg.vocab_size, jnp.int32)}

    return Built(
        step=step, state=state, carry_len=2,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=lambda state: state[0],
        ran=ran | {"seq_len": seq, "global_batch": batch,
                   "ssd_chunk": cfg.ssm_chunk,
                   "attention": cfg.attention_impl},
    )
