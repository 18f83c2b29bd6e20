"""The GPT-2 training step, written as a user of horovod_tpu writes it:
``hvd.init`` -> model from the zoo -> ``hvd.DistributedOptimizer`` ->
one ``shard_map`` + ``jit`` step over ``hvd.mesh("flat")`` with donated
state.  A copy of ``bench.build_gpt_step``'s construction that takes the
published vocabulary (the original has no argument for it) and makes
its state on the device from the seed.
"""

from __future__ import annotations

from benchmark.harness import flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires, at the sizes
    the program was built with (``ran`` over the configuration file)."""
    return flops.gpt_train_flops_per_token({**config, **ran}, ran["seq_len"])


def fault_probes(config: dict, ran: dict) -> dict:
    """The last block made an identity: with its two output projections
    zero it adds nothing to the residual stream."""
    from benchmark.harness.correct import zeroed

    last = f"block{({**config, **ran})['n_layer'] - 1}"
    return {"identity_block": lambda v: zeroed(
        v, [("params", last, "proj"), ("params", last, "fc2")])}


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
    overrides = dict(vocab_size=config["vocab_size"], max_len=seq,
                     remat=bool(params.get("remat", False)))
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        overrides.update(params["overrides"])
    model = gpt(size, attention_impl=params.get("attention", "flash"),
                **overrides)
    # The same parameter tree without the kernel: initialising through
    # it keeps the Pallas call out of the init program.
    init_model = gpt(size, attention_impl="reference", **overrides)
    cfg = model.cfg
    ran = {"n_layer": cfg.num_layers, "n_embd": cfg.emb_dim,
           "n_head": cfg.num_heads, "vocab_size": cfg.vocab_size,
           "n_inner_ratio": cfg.mlp_ratio}
    if not params.get("overrides"):
        ran["n_positions"] = cfg.max_len
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")

    tx = hvd.DistributedOptimizer(optax.adamw(params["learning_rate"]))

    def make_state(key):
        k_params, k_tokens = jax.random.split(key)
        p = init_model.init(k_params, jnp.zeros((1, 8), jnp.int32))
        tokens = jax.random.randint(
            k_tokens, (batch, seq + 1), 0, cfg.vocab_size, jnp.int32)
        return p, tx.init(p), tokens

    state = make_on_device(make_state, seed, described_mesh, (
        replicated(mesh), replicated(mesh), sharded(mesh, hvd.DP_AXIS)))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def loss_fn(p, toks):
        logits = model.apply(p, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]).mean()

    def local_step(p, opt_state, toks):
        loss, grads = jax.value_and_grad(loss_fn)(p, toks)
        updates, opt_state = tx.update(grads, opt_state, p)
        # out_specs P() presents the loss as replicated, so it has to be
        # the global mean.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        # XLA names a fusion after its root, and the optimizer's fusions
        # are rooted at this add: under the scope that
        # ``DistributedOptimizer`` gives the update itself, so that
        # ``optimizer_ms`` finds both.  Metadata only.
        with jax.named_scope(OPTIMIZER_SCOPE):
            p = optax.apply_updates(p, updates)
        return p, opt_state, loss

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    def program_loss(p, b):
        """``loss_fn`` again, keeping each token's term."""
        logits = model.apply(p, b["tokens"][:, :-1])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["tokens"][:, 1:])
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
                   "attention": cfg.attention_impl},
    )
