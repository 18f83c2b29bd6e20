"""The Phi-4-mini-flash-reasoning training step (family ``phi4flash``,
the SambaY decoder-hybrid-decoder: Mamba-1 layers, sliding-window and
full differential attention, then gated memory units and cross-attention
layers that read one layer's scan output and one layer's keys and
values), written as a user of horovod_tpu writes it: ``hvd.init`` ->
model from the zoo -> ``hvd.DistributedOptimizer`` -> one ``shard_map`` +
``jit`` step over ``hvd.mesh("flat")`` with donated state, as
``benchmark/models/granite_hybrid.py`` builds granite.  The zoo's named
configuration holds the published values; this builder overrides only
the cut the configuration file states (the layers kept, with their
published indices and which of them hands on what, and the vocabulary).

Beside the sizes, ``ran`` holds what the program counted while the step
was traced: ``flash_tiles`` (gauges ``flash.tiles_live`` /
``flash.tiles_grid`` by layer type), ``shared_readers`` (how many layers
read the handed-on keys and values, and the memory) and
``sscan_kept_mib`` (what one layer's scan keeps for its backward).
"""

from __future__ import annotations

from benchmark.harness import diff_attn_flops, selective_scan_flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)

# configuration-file key -> the attribute of the program's configuration
# object that has to hold the same value
PUBLISHED = {
    "hidden_size": "emb_dim", "num_attention_heads": "num_heads",
    "num_key_value_heads": "kv_heads", "head_dim": "head_dim",
    "sliding_window": "attention_window", "layer_norm_eps": "norm_eps",
    "mlp_bias": "ffn_bias", "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_len",
    "mamba_d_state": "ssm_state", "mamba_d_conv": "ssm_conv",
    "mamba_dt_rank": "ssm_dt_rank", "mamba_d_inner": "ssm_width",
    "first_layer_index": "first_layer_index",
    "shared_kv_layer": "shared_kv_layer", "memory_layer": "memory_layer",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
}
ATTENTION = ("sliding_attention", "full_attention", "cross_attention")


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one token of a training step requires: the matmuls of
    every layer (a multiply-add is two operations), the conv's taps, the
    selective scan's recurrence (``harness/selective_scan_flops.py``),
    differential attention over the keys a token sees on average
    (``harness/diff_attn_flops.py``: a map's scores over 64 channels and
    its values over 128, a window layer's band counted as a band), the
    tied head once (the lookup is no matmul); backward twice the
    forward; recomputation not counted."""
    c = {**config, **ran}
    d, width = c["hidden_size"], c["intermediate_size"]
    inner, n, rank = c["mamba_d_inner"], c["mamba_d_state"], c["mamba_dt_rank"]
    heads, hd = c["num_attention_heads"], c["head_dim"]
    q_dim, kv_dim = heads * hd, c["num_key_value_heads"] * hd
    seq = ran["seq_len"]
    ffn = 2 * 3 * d * width
    scan = (2 * d * 2 * inner + 2 * c["mamba_d_conv"] * inner
            + 2 * inner * (rank + 2 * n) + 2 * rank * inner
            + selective_scan_flops.forward_flops_per_token(inner, n)
            + 2 * inner * d)
    gmu = 2 * d * inner + 2 * inner * d
    maps = lambda window: diff_attn_flops.forward_flops(
        seq, window, heads, hd) / seq
    mixers = {
        "selective_scan": scan, "gmu": gmu,
        "sliding_attention": 2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
        + maps(c["sliding_window"]),
        "full_attention": 2 * d * (q_dim + 2 * kv_dim) + 2 * q_dim * d
        + maps(None),
        "cross_attention": 2 * d * q_dim + 2 * q_dim * d + maps(None)}
    forward = sum(mixers[kind] + ffn for kind in c["layer_types"])
    return 3.0 * (forward + 2 * d * c["vocab_size"])


def fault_probes(config: dict, ran: dict) -> dict:
    """Three damaged copies the program must fail the checks with (the
    departures no damage of the variables can make -- the memory taken
    after the gate, a cross layer reading a window layer's keys and
    values, the window lifted -- are the reference's ``depart``, read by
    ``benchmark/tools/probe_departures.py``).  ``last_block_identity``:
    the last block's two output projections zero, so it adds nothing.
    ``state_forgets``: every ``A_log`` raised by 10, so the state forgets
    within a token.  ``lambda_zero``: each attention layer's four vectors
    set so that ``exp(lq1 . lk1) - exp(lq2 . lk2) = -lambda_init``:
    plain attention in differential clothing."""
    import math

    import jax.numpy as jnp

    from benchmark.harness.correct import zeroed

    c = {**config, **ran}
    kinds = list(c["layer_types"])
    last = f"block{len(kinds) - 1}"

    def state_forgets(variables):
        blocks = {
            name: ({**blk, "A_log": blk["A_log"] + 10.0}
                   if "A_log" in blk else blk)
            for name, blk in variables["params"].items()}
        return {**variables, "params": blocks}

    def lambda_zero(variables):
        blocks = dict(variables["params"])
        for i, kind in enumerate(kinds):
            if kind not in ATTENTION:
                continue
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * (c["first_layer_index"] + i))
            blk = dict(blocks[f"block{i}"])
            unit = jnp.zeros_like(blk["lambda_q1"]).at[0].set(1.0)
            blk.update(lambda_q1=0 * unit, lambda_k1=0 * unit,
                       lambda_q2=unit * math.log1p(lam0), lambda_k2=unit)
            blocks[f"block{i}"] = blk
        return {**variables, "params": blocks}

    return {"last_block_identity": lambda v: zeroed(
                v, [("params", last, "proj"), ("params", last, "fc2")]),
            "state_forgets": state_forgets, "lambda_zero": lambda_zero}


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.obs.registry import get_registry

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    seq = params["seq_len"]
    batch = params["per_chip_batch"] * chips
    size = config["program"]["size"]
    overrides = dict(num_layers=config["num_hidden_layers"],
                     layer_types=tuple(config["layer_types"]),
                     first_layer_index=config["first_layer_index"],
                     shared_kv_layer=config["shared_kv_layer"],
                     memory_layer=config["memory_layer"],
                     vocab_size=config["vocab_size"],
                     remat=bool(params.get("remat", False)))
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        overrides.update(params["overrides"])
    model = gpt(size, attention_impl=params.get("attention", "flash"),
                **overrides)
    # The same parameter tree without the flash kernel: initialising
    # through it keeps that call out of the init program.
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
        p = init_model.init(k_params, jnp.zeros((1, 16), jnp.int32))
        # rows of seq + 1 tokens: position i predicts token i + 1
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

    ran.update(seq_len=seq, global_batch=batch,
               attention=cfg.attention_impl)

    def variables(state):
        """The tree the reference reads; what the program counted when
        the step was traced goes into ``ran`` on the way."""
        gauge = lambda name, **labels: get_registry().gauge(
            name, **labels).value
        ran["flash_tiles"] = {
            kind: {name: gauge(f"flash.tiles_{name}", layer_type=kind)
                   for name in ("live", "grid")}
            for kind in sorted(set(cfg.layer_types) & set(ATTENTION))}
        ran["shared_readers"] = {"kv": gauge("shared.kv_readers"),
                                 "memory": gauge("shared.memory_readers")}
        ran["sscan_kept_mib"] = gauge("sscan.kept_mib")
        return state[0]

    return Built(
        step=step, state=state, carry_len=2,
        items_per_step=batch * seq, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss),
        sample=sample, variables=variables, ran=ran,
    )
