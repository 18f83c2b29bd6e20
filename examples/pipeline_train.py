#!/usr/bin/env python
"""Pipeline-parallel GPT training — the schedule family end to end.

The block stack splits into stages over a ``pp`` mesh axis built from
THIS process's local devices (pipeline parallelism rides ICI; use the
launcher's data-parallel axis across processes on top of it as in
docs/pipeline.md).  Demonstrates both training schedules:

* contiguous GPipe (``pp_gpt_loss``: stage-local head, scalar rejoin,
  per-tick remat), and
* circular interleaved groups (``pp_gpt_loss_circular``: bubble ÷
  circles).

No reference equivalent — Horovod 0.19.1 is data-parallel only
(SURVEY.md §2.9).

    python examples/pipeline_train.py --smoke             # TPU pod slice
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python examples/pipeline_train.py --smoke --cpu   # 4-dev CPU mesh

(``--cpu`` sets the platform in-process, like tests/conftest.py — more
robust than ``JAX_PLATFORMS=cpu`` in the shell when a TPU plugin is installed but its backend is unreachable.)
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (virtual multi-device "
                   "mesh via XLA_FLAGS=--xla_force_host_platform_"
                   "device_count=N)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--microbatches", type=int, default=4)
    p.add_argument("--circles", type=int, default=0,
                   help=">0 selects the circular schedule with this "
                   "many layer groups per stage")
    args = p.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.parallel import (
        pp_gpt_loss, pp_gpt_loss_circular, stack_pp_params,
        stack_pp_params_circular,
    )

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    if args.smoke:
        args.steps, args.seq_len = 3, 32

    devices = jax.devices()
    pp = len(devices)
    if pp < 2:
        raise SystemExit(
            "pipeline example needs >=2 devices (e.g. XLA_FLAGS="
            "--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu)"
        )
    mesh = Mesh(np.asarray(devices), ("pp",))
    if args.steps <= 0:
        raise SystemExit("--steps must be positive")
    # the circular ring buffer needs microbatches >= pp; round the batch
    # UP to the next multiple so the requested workload is preserved
    args.microbatches = max(args.microbatches, pp)
    if args.batch_size % args.microbatches:
        rounded = -(-args.batch_size // args.microbatches) \
            * args.microbatches
        print(f"# batch {args.batch_size} -> {rounded} "
              f"(must divide microbatches={args.microbatches})")
        args.batch_size = rounded

    circles = args.circles or 1
    per_group = 1 if args.smoke else 2
    layers = per_group * pp * circles
    size_kw = (
        dict(num_heads=4, emb_dim=64, vocab_size=512) if args.smoke
        else {}
    )
    model = gpt("nano", num_layers=layers, max_len=args.seq_len,
                dtype=jnp.float32, attention_impl="reference",
                **size_kw)
    cfg = model.cfg

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(
        rng.randint(0, cfg.vocab_size, (args.batch_size, args.seq_len)),
        jnp.int32,
    )
    targets = jnp.roll(tokens, -1, axis=1)
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    if args.circles:
        staged, replicated = stack_pp_params_circular(
            params, cfg, pp, circles
        )
    else:
        staged, replicated = stack_pp_params(params, cfg, pp)
    # plain SGD: its state is empty, so the carried opt_state is
    # trivially replicated and the out_specs stay simple — a stateful
    # optimizer needs per-tree specs for its moment trees (the staged
    # moments are pp-sharded like the staged params)
    tx = optax.sgd(0.5)
    opt_state = tx.init((staged, replicated))

    def local_step(staged, replicated, opt_state, tok, tgt):
        def loss_fn(trees):
            st, rep = trees
            if args.circles:
                return pp_gpt_loss_circular(
                    st, rep, cfg, tok, tgt, "pp",
                    microbatches=args.microbatches, circles=circles,
                )
            return pp_gpt_loss(st, rep, cfg, tok, tgt, "pp",
                               microbatches=args.microbatches)

        loss, grads = jax.value_and_grad(loss_fn)((staged, replicated))
        updates, opt_state = tx.update(grads, opt_state,
                                       (staged, replicated))
        staged, replicated = optax.apply_updates(
            (staged, replicated), updates
        )
        return staged, replicated, opt_state, loss

    step = jax.jit(
        shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P("pp"), P(), P(), P(), P()),
            out_specs=(P("pp"), P(), P(), P()),
            check_vma=True,
        ),
        # Donate the carried state: the input and output copies of the
        # staged/replicated params and opt_state must not both stay
        # live across a step (hvdtpu-lint HVD009).
        donate_argnums=(0, 1, 2),
    )

    sched = f"circular x{circles}" if args.circles else "gpipe"
    for i in range(args.steps):
        staged, replicated, opt_state, loss = step(
            staged, replicated, opt_state, tokens, targets
        )
        print(f"[{sched} pp={pp} layers={layers}] "
              f"step {i} loss {float(loss):.4f}", flush=True)
    final = float(loss)
    assert np.isfinite(final), "non-finite loss"
    print(f"done: final loss {final:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
