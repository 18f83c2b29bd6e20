#!/usr/bin/env python
"""Synthetic ResNet benchmark example — mirrors the reference's
examples/pytorch_synthetic_benchmark.py CLI (model, batch size, iteration
counts, fp16/bf16 allreduce flag) on the TPU stack.

    python examples/synthetic_benchmark.py --model resnet50 --batch-size 64
    python -m horovod_tpu.run -np 2 python examples/synthetic_benchmark.py

(How a user writes the step, and a rough img/s.  The measurement is
``benchmark/run.py``: ``PERF.md`` has its cells and their numbers.)
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.optim import DistributedOptimizer


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-chip batch size (reference default 32)")
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    p.add_argument("--num-warmup-batches", type=int, default=10)
    p.add_argument("--bf16-allreduce", action="store_true",
                   help="≙ reference --fp16-allreduce: compress grads on the wire")
    args = p.parse_args()

    hvd.init()
    model = getattr(models, args.model.capitalize().replace("net", "Net"))(
        num_classes=1000
    )

    n = hvd.num_devices()
    global_batch = args.batch_size * n
    images = jnp.asarray(
        np.random.RandomState(0).randn(global_batch, 224, 224, 3), jnp.float32
    )
    labels = jnp.asarray(np.random.RandomState(1).randint(0, 1000, (global_batch,)))

    variables = model.init(jax.random.PRNGKey(0), images[:1], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    params = hvd.broadcast_parameters(params)

    compression = (
        hvd.Compression.bf16 if args.bf16_allreduce else hvd.Compression.none
    )
    tx = DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=compression
    )
    opt_state = tx.init(params)

    def local_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, loss

    mesh = hvd.mesh("flat")
    step = jax.jit(
        shard_map(
            local_step, mesh=mesh,
            in_specs=(P(), P(), P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
            out_specs=(P(), P(), P(), P()), check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )

    def run_batches(k):
        nonlocal params, batch_stats, opt_state
        loss = None
        for _ in range(k):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels
            )
        jax.block_until_ready(loss)

    if hvd.rank() == 0:
        print(f"Model: {args.model}, batch size/chip: {args.batch_size}, "
              f"chips: {n}")
    run_batches(args.num_warmup_batches)

    img_secs = []
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        run_batches(args.num_batches_per_iter)
        dt = time.perf_counter() - t0
        rate = global_batch * args.num_batches_per_iter / dt
        img_secs.append(rate)
        if hvd.rank() == 0:
            print(f"Iter #{i}: {rate:.1f} img/sec total")

    if hvd.rank() == 0:
        mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
        print(f"Img/sec per chip: {mean / n:.1f} +- {conf / n:.1f}")
        print(f"Total img/sec on {n} chip(s): {mean:.1f} +- {conf:.1f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
