"""The ResNet training step, written as a user of horovod_tpu writes it
(a copy of ``bench.build_step``'s construction, state made on the device
from the seed): SGD with momentum through ``hvd.DistributedOptimizer``,
bf16 compute, one ``shard_map`` + ``jit`` step with donated state.
"""

from __future__ import annotations

import importlib

from benchmark.harness import flops
from benchmark.models.common import (FRESH, OPTIMIZER_SCOPE, Built,
                                     make_on_device, replicated, seed_key,
                                     sharded)


def train_flops_per_item(config: dict, ran: dict) -> float:
    """Model FLOPs one image of a training step requires, at the sizes
    the program was built with (``ran`` over the configuration file)."""
    return flops.resnet_train_flops_per_image({**config, **ran})


def build(config: dict, params: dict, seed: int,
          described_mesh=None) -> Built:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd

    hvd.init()
    mesh = described_mesh or hvd.mesh("flat")
    chips = mesh.size
    batch = params["per_chip_batch"] * chips
    module, _, name = config["program"]["factory"].rpartition(".")
    factory = getattr(importlib.import_module(module), name)
    image = config["image_size"]
    kwargs = {}
    if params.get("overrides"):  # tiny sizes for the CPU tests only
        over = dict(params["overrides"])
        image = over.pop("image_size", image)
        if "factory" in over:
            factory = getattr(importlib.import_module(module),
                              over.pop("factory"))
        kwargs = over
    model = factory(num_classes=config["num_classes"],
                    compute_dtype=jnp.bfloat16, **kwargs)
    if not params.get("overrides"):
        ran = {"stage_sizes": list(model.stage_sizes),
               "num_filters": model.num_filters,
               "num_classes": model.num_classes, "image_size": image}
        for key, value in ran.items():
            if config[key] != value:
                raise ValueError(
                    f"configuration file says {key}={config[key]}, the "
                    f"program built {value}")
    else:
        ran = {"image_size": image}

    tx = hvd.DistributedOptimizer(
        optax.sgd(params["learning_rate"], momentum=params["momentum"]))

    def make_state(key):
        k_params, k_images, k_labels = jax.random.split(key, 3)
        variables = model.init(
            k_params, jnp.zeros((2, image, image, 3), jnp.bfloat16),
            train=True)
        p = variables["params"]
        images = jax.random.normal(
            k_images, (batch, image, image, 3), jnp.bfloat16)
        labels = jax.random.randint(
            k_labels, (batch,), 0, config["num_classes"], jnp.int32)
        return (p, variables.get("batch_stats", {}), tx.init(p),
                images, labels)

    rep, dp = replicated(mesh), sharded(mesh, hvd.DP_AXIS)
    state = make_on_device(make_state, seed, described_mesh,
                           (rep, rep, rep, dp, dp))
    state = (hvd.broadcast_parameters(state[0], root_rank=0),) + state[1:]

    def loss_fn(p, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": p, "batch_stats": batch_stats}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, mutated["batch_stats"]

    def local_step(p, batch_stats, opt_state, images, labels):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, batch_stats, images, labels)
        updates, opt_state = tx.update(grads, opt_state, p)
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        with jax.named_scope(OPTIMIZER_SCOPE):  # see models/gpt2.py
            p = optax.apply_updates(p, updates)
        return p, new_stats, opt_state, loss

    step = jax.jit(
        jax.shard_map(local_step, mesh=mesh,
                      in_specs=(P(), P(), P(), P(hvd.DP_AXIS),
                                P(hvd.DP_AXIS)),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1, 2))

    def sample(n):
        """``n`` fresh images, not the batch the window trained on."""
        k_images, k_labels = jax.random.split(
            jax.random.fold_in(seed_key(seed), FRESH))
        return {"images": jax.random.normal(
                    k_images, (n, image, image, 3), jnp.bfloat16),
                "labels": jax.random.randint(
                    k_labels, (n,), 0, config["num_classes"], jnp.int32)}

    def program_loss(variables, b):
        """``loss_fn`` again, keeping each image's term."""
        logits, _ = model.apply(variables, b["images"], train=True,
                                mutable=["batch_stats"])
        nll = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["labels"])
        return nll.mean(), -nll

    return Built(
        step=step, state=state, carry_len=3,
        items_per_step=batch, chips=chips, mesh=mesh,
        program_loss=jax.jit(program_loss), sample=sample,
        variables=lambda state: {"params": state[0],
                                 "batch_stats": state[1]},
        ran=ran | {"global_batch": batch},
    )
