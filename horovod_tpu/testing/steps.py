"""The package's synthetic training steps: a ResNet-family classifier and
a GPT causal LM, built the way a user of horovod_tpu writes them
(``hvd.init`` -> model from the zoo -> ``hvd.DistributedOptimizer`` or an
``OverlapPlan`` -> one ``shard_map`` + ``jit`` step over
``hvd.mesh("flat")`` with donated state), on data made from fixed seeds.

For the bring-up check (``chip_smoke.py``), the profiling scripts and the
tests.  The measurement has its own builders under ``benchmark/models/``;
``tests/test_testing_steps.py`` holds the two to the same parameter tree.
Nothing imports this module but its callers: ``import horovod_tpu`` does
not load it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import models
from horovod_tpu.models.transformer import gpt
from horovod_tpu.ops.collectives import shard_map_compat
from horovod_tpu.optim import DistributedOptimizer
from horovod_tpu.optim.overlap import OverlapPlan


def _put(mesh, tree, spec):
    """Place ``tree`` on ``mesh`` under PartitionSpec ``spec`` (one, or
    a tree of them that prefixes ``tree``) — once, before the loop, so no
    step call starts by moving state that was built on device 0 to where
    the compiled program wants it, and the second call finds the carry
    as the first call's trace left it."""
    return jax.device_put(tree, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec,
        is_leaf=lambda s: isinstance(s, P)))


def _dtypes(dtype: str):
    """(compute dtype, activation-storage dtype) of a ``dtype`` name:
    ``fp8`` is bf16 compute with e4m3 activation storage."""
    compute = jnp.float32 if dtype == "fp32" else jnp.bfloat16
    return compute, (jnp.float8_e4m3fn if dtype == "fp8" else None)


def build_gpt_step(size: str, dtype: str, batch_size: int, seq_len: int,
                   attention: str = "flash", remat: bool = False,
                   overlap_mode: str = "off"):
    """GPT causal-LM training step (AdamW 1e-4) — the long-context
    counterpart of ``build_step``.  Returns ``(step, state, static)``
    like it; ``state`` ends in the token batch and ``static["carry_len"]``
    says how many leading entries the step hands back."""
    hvd.init()
    n_chips = hvd.num_devices()

    compute_dtype, act_store = _dtypes(dtype)
    model = gpt(size, dtype=compute_dtype, max_len=seq_len,
                attention_impl=attention, remat=remat,
                act_store_dtype=act_store)
    vocab = model.cfg.vocab_size

    global_batch = batch_size * n_chips
    tokens = np.random.RandomState(0).randint(
        0, vocab, size=(global_batch, seq_len + 1)
    ).astype(np.int32)
    params = jax.jit(model.init)(jax.random.PRNGKey(0),
                                 jnp.asarray(tokens[:2, :-1]))
    params = hvd.broadcast_parameters(params, root_rank=0)

    def make_loss_fn(toks):
        def loss_fn(p):
            logits = model.apply(p, toks[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks[:, 1:]
            ).mean()

        return loss_fn

    mesh = hvd.mesh("flat")
    tokens = _put(mesh, tokens, P(hvd.DP_AXIS))
    if overlap_mode != "off":
        # Backward-overlap plane: per-bucket collectives in the
        # cotangent path (+ optional ZeRO-1 sharded update) instead of
        # the end-of-step fused psum DistributedOptimizer runs.
        plan = OverlapPlan(params, optax.adamw(1e-4), mode=overlap_mode,
                           mesh=mesh)
        spec = plan.state_spec()

        def local_step(ostate, toks):
            body = plan.local_step(make_loss_fn(toks))
            ostate, loss = body(ostate)
            # Mean over the DP axis: out_specs P() presents the loss as
            # replicated, so it must actually BE global (see below).
            return ostate, jax.lax.pmean(loss, hvd.DP_AXIS)

        step = jax.jit(
            shard_map_compat(
                local_step,
                mesh=mesh,
                in_specs=(spec, P(hvd.DP_AXIS)),
                out_specs=(spec, P()),
            ),
            donate_argnums=(0,),
        )
        state = (_put(mesh, plan.init(params), spec), tokens)
        return step, state, {"n_chips": n_chips,
                             "global_batch": global_batch,
                             "carry_len": 1}

    tx = DistributedOptimizer(optax.adamw(1e-4))
    params = _put(mesh, params, P())
    opt_state = _put(mesh, tx.init(params), P())

    def local_step(params, opt_state, toks):
        loss, grads = jax.value_and_grad(make_loss_fn(toks))(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        # Mean over the DP axis: out_specs P() presents the return value as
        # replicated, so the loss must actually BE global — otherwise the
        # reported loss is one shard's and a finite-check could miss a NaN
        # confined to another shard's data.
        loss = jax.lax.pmean(loss, hvd.DP_AXIS)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(
        shard_map_compat(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(hvd.DP_AXIS)),
            out_specs=(P(), P(), P()),
        ),
        donate_argnums=(0, 1),
    )
    state = (params, opt_state, tokens)
    return step, state, {"n_chips": n_chips, "global_batch": global_batch,
                         "carry_len": 2}


def build_step(model_name: str, dtype: str, batch_size: int,
               image_size: int = 224, overlap_mode: str = "off"):
    """Build the jitted image-classifier training step (SGD 0.01,
    momentum 0.9) and its initial state.

    Returns ``(step, state, static)`` where ``state = (params,
    batch_stats, opt_state, images, labels)`` (under an ``overlap_mode``
    the plan's state takes the place of ``params`` and ``opt_state``) and
    ``step`` is the un-lowered jit callable.
    """
    hvd.init()
    n_chips = hvd.num_devices()

    compute_dtype, act_store = _dtypes(dtype)
    model_cls = {
        "resnet50": models.ResNet50,
        "resnet101": models.ResNet101,
        "resnet18": models.ResNet18,
        "vgg16": models.VGG16,
        "vgg19": models.VGG19,
        "inception3": models.InceptionV3,
    }[model_name]
    extra = {}
    if model_name.startswith("resnet"):
        extra = {"act_store_dtype": act_store}
    elif dtype == "fp8":
        raise ValueError("dtype fp8 is resnet-only (e4m3 act storage)")
    model = model_cls(num_classes=1000, compute_dtype=compute_dtype, **extra)

    rng = jax.random.PRNGKey(0)
    global_batch = batch_size * n_chips
    # Inputs in the compute dtype: halves the first conv's HBM read under
    # bf16 and matches what a real bf16 input pipeline would feed.
    images = np.random.RandomState(0).randn(
        global_batch, image_size, image_size, 3
    ).astype(compute_dtype)
    labels = np.random.RandomState(1).randint(
        0, 1000, size=(global_batch,)
    ).astype(np.int32)

    variables = jax.jit(lambda key, x: model.init(key, x, train=True))(
        rng, jnp.asarray(images[:2]))
    # VGG has no BN; {} keeps the step signature uniform across models
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    params = hvd.broadcast_parameters(params, root_rank=0)

    def make_loss_fn(batch_stats, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images,
                train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
            return loss, dict(mutated).get("batch_stats", {})

        return loss_fn

    mesh = hvd.mesh("flat")
    images = _put(mesh, images, P(hvd.DP_AXIS))
    labels = _put(mesh, labels, P(hvd.DP_AXIS))
    batch_stats = _put(mesh, batch_stats, P())
    if overlap_mode != "off":
        # Backward-overlap plane: one fused collective per gradient
        # bucket, emitted inside the backward; zero1 additionally shards
        # the optimizer update.
        plan = OverlapPlan(params, optax.sgd(0.01, momentum=0.9),
                           mode=overlap_mode, mesh=mesh)
        spec = plan.state_spec()

        def local_step(ostate, batch_stats, images, labels):
            body = plan.local_step(
                make_loss_fn(batch_stats, images, labels), has_aux=True
            )
            ostate, loss, new_stats = body(ostate)
            return ostate, new_stats, jax.lax.pmean(loss, hvd.DP_AXIS)

        step = jax.jit(
            shard_map_compat(
                local_step,
                mesh=mesh,
                in_specs=(spec, P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
                out_specs=(spec, P(), P()),
            ),
            donate_argnums=(0, 1),
        )
        state = (_put(mesh, plan.init(params), spec), batch_stats, images,
                 labels)
        return step, state, {"n_chips": n_chips,
                             "global_batch": global_batch,
                             "carry_len": 2}

    tx = DistributedOptimizer(
        optax.sgd(0.01, momentum=0.9), compression=hvd.Compression.none
    )
    params = _put(mesh, params, P())
    opt_state = _put(mesh, tx.init(params), P())

    def local_step(params, batch_stats, opt_state, images, labels):
        loss_fn = make_loss_fn(batch_stats, images, labels)
        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params
        )
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, jax.lax.pmean(loss, hvd.DP_AXIS)

    step = jax.jit(
        shard_map_compat(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
            out_specs=(P(), P(), P(), P()),
        ),
        donate_argnums=(0, 1, 2),
    )
    state = (params, batch_stats, opt_state, images, labels)
    return step, state, {"n_chips": n_chips, "global_batch": global_batch,
                         "carry_len": 3}
