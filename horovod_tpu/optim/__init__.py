"""Distributed optimizer layer.

TPU-native re-design of the reference's ``hvd.DistributedOptimizer``
(horovod/torch/__init__.py:67-222, horovod/tensorflow/__init__.py:266-311):
where the reference intercepts per-parameter gradient hooks and fires
``allreduce_async_`` as each grad materializes, the TPU build expresses the
same contract — "grads are globally reduced before the update" — as an
**optax gradient transformation** that runs inside the jitted SPMD step.

Scheduling caveat: because the transform runs inside ``tx.update``, its
psums sit *after* the whole backward pass in the compiled graph — XLA
will not hoist them into the backward on its own, so the wire time of
one end-of-step exchange is fully exposed.  The backward-overlap plane
(:mod:`horovod_tpu.optim.overlap`) restores the reference's
as-gradients-materialize overlap on the jit path: it plants one fused
collective per size-bounded gradient bucket in the cotangent graph
(``sync_gradients`` / ``OverlapPlan``), where the scheduler can hide it
behind remaining backward compute, and optionally reduce-scatter-shards
the optimizer update (ZeRO-1 shape).  Prefer it for throughput-critical
training; this transform remains the simple, composable default.
"""

from __future__ import annotations

import pickle
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import scopes
from ..basics import DP_AXIS, global_topology, mesh as build_mesh
from ..ops.collectives import (
    Adasum,
    Average,
    ReduceOp,
    Sum,
    allreduce,
    grouped_allreduce,
)
from ..ops.compression import Compression

__all__ = [
    "DistributedOptimizer",
    "DistributedGradientTransform",
    "distribute",
    "broadcast_parameters",
    "broadcast_optimizer_state",
    "broadcast_object",
    "overlap",
    "sync_gradients",
    "OverlapPlan",
]

from . import overlap  # noqa: E402  (backward-overlap gradient plane)
from .overlap import OverlapPlan, sync_gradients  # noqa: E402


def DistributedGradientTransform(
    op: ReduceOp = Average,
    *,
    axis_name: str = DP_AXIS,
    compression=Compression.none,
    gradient_predivide_factor: float = 1.0,
    groups: Optional[int] = None,
    sparse_as_dense: bool = True,
    hierarchical_axes: Optional[tuple] = None,
    dcn_compression=None,
) -> optax.GradientTransformation:
    """An optax transform that allreduces grads across the mesh axis.

    Chain it in front of any optimizer::

        tx = optax.chain(hvd.DistributedGradientTransform(), optax.adam(1e-3))

    ``compression`` casts to a wire dtype around the reduce (reference
    compression.py).  ``gradient_predivide_factor`` splits the averaging
    into a pre-scale (1/f) and post-scale (f/N), the numerically-safer
    ordering for large worlds the reference exposes on its torch optimizer.
    ``groups``: number of fusion groups for grouped_allreduce (None = one
    fused reduce per dtype across the whole pytree, the analog of the 64 MB
    fusion buffer, fusion_buffer_manager.cc).
    ``sparse_as_dense``: IndexedSlices gradient leaves are scatter-added to
    dense before the reduce (reference DistributedOptimizer's
    sparse_as_dense option); with False they take the allgather path
    (horovod/tensorflow/__init__.py:74-89) and stay sparse in the output —
    only meaningful when the downstream optimizer knows how to apply them.
    ``hierarchical_axes``: ``(local_axis, cross_axis)`` of a two-fabric
    mesh (``hvd.mesh('hierarchical')`` or the slice mesh) — the reduce
    runs the 3-phase slice-aware schedule instead of the flat psum:
    reduce-scatter on ICI, cross-fabric exchange on 1/local_size of the
    bytes, gather back on ICI.  With ``op=Adasum`` the cross-fabric
    combiner is the Adasum projection (``hierarchical_adasum`` — the
    reference's AdasumGpuAllreduceOp hierarchy), which is
    order-insensitive, so slices can combine as they arrive.
    ``dcn_compression`` (``"bf16"``/``"fp16"``/None) additionally casts
    only the cross-fabric shard for Sum/Average hierarchical reduces.
    """
    if op not in (Average, Sum, Adasum):
        raise ValueError(f"DistributedGradientTransform supports Average/Sum/Adasum, got {op!r}")
    if hierarchical_axes is not None and len(hierarchical_axes) != 2:
        raise ValueError(
            "hierarchical_axes must be (local_axis, cross_axis), got "
            f"{hierarchical_axes!r}"
        )
    # NOTE: the gradient_predivide_factor x hierarchical incompatibility
    # is validated at the first update_fn call (below), not here: a
    # transform is often constructed generically (CLI-driven configs set
    # both knobs) and never actually run on the hierarchical schedule —
    # erroring at construction punished configurations that would never
    # hit the incompatible path.  update_fn is where the schedule
    # actually used is known.

    pre = 1.0
    post = 1.0
    eff_op = op
    if op == Average and gradient_predivide_factor != 1.0:
        # average = (1/f) before the wire, (f/N) after (reference torch
        # __init__.py gradient_predivide_factor plumbing).
        eff_op = Sum
        pre = 1.0 / gradient_predivide_factor

    def init_fn(params):
        del params
        return optax.EmptyState()

    # One scope over the reduction with its packing and unpacking: in a
    # device trace the fused buffers' concatenates and slices are the
    # reduction's cost, not the optimizer's.
    @jax.named_scope(scopes.GRAD_ALLREDUCE)
    def update_fn(updates, state, params=None):
        del params
        from ..ops.sparse import (  # noqa: PLC0415
            IndexedSlices,
            allreduce_sparse,
            to_dense,
        )

        leaves, treedef = jax.tree_util.tree_flatten(
            updates, is_leaf=lambda x: isinstance(x, IndexedSlices)
        )
        sparse_out = {}
        dense_idx = []
        dense_leaves = []
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, IndexedSlices):
                if sparse_as_dense:
                    dense_idx.append(i)
                    dense_leaves.append(to_dense(leaf))
                else:
                    if op == Adasum:
                        # Reference parity: Adasum rejects sparse tensors
                        # (horovod/torch/mpi_ops.py Adasum+sparse raises).
                        raise ValueError(
                            "Adasum does not support sparse (IndexedSlices) "
                            "gradients; use sparse_as_dense=True or "
                            "op=Average/Sum."
                        )
                    sparse_out[i] = allreduce_sparse(
                        leaf, op, axis_name=axis_name
                    )
            else:
                dense_idx.append(i)
                dense_leaves.append(leaf)
        leaves = dense_leaves
        wire, ctxs = [], []
        for leaf in leaves:
            w, c = compression.compress(leaf)
            wire.append(w)
            ctxs.append(c)

        if hierarchical_axes is not None:
            if gradient_predivide_factor != 1.0:
                raise ValueError(
                    "gradient_predivide_factor is a flat-psum knob; the "
                    "hierarchical schedule applies its averaging once "
                    "after the cross-fabric phase"
                )
            from ..parallel.hierarchical import (  # noqa: PLC0415
                hierarchical_adasum,
                hierarchical_allreduce,
            )

            local_ax, cross_ax = hierarchical_axes
            if eff_op == Adasum:
                reduced = [
                    hierarchical_adasum(
                        w, local_axis=local_ax, cross_axis=cross_ax
                    )
                    for w in wire
                ]
            else:
                reduced = [
                    hierarchical_allreduce(
                        w, eff_op, local_axis=local_ax,
                        cross_axis=cross_ax, compression=dcn_compression,
                    )
                    for w in wire
                ]
        elif eff_op == Adasum:
            from ..ops.adasum import adasum_allreduce  # noqa: PLC0415

            reduced = [adasum_allreduce(w, axis_name=axis_name) for w in wire]
        else:
            post_local = post
            if op == Average and gradient_predivide_factor != 1.0:
                post_local = gradient_predivide_factor / jax.lax.axis_size(axis_name)
            reduced = grouped_allreduce(
                wire,
                eff_op,
                axis_name=axis_name,
                prescale_factor=pre,
                postscale_factor=post_local,
            )
        reduced_dense = [
            compression.decompress(r, c) for r, c in zip(reduced, ctxs)
        ]
        out = [None] * (len(reduced_dense) + len(sparse_out))
        for i, r in zip(dense_idx, reduced_dense):
            out[i] = r
        for i, s in sparse_out.items():
            out[i] = s
        return jax.tree_util.tree_unflatten(treedef, out), state

    return optax.GradientTransformation(init_fn, update_fn)


def _scoped(tx: optax.GradientTransformation, scope: str):
    """``tx`` with its update traced under ``jax.named_scope(scope)``.
    Metadata only: the state, its tree and the numbers are ``tx``'s."""
    tx = optax.with_extra_args_support(tx)
    return optax.GradientTransformationExtraArgs(
        tx.init, jax.named_scope(scope)(tx.update))


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    op: ReduceOp = Average,
    axis_name: str = DP_AXIS,
    compression=Compression.none,
    backward_passes_per_step: int = 1,
    gradient_predivide_factor: float = 1.0,
) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates see globally-reduced gradients
    (reference: hvd.DistributedOptimizer, torch/__init__.py:396-449).

    ``backward_passes_per_step`` accumulates that many microbatch grads
    locally before one fused reduce + update — the reference's gradient
    accumulation knob (torch/__init__.py:101-126), realized with
    ``optax.MultiSteps`` so accumulation happens *before* the wire and each
    network round carries the accumulated sum.
    """
    tx = optax.chain(
        DistributedGradientTransform(
            op,
            axis_name=axis_name,
            compression=compression,
            gradient_predivide_factor=gradient_predivide_factor,
        ),
        _scoped(optimizer, scopes.OPTIMIZER_UPDATE),
    )
    if backward_passes_per_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    return tx


def distribute(
    step_fn,
    *,
    mesh_shape: str = "flat",
    axis_name: str = DP_AXIS,
    in_specs=None,
    out_specs=None,
    donate_argnums=(),
):
    """Turn a per-device train step into a jitted SPMD program over the job
    mesh — the TPU replacement for "launch N copies of the script"
    (SURVEY.md §7: the jit path needs no runtime controller; XLA schedules
    the fused psums).

    Convention when specs are omitted: every argument is replicated except
    the LAST, which is sharded along dim 0 (the batch); outputs are
    replicated.  Pass explicit ``jax.sharding.PartitionSpec`` trees to
    override.
    """
    from jax.sharding import PartitionSpec as P  # noqa: PLC0415

    from ..ops.collectives import shard_map_compat  # noqa: PLC0415

    m = build_mesh(mesh_shape)
    # Build the shard_map/jit pipeline once per argument count (the default
    # in_specs depend on arity); rebuilding per call would defeat the jit
    # cache and recompile the step every iteration.
    compiled: dict = {}

    def wrapper(*args):
        key = len(args)
        fn = compiled.get(key)
        if fn is None:
            specs = (
                in_specs
                if in_specs is not None
                else tuple([P()] * (len(args) - 1) + [P(axis_name)])
            )
            mapped = shard_map_compat(
                step_fn,
                mesh=m,
                in_specs=specs,
                out_specs=out_specs if out_specs is not None else P(),
            )
            fn = jax.jit(mapped, donate_argnums=donate_argnums)
            compiled[key] = fn
        return fn(*args)

    return wrapper


# ---------------------------------------------------------------------------
# State replication (reference: broadcast_parameters /
# broadcast_optimizer_state / broadcast_object, torch/__init__.py:452-648)
# ---------------------------------------------------------------------------


def _engine_active() -> bool:
    """True when the eager engine's background thread is running.

    While it runs, ALL cross-process traffic must flow through it — issuing
    a multihost_utils collective from another thread races the engine's own
    negotiation collectives and deadlocks (the exact hazard the reference's
    one-communication-thread rule exists for, operations.cc:311-330).
    """
    from .._engine_registry import peek_engine  # noqa: PLC0415

    return peek_engine() is not None


def broadcast_parameters(params, root_rank: int = 0):
    """Replicate a parameter pytree from ``root_rank``'s process to all
    (reference: torch/__init__.py:452-508; used at train start so every
    worker begins from identical state).

    Cross-process transport is the eager engine's broadcast when the engine
    is running (single communication owner), otherwise the JAX coordination
    service (multihost broadcast) — the descendants of the reference's
    MPI_Bcast-based parameter broadcast.  Single-process jobs return the
    tree unchanged.
    """
    topo = global_topology()
    if topo.process_count == 1:
        return params
    if _engine_active():
        from ..ops import eager  # noqa: PLC0415

        # Enqueue every leaf first so the engine can fuse them into a few
        # negotiation cycles (the reference enqueues all parameter
        # broadcasts before synchronizing, torch/__init__.py:452-508).
        # Leaves pass through as-is: jax.Array leaves ride the device data
        # plane (no host round-trip); scalars/lists are normalized here.
        leaves, treedef = jax.tree_util.tree_flatten(params)
        # Explicit names: pairing by name (not the auto _seq counter)
        # keeps the exchange robust if a caller wraps this in any
        # conditional — flatten order is identical on every rank, so
        # the index is a rank-stable key.
        handles = [
            eager.broadcast_async(
                l if isinstance(l, (jax.Array, np.ndarray)) else np.asarray(l),
                root_rank=root_rank,
                name=f"hvd.bcast_param.{i}",
            )
            for i, l in enumerate(leaves)
        ]
        outs = [eager.synchronize(h) for h in handles]
        return jax.tree_util.tree_unflatten(treedef, outs)
    from jax.experimental import multihost_utils  # noqa: PLC0415

    return multihost_utils.broadcast_one_to_all(
        params, is_source=topo.process_rank == root_rank
    )


def broadcast_optimizer_state(opt_state, root_rank: int = 0):
    """Replicate optimizer state (reference torch/__init__.py:511-605).

    The reference walks torch state dicts, wraps scalars as tensors, and
    re-casts after the wire; optax state is already a pytree of arrays, so
    it rides the same path as parameters.  Non-array leaves (step schedules
    etc.) travel via :func:`broadcast_object`.
    """
    # Split array leaves from aux python values.
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    is_arr = [isinstance(l, (jnp.ndarray, np.ndarray)) or jnp.isscalar(l) for l in leaves]
    arr_leaves = [l for l, a in zip(leaves, is_arr) if a]
    aux_leaves = [l for l, a in zip(leaves, is_arr) if not a]
    arr_leaves = broadcast_parameters(arr_leaves, root_rank)
    aux_leaves = broadcast_object(aux_leaves, root_rank)
    merged, ai, xi = [], 0, 0
    for a in is_arr:
        if a:
            merged.append(arr_leaves[ai])
            ai += 1
        else:
            merged.append(aux_leaves[xi])
            xi += 1
    return jax.tree_util.tree_unflatten(treedef, merged)


def broadcast_object(obj: Any, root_rank: int = 0) -> Any:
    """Pickle-broadcast an arbitrary python object from ``root_rank``
    (reference: broadcast_object via cloudpickle, torch/__init__.py:608-648).
    """
    topo = global_topology()
    if topo.process_count == 1:
        return obj
    is_source = topo.process_rank == root_rank
    payload = pickle.dumps(obj) if is_source else b""
    if _engine_active():
        from ..ops import eager  # noqa: PLC0415

        # Two-phase: broadcast length, then the byte buffer (the reference
        # broadcasts a size tensor then the bytes, torch/__init__.py:627-641).
        # Named so the two-phase exchange pairs by key on every rank
        # even when a caller guards broadcast_object in a conditional.
        length = int(
            eager.broadcast(
                np.asarray([len(payload)], np.int64), root_rank=root_rank,
                name="hvd.bcast_obj.len",
            )[0]
        )
        buf = np.zeros(length, np.uint8)
        if is_source:
            buf[:] = np.frombuffer(payload, np.uint8)
        buf = eager.broadcast(buf, root_rank=root_rank,
                              name="hvd.bcast_obj.buf")
        return pickle.loads(np.asarray(buf).tobytes()) if length else None
    from jax.experimental import multihost_utils  # noqa: PLC0415

    length = multihost_utils.broadcast_one_to_all(
        np.asarray(len(payload), np.int64), is_source=is_source
    )
    buf = np.zeros(int(length), np.uint8)
    if is_source:
        buf[:] = np.frombuffer(payload, np.uint8)
    buf = multihost_utils.broadcast_one_to_all(buf, is_source=is_source)
    return pickle.loads(np.asarray(buf).tobytes()) if int(length) else None
