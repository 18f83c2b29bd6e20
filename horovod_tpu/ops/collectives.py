"""SPMD collectives with Horovod's autodiff rules.

These are the jit-path primitives: call them inside ``shard_map`` / ``pjit``
over a named mesh axis (default :data:`horovod_tpu.basics.DP_AXIS`).  XLA
lowers them to ICI/DCN collectives; there is no runtime controller on this
path (SPMD program order already guarantees every chip issues the same
collectives in the same order, which is the invariant the reference's rank-0
negotiation protocol exists to enforce — horovod/common/controller.h:62-97).

Autodiff rules are ported from the reference's autograd Functions
(horovod/torch/mpi_ops.py):

* allreduce  backward = allreduce of the cotangent        (mpi_ops.py:158-171)
* allgather  backward = reduce, then slice own rank chunk (mpi_ops.py:289-307)
* broadcast  backward = reduce to root, zero elsewhere    (mpi_ops.py:371-385)

``Average`` is implemented as Sum + divide, exactly as the reference does in
framework code because its core rejects AVERAGE
(horovod/common/operations.cc:812-819, horovod/torch/mpi_ops.py:94-129).
"""

from __future__ import annotations

import enum
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from ..basics import DP_AXIS
from .. import scopes

__all__ = [
    "ReduceOp",
    "Average",
    "Sum",
    "Adasum",
    "Min",
    "Max",
    "allreduce",
    "allreduce_",
    "grouped_allreduce",
    "allgather",
    "broadcast",
    "broadcast_",
    "alltoall",
    "reducescatter",
    "reduce_scatter_flat",
    "all_gather_flat",
    "axis_rank",
    "axis_size",
]


class ReduceOp(enum.IntEnum):
    """Reduction ops (reference: horovod_reduce_op_{average,sum,adasum},
    horovod/common/operations.cc:726-799)."""

    AVERAGE = 1
    SUM = 2
    ADASUM = 3
    MIN = 4
    MAX = 5


Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX


def _check_eager_axis(axis_name: str) -> None:
    """The eager engine always reduces over the whole process world; a
    non-default axis_name on a concrete array would silently mean something
    else, so reject it loudly (sub-axis eager collectives belong under
    shard_map)."""
    if axis_name != DP_AXIS:
        raise ValueError(
            f"axis_name={axis_name!r} is only meaningful under tracing "
            f"(shard_map/pjit); the eager path always operates over the "
            f"full process world."
        )


def _is_traced(tensor) -> bool:
    """True when we're under jit/shard_map tracing — the SPMD path.

    Concrete arrays outside a trace take the eager engine instead, so a
    single ``hvd.allreduce`` spelling serves both worlds (the reference has
    one eager spelling; its graph mode is the framework's tracer doing the
    same dispatch)."""
    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves(tensor)
    )


def axis_rank(axis_name: str = DP_AXIS):
    """This shard's index along the collective axis (trace-time value)."""
    return lax.axis_index(axis_name)


def axis_size(axis_name: str = DP_AXIS) -> int:
    """Static width of the collective axis."""
    return lax.axis_size(axis_name)


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check — the ONE
    spelling the data plane, the jit optimizer path and the bench all
    build their shard_maps through."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# allreduce
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _allreduce_sum(x, axis_name, average):
    y = lax.psum(x, axis_name)
    if average:
        y = y / axis_size(axis_name)
    return y


def _allreduce_fwd(x, axis_name, average):
    # The residual is an empty slice of x: it costs nothing and carries
    # x's varying-axes type into the backward rule.
    return _allreduce_sum(x, axis_name, average), jnp.ravel(x)[:0]


def _allreduce_bwd(axis_name, average, like_x, g):
    # Reference rule: backward of allreduce is allreduce with the same op
    # (horovod/torch/mpi_ops.py:158-171).
    ct = _allreduce_sum(g, axis_name, average)
    # A psum's result is replicated over its axes, but the cotangent must
    # have the primal input's type: under a replication-checked shard_map
    # (check_vma=True) mark it varying over the axes x varied over.
    names = axis_name if isinstance(axis_name, tuple) else (axis_name,)
    varying = tuple(a for a in names if a in jax.typeof(like_x).vma)
    if varying:
        ct = lax.pcast(ct, varying, to="varying")
    return (ct,)


_allreduce_sum.defvjp(_allreduce_fwd, _allreduce_bwd)


def _eager_tree(tensor, name, call):
    """Flatten a pytree, derive per-leaf negotiation names (suffix ``.i``
    only for multi-leaf pytrees), call, unflatten — the ONE definition of
    the eager naming convention shared by every collective, so the keys
    that pair tensors across ranks can never drift between ops."""
    leaves, treedef = jax.tree_util.tree_flatten(tensor)
    outs = [
        call(leaf, f"{name}.{i}" if name and len(leaves) > 1 else name)
        for i, leaf in enumerate(leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, outs)


def allreduce(
    tensor,
    op: ReduceOp = Average,
    *,
    axis_name: str = DP_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    name: Optional[str] = None,
):
    """Allreduce across the mesh axis (reference: hvd.allreduce,
    horovod/torch/mpi_ops.py:94-155; EnqueueTensorAllreduce,
    horovod/common/operations.cc:803).

    Works on a single array or an arbitrary pytree (each leaf reduced).
    Under tracing this is a psum over ``axis_name``; on concrete arrays it
    routes through the eager engine (named-tensor negotiation).  An
    ``IndexedSlices`` input takes the sparse allgather path (reference:
    horovod/tensorflow/__init__.py:74-89).
    """
    from .sparse import IndexedSlices, allreduce_sparse  # noqa: PLC0415

    def _sparse(s, suffix=""):
        return allreduce_sparse(
            s,
            op,
            axis_name=axis_name,
            name=(f"{name}{suffix}" if name else None),
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )

    if isinstance(tensor, IndexedSlices):
        return _sparse(tensor)
    s_leaves, s_treedef = jax.tree_util.tree_flatten(
        tensor, is_leaf=lambda x: isinstance(x, IndexedSlices)
    )
    if any(isinstance(l, IndexedSlices) for l in s_leaves):
        # Mixed pytree: sparse leaves take the allgather path, dense leaves
        # recurse onto the ordinary reduce (an IndexedSlices is itself a
        # NamedTuple pytree, so without is_leaf it would be flattened and
        # its integer indices psum'd into garbage).
        outs = [
            _sparse(l, suffix=f".{i}")
            if isinstance(l, IndexedSlices)
            else allreduce(
                l,
                op,
                axis_name=axis_name,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
                name=(f"{name}.{i}" if name else None),
            )
            for i, l in enumerate(s_leaves)
        ]
        return jax.tree_util.tree_unflatten(s_treedef, outs)
    if not _is_traced(tensor):
        _check_eager_axis(axis_name)
        from . import eager  # noqa: PLC0415

        return _eager_tree(
            tensor, name,
            lambda leaf, nm: eager.allreduce(
                leaf, op, name=nm,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            ),
        )
    del name
    if op == Adasum:
        from .adasum import adasum_allreduce  # noqa: PLC0415

        with jax.named_scope(scopes.ALLREDUCE):
            return adasum_allreduce(tensor, axis_name=axis_name)

    def one(x):
        x = jnp.asarray(x)
        if prescale_factor != 1.0:
            x = x * prescale_factor
        if op in (Average, Sum):
            y = _allreduce_sum(x, axis_name, op == Average)
        elif op == Min:
            y = lax.pmin(x, axis_name)
        elif op == Max:
            y = lax.pmax(x, axis_name)
        else:
            raise ValueError(f"unsupported reduce op {op!r}")
        if postscale_factor != 1.0:
            y = y * postscale_factor
        return y

    with jax.named_scope(scopes.ALLREDUCE):
        return jax.tree_util.tree_map(one, tensor)


def allreduce_(tensor, op: ReduceOp = Average, **kwargs):
    """In-place-spelled alias (JAX arrays are immutable; returns the result).

    Exists so reference call sites (``hvd.allreduce_``) port mechanically."""
    return allreduce(tensor, op, **kwargs)


@jax.named_scope(scopes.ALLREDUCE)  # the packing and unpacking too
def grouped_allreduce(
    tensors: Sequence,
    op: ReduceOp = Average,
    *,
    axis_name: str = DP_AXIS,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    fusion_threshold_bytes: Optional[int] = None,
):
    """Fused allreduce of a list of tensors via flat buffers.

    TPU-native tensor fusion: the reference memcpys entries into a 64 MB
    fusion buffer around one NCCL call
    (horovod/common/fusion_buffer_manager.cc,
    collective_operations.cc:159-210); here we flatten+concat into 1-D
    buffers, issue one psum per buffer, and split back.  Like the
    reference's FuseResponses (controller.cc:640-761), fused bins are
    capped at the fusion threshold (HVDTPU_FUSION_THRESHOLD, default
    64 MB) per dtype, so the flat buffer never materializes an unbounded
    extra copy of the gradients at peak memory.  A single leaf larger
    than the threshold gets its own bin (the reference likewise never
    splits one tensor across fusion buffers).
    """
    leaves, treedef = jax.tree_util.tree_flatten(list(tensors))
    if not leaves:
        return tensors
    if fusion_threshold_bytes is None:
        from ..utils import env as envmod  # noqa: PLC0415

        fusion_threshold_bytes = envmod.env_int(
            envmod.FUSION_THRESHOLD, envmod.DEFAULT_FUSION_BYTES
        )
    # Fuse only same-dtype runs (the reference fuses per dtype too —
    # controller.cc:676-689 look-ahead keeps dtypes homogeneous per
    # fusion), then chunk each dtype's leaves into <=threshold bins.
    out = [None] * len(leaves)
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    def _reduce_bin(idxs):
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = allreduce(
                jnp.asarray(leaves[i]),
                op,
                axis_name=axis_name,
                prescale_factor=prescale_factor,
                postscale_factor=postscale_factor,
            )
            return
        flat = jnp.concatenate(
            [jnp.ravel(jnp.asarray(leaves[i])) for i in idxs]
        )
        reduced = allreduce(
            flat,
            op,
            axis_name=axis_name,
            prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
        )
        offset = 0
        for i in idxs:
            n = jnp.asarray(leaves[i]).size
            out[i] = lax.dynamic_slice_in_dim(reduced, offset, n).reshape(
                jnp.shape(leaves[i])
            )
            offset += n

    for dtype, idxs in by_dtype.items():
        itemsize = jnp.dtype(dtype).itemsize
        bin_idxs: list = []
        bin_bytes = 0
        for i in idxs:
            nbytes = jnp.asarray(leaves[i]).size * itemsize
            if bin_idxs and bin_bytes + nbytes > fusion_threshold_bytes:
                _reduce_bin(bin_idxs)
                bin_idxs, bin_bytes = [], 0
            bin_idxs.append(i)
            bin_bytes += nbytes
        if bin_idxs:
            _reduce_bin(bin_idxs)
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# allgather
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _allgather(x, axis_name):
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def _allgather_fwd(x, axis_name):
    return _allgather(x, axis_name), jnp.shape(x)[0]


def _allgather_bwd(axis_name, dim0, g):
    # Reference rule: reduce the gathered cotangent, then every rank keeps
    # its own slice (horovod/torch/mpi_ops.py:289-307).  psum_scatter does
    # both in one collective (reduce-scatter), which is strictly cheaper
    # than the reference's allreduce + narrow.
    del dim0
    return (lax.psum_scatter(g, axis_name, scatter_dimension=0, tiled=True),)


_allgather.defvjp(_allgather_fwd, _allgather_bwd)


def allgather(tensor, *, axis_name: str = DP_AXIS, name: Optional[str] = None):
    """Concatenate each shard's tensor along dim 0 (reference: hvd.allgather,
    horovod/torch/mpi_ops.py:231-307; EnqueueTensorAllgather,
    operations.cc:856).

    The jit path requires equal dim-0 sizes (static shapes; XLA constraint).
    Ragged gathers — the reference negotiates per-rank sizes at runtime
    (controller.cc:453-518) — are served by the eager path, which pads to
    the negotiated max and slices on the host.
    """
    if not _is_traced(tensor):
        _check_eager_axis(axis_name)
        from . import eager  # noqa: PLC0415

        return _eager_tree(
            tensor, name, lambda leaf, nm: eager.allgather(leaf, name=nm)
        )
    del name
    return jax.tree_util.tree_map(
        lambda x: _allgather(jnp.asarray(x), axis_name), tensor
    )


# ---------------------------------------------------------------------------
# broadcast
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _broadcast(x, root_rank, axis_name):
    # One psum of a masked value: every non-root contributes zeros, so the
    # sum is exactly the root's tensor.  XLA lowers this to a single
    # all-reduce; on TPU this beats gather-then-index.
    mask = (lax.axis_index(axis_name) == root_rank).astype(x.dtype)
    return lax.psum(x * mask, axis_name)


def _broadcast_fwd(x, root_rank, axis_name):
    return _broadcast(x, root_rank, axis_name), None


def _broadcast_bwd(root_rank, axis_name, _, g):
    # Reference rule: sum cotangents to the root, zeros elsewhere
    # (horovod/torch/mpi_ops.py:371-385).
    summed = lax.psum(g, axis_name)
    mask = (lax.axis_index(axis_name) == root_rank).astype(g.dtype)
    return (summed * mask,)


_broadcast.defvjp(_broadcast_fwd, _broadcast_bwd)


def broadcast(
    tensor, root_rank: int, *, axis_name: str = DP_AXIS, name: Optional[str] = None
):
    """Broadcast the root shard's value to every shard (reference:
    hvd.broadcast, horovod/torch/mpi_ops.py:330-406; EnqueueTensorBroadcast,
    operations.cc:891)."""
    if not _is_traced(tensor):
        _check_eager_axis(axis_name)
        from . import eager  # noqa: PLC0415

        return _eager_tree(
            tensor, name,
            lambda leaf, nm: eager.broadcast(leaf, root_rank, name=nm),
        )
    del name
    return jax.tree_util.tree_map(
        lambda x: _broadcast(jnp.asarray(x), root_rank, axis_name), tensor
    )


def broadcast_(tensor, root_rank: int, **kwargs):
    """In-place-spelled alias; see :func:`allreduce_`."""
    return broadcast(tensor, root_rank, **kwargs)


# ---------------------------------------------------------------------------
# flat reduce-scatter / all-gather pair (the ZeRO-shape building blocks)
# ---------------------------------------------------------------------------
#
# 1-D tiled scatter/gather with each other as VJP: the backward of
# gathering shards into a full buffer is reduce-scattering the cotangent
# (and vice versa).  This is what lets the overlap plane
# (horovod_tpu.optim.overlap) express a ZeRO-1 step as "all-gather the
# parameter shards in the forward" and get the per-bucket gradient
# reduce-scatter emitted *inside the backward graph* for free — the
# cotangent of each bucket's gather fires the moment that bucket's last
# gradient materializes, which is the position XLA's latency-hiding
# scheduler needs to overlap the wire with remaining backward compute.


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _reduce_scatter_flat(x, axis_name):
    return lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)


def _reduce_scatter_flat_fwd(x, axis_name):
    return _reduce_scatter_flat(x, axis_name), None


def _reduce_scatter_flat_bwd(axis_name, _, g):
    # d(reduce_scatter)/dx: every rank's contribution to every element is
    # weighted 1, so the cotangent of the owned shard broadcasts back to
    # the full buffer — one tiled all-gather.
    return (lax.all_gather(g, axis_name, axis=0, tiled=True),)


_reduce_scatter_flat.defvjp(_reduce_scatter_flat_fwd, _reduce_scatter_flat_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _all_gather_flat(x, axis_name):
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def _all_gather_flat_fwd(x, axis_name):
    return _all_gather_flat(x, axis_name), None


def _all_gather_flat_bwd(axis_name, _, g):
    # Reference allgather rule (mpi_ops.py:289-307) on the flat buffer:
    # reduce the gathered cotangent and keep the own-rank chunk —
    # psum_scatter does both in one collective.
    return (lax.psum_scatter(g, axis_name, scatter_dimension=0, tiled=True),)


_all_gather_flat.defvjp(_all_gather_flat_fwd, _all_gather_flat_bwd)


def reduce_scatter_flat(flat, op: ReduceOp = Sum, *,
                        axis_name: str = DP_AXIS):
    """Reduce a 1-D buffer across the axis, keep this shard's tiled chunk
    (``dim0`` must divide the axis size — pad first).  The element-wise
    result is bitwise-identical to the matching slice of a full ``psum``,
    which is what makes a reduce-scatter-sharded optimizer update provably
    equivalent to the replicated one (tests/test_overlap.py)."""
    if op not in (Sum, Average):
        raise ValueError(f"reduce_scatter_flat supports Sum/Average, got {op!r}")
    y = _reduce_scatter_flat(jnp.asarray(flat), axis_name)
    if op == Average:
        y = y / axis_size(axis_name)
    return y


def all_gather_flat(shard, *, axis_name: str = DP_AXIS):
    """Concatenate each rank's 1-D shard along dim 0 (tiled), the exact
    inverse of :func:`reduce_scatter_flat`'s slicing.  Its VJP is the
    reduce-scatter of the cotangent, so gathering parameter shards in a
    forward pass plants the gradient reduce-scatter inside the backward."""
    return _all_gather_flat(jnp.asarray(shard), axis_name)


# ---------------------------------------------------------------------------
# alltoall / reducescatter (TPU-first extensions)
# ---------------------------------------------------------------------------


def alltoall(tensor, *, axis_name: str = DP_AXIS,
             name: Optional[str] = None):
    """Scatter dim-0 chunks to each shard and gather their chunks (the
    primitive behind Ulysses-style sequence parallelism).  Not present in
    the reference at 0.19.1 (SURVEY.md §2.9); provided because all-to-all is
    first-class on the ICI torus and later Horovod grew it.  ``name`` keys
    the eager negotiation, like allreduce's."""
    if not _is_traced(tensor):
        _check_eager_axis(axis_name)
        from . import eager  # noqa: PLC0415

        return _eager_tree(
            tensor, name, lambda leaf, nm: eager.alltoall(leaf, name=nm)
        )

    def one(x):
        x = jnp.asarray(x)
        n = axis_size(axis_name)
        if x.shape[0] % n != 0:
            raise ValueError(
                f"alltoall dim0 ({x.shape[0]}) must divide the axis size ({n})"
            )
        return lax.all_to_all(
            x.reshape((n, x.shape[0] // n) + x.shape[1:]),
            axis_name,
            split_axis=0,
            concat_axis=0,
            tiled=False,
        ).reshape(x.shape)

    return jax.tree_util.tree_map(one, tensor)


def reducescatter(tensor, op: ReduceOp = Average, *,
                  axis_name: str = DP_AXIS, name: Optional[str] = None):
    """Sum across shards, keep only this shard's dim-0 slice — the first leg
    of the reference's hierarchical allreduce (nccl_operations.cc:218-229)
    exposed as a user op.  Under tracing this is ``lax.psum_scatter``
    (dim0 must divide the axis size — XLA static shapes); on concrete
    arrays the eager engine serves it with the uneven-dim0 convention
    (first ``dim0 % world`` ranks get one extra row).  ``name`` keys the
    eager negotiation, like allreduce's."""
    if not _is_traced(tensor):
        _check_eager_axis(axis_name)
        from . import eager  # noqa: PLC0415

        return _eager_tree(
            tensor, name,
            lambda leaf, nm: eager.reducescatter(leaf, op, name=nm),
        )

    def one(x):
        x = jnp.asarray(x)
        y = lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)
        if op == Average:
            y = y / axis_size(axis_name)
        elif op != Sum:
            raise ValueError(f"reducescatter supports Sum/Average, got {op!r}")
        return y

    return jax.tree_util.tree_map(one, tensor)
