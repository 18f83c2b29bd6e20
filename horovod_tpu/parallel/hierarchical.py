"""Hierarchical (2-level) allreduce over a cross x local mesh.

Reference: NCCLHierarchicalAllreduce (horovod/common/ops/nccl_operations.cc:162-300,
strategy comment :218-229): NCCL ReduceScatter within the node, MPI
allreduce across nodes on the scattered shards, NCCL Allgather back.  The
point is to put the bisection-heavy phase on the fast local fabric and send
only 1/local_size of the bytes over the slow cross fabric.

TPU mapping: LOCAL_AXIS rides ICI (fast, within a slice) and CROSS_AXIS
rides DCN (across slices), so the same 3-phase schedule applies verbatim:

    psum_scatter(LOCAL) -> psum(CROSS) -> all_gather(LOCAL)

For single-slice jobs a flat psum is both simpler and optimal; XLA already
picks torus-optimal ring/tree schedules within ICI.  This op exists for the
multi-slice (DCN-connected) topology, where the reference's reasoning
about heterogeneous fabrics carries over unchanged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes
from ..basics import CROSS_AXIS, LOCAL_AXIS
from ..ops.collectives import Average, ReduceOp, Sum, axis_size

__all__ = [
    "hierarchical_allreduce",
    "hierarchical_adasum",
    "hierarchical_reduce_scatter",
    "hierarchical_all_gather",
]


def _resolve_compressor(compression):
    """``None``/``"none"``/name/Compressor -> Compressor class or None.
    String names resolve through ops.compression.Compression so the CLI
    knob (``--dcn-compression bf16``) and the API accept the same
    vocabulary."""
    if compression in (None, "none"):
        return None
    if isinstance(compression, str):
        from ..ops.compression import Compression  # noqa: PLC0415

        # Explicit whitelist, NOT getattr over the namespace: only pure
        # cast compressors can live inside the jitted schedule (the
        # stateful error-feedback wrapper would leak tracers), so names
        # like "ef_bf16" must fail HERE with a clear message, not
        # mid-trace.
        comp = {"bf16": Compression.bf16, "fp16": Compression.fp16}.get(
            compression
        )
        if comp is None:
            raise ValueError(
                f"unknown dcn compression {compression!r}; choices: "
                f"none, bf16, fp16"
            )
        return comp
    return compression


def hierarchical_reduce_scatter(
    flat,
    op: ReduceOp = Sum,
    *,
    local_axis: str = LOCAL_AXIS,
    cross_axis: str = CROSS_AXIS,
    compression=None,
):
    """Reduce a 1-D buffer over BOTH fabrics, keep this rank's
    1/(local*cross) shard: psum_scatter on ICI, then psum_scatter of the
    slice-partial shard on DCN — so the cross-slice leg moves only
    1/local_size of the bytes, and on a compressed wire when one is
    configured.  ``flat.size`` must divide local*cross (pad first).

    This is the scatter half of the ZeRO-1 schedule composed with the
    two-fabric plane: the element-wise result equals the matching slice
    of :func:`hierarchical_allreduce` exactly (uncompressed)."""
    if op not in (Average, Sum):
        raise ValueError(
            f"hierarchical_reduce_scatter supports Average/Sum, got {op!r}"
        )
    comp = _resolve_compressor(compression)
    x = jnp.asarray(flat)
    shard = lax.psum_scatter(x, local_axis, scatter_dimension=0, tiled=True)
    if comp is not None:
        wire, ctx = comp.compress(shard)
        shard = comp.decompress(
            lax.psum_scatter(wire, cross_axis, scatter_dimension=0,
                             tiled=True),
            ctx,
        )
    else:
        shard = lax.psum_scatter(shard, cross_axis, scatter_dimension=0,
                                 tiled=True)
    if op == Average:
        shard = shard / (axis_size(local_axis) * axis_size(cross_axis))
    return shard


def hierarchical_all_gather(
    shard,
    *,
    local_axis: str = LOCAL_AXIS,
    cross_axis: str = CROSS_AXIS,
):
    """Inverse of :func:`hierarchical_reduce_scatter`'s slicing: gather
    the cross-fabric chunks back into the slice-local shard (1/local of
    the bytes on DCN), then gather the local shards on ICI."""
    x = jnp.asarray(shard)
    x = lax.all_gather(x, cross_axis, axis=0, tiled=True)
    return lax.all_gather(x, local_axis, axis=0, tiled=True)


def hierarchical_allreduce(
    tensor,
    op: ReduceOp = Average,
    *,
    local_axis: str = LOCAL_AXIS,
    cross_axis: str = CROSS_AXIS,
    compression=None,
):
    """Allreduce across both mesh axes, scattering over the local axis so
    the cross-fabric phase moves 1/local_size of the bytes.

    Call inside shard_map over the 2D ``mesh("hierarchical")`` (or the
    outer two axes of ``mesh("slice")``).  ``compression`` (None/"bf16"/
    "fp16"/a Compressor) casts ONLY the cross-fabric shard down before
    the DCN psum and widens right after — the ICI phases stay exact, so
    total error is bounded by one cast round-trip on slice-partial sums.
    """
    if op not in (Average, Sum):
        raise ValueError(f"hierarchical_allreduce supports Average/Sum, got {op!r}")
    comp = _resolve_compressor(compression)

    def one(x):
        x = jnp.asarray(x)
        shape = x.shape
        local_n = axis_size(local_axis)
        flat = jnp.ravel(x)
        pad = (-flat.size) % local_n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        # Phase 1 (ICI): reduce-scatter so each local rank owns a shard.
        shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0, tiled=True)
        # Phase 2 (DCN): allreduce only the shard across slices — on the
        # compressed wire when one is configured.
        if comp is not None:
            wire, ctx = comp.compress(shard)
            shard = comp.decompress(lax.psum(wire, cross_axis), ctx)
        else:
            shard = lax.psum(shard, cross_axis)
        # Phase 3 (ICI): gather the fully-reduced shards back.
        full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        out = full.reshape(shape)
        if op == Average:
            out = out / (local_n * axis_size(cross_axis))
        return out

    with jax.named_scope(scopes.ALLREDUCE):
        return jax.tree_util.tree_map(one, tensor)


def hierarchical_adasum(
    tensor,
    *,
    local_axis: str = LOCAL_AXIS,
    cross_axis: str = CROSS_AXIS,
):
    """Two-level Adasum (reference AdasumGpuAllreduceOp,
    horovod/common/ops/adasum_gpu_operations.cc: NCCL ReduceScatter
    intra-node -> Adasum-MPI VHDD across nodes -> NCCL Allgather).

    Local ranks hold correlated gradients (same data distribution), so a
    plain sum intra-slice is the right estimator; the Adasum projection is
    applied only across slices, exactly the reference's hierarchy.  Call
    inside shard_map over ``mesh("hierarchical")``; the cross axis must be
    a power of two (VHDD pairing).
    """
    from ..ops.adasum import adasum_allreduce  # noqa: PLC0415

    def one(x):
        x = jnp.asarray(x)
        shape = x.shape
        local_n = axis_size(local_axis)
        flat = jnp.ravel(x)
        pad = (-flat.size) % local_n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        # Phase 1 (ICI): reduce-scatter, averaging within the slice (the
        # reference scales by 1/local_size before the cross-node VHDD —
        # adasum_gpu_operations.cc ScaleBuffer path).
        shard = (
            lax.psum_scatter(flat, local_axis, scatter_dimension=0, tiled=True)
            / local_n
        )
        # Phase 2 (DCN): Adasum projection on the shards across slices.
        shard = adasum_allreduce(shard, axis_name=cross_axis)
        # Phase 3 (ICI): gather the combined shards back.
        full = lax.all_gather(shard, local_axis, axis=0, tiled=True)
        if pad:
            full = full[:-pad]
        return full.reshape(shape)

    return jax.tree_util.tree_map(one, tensor)
