"""Backward-overlap gradient plane (optim/overlap.py, ISSUE 9).

The load-bearing claims, each pinned here:

* ``off`` / ``bucket`` / ``bucket+zero1`` training is the same
  arithmetic — a psum is element-wise, so re-bucketing only regroups
  independent reductions, and a reduce-scatter shard equals the matching
  slice of the full psum.  The three are three XLA programs, though, and
  XLA promises no two programs the same order inside a fused reduction:
  they agree to float32 rounding (``_assert_same_training``), with tree
  structure, shapes and dtypes exact.  Covered over the flat 8-device
  mesh AND the 2x4 (cross x local) two-fabric mesh, with odd-sized leaves
  straddling bucket boundaries, a dtype mix, and an N→M bucket-count
  change mid-training.
* The schedule inspector reads where the gradient collectives sit.  What
  the TPU compiler does with the buckets is in ``tests/
  test_tpu_compile.py`` (the one file that compiles for a described
  chip); XLA:CPU merges them, so its text is no proof of overlap.
* Params/opt_state stay donated end-to-end through the wrapper
  (``input_output_alias`` in the compiled module, not just the kwarg).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.optim import overlap
from horovod_tpu.ops.collectives import shard_map_compat
from horovod_tpu.runtime.autotune import (
    GRAD_BUCKET_BOUNDS_MB,
    grad_bucket_candidates,
    resolve_grad_bucket_bytes,
)

N = 8
AX = hvd.DP_AXIS
KB = 1024


def _flat_mesh():
    return Mesh(np.asarray(jax.devices()[:N], dtype=object).reshape(N),
                (AX,))


def _mesh2d():
    devices = np.asarray(jax.devices()[:N], dtype=object).reshape(2, 4)
    return Mesh(devices, (hvd.CROSS_AXIS, hvd.LOCAL_AXIS))


def _init_params(dtype_mix=False):
    """A 4-layer MLP with odd-sized leaves (37, 41) so buckets straddle
    leaf boundaries; optionally with bf16 leaves mixed in."""
    sizes = [32, 64, 37, 41, 10]
    key = jax.random.PRNGKey(0)
    params = []
    for i in range(4):
        k, key = jax.random.split(key)
        dt = (jnp.bfloat16 if dtype_mix and i % 2 else jnp.float32)
        params.append({
            "w": (jax.random.normal(k, (sizes[i], sizes[i + 1]))
                  * 0.1).astype(dt),
            "b": jnp.zeros(sizes[i + 1], dt),
        })
    return params


def _loss_fn(params, x, y):
    h = x
    for i, layer in enumerate(params):
        h = (h @ layer["w"].astype(jnp.float32)
             + layer["b"].astype(jnp.float32))
        if i < 3:
            h = jax.nn.relu(h)
    return jnp.mean((h - y) ** 2)


def _batch():
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 10))
    return x, y


def _build(params, tx, mode, *, mesh=None, hier=None, bucket_kb=8,
           comp=None, data_spec=P(AX)):
    mesh = mesh or _flat_mesh()
    plan = overlap.OverlapPlan(
        params, tx, mode=mode, mesh=mesh, bucket_mb=bucket_kb / 1024.0,
        hierarchical_axes=hier, dcn_compression=comp,
    )
    spec = plan.state_spec()
    step = jax.jit(
        shard_map_compat(
            plan.local_step(_loss_fn), mesh=mesh,
            in_specs=(spec, data_spec, data_spec),
            out_specs=(spec, P()),
        ),
        donate_argnums=(0,),
    )
    return plan, plan.init(params), step


def _train(plan, state, step, x, y, steps=4):
    losses = []
    for _ in range(steps):
        state, loss = step(state, x, y)
        losses.append(float(loss))
    return jax.tree_util.tree_leaves(plan.materialize(state)), losses, state


def _assert_bitwise(a_leaves, b_leaves):
    assert len(a_leaves) == len(b_leaves)
    for a, b in zip(a_leaves, b_leaves):
        assert a.dtype == b.dtype
        assert bool(jnp.all(a == b)), "params diverged bitwise"


# Two gradient planes are two XLA programs.  Observed after four steps on
# these parameters (magnitude up to 0.34, where float32 numbers lie
# 2.98e-8 apart): at most 2.98e-8 between any two modes, on either mesh
# and across a re-bucketing; the losses (near 0.93) equal.  The limits
# are ten times the largest difference seen and ten float32 spacings of
# the loss.  A bfloat16 leaf may land one of its own spacings (2^-8 of
# its magnitude) away when the float32 value behind it sits on a
# rounding boundary; none did.
PARAM_ATOL = 3e-7
LOSS_ATOL = 6e-7


def _assert_same_training(ref, got):
    """``(leaves, losses)`` of two runs of the same training under two
    gradient planes: structure, shapes and dtypes exactly, values to the
    stated rounding."""
    (a_leaves, a_losses), (b_leaves, b_losses) = ref, got
    np.testing.assert_allclose(b_losses, a_losses, rtol=0, atol=LOSS_ATOL)
    assert len(a_leaves) == len(b_leaves)
    for a, b in zip(a_leaves, b_leaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        a32 = np.asarray(a.astype(jnp.float32))
        atol = PARAM_ATOL
        if a.dtype == jnp.bfloat16:
            atol = max(atol, float(np.abs(a32).max()) * 2.0 ** -8)
        np.testing.assert_allclose(np.asarray(b.astype(jnp.float32)), a32,
                                   rtol=0, atol=atol)


# ---------------------------------------------------------------------------
# bucket layout
# ---------------------------------------------------------------------------


def test_layout_reverse_topological_and_size_bounded():
    params = _init_params()
    leaves = jax.tree_util.tree_leaves(params)
    layout = overlap.build_layout(params, 8 * KB)
    covered = [i for b in layout.buckets for i in b.leaf_indices]
    assert sorted(covered) == list(range(len(leaves)))
    # reverse-topological: bucket 0 starts at the LAST leaf
    assert layout.buckets[0].leaf_indices[0] == len(leaves) - 1
    # concatenation of buckets walks leaves in strictly reverse order
    assert covered == list(reversed(range(len(leaves))))
    for b in layout.buckets:
        # size-bounded unless the bucket is a single oversized leaf
        assert b.nbytes <= 8 * KB or len(b.leaf_indices) == 1


def test_layout_splits_on_dtype_change():
    params = _init_params(dtype_mix=True)
    layout = overlap.build_layout(params, 1 << 20)
    for b in layout.buckets:
        leaves = jax.tree_util.tree_leaves(params)
        assert len({leaves[i].dtype for i in b.leaf_indices}) == 1


def test_layout_pads_to_shard_ways():
    params = _init_params()
    layout = overlap.build_layout(params, 8 * KB, shard_ways=8)
    for b in layout.buckets:
        assert b.padded_size % 8 == 0
        assert 0 <= b.pad < 8


def test_layout_rejects_non_float_leaves():
    with pytest.raises(ValueError, match="non-float"):
        overlap.build_layout({"w": jnp.ones(4), "step": jnp.zeros((), jnp.int32)},
                             1 << 20)


def test_bucket_knob_resolution(monkeypatch):
    assert resolve_grad_bucket_bytes(4) == 4 << 20
    monkeypatch.setenv("HVDTPU_GRAD_BUCKET_MB", "2")
    assert resolve_grad_bucket_bytes() == 2 << 20
    with pytest.raises(ValueError):
        resolve_grad_bucket_bytes(0)
    cands = grad_bucket_candidates()
    assert cands[0] == GRAD_BUCKET_BOUNDS_MB[0]
    assert cands[-1] <= GRAD_BUCKET_BOUNDS_MB[1]
    assert all(b == 2 * a for a, b in zip(cands, cands[1:]))


# ---------------------------------------------------------------------------
# equivalence: off vs bucket vs bucket+zero1
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype_mix", [False, True])
def test_modes_agree_flat_mesh(dtype_mix):
    params = _init_params(dtype_mix=dtype_mix)
    x, y = _batch()
    tx = optax.sgd(0.05, momentum=0.9)
    ref = None
    for mode in overlap.MODES:
        plan, state, step = _build(params, tx, mode)
        leaves, losses, _ = _train(plan, state, step, x, y)
        if ref is None:
            ref = (leaves, losses)
        else:
            _assert_same_training(ref, (leaves, losses))


def test_zero1_adamw_agrees_with_off():
    """The stateful-optimizer case the ZeRO memory math is about."""
    params = _init_params()
    x, y = _batch()
    tx = optax.adamw(1e-3)
    plan_o, state_o, step_o = _build(params, tx, "off")
    leaves_o, losses_o, _ = _train(plan_o, state_o, step_o, x, y)
    plan_z, state_z, step_z = _build(params, tx, "bucket+zero1")
    leaves_z, losses_z, _ = _train(plan_z, state_z, step_z, x, y)
    _assert_same_training((leaves_o, losses_o), (leaves_z, losses_z))


def test_modes_agree_2x4_two_fabric_mesh():
    """The hierarchical composition: every mode rides the 3-phase
    slice-aware schedule (scatter ICI -> exchange DCN -> gather ICI),
    and the three modes still agree."""
    params = _init_params()
    x, y = _batch()
    tx = optax.adamw(1e-3)
    mesh = _mesh2d()
    hier = (hvd.LOCAL_AXIS, hvd.CROSS_AXIS)
    data = P((hvd.CROSS_AXIS, hvd.LOCAL_AXIS))
    ref = None
    for mode in overlap.MODES:
        plan, state, step = _build(params, tx, mode, mesh=mesh, hier=hier,
                                   data_spec=data)
        leaves, losses, _ = _train(plan, state, step, x, y)
        if ref is None:
            ref = (leaves, losses)
        else:
            _assert_same_training(ref, (leaves, losses))


def test_compressed_dcn_wire_stays_within_cast_tolerance():
    """bf16 on the cross-fabric leg only: one cast round-trip on
    slice-partial sums, so params stay within a bf16 ulp-scale bound of
    the exact run (same bound family as test_multislice's wire checks)."""
    params = _init_params()
    x, y = _batch()
    tx = optax.sgd(0.05)
    mesh = _mesh2d()
    hier = (hvd.LOCAL_AXIS, hvd.CROSS_AXIS)
    data = P((hvd.CROSS_AXIS, hvd.LOCAL_AXIS))
    plan_o, state_o, step_o = _build(params, tx, "bucket", mesh=mesh,
                                     hier=hier, data_spec=data)
    leaves_o, _, _ = _train(plan_o, state_o, step_o, x, y, steps=3)
    for mode in ("bucket", "bucket+zero1"):
        plan_c, state_c, step_c = _build(params, tx, mode, mesh=mesh,
                                         hier=hier, comp="bf16",
                                         data_spec=data)
        leaves_c, _, _ = _train(plan_c, state_c, step_c, x, y, steps=3)
        err = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(leaves_o, leaves_c)
        )
        assert err < 1e-2, f"{mode}: compressed wire drifted {err}"


def test_rebucket_n_to_m_midtraining_continues_the_run():
    """Re-tune the bucket size mid-training (N buckets -> M buckets):
    params AND momentum state carry over, so the continued run matches
    the uninterrupted off-mode run."""
    params = _init_params()
    x, y = _batch()
    tx = optax.sgd(0.05, momentum=0.9)
    plan_o, state_o, step_o = _build(params, tx, "off")
    leaves_o, losses_o, _ = _train(plan_o, state_o, step_o, x, y, steps=4)

    plan_a, state_a, step_a = _build(params, tx, "bucket+zero1",
                                     bucket_kb=8)
    _, losses_a, state_a = _train(plan_a, state_a, step_a, x, y, steps=2)
    mesh = _flat_mesh()
    plan_b = overlap.OverlapPlan(params, tx, mode="bucket+zero1",
                                 mesh=mesh, bucket_mb=64 / 1024.0)
    assert len(plan_b.layout.buckets) != len(plan_a.layout.buckets)
    state_b = plan_a.rebucket(state_a, plan_b)
    spec_b = plan_b.state_spec()
    step_b = jax.jit(
        shard_map_compat(
            plan_b.local_step(_loss_fn), mesh=mesh,
            in_specs=(spec_b, P(AX), P(AX)), out_specs=(spec_b, P()),
        ),
        donate_argnums=(0,),
    )
    leaves_b, losses_b, _ = _train(plan_b, state_b, step_b, x, y, steps=2)
    _assert_same_training((leaves_o, losses_o),
                          (leaves_b, losses_a + losses_b))


def test_rebucket_rejects_non_zero1_plans():
    params = _init_params()
    tx = optax.sgd(0.05)
    plan, state, _ = _build(params, tx, "bucket")
    with pytest.raises(ValueError, match="bucket\\+zero1"):
        plan.rebucket(state, plan)


# ---------------------------------------------------------------------------
# sync_gradients (the standalone wrapper)
# ---------------------------------------------------------------------------


def test_sync_gradients_matches_reduced_value_and_grad():
    params = _init_params()
    x, y = _batch()
    mesh = _flat_mesh()

    def synced(px, xb, yb):
        loss, grads = overlap.sync_gradients(
            _loss_fn, px, xb, yb, bucket_mb=8 / 1024.0
        )
        return loss, grads

    def reference(px, xb, yb):
        loss, grads = jax.value_and_grad(_loss_fn)(px, xb, yb)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, AX) / N, grads
        )
        return loss, grads

    outs = []
    for fn in (synced, reference):
        outs.append(jax.jit(shard_map_compat(
            fn, mesh=mesh, in_specs=(P(), P(AX), P(AX)),
            out_specs=(P(), P()),
        ))(params, x, y))
    (loss_s, grads_s), (loss_r, grads_r) = outs
    assert float(loss_s) == float(loss_r)
    _assert_bitwise(jax.tree_util.tree_leaves(grads_s),
                    jax.tree_util.tree_leaves(grads_r))


def test_sync_gradients_has_aux():
    params = _init_params()
    x, y = _batch()
    mesh = _flat_mesh()

    def loss_aux(p, xb, yb):
        return _loss_fn(p, xb, yb), {"n": jnp.asarray(1.0)}

    def run(px, xb, yb):
        (loss, aux), grads = overlap.sync_gradients(
            loss_aux, px, xb, yb, has_aux=True, bucket_mb=8 / 1024.0
        )
        return loss, aux["n"], grads

    loss, n, grads = jax.jit(shard_map_compat(
        run, mesh=mesh, in_specs=(P(), P(AX), P(AX)),
        out_specs=(P(), P(), P()),
    ))(params, x, y)
    assert float(n) == 1.0
    assert np.isfinite(float(loss))
    assert jax.tree_util.tree_structure(grads) \
        == jax.tree_util.tree_structure(params)


def test_sync_gradients_rejects_unsupported_op():
    with pytest.raises(ValueError, match="Average/Sum"):
        overlap.sync_gradients(_loss_fn, _init_params(), op=hvd.Adasum)


# ---------------------------------------------------------------------------
# HLO schedule inspector
# ---------------------------------------------------------------------------


def test_inspector_zero1_reduce_scatters_and_gathers():
    params = _init_params()
    x, y = _batch()
    tx = optax.adamw(1e-3)
    plan, state, step = _build(params, tx, "bucket+zero1")
    rep = overlap.inspect_schedule(step.lower(state, x, y))
    n_buckets = len(plan.layout.buckets)
    assert rep.gradient_collectives >= n_buckets
    assert rep.gather_collectives >= n_buckets  # forward param gathers
    assert rep.in_backward >= 2
    opcodes = {c["opcode"] for c in rep.collectives}
    assert "reduce-scatter" in opcodes or "all-reduce" in opcodes


def test_inspector_accepts_text_and_filters_scalar_collectives():
    text = """HloModule m, is_scheduled=true

ENTRY %main (p: f32[8]) -> f32[8] {
  %f1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop
  %ar1 = f32[8]{0} all-reduce(f32[8]{0} %f1), channel_id=1
  %f2 = f32[8]{0} fusion(f32[8]{0} %ar1), kind=kLoop
  %ar2 = f32[8]{0} all-reduce(f32[8]{0} %f2), channel_id=2
  %scalar = f32[] all-reduce(f32[] %loss), channel_id=3
  ROOT %done = f32[8]{0} fusion(f32[8]{0} %ar2), kind=kLoop
}
"""
    rep = overlap.inspect_schedule(text)
    assert rep.gradient_collectives == 2  # scalar loss psum filtered
    assert rep.in_backward == 1  # ar1 precedes f2, which precedes ar2


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", overlap.MODES)
def test_state_stays_donated_end_to_end(mode):
    params = _init_params()
    x, y = _batch()
    tx = optax.sgd(0.05, momentum=0.9)
    plan, state, step = _build(params, tx, mode)
    compiled = step.lower(state, x, y).compile()
    n_leaves = len(jax.tree_util.tree_leaves(state))
    audit = overlap.audit_donation(compiled, n_leaves)
    assert audit["ok"], audit
    assert overlap.donated_params(compiled)


def test_audit_reports_missing_donation():
    params = _init_params()
    x, y = _batch()
    tx = optax.sgd(0.05)
    plan = overlap.OverlapPlan(params, tx, mode="bucket",
                               mesh=_flat_mesh(), bucket_mb=8 / 1024.0)
    spec = plan.state_spec()
    step = jax.jit(shard_map_compat(
        plan.local_step(_loss_fn), mesh=_flat_mesh(),
        in_specs=(spec, P(AX), P(AX)), out_specs=(spec, P()),
    ))  # no donate_argnums
    state = plan.init(params)
    audit = overlap.audit_donation(step.lower(state, x, y).compile(),
                                   len(jax.tree_util.tree_leaves(state)))
    assert not audit["ok"]


# ---------------------------------------------------------------------------
# plan plumbing
# ---------------------------------------------------------------------------


def test_plan_publishes_overlap_gauges():
    from horovod_tpu.obs import get_registry

    params = _init_params()
    plan = overlap.OverlapPlan(params, optax.sgd(0.1), mode="bucket",
                               mesh=_flat_mesh(), bucket_mb=8 / 1024.0)
    snap = {(m["name"], tuple(sorted((m.get("tags") or {}).items()))):
            m.get("value") for m in get_registry().snapshot()}
    assert snap[("overlap.mode", ())] == 1
    assert snap[("overlap.buckets", ())] == len(plan.layout.buckets)
    for b in plan.layout.buckets:
        assert snap[("overlap.bucket_bytes",
                     (("bucket", str(b.index)),))] == b.nbytes


def test_plan_rejects_bad_mode_and_op():
    params = _init_params()
    with pytest.raises(ValueError, match="mode"):
        overlap.OverlapPlan(params, optax.sgd(0.1), mode="zero3")
    with pytest.raises(ValueError, match="Average/Sum"):
        overlap.OverlapPlan(params, optax.sgd(0.1), op=hvd.Min)


def test_predivide_validation_moved_to_update_time():
    """Satellite: constructing the transform with hierarchical axes AND
    a predivide factor no longer raises (CLI-driven configs build it
    generically); the incompatibility surfaces at the first update_fn
    call, where the schedule actually used is known."""
    from horovod_tpu.optim import DistributedGradientTransform

    tx = DistributedGradientTransform(
        hvd.Average,
        hierarchical_axes=(hvd.LOCAL_AXIS, hvd.CROSS_AXIS),
        gradient_predivide_factor=2.0,
    )  # must NOT raise
    state = tx.init({"w": jnp.ones(4)})
    with pytest.raises(ValueError, match="flat-psum knob"):
        tx.update({"w": jnp.ones(4)}, state)
