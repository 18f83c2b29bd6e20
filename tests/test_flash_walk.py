"""The flash kernels over the table of live tiles against the same
kernels over the whole rectangle, to the bit (the Pallas interpreter on
the CPU).  (Moved whole from ``tests/test_flash_attention.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_oracle import (WALK_BK, WALK_BQ, WALK_D, WALK_MASKS, WALK_SEQ,
                          folded_plan, grouped_blockwise)
from horovod_tpu.parallel import local_attention


# (id, query heads, key/value heads, value width)
_WALK_HEADS = [
    ("h_is_hkv", 2, 2, 16),
    ("grouped_3_values_32", 6, 2, 32),
]
_WALK_FORMS = ["dkdv_resident", "dq_resident", "two_passes"]
# sha256[:16] over o, lse, dq, dk, dv of the PARENT's kernels (commit
# 9ced719, the whole nq x nk rectangle under a ``needed`` predicate) on
# ``_walk_inputs`` in float32, by (mask, heads) and, in ``_WALK_FORMS``'
# order, backward form: made by running ``_walk_results`` with the
# parent's package on the path.  The
# bfloat16 cases pin none (a bfloat16 result's last bit is the host CPU's:
# PR 48); they are held, as every case is, to this tree's own rectangle
# (``live_tiles=None``), which is the parent's walk, in this process.
_WALK_PARENT_DIGESTS = {
    ("noncausal", "h_is_hkv"): (
        "76aa08ded2c9e1a3", "76aa08ded2c9e1a3", "76aa08ded2c9e1a3"),
    ("noncausal", "grouped_3_values_32"): (
        "bea937c82125c187", "bea937c82125c187", "bea937c82125c187"),
    ("causal", "h_is_hkv"): (
        "c5e141324daaddf7", "c5e141324daaddf7", "c5e141324daaddf7"),
    ("causal", "grouped_3_values_32"): (
        "d7227a652c7734c1", "d7227a652c7734c1", "d7227a652c7734c1"),
    ("window_8_under_the_tiles", "h_is_hkv"): (
        "6dbf65d7680af580", "6dbf65d7680af580", "6dbf65d7680af580"),
    ("window_8_under_the_tiles", "grouped_3_values_32"): (
        "87d28254e926a078", "87d28254e926a078", "87d28254e926a078"),
    ("window_16_a_k_tile", "h_is_hkv"): (
        "343f8130bd91f4da", "343f8130bd91f4da", "343f8130bd91f4da"),
    ("window_16_a_k_tile", "grouped_3_values_32"): (
        "44afd7410a17617b", "44afd7410a17617b", "44afd7410a17617b"),
    ("window_20_no_multiple", "h_is_hkv"): (
        "cc45cc91adb5148d", "cc45cc91adb5148d", "cc45cc91adb5148d"),
    ("window_20_no_multiple", "grouped_3_values_32"): (
        "5975c32a82d5fa3d", "5975c32a82d5fa3d", "5975c32a82d5fa3d"),
}


def _walk_inputs(h, hkv, dv, dtype):
    rng = np.random.RandomState(49)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(2 * heads, WALK_SEQ, width) * 0.7, dtype)
    return mk(h, WALK_D), mk(hkv, WALK_D), mk(hkv, dv), mk(h, dv)


# what each variant of a case's plan changes in it
_WALK_VARIANTS = {"the table": {},
                  "the rectangle": {"live_tiles": None},
                  "the streamed forward's table": {"fwd_kv_resident": False}}


def _walk_variant(plan, which):
    from dataclasses import replace

    return replace(plan, **_WALK_VARIANTS[which])


@functools.cache
def _walk_forward(causal, window, h, hkv, dv, dtype, which):
    """``o`` and ``lse`` of the forward under one variant of the plan,
    once for a (mask, heads, dtype): the forward knows no backward form,
    so the three forms' cases read the same two arrays."""
    from horovod_tpu.ops import flash_attention as fa

    plan = _walk_variant(_walk_plan(causal, window, h, hkv, dv, dtype,
                                    _WALK_FORMS[0]), which)
    q, k, v, _ = _walk_inputs(h, hkv, dv, dtype)
    return jax.jit(lambda q, k, v: fa._flash_fwd_kernel(
        q, k, v, plan, WALK_D ** -0.5, True))(q, k, v)


def _walk_results(case, form, which="the table"):
    """o, lse, dq, dk, dv of the kernels under the case's plan with the
    backward ``form``, in the variant ``which``, folded."""
    from horovod_tpu.ops import flash_attention as fa

    _, _, h, hkv, dv, dtype = case
    plan = _walk_variant(_walk_plan(*case, form), which)
    q, k, v, do = _walk_inputs(h, hkv, dv, dtype)
    o, lse = _walk_forward(*case, which)
    return (o, lse) + tuple(jax.jit(lambda *a: fa._flash_bwd_pallas(
        *a, plan, WALK_D ** -0.5, True))(q, k, v, o, lse, do))


@functools.cache
def _walk_oracles(case):
    """The plain attention's output and the blockwise scan's gradients
    from the table's ``o`` and ``lse``, unfolded: once for the three
    forms."""
    causal, window, h, hkv, dv, dtype = case
    q, k, v, do = _walk_inputs(h, hkv, dv, dtype)
    o, lse = _walk_forward(*case, "the table")
    unfold = lambda x, heads: x.reshape(2, heads, WALK_SEQ, -1).transpose(
        0, 2, 1, 3).astype(jnp.float32)
    rep = lambda x: jnp.repeat(unfold(x, hkv), h // hkv, axis=2)
    want = local_attention(unfold(q, h), rep(k), rep(v), causal=causal,
                           window=window)
    err = np.abs(np.asarray(unfold(o, h)) - np.asarray(want)).max()
    return err, grouped_blockwise(q, k, v, o, lse, do, causal,
                                  WALK_D ** -0.5, WALK_BK, window, h, hkv)


def _walk_digest(results):
    import hashlib

    sha = hashlib.sha256()
    for a in results:
        sha.update(np.asarray(a).tobytes())
    return sha.hexdigest()[:16]


def _walk_plan(causal, window, h, hkv, dv, dtype, form):
    from dataclasses import replace

    q, k, v, _ = (jax.ShapeDtypeStruct(x.shape, x.dtype)
                  for x in _walk_inputs(h, hkv, dv, dtype))
    plan = folded_plan(q, k, v, causal, WALK_BQ, WALK_BK, h, hkv, window)
    assert (plan.bwd_form, plan.fwd_kv_resident) == ("dkdv_resident", True)
    return replace(plan, bwd_form=form,
                   bwd_vmem_bytes=0 if form == "two_passes"
                   else plan.bwd_vmem_bytes)


def walk_cases(dtype):
    """The walk's parametrisation at one dtype, ids as (mask, heads,
    dtype, form): the float32 cases are this file's and the bfloat16 ones
    ``tests/test_flash_walk_bfloat16.py``'s, so that neither file is a
    worker's longest load."""
    def parametrised(test):
        for mark in (
                pytest.mark.parametrize(
                    "causal,window", [c[1:] for c in WALK_MASKS],
                    ids=[c[0] for c in WALK_MASKS]),
                pytest.mark.parametrize(
                    "h,hkv,dv", [c[1:] for c in _WALK_HEADS],
                    ids=[c[0] for c in _WALK_HEADS]),
                pytest.mark.parametrize(
                    "dtype", [dtype], ids=[jnp.dtype(dtype).name]),
                pytest.mark.parametrize("form", _WALK_FORMS)):
            test = mark(test)
        return test
    return parametrised


def walk_case(case_id, causal, window, h, hkv, dv, dtype, form):
    """Forward and every backward form over the table of live tiles
    against the same kernels over the whole rectangle under the predicate
    (``live_tiles=None``: what a table past the SMEM limit falls back to,
    and what the parent ran): a dead step added nothing, so ``o``,
    ``lse``, ``dq``, ``dk``, ``dv`` are equal to the bit, streamed
    forward and resident alike; in float32 equal to the digest pinned
    from the parent's kernels; and within the standing tolerances of the
    plain attention and the blockwise scan."""
    case = (causal, window, h, hkv, dv, dtype)
    plan = _walk_plan(*case, form)
    assert plan.live_tiles is not None
    assert plan.tiles_grid == plan.tiles_live == \
        2 * h * len(plan.live_tiles)
    table = _walk_results(case, form)
    others = ["the rectangle"]
    if form == _WALK_FORMS[0]:   # the forward knows no backward form
        others.append("the streamed forward's table")
    names = ("o", "lse", "dq", "dk", "dv")
    for which in others:
        for name, a, r in zip(names, table,
                              _walk_results(case, form, which)):
            assert a.dtype == r.dtype and a.shape == r.shape, name
            assert np.asarray(a).tobytes() == np.asarray(r).tobytes(), (
                f"{name}: the table against {which}")
    mask, heads = case_id.split("-")[:2]
    if dtype == jnp.float32:
        assert _walk_digest(table) == _WALK_PARENT_DIGESTS[mask, heads][
            _WALK_FORMS.index(form)], "the parent's kernels, to the bit"
    err, ref = _walk_oracles(case)
    assert err <= (2e-5 if dtype == jnp.float32 else 3e-2), err
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for name, a, r in zip(names[2:], table[2:], ref):
        got, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.abs(got - r).max() <= tol * np.abs(r).max(), name


@walk_cases(jnp.float32)
def test_the_table_walk_is_the_rectangle_to_the_bit(
        request, causal, window, h, hkv, dv, dtype, form):
    walk_case(request.node.callspec.id, causal, window, h, hkv, dv, dtype,
              form)
