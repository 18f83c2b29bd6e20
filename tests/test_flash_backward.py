"""The flash backward as one kernel, in both its forms, against the two
passes and the blockwise scan (the Pallas interpreter on the CPU).
(Moved whole from ``tests/test_flash_attention.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_oracle import (folded_plan, grouped_blockwise, pallas_calls,
                          vmem_limits)


# (id, causal, window, q heads, kv heads, S, block_q, block_k, scale)
_BWD_PATH_CASES = [
    ("noncausal", False, None, 2, 2, 64, 16, 16, None),
    ("causal", True, None, 2, 2, 64, 16, 16, None),
    ("window24_of_64", True, 24, 2, 2, 64, 16, 16, None),
    ("gqa_4_to_a_kv_head", True, None, 8, 2, 64, 16, 16, None),
    ("gqa_noncausal", False, None, 8, 2, 64, 16, 16, None),
    ("mqa_4_on_1", True, None, 4, 1, 64, 16, 16, None),
    ("stated_scale", True, None, 4, 1, 64, 16, 16, 0.015625),
    ("nq_3_nk_6", True, None, 2, 2, 48, 16, 8, None),
    ("gqa_window_nq_2_nk_8", True, 20, 4, 2, 64, 32, 8, 0.3),
    ("one_tile", True, None, 2, 1, 32, 32, 32, None),
]


def _backward(operands, plan, scale):
    """dq, dk, dv of the Pallas backward under ``plan``, and its
    ``pallas_call``s by name."""
    from horovod_tpu.ops import flash_attention as fa

    run = lambda: fa._flash_bwd_pallas(*operands, plan, scale, True)
    return run(), list(pallas_calls(jax.make_jaxpr(run)().jaxpr))


@functools.cache
def _case(causal, window, h, hkv, s, bq, bk, scale, dtype):
    """What both one-kernel forms of a (shape, dtype) are compared with,
    computed once: the operands with the forward's ``o`` and ``lse``, the
    plan the shape takes, the two passes' gradients (the plan made under
    a VMEM limit of 0, as the gate reads it) and the blockwise scan's."""
    from horovod_tpu.ops import flash_attention as fa

    b, d = 2, 16
    rng = np.random.RandomState(11)
    mk = lambda heads: jnp.asarray(rng.randn(b * heads, s, d) * 0.7, dtype)
    q, do, k, v = mk(h), mk(h), mk(hkv), mk(hkv)
    scale = d ** -0.5 if scale is None else scale
    plan = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
    o, lse = fa._flash_fwd_kernel(q, k, v, plan, scale, True)
    operands = (q, k, v, o, lse, do)
    with pytest.MonkeyPatch.context() as monkeypatch:
        vmem_limits(monkeypatch, 0)
        two_passes = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
    assert (two_passes.bwd_form, two_passes.bwd_vmem_bytes) == (
        "two_passes", 0)
    two, names = _backward(operands, two_passes, scale)
    assert names == ["flash_bwd_dkdv", "flash_bwd_dq"]
    ref = grouped_blockwise(q, k, v, o, lse, do, causal, scale, bk, window,
                            h, hkv)
    return operands, plan, scale, two, ref


@pytest.mark.parametrize("form", ["dkdv_resident", "dq_resident"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,s,bq,bk,scale", [c[1:] for c in _BWD_PATH_CASES],
    ids=[c[0] for c in _BWD_PATH_CASES],
)
def test_one_kernel_backward_matches_two_passes_and_oracle(
        causal, window, h, hkv, s, bq, bk, scale, dtype, form):
    """The backward as one kernel (dq, dk and dv from one p and ds a
    tile), in both its forms (a kv row's dk and dv accumulators resident
    under the Q tiles; a kv row's dq resident under the K tiles, which is
    what 8192 keys at head size 256 take), against the two passes it
    replaced and against the blockwise scan.  One kernel and two passes
    add the same float32 terms in the same order (a dk row block gets
    its terms by query head, then Q tile, a dq block by K tile, in all
    three), so they agree to the bit; the scan sums in another order."""
    from dataclasses import replace

    operands, plan, scale, two, ref = _case(
        causal, window, h, hkv, s, bq, bk, scale, dtype)
    assert plan.bwd_form == "dkdv_resident"
    one, names = _backward(operands, replace(plan, bwd_form=form), scale)
    assert names == ["flash_bwd_dkdv"]
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    for name, a, t, r in zip(("dq", "dk", "dv"), one, two, ref):
        assert a.dtype == dtype and a.shape == r.shape, name
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(t, np.float32),
            err_msg=f"{name}: one kernel against two passes")
        for which, got in (("one kernel", a), ("two passes", t)):
            got, want = np.asarray(got, np.float32), np.asarray(r)
            err = np.abs(got - want).max() / np.abs(want).max()
            assert err <= tol, (
                f"{name}, {which}: {err:.3g} of the largest entry")
