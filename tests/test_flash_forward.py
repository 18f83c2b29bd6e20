"""The flash forward's forms on the CPU (the Pallas interpreter): the row
statistics across tiles, values of another width than the keys, and the
forward that holds a kv row resident against the one that streams it.
(Moved whole from ``tests/test_flash_attention.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_oracle import (folded_plan, force, forward_call,
                          grouped_blockwise, out_and_grads, pallas_calls,
                          vmem_limits)
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import local_attention


# (id, causal, window, q heads, kv heads, block_q, block_k) at S=256, d=64:
# at least 2 Q tiles and 4 K tiles everywhere.
_ROW_STAT_CASES = [
    ("noncausal", False, None, 2, 2, 128, 64),
    ("causal", True, None, 2, 2, 128, 64),
    ("window96", True, 96, 2, 2, 128, 64),
    ("mqa_4_on_1", True, None, 4, 1, 128, 64),
    ("mqa_4_on_1_noncausal", False, None, 4, 1, 128, 64),
    ("window24_under_a_k_tile", True, 24, 2, 2, 128, 64),
    ("causal_4x8_tiles", True, None, 2, 2, 64, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,bq,bk", [c[1:] for c in _ROW_STAT_CASES],
    ids=[c[0] for c in _ROW_STAT_CASES],
)
def test_row_statistics_across_tiles(causal, window, h, hkv, bq, bk, dtype):
    """The per-query statistics (running max and sum, the saved
    logsumexp, the backward's delta) over several K tiles: scores GROW
    along the key axis, so the running max changes in every tile and
    every earlier partial sum is rescaled each time.  Output, saved lse
    and dq/dk/dv against float32 references on the same inputs."""
    from horovod_tpu.ops.flash_attention import _flash_fwd_kernel

    b, s, d = 1, 256, 64
    rng = np.random.RandomState(5)
    f32 = jnp.float32
    # q positive, k a ramp along the sequence: q.k rises by about 3 per
    # 64 keys after the 1/8 scale
    q = jnp.asarray(0.5 + 0.3 * np.abs(rng.randn(b, s, h, d)), dtype)
    ramp = (np.arange(s) / s)[None, :, None, None]
    k = jnp.asarray(2.0 * ramp + 0.1 * rng.randn(b, s, hkv, d), dtype)
    v = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    wgt = jnp.asarray(rng.randn(b, s, h, d), f32)
    qf, kf, vf = (x.astype(f32) for x in (q, k, v))
    rep = lambda t: jnp.repeat(t, h // hkv, axis=2)
    scale = d ** -0.5

    def scores(q, k):
        st = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) * scale
        q_pos = jnp.arange(s)[:, None]
        k_pos = jnp.arange(s)[None, :]
        if causal:
            st = jnp.where(k_pos > q_pos, -jnp.inf, st)
        if window is not None:
            st = jnp.where(k_pos < q_pos - (window - 1), -jnp.inf, st)
        return st

    def reference(q, k, v):
        if window is None:
            return local_attention(q, rep(k), rep(v), causal=causal)
        p = jax.nn.softmax(scores(q, k), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, rep(v))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk)

    out, got_g = out_and_grads(flash, wgt, q, k, v)
    want, want_g = out_and_grads(reference, wgt, qf, kf, vf)

    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, s, d)
    folded = fold(q), fold(k), fold(v)
    _, lse = _flash_fwd_kernel(
        *folded, folded_plan(*folded, causal, bq, bk, h, hkv, window),
        scale, True)
    want_lse = jax.nn.logsumexp(scores(qf, kf), axis=-1).reshape(-1, s)
    # the running max moved in every K tile of the last row
    last_row = np.asarray(scores(qf, kf))[0, 0, -1]
    tile_max = last_row.reshape(-1, bk).max(-1)
    live = np.isfinite(tile_max)
    assert live.sum() >= (1 if window else 4)
    assert np.all(np.diff(tile_max[live]) > 0)

    # float32: rounding of sums only (seen: 4e-7 out, 3e-6 gradients);
    # bfloat16: the outputs, and the o that the backward's delta reads,
    # are rounded to 8 bits of mantissa (seen: 0.004 out, 0.017 dq)
    tol, grad_tol = (5e-6, 2e-5) if dtype == jnp.float32 else (1e-2, 3e-2)
    assert out.dtype == dtype and lse.dtype == f32
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=2e-5, rtol=2e-5)

    def close(name, a, r, tol):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert a.shape == r.shape, name
        err = np.abs(a - r).max() / np.abs(r).max()
        assert err <= tol, f"{name}: {err:.3g} of the largest entry"

    close("out", out, want, tol)
    for name, a, r in zip(("dq", "dk", "dv"), got_g, want_g):
        close(name, a, r, grad_tol)


# ------------------------------------------ values wider than keys (PR 42)
# The value width is the values' own: differential attention reads values
# twice as wide as its keys (phi4mf_train_s8192: 40 query rows of 64 over 20
# key/value rows, values 128).  Grouped heads 2:1 as there, tiles 16 x 8.
_WIDTH_MASKS = [
    ("causal", True, None),
    ("causal_window_20", True, 20),   # a multiple of neither tile
    ("noncausal", False, None),
]


_WIDTH_SIZES = 2, 64, 4, 2, 16, 16, 8   # b, s, h, hkv, d, bq, bk


def _fold(x):
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


@functools.cache
def _width_oracles(dv, causal, window):
    """The operands of a (value width, mask) and what every backward form
    is compared with, computed once for the three: ``local_attention``'s
    output and the blockwise scan's dq, dk, dv (folded) from the flash
    forward's ``o`` and ``lse``."""
    from horovod_tpu.ops import flash_attention as fa

    b, s, h, hkv, d, bq, bk = _WIDTH_SIZES
    rng = np.random.RandomState(13)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(b, s, heads, width) * 0.7, jnp.float32)
    q, k, v, do = mk(h, d), mk(hkv, d), mk(hkv, dv), mk(h, dv)
    rep = lambda x: jnp.repeat(x, h // hkv, axis=2)
    plain = local_attention(q, rep(k), rep(v), causal=causal, window=window)
    folded = _fold(q), _fold(k), _fold(v)
    o, lse = fa._flash_fwd_kernel(
        *folded, folded_plan(*folded, causal, bq, bk, h, hkv, window),
        d ** -0.5, True)
    want = grouped_blockwise(*folded, o, lse, _fold(do), causal, d ** -0.5,
                             bk, window, h, hkv)
    return (q, k, v, do), plain, want


@pytest.mark.parametrize("backward",
                         ["one_kernel", "dq_resident", "two_passes"])
@pytest.mark.parametrize("causal,window", [c[1:] for c in _WIDTH_MASKS],
                         ids=[c[0] for c in _WIDTH_MASKS])
@pytest.mark.parametrize("dv", [32, 8], ids=["values_2d", "values_half_d"])
def test_values_of_another_width_than_the_keys(monkeypatch, dv, causal,
                                               window, backward):
    """``v`` twice and half as wide as ``q`` and ``k`` (16): the forward
    against ``local_attention`` (an einsum, which never asked for one
    width), and dq, dk, dv of each of the three backward forms against the
    blockwise scan at the values' own width; the scale is the keys'."""
    names = force(monkeypatch, backward)
    b, s, h, hkv, d, bq, bk = _WIDTH_SIZES
    (q, k, v, do), plain, want = _width_oracles(dv, causal, window)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=bq, block_k=bk)

    out = flash(q, k, v)
    assert out.shape == (b, s, h, dv)
    np.testing.assert_allclose(out, plain, atol=2e-5)
    grad = jax.grad(lambda *a: (flash(*a) * do).sum(), argnums=(0, 1, 2))
    assert list(pallas_calls(jax.make_jaxpr(grad)(q, k, v).jaxpr)) \
        == ["flash_fwd"] + names
    for name, a, r in zip(("dq", "dk", "dv"), jax.jit(grad)(q, k, v), want):
        assert a.shape == (b, s, h if name == "dq" else hkv,
                           dv if name == "dv" else d), name
        np.testing.assert_allclose(_fold(a), r, atol=2e-5, err_msg=name)


# (id, causal, window, h, hkv, s, d, dv, bq, bk)
_FWD_FORM_CASES = [
    ("causal", True, None, 2, 2, 64, 16, 16, 32, 16),
    ("noncausal", False, None, 2, 2, 64, 16, 16, 32, 16),
    ("window_20", True, 20, 2, 2, 64, 16, 16, 32, 8),
    ("window_of_one_tile", True, 8, 2, 2, 64, 16, 16, 16, 16),
    ("mqa_4_on_1", True, None, 4, 1, 64, 16, 16, 32, 16),
    ("gqa_7_to_a_kv_head", True, None, 14, 2, 64, 16, 16, 32, 16),
    ("gqa_7_window", True, 24, 7, 1, 96, 16, 16, 32, 16),
    ("values_128_on_keys_64", True, None, 4, 2, 64, 64, 128, 32, 16),
    ("values_128_on_keys_64_window", True, 20, 4, 2, 64, 64, 128, 32, 16),
    ("values_narrower", False, None, 2, 1, 64, 16, 8, 32, 16),
    ("one_tile", True, None, 2, 1, 32, 16, 16, 32, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize(
    "causal,window,h,hkv,s,d,dv,bq,bk", [c[1:] for c in _FWD_FORM_CASES],
    ids=[c[0] for c in _FWD_FORM_CASES])
def test_resident_forward_equals_streamed_to_the_bit(
        monkeypatch, causal, window, h, hkv, s, d, dv, bq, bk, dtype):
    """The forward with a kv row's K and V resident in VMEM (whole-row
    blocks whose index moves once a kv row, the body slicing its tile)
    against the streamed tiles it replaced wherever a row fits (forced
    here by a limit no row fits, as ``vmem_limits`` forces the two
    backward passes): the same tiles, the same float32 sums in the same
    order, so ``o`` and ``lse`` are equal to the bit; and against the
    plain attention."""
    from horovod_tpu.ops import flash_attention as fa

    b = 2
    rng = np.random.RandomState(5)
    mk = lambda heads, width: jnp.asarray(
        rng.randn(b * heads, s, width) * 0.7, dtype)
    q, k, v = mk(h, d), mk(hkv, d), mk(hkv, dv)

    def forward(resident):
        plan = folded_plan(q, k, v, causal, bq, bk, h, hkv, window)
        assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (resident, 0)
        run = lambda: fa._flash_fwd_kernel(q, k, v, plan, d ** -0.5, True)
        call, = pallas_calls(jax.make_jaxpr(run)().jaxpr, forward_call)
        return call, run()

    call, resident = forward(True)
    assert call == (s, None)
    vmem_limits(monkeypatch, 0)
    call, streamed = forward(False)
    assert call == (bk, None)
    for part, a, t in zip(("o", "lse"), resident, streamed):
        assert a.dtype == t.dtype and a.shape == t.shape
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(t, np.float32),
            err_msg=f"{part}: resident against streamed")
    unfold = lambda x: x.reshape(b, -1, s, x.shape[-1]).transpose(0, 2, 1, 3)
    kx, vx = (jnp.repeat(unfold(x).astype(jnp.float32), h // hkv, axis=2)
              for x in (k, v))
    want = jax.jit(functools.partial(
        local_attention, causal=causal, window=window))(
            unfold(q).astype(jnp.float32), kx, vx)
    err = np.abs(np.asarray(unfold(resident[0]), np.float32)
                 - np.asarray(want)).max()
    assert err <= (2e-5 if dtype == jnp.float32 else 3e-2), err
