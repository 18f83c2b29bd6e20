"""The Mamba-1 selective scan (``ops/selective_scan.py``) through the
Pallas interpreter against the token-by-token recurrence, forward and
every gradient, at lengths that are no multiple of its time block; what
it keeps and what it refuses; and differential attention
(``models/transformer.py:_attend_differential``) against the dense
formula, through the reference attention and the flash kernels, for a
window, a full and a cross layer.  On the CPU at small sizes.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import (_attend_differential,
                                            differential_lambda_init, gpt)
from horovod_tpu.ops.selective_scan import kept_mib, selective_scan

SEQ = 32


def small_model(**overrides):
    """8 sub-heads over 4, a window of 8; two layers are enough to carry
    the configuration ``_attend_differential`` reads."""
    return gpt("phi-4-mini-flash-reasoning", **{**dict(
        num_layers=2, layer_types=("sliding_attention", "full_attention"),
        shared_kv_layer=None, memory_layer=None, vocab_size=96, emb_dim=64,
        num_heads=8, num_kv_heads=4, attention_window=8, max_len=64,
        flash_block_q=16, flash_block_k=4, dtype=jnp.float32), **overrides})


# ------------------------------------------------------- the scan kernel


def _recurrence(u, dt, A, B, C, D):
    def one(u, dt, B, C):
        def step(h, x):
            u_t, dt_t, b_t, c_t = x
            h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * u_t)[:, None] * b_t
            return h, (h * c_t).sum(-1) + D * u_t

        return jax.lax.scan(step, jnp.zeros(A.shape), (u, dt, B, C))[1]

    return jax.vmap(one)(u, dt, B, C)


def _scan_inputs(b, s, c, n):
    ks = jax.random.split(jax.random.PRNGKey(3), 7)
    return (jax.random.normal(ks[0], (b, s, c)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, c)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (c, n))),
            jax.random.normal(ks[3], (b, s, n)),
            jax.random.normal(ks[4], (b, s, n)),
            jax.random.normal(ks[5], (c,))), jax.random.normal(
                ks[6], (b, s, c))


# 40 tokens pad to 48 (one time block); 200 to two blocks of 128, the
# state and its gradient crossing the boundary; 256 channels are one
# block of two lane groups
@pytest.mark.parametrize("b,s,c", [(2, 40, 128), (1, 200, 256)])
def test_scan_kernel_matches_the_recurrence(b, s, c):
    """Forward and every gradient, through the interpreter, at lengths
    that are no multiple of the kernel's time block."""
    args, weight = _scan_inputs(b, s, c, 16)
    np.testing.assert_allclose(selective_scan(*args), _recurrence(*args),
                               atol=2e-5)
    grads = lambda fn: jax.grad(
        lambda *a: (fn(*a) * weight).sum(), argnums=tuple(range(6)))(*args)
    for name, got, want in zip("u dt A B C D".split(),
                               grads(selective_scan), grads(_recurrence)):
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-5 * float(jnp.abs(want).max()),
            err_msg=name)


def test_scan_keeps_its_states_and_refuses_what_does_not_fit():
    # a state of 16 x 5120 float32 every 128 tokens
    assert kept_mib(1, 8192, 5120, 16) == 64 * 16 * 5120 * 4 / 2 ** 20
    assert kept_mib(2, 200, 256, 16) == 2 * 2 * 16 * 256 * 4 / 2 ** 20
    args, _ = _scan_inputs(1, 16, 128, 16)
    with pytest.raises(ValueError, match="do not belong together"):
        selective_scan(args[0], args[1][:, :8], *args[2:])
    from horovod_tpu.ops import selective_scan as module
    with pytest.raises(ValueError, match="channels=96 is not a multiple"):
        module._check_tiles(96, 16)
    with pytest.raises(ValueError, match="state=12"):
        module._check_tiles(128, 12)


# ------------------------------------------------ differential attention


@pytest.mark.parametrize("kind,window", [("sliding_attention", 8),
                                         ("full_attention", None),
                                         ("cross_attention", None)])
@pytest.mark.parametrize("attention", ["reference", "flash"])
def test_differential_attention_matches_the_dense_formula(attention, kind,
                                                          window):
    """``(1 - lam0) RMSNorm(P1 V - lam P2 V)`` with the maps written out
    densely: 8 sub-heads over 4, so query pair p reads K/V pair p // 2 and
    both of its maps read that pair's values, 2 hd wide.  The output and
    the gradient into q, k, v, the four lambda vectors and the sub-norm's
    scale; through the flash schedule that is ONE call over the 8
    sub-heads on 4 key rows with values 2 hd wide."""
    cfg = small_model(attention_impl=attention).cfg
    b, s, hd = 2, SEQ, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    q = jax.random.normal(ks[0], (b, s, 8, hd))
    k = jax.random.normal(ks[1], (b, s, 4, hd))
    v = jax.random.normal(ks[2], (b, s, 4, hd))
    lambdas = [0.5 * jax.random.normal(ks[3 + i], (hd,)) for i in range(4)]
    scale = 1.0 + 0.3 * jax.random.normal(ks[7], (2 * hd,))
    weight = jax.random.normal(ks[8], (b, s, 4, 2 * hd))
    lam0 = differential_lambda_init(17)
    t, u = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (u <= t) if window is None else (u <= t) & (t - u < window)

    def subln(t, scale):
        return t * jax.lax.rsqrt(
            jnp.mean(t * t, axis=-1, keepdims=True) + 1e-5) * scale

    def program(q, k, v, lambdas, scale):
        return _attend_differential(
            cfg, q, k, v, jnp.arange(s), kind, lambdas=lambdas,
            subln=lambda t: subln(t, scale), lambda_init=lam0)

    def dense(q, k, v, lambdas, scale):
        lam = (jnp.exp(lambdas[0] @ lambdas[1])
               - jnp.exp(lambdas[2] @ lambdas[3]) + lam0)
        want = []
        for p in range(4):
            wide = jnp.concatenate([v[:, :, 2 * (p // 2)],
                                    v[:, :, 2 * (p // 2) + 1]], axis=-1)
            maps = [jax.nn.softmax(jnp.where(seen, jnp.einsum(
                "bqd,bkd->bqk", q[:, :, 2 * p + j], k[:, :, 2 * (p // 2) + j])
                / 4.0, -jnp.inf), axis=-1) for j in range(2)]
            want.append(subln(maps[0] @ wide - lam * (maps[1] @ wide), scale)
                        * (1.0 - lam0))
        return jnp.stack(want, axis=2)

    args = (q, k, v, lambdas, scale)
    (out, got), (dense_out, want) = (  # one trace a side
        jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *b: (f(*b) * weight).sum(),
            argnums=(0, 1, 2, 3, 4))(*a)))(*args)
        for f in (program, dense))
    np.testing.assert_allclose(out, dense_out, atol=2e-5)
    for name, a, r in zip(("q", "k", "v", "lambdas", "scale"), got, want):
        for leaf, want_leaf in zip(jax.tree.leaves(a), jax.tree.leaves(r)):
            np.testing.assert_allclose(leaf, want_leaf, atol=5e-5,
                                       rtol=2e-3, err_msg=name)
    assert lam0 == pytest.approx(0.8 - 0.6 * math.exp(-5.1))
