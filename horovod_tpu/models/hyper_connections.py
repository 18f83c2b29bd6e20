"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): a residual stream of ``n`` copies
that every sub-layer reads through learned weights and writes back
through a doubly stochastic ``n x n`` map, all three made per token from
the streams themselves.

The stream is one array ``[batch, seq, n * C]``, stream ``j`` the channels
``j C .. (j + 1) C - 1`` (``vec(X[t])`` of the papers), in the model's
compute dtype.  One sub-layer, ``F`` the mixer or the feed-forward with
its norm, per token::

    r                  = RMSNorm_g(vec(X))             over all n C channels
    [p ; q ; R]        = r phi                         n, n and n^2 numbers
    H_pre              = sigmoid(a_pre p + b_pre)
    H_post             = 2 sigmoid(a_post q + b_post)
    H_res              = Sinkhorn(exp(clip(a_res R + b_res, lo, hi)))
    u                  = sum_j H_pre[j] X[j]                       read-out
    X'[i]              = sum_j H_res[i, j] X[j] + H_post[i] F(u)   write-back

:func:`coefficients` is the first five lines, float32 whatever the
compute dtype, with the tokens on the minor axis (``[n, batch, seq]`` and
``[n, n, batch, seq]``): Sinkhorn's sums over rows and columns are then
sums of whole planes, not of four lanes.  :func:`read_out` and
:func:`write_back` are the last two; the write-back adds up in float32
before it stores.  ``models/transformer.py:block_math`` is the one caller
of all three; each traces under a scope of its own (``hc_coeff``,
``hc_read``, ``hc_write``), outside the halves' ``attn`` and ``mlp``.
Plain ``jax.numpy`` under autodiff: twenty Sinkhorn steps keep ``seq x
16`` floats each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import scopes


def sinkhorn(logits, iters: int, eps: float):
    """``logits`` float32 ``[n, n, ...]`` (row, column, then anything) ->
    ``exp(logits)`` after ``iters`` rounds of Sinkhorn-Knopp: every
    column divided by its sum plus ``eps``, then every row by its.  Rows
    sum to 1 after the last round and columns up to the iteration's
    residue."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (m.sum(axis=0, keepdims=True) + eps)
        m = m / (m.sum(axis=1, keepdims=True) + eps)
    return m


def stochastic_err(res):
    """The largest ``|rowsum - 1|`` or ``|colsum - 1|`` of ``res``
    ``[n, n, ...]`` over everything behind the two axes."""
    return jnp.maximum(jnp.abs(res.sum(axis=1) - 1.0).max(),
                       jnp.abs(res.sum(axis=0) - 1.0).max())


def coefficients(x, n: int, *, scale, phi, b, alpha, norm_eps: float,
                 iters: int, eps: float, clamp: tuple):
    """The three maps of one sub-layer from the stream ``x`` ``[batch,
    seq, n C]``: ``(pre [n, batch, seq], post [n, batch, seq], res [n, n,
    batch, seq])``, float32.  ``scale`` ``[n C]`` is the norm's weight,
    ``phi`` ``[n C, n^2 + 2 n]`` and ``b`` ``[n^2 + 2 n]`` the projection
    (``pre``'s ``n`` columns first, then ``post``'s, then ``res``'s ``n^2``
    row by row), ``alpha`` the three gains in that order.

    The norm's weight goes into the matrix and its root behind the
    matmul (``(x * g / rms) phi = (x (g phi)) / rms``): the one large
    operand the matmul reads is the stream itself, not a float32 copy of
    it."""
    with jax.named_scope(scopes.HC_COEFF):
        x32 = x.astype(jnp.float32)
        inv_rms = lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1) + norm_eps)
        weights = scale.astype(jnp.float32)[:, None] * phi.astype(jnp.float32)
        raw = jnp.einsum("bsc,ck->kbs", x32, weights,
                         precision=lax.Precision.HIGHEST) * inv_rms
        b = b.astype(jnp.float32)[:, None, None]
        a_pre, a_post, a_res = (alpha.astype(jnp.float32)[i]
                                for i in range(3))
        pre = jax.nn.sigmoid(a_pre * raw[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a_post * raw[n:2 * n] + b[n:2 * n])
        logits = jnp.clip(a_res * raw[2 * n:] + b[2 * n:], *clamp)
        res = sinkhorn(logits.reshape(n, n, *logits.shape[1:]), iters, eps)
        return pre, post, res


def _streams(x, n: int):
    """The ``n`` streams of ``x`` ``[batch, seq, n C]``, each ``[batch,
    seq, C]``: slices on whole lanes where ``C`` is a multiple of 128."""
    width = x.shape[-1] // n
    return [x[..., j * width:(j + 1) * width] for j in range(n)]


def read_out(x, pre):
    """``u = sum_j pre[j] X[j]``: ``x`` ``[batch, seq, n C]`` and ``pre``
    ``[n, batch, seq]`` -> ``[batch, seq, C]`` in ``x``'s dtype, summed in
    float32."""
    with jax.named_scope(scopes.HC_READ):
        n = pre.shape[0]
        u = sum(pre[j][..., None] * stream.astype(jnp.float32)
                for j, stream in enumerate(_streams(x, n)))
        # ``u`` is made once and stored: without the barrier XLA folds the
        # sum into each pass of the norm that reads it, which then reads
        # the ``n`` streams per pass, and a device trace charges them to
        # the half's scope and not to this one
        return lax.optimization_barrier(u.astype(x.dtype))


def write_back(x, y, post, res):
    """``X'[i] = sum_j res[i, j] X[j] + post[i] y``: the stream ``x``
    ``[batch, seq, n C]``, the branch's output ``y`` ``[batch, seq, C]``,
    ``post`` ``[n, batch, seq]`` and ``res`` ``[n, n, batch, seq]`` -> the
    next stream in ``x``'s dtype, each of its copies summed in float32
    before the store."""
    with jax.named_scope(scopes.HC_WRITE):
        n = post.shape[0]
        streams = [s.astype(jnp.float32) for s in _streams(x, n)]
        # the branch's output as its last matmul stored it (and, backward,
        # its gradient as one array, not the ``n`` streams' gradients
        # inside the matmul's operand): the read-out's barrier says why
        y32 = lax.optimization_barrier(y).astype(jnp.float32)
        return jnp.concatenate([
            (sum(res[i, j][..., None] * streams[j] for j in range(n))
             + post[i][..., None] * y32).astype(x.dtype)
            for i in range(n)], axis=-1)


def publish_stats(hc_stats, registry=None) -> dict:
    """The collection ``hc_stats`` of a step's carry (each connection's
    :func:`stochastic_err` of its last step) as the gauge
    ``hc.stochastic_err``, the largest over the sub-layers; returns what
    it set."""
    from ..obs.registry import get_registry  # noqa: PLC0415

    registry = registry or get_registry()
    leaves = jax.tree.leaves(hc_stats)
    if not leaves:
        return {}
    worst = max(float(leaf) for leaf in leaves)
    registry.gauge("hc.stochastic_err").set(worst)
    return {"stochastic_err": worst}
