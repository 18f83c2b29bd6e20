"""The Mamba-2 selective state-space scan, chunked (state-space duality).

Per head, with a state ``S`` of ``head_dim x state`` that starts at zero::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

computed without a loop over single tokens and without a tensor of
sequence x sequence: the sequence is cut into chunks of ``chunk`` tokens.
Inside a chunk the recurrence unrolls into a masked ``chunk x chunk``
product (``y_i = sum_{j<=i} (C_i . B_j) exp(a_j+1..i) dt_j x_j``: matmuls,
the MXU's work); across chunks only the state at each chunk's end is
carried, by a recurrence over the chunks (32 steps for 8192 tokens at
chunk 256, written as one small triangular product), and read out
through ``C``.

The matmul form through XLA, differentiated by plain autodiff: no Pallas
kernel and no ``custom_vjp`` yet.  ``dt``, ``A``, the cumulated log-decays
and the states are float32 whatever ``x`` is; the operands of the
matmuls take ``x``'s dtype and accumulate in float32.  The
``chunk x chunk`` tensors (one per chunk and head: 0.5 GiB each in
float32 at 8192 tokens, 64 heads, chunk 256) are made for all chunks at
once: the TPU compiler fuses their making into the products' operands,
and cutting them into passes of a few chunks, each recomputed in the
backward pass, saved 50 MiB of 10.86 GiB on the cell that runs this (my
sandbox compile, PR 28) for a third forward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import scopes


def _chunk_outputs(x, dt, a_cs, B, C, s_in):
    """``y`` without the skip term for all ``m`` chunks: the masked
    intra-chunk product plus the read-out of the state each chunk
    starts from.

    ``x`` [m, L, g, r, p]; ``dt``, ``a_cs`` [m, L, g, r] float32
    (``a_cs`` the log-decay cumulated from the chunk's start, inclusive);
    ``B``, ``C`` [m, L, g, n]; ``s_in`` [m, g, r, p, n] float32."""
    length = x.shape[1]
    cb = jnp.einsum("mign,mjgn->mgij", C, B,
                    preferred_element_type=jnp.float32)
    a_t = jnp.moveaxis(a_cs, 1, -1)                       # [m, g, r, L]
    seg = a_t[..., :, None] - a_t[..., None, :]           # decay j -> i
    causal = jnp.tril(jnp.ones((length, length), bool))
    # -inf before the exp, not a zero after it: above the diagonal the
    # difference is positive and its exp may overflow
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    m = cb[:, :, None] * decay * jnp.moveaxis(dt, 1, -1)[..., None, :]
    y = jnp.einsum("mgrij,mjgrp->migrp", m.astype(x.dtype), x,
                   preferred_element_type=jnp.float32)
    off = jnp.einsum("mign,mgrpn->migrp", C, s_in.astype(x.dtype),
                     preferred_element_type=jnp.float32)
    return y + off * jnp.exp(a_cs)[..., None]


def ssd_scan(x, dt, A, B, C, D, chunk: int):
    """``x`` [batch, seq, heads, head_dim]; ``dt`` [batch, seq, heads]
    (positive: after its softplus); ``A`` [heads] (negative); ``B``, ``C``
    [batch, seq, groups, state], each group shared by ``heads // groups``
    heads; ``D`` [heads].  Returns ``y`` like ``x``.  ``seq`` must be a
    multiple of ``chunk``."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    if s % chunk:
        raise ValueError(
            f"ssd_scan: seq={s} is not a multiple of chunk={chunk}")
    if h % g:
        raise ValueError(f"ssd_scan: heads={h} not a multiple of groups={g}")
    r, c = h // g, s // chunk

    with jax.named_scope(scopes.SSD_SCAN):
        f32 = jnp.float32
        xc = x.reshape(b * c, chunk, g, r, p)
        dtc = dt.astype(f32).reshape(b * c, chunk, g, r)
        Bc = B.astype(x.dtype).reshape(b * c, chunk, g, n)
        Cc = C.astype(x.dtype).reshape(b * c, chunk, g, n)
        a_cs = jnp.cumsum(dtc * A.astype(f32).reshape(g, r), axis=1)
        a_end = a_cs[:, -1]                               # [m, g, r]

        # The state each chunk adds: every token's dt x (outer) B decayed
        # to the chunk's end.
        w = jnp.exp(a_end[:, None] - a_cs) * dtc
        added = jnp.einsum(
            "mjgn,mjgrp->mgrpn", Bc, (xc * w[..., None]).astype(x.dtype),
            preferred_element_type=f32)

        # The recurrence over chunks, S_c = exp(a_end[c-1]) S_{c-1} +
        # added[c-1] from zero, unrolled into one product with the
        # [chunks, chunks] matrix of decays from the end of chunk c' to
        # the start of chunk c (float32, full precision: the chunk
        # states are the one thing the scan carries far).
        added = added.reshape(b, c, g, r, p, n)
        upto = jnp.cumsum(a_end.reshape(b, c, g, r), axis=1)
        upto = jnp.concatenate([jnp.zeros_like(upto[:, :1]), upto], axis=1)
        span = upto[:, :-1, None] - upto[:, None, 1:]     # [b, c, c', g, r]
        earlier = jnp.tril(jnp.ones((c, c), bool), -1)[:, :, None, None]
        between = jnp.exp(jnp.where(earlier, span, -jnp.inf))
        s_in = jnp.einsum("bzcgr,bcgrpn->bzgrpn", between, added,
                          precision=jax.lax.Precision.HIGHEST)
        s_in = s_in.reshape(b * c, g, r, p, n)

        y = _chunk_outputs(xc, dtc, a_cs, Bc, Cc, s_in)
        y = y.reshape(b, s, h, p)
        y = y + D.astype(f32)[:, None] * x.astype(f32)
        return y.astype(x.dtype)
