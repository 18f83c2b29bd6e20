"""What stands between an attention layer's fused ``q/k/v`` matmul and
the flash kernels (``models/transformer.py:attention_mixer``), as one
Pallas TPU kernel pair under a ``custom_vjp``.

From ``fused = qkv(h)`` ``[batch, seq, (heads + 2 kv heads) head_dim]``,
whose lanes are the heads of ``q``, then of ``k``, then of ``v``::

    q, k = rope(round(rms_norm(q))), rope(round(rms_norm(k)))
    q, k, v -> [batch heads, seq, head_dim]     scope ``attn_prep``

the norm over each head's channels in float32 with its learned scale
(``x (rsqrt(mean(x^2) + eps) scale)``, as ``flax.linen.RMSNorm``
multiplies), rounded to ``fused``'s dtype, the rotation by position in
float32 (``ops/rope.py``, split halves) and rounded again, and all three
head-major, as ``ops/flash_attention.py:_flash`` takes them.  XLA
computes that as a chain of fusions with float32 arrays of ``[seq,
heads, head_dim]`` between them and a transposed copy of each of the
three at its end; here it is one pass over ``fused`` forward
(``attn_prep_fwd``) and one backward (``attn_prep_bwd``), which keeps
``fused`` alone and forms the statistics again on the tile.

A program takes ``[token tile, heads a program]`` of one of the three
streams: the grid is (batch, token tiles, head blocks of ``q``, then of
``k``, then of ``v``).  A head is a whole number of 128-lane blocks of
``fused``, so a program's block is a block of the one array and nothing
is sliced or reshaped in front of the call; the three outputs are three
arrays ``[batch, heads, seq, head_dim]``, each standing still while a
program works on another (their index maps are clamped: Pallas moves a
block only when its index changes, which is why the last grid axis is
sequential).  The rotation is ``x [c | c] + swap_halves(x) [-s | s]``,
the halves swapped by one roll over the lanes, the two tables widened to
``[seq, head_dim]`` outside the call; a ``v`` head is copied.  Backward
the three cotangents come in head-major and ONE ``d fused`` goes out
through the forward's index maps, with the two scales' gradients as one
partial sum a program, which XLA adds up.

Which calls take the pair is :func:`plan`'s to say, from what the caller
can see; the caller runs its XLA chain (``attn_prep_chain``) on the
others.  The rule is one for both backends (whole 128-lane tiles a
head), so a model too narrow for the chip's kernels runs the chain under
the interpreter's backend too.  Off the TPU the kernels run through the
Pallas interpreter (``flash_attention._interpret_for_backend``, looked
up at call time).  The calls sit behind an inner ``jax.jit``, so the
layers of a model lower each kernel once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .kda_prep import _CARRY, _HALO, _by_tile, _steps

_F32 = jnp.float32
_LANES = 128
# Tokens and heads a program takes (inside it ``ops/kda_prep.py``'s
# steps of 64 tokens).  Of scripts/attn_prep_sweep.py's whole steps on a
# v5e (PERF.md section 6, PR 63) token tiles of 256 and 1024 read SDAR's
# step within 0.1 % and 0.8 % of this one and Trinity-Mini's within
# 0.1 %; one or two heads a program read SDAR's 1.4 % and 1.1 % slower.
TOKEN_TILE = 512
HEAD_BLOCK = 4
# What the calls state: the compiler's default, of which the backward's
# blocks, two buffers each, take 6 MiB at 512 tokens by 4 heads of 128.
# 12 MiB read SDAR's step 0.9 % slower, 32 MiB both cells' within 0.4 %.
_VMEM_LIMIT = 16 * 2 ** 20


def plan(seq: int, heads: int, kv_heads: int, head_dim: int, *, norm,
         rotates: bool, flash: bool, plain: bool):
    """``(token tile, heads a program)`` for the kernels, or ``None``
    where the caller's chain runs.  ``norm`` is the norm over each head
    of ``q`` and ``k`` (``None``: the layer has none; the kernels take
    ``"rmsnorm"``), ``rotates`` whether the layer turns them by
    position; one of the two there must be.  ``flash``: the flash
    kernels attend (the configured schedule, no ``attend`` handed in);
    ``plain``: the layer makes its own keys and values, hands none on
    and is not differential.  A head must be whole 128-lane tiles
    (compiled or interpreted), and the token tile (the largest multiple
    of ``_HALO`` up to ``TOKEN_TILE`` that divides ``seq``) must exist;
    the heads a program takes are the most up to ``HEAD_BLOCK`` that
    divide both head counts."""
    if (not flash or not plain or norm not in (None, "rmsnorm")
            or not (norm or rotates) or head_dim % _LANES
            or heads % kv_heads):
        return None
    tq = min(TOKEN_TILE, seq) // _HALO * _HALO
    while tq and seq % tq:
        tq -= _HALO
    hb = min(HEAD_BLOCK, kv_heads)
    while kv_heads % hb or heads % hb:
        hb -= 1
    return (tq, hb) if tq else None


def attn_prep(fused, scales, tables, *, heads, kv_heads, eps, tiles):
    """``fused`` [batch, seq, (heads + 2 kv_heads) head_dim]; ``scales``
    the two norms' ``(q scale, k scale)``, each [head_dim], or ``None``
    for a layer without them (``eps`` is then not read); ``tables``
    ``(cos, sin)`` [seq, head_dim / 2] of ``ops/rope.py:rope_tables``,
    or ``None`` for a layer that sees no positions; ``tiles`` what
    :func:`plan` gave.  Returns ``q`` [batch heads, seq, head_dim] and
    ``k``, ``v`` [batch kv_heads, seq, head_dim] in ``fused``'s dtype."""
    b, s, width = fused.shape
    hd = width // (heads + 2 * kv_heads)
    if scales is not None:
        scales = jnp.stack([t.astype(_F32) for t in scales])
    if tables is not None:
        cos, sin = (t.astype(_F32) for t in tables)
        tables = (jnp.concatenate([cos, cos], axis=-1),
                  jnp.concatenate([-sin, sin], axis=-1))
    q, k, v = _prep(fused, scales, tables,
                    (heads, kv_heads, float(eps), *tiles),
                    bool(flash_attention._interpret_for_backend(
                        jax.default_backend())))
    return (q.reshape(b * heads, s, hd), k.reshape(b * kv_heads, s, hd),
            v.reshape(b * kv_heads, s, hd))


# ``fused``, the scales and the tables alone are kept; the outputs carry
# no name of scopes.KERNEL_OUTPUTS, so a rematerialised block runs the
# forward again and keeps nothing of [seq, heads, head_dim] for it
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _prep(fused, scales, tables, shape, interpret):
    return _forward(fused, scales, tables, shape, interpret)


def _prep_fwd(fused, scales, tables, shape, interpret):
    return (_forward(fused, scales, tables, shape, interpret),
            (fused, scales, tables))


def _prep_bwd(shape, interpret, res, cotangents):
    fused, scales, tables = res
    dfused, dscales = _backward(fused, scales, tables, *cotangents, shape,
                                interpret)
    return dfused, dscales, jax.tree.map(jnp.zeros_like, tables)


_prep.defvjp(_prep_fwd, _prep_bwd)


def _swapped(t, compiled):
    """A head's two halves, each in the other's place."""
    half = t.shape[-1] // 2
    return pltpu.roll(t, half, 1) if compiled else jnp.roll(t, half, 1)


def _streams(body, nq, nk, hb, hd):
    """Run ``body(kind, head, lanes)`` over the heads of the program's
    block, for the stream its place on the last grid axis says: ``nq``
    head blocks of ``q`` (kind 0), then ``nk`` of ``k`` (1), then ``nk``
    of ``v`` (2).  The heads are a loop, not copies of the body."""
    j = pl.program_id(2)

    def heads(kind):
        def head(h, carry):
            body(kind, h, pl.ds(pl.multiple_of(h * hd, hd), hd))
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    for kind, of in enumerate((j < nq, (j >= nq) & (j < nq + nk),
                               j >= nq + nk)):
        pl.when(of)(functools.partial(heads, kind))


def _refs(refs, norms, rotates):
    """A kernel's leading references: ``fused``'s block, the scales'
    (``None`` without norms), the two tables' (likewise), and the
    rest."""
    refs = list(refs)
    x_ref = refs.pop(0)
    scale_ref = refs.pop(0) if norms else None
    tables = (refs.pop(0), refs.pop(0)) if rotates else None
    return x_ref, scale_ref, tables, refs


def _normed(x, scale, eps):
    """``x`` [rows, head_dim] float32 times ``rsqrt(mean(x^2) + eps)
    scale``; and the root."""
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                      * (1.0 / x.shape[-1]) + eps)
    return x * (r * scale), r


def _fwd_kernel(*refs, nq, nk, hb, eps, norms, rotates, compiled):
    x_ref, scale_ref, tables, outs = _refs(refs, norms, rotates)
    hd = x_ref.shape[2] // hb

    def body(kind, h, lanes):
        out_ref = outs[kind]
        if kind == 2:
            out_ref[0, h] = x_ref[0, :, lanes]
            return
        for rows in _steps(x_ref.shape[1]):
            x = x_ref[0, rows, lanes].astype(_F32)
            if norms:
                x, _ = _normed(x, scale_ref[kind:kind + 1], eps)
                x = x.astype(out_ref.dtype).astype(_F32)
            if rotates:
                x = (x * tables[0][rows]
                     + _swapped(x, compiled) * tables[1][rows])
            out_ref[0, h, rows] = x.astype(out_ref.dtype)

    _streams(body, nq, nk, hb, hd)


def _bwd_kernel(*refs, nq, nk, hb, eps, norms, rotates, compiled):
    x_ref, scale_ref, tables, rest = _refs(refs, norms, rotates)
    douts, dx_ref = rest[:3], rest[3]
    hd = x_ref.shape[2] // hb
    if norms:
        dscale_ref = rest[4]
        # a program's own partial sum: a v program's is zero
        dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def body(kind, h, lanes):
        dout_ref = douts[kind]
        if kind == 2:
            dx_ref[0, :, lanes] = dout_ref[0, h]
            return
        acc = jnp.zeros((_CARRY, hd), _F32)
        for rows in _steps(x_ref.shape[1]):
            g = dout_ref[0, h, rows].astype(_F32)
            if rotates:
                # the rotation's transpose, and the rounding of the
                # cotangent of the rounded value it turned
                g = (g * tables[0][rows]
                     - _swapped(g, compiled) * tables[1][rows])
                g = g.astype(dx_ref.dtype).astype(_F32)
            if norms:
                x = x_ref[0, rows, lanes].astype(_F32)
                scale = scale_ref[kind:kind + 1]
                _, r = _normed(x, scale, eps)
                acc = acc + _by_tile(g * (x * r))
                g = g * scale
                along = jnp.sum(g * x, axis=-1, keepdims=True) * (1.0 / hd)
                g = g * r - x * (r * r * r * along)
            dx_ref[0, rows, lanes] = g.astype(dx_ref.dtype)
        if norms:
            dscale_ref[0, 0, 0] += acc.sum(0, keepdims=True)

    _streams(body, nq, nk, hb, hd)


def _specs(tq, hb, hd, nq, nk):
    """The block specs over the grid (batch, token tiles, ``nq + 2 nk``
    head blocks): ``wide`` a ``[tq, hb hd]`` block of ``fused`` or of
    ``d fused``; ``major(kind)`` the same heads of a head-major stream,
    ``[hb, tq, hd]`` of ``[batch, heads, seq, head_dim]``, standing
    still outside the stream's own programs; ``row`` the two scales;
    ``table`` a token tile of a rotation table; ``partial`` a program's
    partial sum."""
    first, count = (0, nq, nq + nk), (nq, nk, nk)
    wide = pl.BlockSpec((1, tq, hb * hd), lambda b, i, j: (b, i, j))
    major = lambda kind: pl.BlockSpec(
        (1, hb, tq, hd), lambda b, i, j: (
            b, jnp.clip(j - first[kind], 0, count[kind] - 1), i, 0))
    row = pl.BlockSpec((2, hd), lambda b, i, j: (0, 0))
    table = pl.BlockSpec((tq, hd), lambda b, i, j: (i, 0))
    partial = pl.BlockSpec((1, 1, 1, 1, hd), lambda b, i, j: (b, i, j, 0, 0))
    return wide, major, row, table, partial


_PARAMS = dict(
    # a stream's output stands still while the others' programs run
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _launch(kernel, name, fused, scales, tables, cotangents, shape,
            interpret):
    """One of the two calls: the forward without ``cotangents``, which
    writes the three head-major streams; the backward with ``(dq, dk,
    dv)``, which writes ``d fused`` and, with norms, a partial sum of
    the scales' gradients a program."""
    heads, kv_heads, eps, tq, hb = shape
    b, s, width = fused.shape
    hd = width // (heads + 2 * kv_heads)
    nq, nk = heads // hb, kv_heads // hb
    wide, major, row, table, partial = _specs(tq, hb, hd, nq, nk)
    norms, rotates = scales is not None, tables is not None
    majors = [major(kind) for kind in range(3)]
    out_specs, out_shape = majors, [
        jax.ShapeDtypeStruct((b, n, s, hd), fused.dtype)
        for n in (heads, kv_heads, kv_heads)]
    if cotangents:
        out_specs = [wide, *([partial] if norms else [])]
        out_shape = [jax.ShapeDtypeStruct(fused.shape, fused.dtype),
                     *([jax.ShapeDtypeStruct(
                         (b, s // tq, nq + 2 * nk, 1, hd), _F32)]
                       if norms else [])]
    return pl.pallas_call(
        functools.partial(
            kernel, nq=nq, nk=nk, hb=hb, eps=eps, norms=norms,
            rotates=rotates, compiled=not interpret),
        grid=(b, s // tq, nq + 2 * nk),
        in_specs=[wide, *([row] if norms else []),
                  *([table, table] if rotates else []),
                  *(majors if cotangents else [])],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name=name,
    )(fused, *([scales] if norms else []), *(tables if rotates else []),
      *cotangents)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _forward(fused, scales, tables, shape, interpret):
    return _launch(_fwd_kernel, "attn_prep_fwd", fused, scales, tables, (),
                   shape, interpret)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _backward(fused, scales, tables, dq, dk, dv, shape, interpret):
    dfused, *dscale = _launch(_bwd_kernel, "attn_prep_bwd", fused, scales,
                              tables, (dq, dk, dv), shape, interpret)
    if scales is None:
        return dfused, None
    # q's head blocks, then k's; v's programs wrote zeros
    heads, kv_heads, _, _, hb = shape
    nq, nk = heads // hb, kv_heads // hb
    by_block = dscale[0].sum(axis=(0, 1, 3))
    return dfused, jnp.stack([by_block[:nq].sum(0),
                              by_block[nq:nq + nk].sum(0)])
