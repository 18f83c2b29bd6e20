"""The float32 chain in front of Kimi Delta Attention's rule
(``models/transformer.py:kda_mixer``, scope ``kda_prep``) as two Pallas
TPU kernels under a ``custom_vjp``.

From the fused projection ``[q ; k ; v]`` and the decay's projection::

    t  = silu(filter(x))                  a causal depthwise filter of
                                          ``taps`` tokens, zeros before
                                          the sequence
    q  = t / sqrt(sum_head t^2 + 1e-6) * head_dim ** -0.5
    k  = t / sqrt(sum_head t^2 + 1e-6)
    v  = t
    g  = -exp(a_log) * softplus(decay + dt_bias)

everything float32 from the filter on, ``q``, ``k`` and ``v`` rounded
once to the projection's dtype, ``g`` float32.  XLA computes that as two
dozen fusions with float32 arrays of ``[seq, 3 inner]`` written out
between them; here it is one pass over the projection's output forward
(``kda_prep_fwd``) and one backward (``kda_prep_bwd``), which keeps the
inputs alone and forms filter, silu and norms again on the tile.

**A program** takes ``[token tile, head block]`` of ONE of the four
streams: the grid is (batch, token tiles, 4 x head blocks), the last
axis walking the head blocks of ``q``, then ``k``, ``v`` and the decay.
So the three thirds of the fused array are blocks of the one array, in
and (backward) out, and nothing is sliced, concatenated or reshaped
around the calls.  The other streams' blocks stand still while a
program does not work on them (their index maps are clamped), which
costs no traffic: Pallas moves a block only when its index changes.
That is why the last grid axis is sequential.  The filter reaches
``taps - 1`` tokens back: a second block of ``_HALO`` rows on the
previous tile (zeros at the first).  Backward the transposed filter
reaches forward: the next tile's first rows of the filter's gradient are
formed again from that tile's ``_HALO`` rows of the input and of the
cotangent (zeros past the last).  The small gradients (taps, ``dt_bias``,
``a_log``) leave as one partial sum a token tile, which XLA adds up.

Inside a program the work goes a head (``head_dim`` lanes) and
``_ROWS`` tokens at a time, so that a step of the chain stays in vector
registers; the filter's reach crosses those steps as a carry of eight
rows.  A head's norm is a lane reduction.  The sums run in the chain's
order and the sigmoid divides exactly, as XLA's does (15 operations of
the vector unit a register, a quarter of the kernels' time; ``tanh`` is
cheaper and 2e-5 away): on a v5e ``q``, ``k``, ``v`` and ``g`` are the
chain's bit for bit (``scripts/kda_sweep.py --chain``; PERF.md section
6, PR 52).

Which shapes the kernels take is :func:`plan`'s to say; the caller runs
its XLA chain on the others.  Off the TPU the same kernels run through
the Pallas interpreter at any head size (``flash_attention.
_interpret_for_backend``, looked up at call time).  The calls sit behind
an inner ``jax.jit``, so the layers of a model lower each kernel once.
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention

_F32 = jnp.float32
_EPS = 1e-6
# Rows of the neighbouring tile a program sees: one bfloat16 tile.
_HALO = 16
# Rows of it that are used, and that cross the steps inside a program:
# one float32 tile, which bounds the filter's reach.
_CARRY = 8
# Tokens and heads a program takes, and tokens a step inside it: the
# fastest of scripts/kda_sweep.py --chain at the cell's shape (PERF.md
# section 6, PR 52); the backward's blocks, two buffers each, are 18 MiB
# of the 32 the calls state (2048 tokens or 8 heads do not fit).
TOKEN_TILE = 1024
HEAD_BLOCK = 4
_ROWS = 64
_VMEM_LIMIT = 32 * 2 ** 20


def plan(seq: int, heads: int, head_dim: int, taps: int):
    """``(token tile, heads a program)`` for the kernels, or ``None``
    where the caller's chain runs: the token tile (the largest multiple
    of ``_HALO`` up to ``TOKEN_TILE`` that divides ``seq``) must exist,
    the filter reach no further than ``_CARRY`` rows, and, compiled,
    a head be whole 128-lane tiles."""
    interpret = flash_attention._interpret_for_backend(jax.default_backend())
    if taps - 1 > _CARRY or (not interpret and head_dim % 128):
        return None
    tq = min(TOKEN_TILE, seq) // _HALO * _HALO
    while tq and seq % tq:
        tq -= _HALO
    if not tq:
        return None
    hb = min(HEAD_BLOCK, heads)
    while heads % hb:
        hb -= 1
    return tq, hb


def kda_prep(fused, conv_kernel, decay, dt_bias, a_log, *, tiles):
    """``fused`` [batch, seq, 3 inner] (``[q ; k ; v]``), ``conv_kernel``
    [taps, 3 inner], ``decay`` [batch, seq, inner], ``dt_bias`` [inner],
    ``a_log`` [heads]; ``tiles`` what :func:`plan` gave for the shape.
    Returns ``q``, ``k``, ``v`` [batch, seq, heads, head_dim] in
    ``fused``'s dtype and ``g`` likewise in float32."""
    b, s, inner = decay.shape
    heads = a_log.shape[0]
    interpret = flash_attention._interpret_for_backend(jax.default_backend())
    out = _prep(fused, conv_kernel, decay, dt_bias, a_log,
                (inner // heads, *tiles), interpret)
    return tuple(t.reshape(b, s, heads, inner // heads) for t in out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _prep(fused, conv_kernel, decay, dt_bias, a_log, shape, interpret):
    return _forward(fused, conv_kernel, decay, dt_bias, a_log, shape,
                    interpret)


def _prep_fwd(fused, conv_kernel, decay, dt_bias, a_log, shape, interpret):
    # the inputs alone are kept; the outputs carry no name of
    # scopes.KERNEL_OUTPUTS, so a rematerialised block runs the forward
    # again and keeps nothing of [seq, 3 inner] for it
    return (_forward(fused, conv_kernel, decay, dt_bias, a_log, shape,
                     interpret),
            (fused, conv_kernel, decay, dt_bias, a_log))


def _prep_bwd(shape, interpret, res, cotangents):
    return _backward(*res, *cotangents, shape, interpret)


_prep.defvjp(_prep_fwd, _prep_bwd)


def _filtered(before, x, w):
    """``x`` [rows, lanes] float32 through the causal filter ``w``
    [taps, lanes], ``before`` the ``_CARRY`` rows in front of it: the
    filter's output, and its input under each tap."""
    taps = w.shape[0]
    ext = jnp.concatenate([before, x], axis=0)
    under = [_moved(ext, taps - 1 - i)[_CARRY:] for i in range(taps)]
    return _add(t * w[i:i + 1] for i, t in enumerate(under)), under


def _moved(t, by):
    """``t`` with every row ``by`` rows further down (up where negative),
    around the ends: a rotation of the sublanes whose result lies on
    whole tiles again, where a slice at an odd row leaves every later
    operation to shift its operands anew."""
    return pltpu.roll(t, by % t.shape[0], 0) if by else t


def _add(terms):
    return functools.reduce(operator.add, terms)


def _silu(y):
    s = jax.nn.sigmoid(y)
    return y * s, s


def _steps(rows):
    """The row slices a program works through."""
    step = math.gcd(rows, _ROWS)
    return [slice(r, r + step) for r in range(0, rows, step)]


def _kinds(body, nhb, hd, hb):
    """Run ``body(kind, lanes)`` over the heads of the program's block,
    for the stream its place on the last grid axis says: 0 ``q``, 1
    ``k``, 2 ``v``, 3 the decay.  The heads are a loop, not copies of the
    body: a program's code does not grow with the heads it takes."""
    j = pl.program_id(2)

    def heads(kind):
        def head(h, carry):
            body(kind, pl.ds(pl.multiple_of(h * hd, hd), hd))
            return carry

        jax.lax.fori_loop(0, hb, head, 0)

    for kind in range(4):
        pl.when(j // nhb == kind)(functools.partial(heads, kind))


def _decay(decay_ref, bias_ref, alog_ref, rows, lanes):
    """``-exp(a_log)`` and ``decay + dt_bias`` there, float32."""
    return (-jnp.exp(alog_ref[:, lanes]),
            decay_ref[0, rows, lanes].astype(_F32) + bias_ref[:, lanes])


def _fwd_kernel(x_ref, before_ref, w_ref, decay_ref, bias_ref, alog_ref,
                q_ref, k_ref, v_ref, g_ref, *, hd, hb, nhb):
    first = pl.program_id(1) == 0

    def body(kind, lanes):
        if kind == 3:
            for rows in _steps(decay_ref.shape[1]):
                a, z = _decay(decay_ref, bias_ref, alog_ref, rows, lanes)
                g_ref[0, rows, lanes] = a * _softplus(z)
            return
        out_ref = (q_ref, k_ref, v_ref)[kind]
        w = w_ref[:, lanes]
        before = jnp.where(
            first, 0.0, before_ref[0, :, lanes].astype(_F32)[-_CARRY:])
        for rows in _steps(x_ref.shape[1]):
            x = x_ref[0, rows, lanes].astype(_F32)
            y, _ = _filtered(before, x, w)
            t, _ = _silu(y)
            if kind < 2:
                t = t * jax.lax.rsqrt(
                    jnp.sum(t * t, axis=-1, keepdims=True) + _EPS)
            if kind == 0:
                t = t * hd ** -0.5
            out_ref[0, rows, lanes] = t.astype(out_ref.dtype)
            before = x[-_CARRY:]

    _kinds(body, nhb, hd, hb)


def _softplus(z):
    return jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z)))


def _bwd_kernel(x_ref, before_ref, after_ref, w_ref, decay_ref, bias_ref,
                alog_ref, dq_ref, dq_after_ref, dk_ref, dk_after_ref,
                dv_ref, dv_after_ref, dg_ref, dx_ref, ddecay_ref, dw_ref,
                dsmall_ref, *, hd, hb, nhb):
    ti = pl.program_id(1)
    first, last = ti == 0, ti == pl.num_programs(1) - 1

    def through(kind, before, x, w, dout):
        """The filter's gradient ``dy`` for the rows ``x`` [rows, hd],
        ``before`` the rows in front of them, ``dout`` the cotangent of
        the stream's output there; and the filter's input under each
        tap."""
        y, under = _filtered(before, x, w)
        t, s = _silu(y)
        if kind < 2:
            r = jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + _EPS)
            along = jnp.sum(dout * t, axis=-1, keepdims=True)
            dt = r * (dout - t * (r * r * along))
            if kind == 0:
                dt = dt * hd ** -0.5
        else:
            dt = dout
        return dt * (s * (1.0 + y * (1.0 - s))), under

    def body(kind, lanes):
        if kind == 3:
            dbias = dalog = jnp.zeros((_CARRY, hd), _F32)
            for rows in _steps(decay_ref.shape[1]):
                a, z = _decay(decay_ref, bias_ref, alog_ref, rows, lanes)
                dg = dg_ref[0, rows, lanes]
                dz = dg * a * jax.nn.sigmoid(z)
                ddecay_ref[0, rows, lanes] = dz.astype(ddecay_ref.dtype)
                dbias += _by_tile(dz)
                dalog += _by_tile(dg * (a * _softplus(z)))
            dsmall_ref[0, 0, 0:1, lanes] = dbias.sum(0, keepdims=True)
            dsmall_ref[0, 0, 1:2, lanes] = dalog.sum(0, keepdims=True)
            return
        dout_ref, dout_after_ref = ((dq_ref, dq_after_ref),
                                    (dk_ref, dk_after_ref),
                                    (dv_ref, dv_after_ref))[kind]
        tq = x_ref.shape[1]
        w = w_ref[:, lanes]
        taps = w.shape[0]
        rows_before = lambda at: (
            jnp.where(first, 0.0, before_ref[0, :, lanes].astype(_F32))
            if at == 0 else x_ref[0, at - _HALO:at, lanes].astype(_F32)
        )[-_CARRY:]
        # the next tile's first rows of dy, zeros past the sequence
        dy_after, _ = through(
            kind, rows_before(tq),
            after_ref[0, :, lanes].astype(_F32)[:_CARRY], w,
            dout_after_ref[0, :, lanes].astype(_F32)[:_CARRY])
        dy_after = jnp.where(last, 0.0, dy_after)
        dw = [jnp.zeros((_CARRY, hd), _F32)] * taps
        for rows in reversed(_steps(tq)):
            dy, under = through(
                kind, rows_before(rows.start),
                x_ref[0, rows, lanes].astype(_F32), w,
                dout_ref[0, rows, lanes].astype(_F32))
            ext = jnp.concatenate([dy, dy_after], axis=0)
            dx = _add(_moved(ext, i - (taps - 1))[:-_CARRY] * w[i:i + 1]
                      for i in range(taps))
            dx_ref[0, rows, lanes] = dx.astype(dx_ref.dtype)
            dw = [acc + _by_tile(dy * t) for acc, t in zip(dw, under)]
            dy_after = dy[:_CARRY]
        for i, acc in enumerate(dw):
            dw_ref[0, 0, i:i + 1, lanes] = acc.sum(0, keepdims=True)

    _kinds(body, nhb, hd, hb)


def _by_tile(t):
    """``t`` [rows, lanes] summed over its ``_CARRY``-row tiles: the
    vector unit's adds, the one sum over sublanes left to the end."""
    return _add(t[r:r + _CARRY] for r in range(0, t.shape[0], _CARRY))


def _specs(s, tq, width, nhb):
    """The block specs over the grid (batch, token tiles, 4 x head
    blocks): ``wide(kind)`` a stream's ``[tq, width]`` block, standing
    still outside the stream's own programs (kind ``None``: the fused
    array's three streams, one after the other); ``before`` and
    ``after`` the neighbouring tiles' ``_HALO`` rows; ``row`` a block of
    a ``[rows, lanes]`` parameter."""
    def col(kind):
        if kind is None:
            return lambda j: jnp.minimum(j, 3 * nhb - 1)
        return lambda j: jnp.clip(j - kind * nhb, 0, nhb - 1)

    per, halos = tq // _HALO, s // _HALO
    wide = lambda kind: pl.BlockSpec(
        (1, tq, width), lambda b, i, j: (b, i, col(kind)(j)))
    before = lambda kind: pl.BlockSpec(
        (1, _HALO, width),
        lambda b, i, j: (b, jnp.maximum(i * per - 1, 0), col(kind)(j)))
    after = lambda kind: pl.BlockSpec(
        (1, _HALO, width),
        lambda b, i, j: (b, jnp.minimum((i + 1) * per, halos - 1),
                         col(kind)(j)))
    row = lambda rows, kind: pl.BlockSpec(
        (rows, width), lambda b, i, j: (0, col(kind)(j)))
    partial = lambda rows, kind: pl.BlockSpec(
        (1, 1, rows, width), lambda b, i, j: (b, i, 0, col(kind)(j)))
    return wide, before, after, row, partial


_PARAMS = dict(
    # a stream's blocks stand still while the others' programs run
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


def _per_channel(dt_bias, a_log, hd):
    return (dt_bias.astype(_F32)[None],
            jnp.repeat(a_log.astype(_F32), hd)[None])


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _forward(fused, conv_kernel, decay, dt_bias, a_log, shape, interpret):
    hd, tq, hb = shape
    b, s, inner = decay.shape
    nhb = inner // (hd * hb)
    wide, before, _, row, _ = _specs(s, tq, hd * hb, nhb)
    taps = conv_kernel.shape[0]
    like = lambda dtype: jax.ShapeDtypeStruct((b, s, inner), dtype)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hd=hd, hb=hb, nhb=nhb),
        grid=(b, s // tq, 4 * nhb),
        in_specs=[wide(None), before(None), row(taps, None), wide(3),
                  row(1, 3), row(1, 3)],
        out_specs=[wide(0), wide(1), wide(2), wide(3)],
        out_shape=[like(fused.dtype)] * 3 + [like(_F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="kda_prep_fwd",
    )(fused, fused, conv_kernel.astype(_F32), decay,
      *_per_channel(dt_bias, a_log, hd))


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _backward(fused, conv_kernel, decay, dt_bias, a_log, dq, dk, dv, dg,
              shape, interpret):
    hd, tq, hb = shape
    b, s, inner = decay.shape
    nt, nhb = s // tq, inner // (hd * hb)
    wide, before, after, row, partial = _specs(s, tq, hd * hb, nhb)
    taps = conv_kernel.shape[0]
    flat = lambda t: t.reshape(b, s, inner)
    dfused, ddecay, dw, dsmall = pl.pallas_call(
        functools.partial(_bwd_kernel, hd=hd, hb=hb, nhb=nhb),
        grid=(b, nt, 4 * nhb),
        in_specs=[wide(None), before(None), after(None), row(taps, None),
                  wide(3), row(1, 3), row(1, 3),
                  *(spec(kind) for kind in range(3)
                    for spec in (wide, after)), wide(3)],
        out_specs=[wide(None), wide(3), partial(taps, None), partial(2, 3)],
        out_shape=[
            jax.ShapeDtypeStruct(fused.shape, fused.dtype),
            jax.ShapeDtypeStruct(decay.shape, decay.dtype),
            jax.ShapeDtypeStruct((b, nt, taps, 3 * inner), _F32),
            jax.ShapeDtypeStruct((b, nt, 2, inner), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="kda_prep_bwd",
    )(fused, fused, fused, conv_kernel.astype(_F32), decay,
      *_per_channel(dt_bias, a_log, hd),
      *(flat(t) for t in (dq, dk, dv) for _ in (wide, after)), flat(dg))
    dsmall = dsmall.sum(axis=(0, 1))
    return (dfused, dw.sum(axis=(0, 1)).astype(conv_kernel.dtype), ddecay,
            dsmall[0].astype(dt_bias.dtype),
            dsmall[1].reshape(-1, hd).sum(axis=1).astype(a_log.dtype))
