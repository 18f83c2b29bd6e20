"""The two float32 elementwise chains of a Mamba-2 mixer
(``models/transformer.py:mamba_mixer``), one on each side of the scan,
as two Pallas TPU kernel pairs, each under a ``custom_vjp``.

From ``fused = in_proj(h)``, whose lanes are ``[z ; xBC ; dt]`` with
``xBC = [x ; B ; C]`` (``inner``, ``groups x state`` and again
``groups x state`` wide)::

    x, B, C = split(silu(filter(xBC) + bias))     scope ``ssm_prep``:
                                          a causal depthwise filter of
                                          ``taps`` tokens, zeros before
                                          the sequence
    gated   = y * silu(z)                 scope ``ssm_norm``: ``y`` the
    normed  = gated * rsqrt(mean_group(gated^2) + eps) * scale
                                          scan's output, each of
                                          ``groups`` groups of lanes
                                          with its own mean square

everything float32 from the first load on, ``x``, ``B``, ``C`` and
``normed`` rounded once to ``fused``'s dtype.  XLA computes each as some
two dozen fusions with float32 arrays of ``[seq, W]`` and ``[seq,
inner]`` written out between them; here each is one pass over its inputs
forward (``ssm_prep_fwd``, ``ssm_norm_fwd``) and one backward
(``ssm_prep_bwd``, ``ssm_norm_bwd``), which keeps the inputs alone and
forms filter, silu and the statistics again on the tile.

**The front pair.**  A program takes ``[token tile, lane block]`` of
``xBC``: the grid is (batch, token tiles, lane blocks of ``x``, then of
``B``, then of ``C``).  ``xBC`` starts at lane ``inner`` of ``fused``, a
whole number of lane blocks in, so its blocks are blocks of the one
array (the index map adds the offset) and nothing is sliced or
concatenated in front of the call; the three outputs are three arrays
(``B`` and ``C`` by group, ``[batch, groups, seq, state]``, as the
scan's kernels read them: no copy between the calls),
each standing still while a program works on another (their index maps
are clamped: Pallas moves a block only when its index changes, which is
why the last grid axis is sequential).  The filter's halo, the carry
across the steps inside a program and the transposed filter backward
are ``ops/kda_prep.py``'s, whose helpers this module reads.  Backward
the three cotangents come in and one ``d xBC`` ``[batch, seq, W]`` goes
out, with the filter's and the bias's gradients as one partial sum a
token tile, which XLA adds up.

**The gate-and-norm pair.**  A program takes ``[token tile, one group's
lanes]`` of ``y`` and of ``z`` (lanes ``0 : inner`` of ``fused``, by
index map) and works through it ``_NORM_ROWS`` tokens at a time in two
sweeps over ``_NORM_LANES``-lane pieces: the first forms ``gated`` (and
backward the sigmoid) into a float32 scratch and the group's sums, the
second scales and stores.  Backward ``dy``, ``dz`` ``[batch, seq,
inner]`` and the scale's partial sums a token tile.

``d fused`` is put together outside the calls (``d xBC`` and ``dz`` each
padded to ``fused``'s width and added to ``dt``'s lanes: on a v5e the
compiler fuses that into the operands of ``in_proj``'s two backward
matmuls, no pass of its own).  The sigmoid divides exactly, as XLA's
does.

Which shapes the kernels take is :func:`plan`'s to say; the caller runs
its XLA chains on the others.  The rule is one for both backends (whole
128-lane tiles), so a model too narrow for the chip's kernels runs the
chains under the interpreter's backend too.  Off the TPU the kernels run
through the Pallas interpreter (``flash_attention.
_interpret_for_backend``, looked up at call time).  The calls sit behind
an inner ``jax.jit``, so the layers of a model lower each kernel once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention
from .kda_prep import (_CARRY, _HALO, _add, _by_tile, _filtered, _moved,
                       _silu, _steps)

_F32 = jnp.float32
_LANES = 128
# The front pair: tokens and lanes a program takes (the lanes whole
# groups of ``B``: :func:`plan`).  Of scripts/ssm_chain_sweep.py's whole
# steps on a v5e (PERF.md section 6, PR 62) a token tile of 512 or 2048
# and a lane block of 256 move the two cells' steps by 0.4 % at most.
TOKEN_TILE = 1024
LANE_BLOCK = 512
# The gate-and-norm pair: elements of a program's block of one group
# (its token tile is this over the group's width; 256 Ki and 1024 Ki
# read the same step), the tokens a sub-step inside it takes, the lanes
# a piece of a sub-step and the elements a step of several sub-steps.
NORM_BLOCK = 512 * 1024
_NORM_ROWS = 16
_NORM_LANES = 512
_NORM_STEP = 64 * 1024
# What the calls state: their blocks, two buffers each, are 10 MiB.  At
# 32 MiB granite's step is 5 ms slower (XLA keeps less of its own in
# VMEM around a call that states more: PR 31's finding again).
_VMEM_LIMIT = 16 * 2 ** 20


def plan(seq: int, inner: int, groups: int, state: int, taps: int):
    """``(token tile, lane block, the norm's token tile)`` for the
    kernels, or ``None`` where the caller's chains run: ``inner``, the
    ``state`` and a group's ``inner / groups`` lanes must be whole
    128-lane tiles (compiled or interpreted), the filter reach no
    further than ``_CARRY`` rows, both token tiles (the largest
    multiples of ``_HALO`` up to ``TOKEN_TILE``, and up to
    ``NORM_BLOCK`` elements of a group, that divide ``seq``) exist, and
    so must the lane block: whole groups of ``B`` (the largest multiple
    of ``state`` up to ``LANE_BLOCK``, or one ``state`` where that is
    wider) that divide both ``inner`` and ``groups x state``."""
    if (taps - 1 > _CARRY or inner % groups or inner % _LANES
            or state % _LANES or inner // groups % _LANES):
        return None
    tq = _tile(seq, TOKEN_TILE)
    norm_tq = _tile(seq, NORM_BLOCK // (inner // groups))
    both = math.gcd(inner, groups * state)
    lb = max(LANE_BLOCK // state, 1) * state
    while lb and both % lb:
        lb -= state
    if not tq or not norm_tq or not lb:
        return None
    return tq, lb, norm_tq


def _tile(seq, most):
    """The largest multiple of ``_HALO`` up to ``most`` that divides
    ``seq``; 0 where there is none."""
    tq = min(most, seq) // _HALO * _HALO
    while tq and seq % tq:
        tq -= _HALO
    return tq


def _interpret():
    return bool(flash_attention._interpret_for_backend(jax.default_backend()))


def ssm_prep(fused, conv_kernel, conv_bias, *, inner, heads, groups, tiles):
    """``fused`` [batch, seq, 2 inner + 2 groups state + heads],
    ``conv_kernel`` [taps, W] and ``conv_bias`` [W] or ``None`` over the
    ``W = inner + 2 groups state`` lanes of ``xBC``; ``tiles`` what
    :func:`plan` gave for the shape.  Returns ``x`` [batch, seq, heads,
    inner / heads], ``B`` and ``C`` [batch, seq, groups, state] in
    ``fused``'s dtype; ``B`` and ``C`` are written by group, ``[batch,
    groups, seq, state]``, as ``ops/ssd.py``'s kernels read them, so the
    transpose here and the scan's own cancel."""
    b, s, _ = fused.shape
    state = (conv_kernel.shape[1] - inner) // (2 * groups)
    if conv_bias is None:
        conv_bias = jnp.zeros(conv_kernel.shape[1:], _F32)
    x, B, C = _prep(fused, conv_kernel, conv_bias,
                    (inner, groups, state, *tiles[:2]), _interpret())
    return (x.reshape(b, s, heads, inner // heads),
            B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3))


def ssm_norm(y, fused, norm_scale, *, groups, eps, tiles):
    """``y`` [batch, seq, heads, head_dim] the scan's output, ``fused``
    as :func:`ssm_prep` takes it (its lanes ``0 : inner`` are ``z``),
    ``norm_scale`` [inner].  Returns the gated, normed and scaled
    ``[batch, seq, inner]`` in ``fused``'s dtype."""
    b, s, _ = fused.shape
    inner = norm_scale.shape[0]
    return _norm(y.reshape(b, s, inner), fused, norm_scale,
                 (inner // groups, float(eps), tiles[2]), _interpret())


# the inputs alone are kept (both pairs); the outputs carry no name of
# scopes.KERNEL_OUTPUTS, so a rematerialised block runs each forward
# again and keeps nothing of [seq, W] or [seq, inner] for it
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _prep(fused, conv_kernel, conv_bias, shape, interpret):
    return _prep_forward(fused, conv_kernel, conv_bias, shape, interpret)


def _prep_fwd(fused, conv_kernel, conv_bias, shape, interpret):
    return (_prep_forward(fused, conv_kernel, conv_bias, shape, interpret),
            (fused, conv_kernel, conv_bias))


def _prep_bwd(shape, interpret, res, cotangents):
    return _prep_backward(*res, *cotangents, shape, interpret)


_prep.defvjp(_prep_fwd, _prep_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _norm(y, fused, norm_scale, shape, interpret):
    return _norm_forward(y, fused, norm_scale, shape, interpret)


def _norm_fwd(y, fused, norm_scale, shape, interpret):
    return (_norm_forward(y, fused, norm_scale, shape, interpret),
            (y, fused, norm_scale))


def _norm_bwd(shape, interpret, res, dout):
    return _norm_backward(*res, dout, shape, interpret)


_norm.defvjp(_norm_fwd, _norm_bwd)


def _lane_tiles(body, width):
    """Run ``body(tile)`` over the 128-lane tiles of a block ``width``
    lanes wide: a loop, not copies of the body."""
    def tile(l, carry):
        body(l)
        return carry

    jax.lax.fori_loop(0, width // _LANES, tile, 0)


def _at(ref, tile, rows=slice(None)):
    """Where lane tile ``tile`` of a block lies in ``ref``, at ``rows``:
    a block ``[1, rows, lanes]``, or one of ``B`` or ``C`` by group,
    ``[1, groups, rows, state]``, whose groups lie side by side in
    ``xBC``'s lanes."""
    lanes = lambda t: pl.ds(pl.multiple_of(t * _LANES, _LANES), _LANES)
    if len(ref.shape) == 3:
        return 0, rows, lanes(tile)
    per = ref.shape[3] // _LANES
    return 0, tile // per, rows, lanes(tile % per)


def _streams(body, refs, nx, nb):
    """Run ``body(*refs[kind])`` for the stream the program's place on
    the last grid axis says: ``nx`` lane blocks of ``x``, then ``nb`` of
    ``B``, then ``nb`` of ``C``."""
    j = pl.program_id(2)
    for kind, of in enumerate((j < nx, (j >= nx) & (j < nx + nb),
                               j >= nx + nb)):
        pl.when(of)(functools.partial(body, *refs[kind]))


def _prep_fwd_kernel(x_ref, before_ref, w_ref, bias_ref, x_out, b_out,
                     c_out, *, nx, nb):
    first = pl.program_id(1) == 0

    def stream(out_ref):
        def tile(l):
            lanes = _at(x_ref, l)[2]
            w, bias = w_ref[:, lanes], bias_ref[:, lanes]
            before = jnp.where(
                first, 0.0, before_ref[0, :, lanes].astype(_F32)[-_CARRY:])
            for rows in _steps(x_ref.shape[1]):
                x = x_ref[0, rows, lanes].astype(_F32)
                y, _ = _filtered(before, x, w)
                t, _ = _silu(y + bias)
                out_ref[_at(out_ref, l, rows)] = t.astype(out_ref.dtype)
                before = x[-_CARRY:]

        _lane_tiles(tile, x_ref.shape[2])

    _streams(stream, ((x_out,), (b_out,), (c_out,)), nx, nb)


def _prep_bwd_kernel(x_ref, before_ref, after_ref, w_ref, bias_ref, dx_ref,
                     dx_after_ref, db_ref, db_after_ref, dc_ref,
                     dc_after_ref, dxbc_ref, dsmall_ref, *, nx, nb):
    ti = pl.program_id(1)
    first, last = ti == 0, ti == pl.num_programs(1) - 1
    tq = x_ref.shape[1]

    def through(before, x, w, bias, dout):
        """The filter's gradient ``dy`` for the rows ``x``, ``before``
        the rows in front of them, ``dout`` the cotangent of the
        stream's output there; and the filter's input under each tap."""
        y, under = _filtered(before, x, w)
        y = y + bias
        _, s = _silu(y)
        return dout * (s * (1.0 + y * (1.0 - s))), under

    def stream(dout_ref, dout_after_ref):
        def tile(l):
            lanes = _at(x_ref, l)[2]
            w, bias = w_ref[:, lanes], bias_ref[:, lanes]
            taps = w.shape[0]
            rows_before = lambda at: (
                jnp.where(first, 0.0, before_ref[0, :, lanes].astype(_F32))
                if at == 0 else x_ref[0, at - _HALO:at, lanes].astype(_F32)
            )[-_CARRY:]
            # the next tile's first rows of dy, zeros past the sequence
            dy_after, _ = through(
                rows_before(tq),
                after_ref[0, :, lanes].astype(_F32)[:_CARRY], w, bias,
                dout_after_ref[_at(dout_after_ref, l)].astype(_F32)[:_CARRY])
            dy_after = jnp.where(last, 0.0, dy_after)
            dw = [jnp.zeros((_CARRY, _LANES), _F32)] * (taps + 1)
            for rows in reversed(_steps(tq)):
                dy, under = through(
                    rows_before(rows.start),
                    x_ref[0, rows, lanes].astype(_F32), w, bias,
                    dout_ref[_at(dout_ref, l, rows)].astype(_F32))
                ext = jnp.concatenate([dy, dy_after], axis=0)
                dx = _add(_moved(ext, i - (taps - 1))[:-_CARRY] * w[i:i + 1]
                          for i in range(taps))
                dxbc_ref[0, rows, lanes] = dx.astype(dxbc_ref.dtype)
                # the taps' gradients, and last the bias's
                dw = [acc + _by_tile(term) for acc, term in zip(
                    dw, (*(dy * t for t in under), dy))]
                dy_after = dy[:_CARRY]
            for i, acc in enumerate(dw):
                dsmall_ref[0, 0, i:i + 1, lanes] = acc.sum(0, keepdims=True)

        _lane_tiles(tile, x_ref.shape[2])

    _streams(stream, ((dx_ref, dx_after_ref), (db_ref, db_after_ref),
                      (dc_ref, dc_after_ref)), nx, nb)


def _prep_specs(s, tq, lb, nx, nb, state):
    """The front pair's block specs over the grid (batch, token tiles,
    ``nx + 2 nb`` lane blocks): ``wide(kind)`` a ``[tq, lb]`` block
    (kind 0: of ``x``, standing still outside its own programs;
    ``"fused"``: of ``fused``, whose ``xBC`` starts ``nx`` blocks in;
    ``None``: of an array ``xBC`` wide) or, kind 1 and 2, the same lanes
    of ``B`` or ``C`` by group, ``[lb / state, tq, state]`` of ``[batch,
    groups, seq, state]``, standing still likewise; ``before`` and
    ``after`` the neighbouring tiles' ``_HALO`` rows; ``row`` a block of
    a ``[rows, W]`` parameter, ``partial`` of a partial sum a token
    tile."""
    first = (0, nx, nx + nb)
    count = (nx, nb, nb)

    def col(kind):
        if kind is None:
            return lambda j: j
        if kind == "fused":
            return lambda j: nx + j
        return lambda j: jnp.clip(j - first[kind], 0, count[kind] - 1)

    per, halos = tq // _HALO, s // _HALO

    def rows_of(rows, row):
        """A block of ``rows`` tokens at token block ``row(i)``."""
        def spec(kind):
            if kind in (1, 2):
                return pl.BlockSpec(
                    (1, lb // state, rows, state),
                    lambda b, i, j: (b, col(kind)(j), row(i), 0))
            return pl.BlockSpec(
                (1, rows, lb), lambda b, i, j: (b, row(i), col(kind)(j)))
        return spec

    wide = rows_of(tq, lambda i: i)
    before = rows_of(_HALO, lambda i: jnp.maximum(i * per - 1, 0))
    after = rows_of(_HALO, lambda i: jnp.minimum((i + 1) * per, halos - 1))
    row = lambda rows: pl.BlockSpec((rows, lb), lambda b, i, j: (0, j))
    partial = lambda rows: pl.BlockSpec(
        (1, 1, rows, lb), lambda b, i, j: (b, i, 0, j))
    return wide, before, after, row, partial


_PREP_PARAMS = dict(
    # a stream's output stands still while the others' programs run
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _prep_forward(fused, conv_kernel, conv_bias, shape, interpret):
    inner, groups, state, tq, lb = shape
    b, s, _ = fused.shape
    nx, nb = inner // lb, groups * state // lb
    wide, before, _, row, _ = _prep_specs(s, tq, lb, nx, nb, state)
    like = lambda *shape: jax.ShapeDtypeStruct((b, *shape), fused.dtype)
    return pl.pallas_call(
        functools.partial(_prep_fwd_kernel, nx=nx, nb=nb),
        grid=(b, s // tq, nx + 2 * nb),
        in_specs=[wide("fused"), before("fused"),
                  row(conv_kernel.shape[0]), row(1)],
        out_specs=[wide(0), wide(1), wide(2)],
        out_shape=[like(s, inner), like(groups, s, state),
                   like(groups, s, state)],
        compiler_params=pltpu.CompilerParams(**_PREP_PARAMS),
        interpret=interpret,
        name="ssm_prep_fwd",
    )(fused, fused, conv_kernel.astype(_F32), conv_bias.astype(_F32)[None])


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _prep_backward(fused, conv_kernel, conv_bias, dx, dB, dC, shape,
                   interpret):
    inner, groups, state, tq, lb = shape
    b, s, width = fused.shape
    nt, nx, nb = s // tq, inner // lb, groups * state // lb
    wide, before, after, row, partial = _prep_specs(s, tq, lb, nx, nb, state)
    taps, xbc = conv_kernel.shape
    dxbc, dsmall = pl.pallas_call(
        functools.partial(_prep_bwd_kernel, nx=nx, nb=nb),
        grid=(b, nt, nx + 2 * nb),
        in_specs=[wide("fused"), before("fused"), after("fused"),
                  row(taps), row(1),
                  *(spec(kind) for kind in range(3)
                    for spec in (wide, after))],
        out_specs=[wide(None), partial(taps + 1)],
        out_shape=[jax.ShapeDtypeStruct((b, s, xbc), fused.dtype),
                   jax.ShapeDtypeStruct((b, nt, taps + 1, xbc), _F32)],
        compiler_params=pltpu.CompilerParams(**_PREP_PARAMS),
        interpret=interpret,
        name="ssm_prep_bwd",
    )(fused, fused, fused, conv_kernel.astype(_F32),
      conv_bias.astype(_F32)[None],
      *(t for t in (dx, dB, dC) for _ in (wide, after)))
    dsmall = dsmall.sum(axis=(0, 1))
    return (_into_fused(dxbc, inner, width),
            dsmall[:taps].astype(conv_kernel.dtype),
            dsmall[taps].astype(conv_bias.dtype))


def _into_fused(t, at, width):
    """``t`` [batch, seq, lanes] as lanes ``at : at + lanes`` of an
    array ``width`` wide, zeros elsewhere: one producer's part of
    ``d fused``, which XLA adds to the others' in one fusion."""
    return jnp.pad(t, ((0, 0), (0, 0), (at, width - at - t.shape[2])))


def _pieces(width):
    """The lane slices a step of the norm's kernels works through."""
    step = math.gcd(width, _NORM_LANES)
    return [slice(c, c + step) for c in range(0, width, step)]


def _sub_steps(rows, width):
    """The ``_NORM_ROWS``-token sub-steps a step of the norm's kernels
    takes: as many as make ``_NORM_STEP`` elements, so that a narrow
    group's step is long enough to fill the vector unit's slots."""
    return math.gcd(max(1, _NORM_STEP // (_NORM_ROWS * width)),
                    rows // _NORM_ROWS)


def _row_steps(body, rows, width):
    """Run ``body(rows, slot)`` over a block's tokens, ``_NORM_ROWS`` at
    a time: a loop over steps of :func:`_sub_steps` copies of the body,
    ``slot`` the copy's own part of the scratch (the copies then do not
    wait for one another)."""
    per = _sub_steps(rows, width)

    def step(r, carry):
        for slot in range(per):
            body(pl.ds(pl.multiple_of((r * per + slot) * _NORM_ROWS,
                                      _NORM_ROWS), _NORM_ROWS), slot)
        return carry

    jax.lax.fori_loop(0, rows // (_NORM_ROWS * per), step, 0)


def _norm_fwd_kernel(y_ref, z_ref, scale_ref, out_ref, gated_ref, *, eps):
    width = y_ref.shape[2]

    def step(rows, slot):
        squares = []
        for lanes in _pieces(width):
            z, _ = _silu(z_ref[0, rows, lanes].astype(_F32))
            gated = y_ref[0, rows, lanes].astype(_F32) * z
            gated_ref[slot, :, lanes] = gated
            squares.append(gated * gated)
        r = jax.lax.rsqrt(
            jnp.sum(_add(squares), axis=-1, keepdims=True) * (1.0 / width)
            + eps)
        for lanes in _pieces(width):
            out_ref[0, rows, lanes] = (
                gated_ref[slot, :, lanes] * r * scale_ref[:, lanes]
            ).astype(out_ref.dtype)

    _row_steps(step, y_ref.shape[1], width)


def _norm_bwd_kernel(y_ref, z_ref, scale_ref, dout_ref, dy_ref, dz_ref,
                     dscale_ref, sig_ref, acc_ref, *, eps):
    width = y_ref.shape[2]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def loaded(rows, lanes):
        return (y_ref[0, rows, lanes].astype(_F32),
                z_ref[0, rows, lanes].astype(_F32),
                dout_ref[0, rows, lanes].astype(_F32))

    def step(rows, slot):
        squares, alongs = [], []
        for lanes in _pieces(width):
            y, z, dout = loaded(rows, lanes)
            silu, sig = _silu(z)
            sig_ref[slot, :, lanes] = sig
            gated = y * silu
            squares.append(gated * gated)
            alongs.append(dout * scale_ref[:, lanes] * gated)
        total = lambda terms: jnp.sum(_add(terms), axis=-1, keepdims=True)
        r = jax.lax.rsqrt(total(squares) * (1.0 / width) + eps)
        back = r * r * r * (total(alongs) * (1.0 / width))
        for lanes in _pieces(width):
            y, z, dout = loaded(rows, lanes)
            sig = sig_ref[slot, :, lanes]
            silu = z * sig
            gated = y * silu
            acc_ref[:, lanes] += _by_tile(dout * (gated * r))
            dgated = dout * scale_ref[:, lanes] * r - gated * back
            dy_ref[0, rows, lanes] = (dgated * silu).astype(dy_ref.dtype)
            dz_ref[0, rows, lanes] = (
                dgated * y * (sig * (1.0 + z * (1.0 - sig)))
            ).astype(dz_ref.dtype)

    _row_steps(step, y_ref.shape[1], width)
    dscale_ref[0, 0] = acc_ref[...].sum(0, keepdims=True)


def _norm_specs(tq, gw):
    wide = pl.BlockSpec((1, tq, gw), lambda b, i, j: (b, i, j))
    row = pl.BlockSpec((1, gw), lambda b, i, j: (0, j))
    partial = pl.BlockSpec((1, 1, 1, gw), lambda b, i, j: (b, i, 0, j))
    return wide, row, partial


_NORM_PARAMS = dict(
    dimension_semantics=("parallel", "parallel", "parallel"),
    vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _norm_forward(y, fused, norm_scale, shape, interpret):
    gw, eps, tq = shape
    b, s, inner = y.shape
    wide, row, _ = _norm_specs(tq, gw)
    return pl.pallas_call(
        functools.partial(_norm_fwd_kernel, eps=eps),
        grid=(b, s // tq, inner // gw),
        in_specs=[wide, wide, row],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(y.shape, fused.dtype),
        scratch_shapes=[pltpu.VMEM((_sub_steps(tq, gw), _NORM_ROWS, gw), _F32)],
        compiler_params=pltpu.CompilerParams(**_NORM_PARAMS),
        interpret=interpret,
        name="ssm_norm_fwd",
    )(y, fused, norm_scale.astype(_F32)[None])


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def _norm_backward(y, fused, norm_scale, dout, shape, interpret):
    gw, eps, tq = shape
    b, s, inner = y.shape
    nt = s // tq
    wide, row, partial = _norm_specs(tq, gw)
    dy, dz, dscale = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, eps=eps),
        grid=(b, nt, inner // gw),
        in_specs=[wide, wide, row, wide],
        out_specs=[wide, wide, partial],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, fused.dtype),
                   jax.ShapeDtypeStruct((b, nt, 1, inner), _F32)],
        scratch_shapes=[pltpu.VMEM((_sub_steps(tq, gw), _NORM_ROWS, gw), _F32),
                        pltpu.VMEM((_CARRY, gw), _F32)],
        compiler_params=pltpu.CompilerParams(**_NORM_PARAMS),
        interpret=interpret,
        name="ssm_norm_bwd",
    )(y, fused, norm_scale.astype(_F32)[None], dout)
    return (dy, _into_fused(dz, 0, fused.shape[2]),
            dscale.sum(axis=(0, 1, 2)).astype(norm_scale.dtype))
