"""The Mamba-1 selective scan as two Pallas TPU kernels under a
``custom_vjp``.

Per channel ``c``, with a state ``h`` of ``state`` numbers that starts at
zero::

    h_t[c, n] = exp(dt_t[c] * A[c, n]) * h_{t-1}[c, n] + dt_t[c] * B_t[n] * u_t[c]
    y_t[c]    = sum_n C_t[n] * h_t[c, n] + D[c] * u_t[c]

The decay is its own for every channel AND state, so the recurrence does
not unroll into a ``chunk x chunk`` product as Mamba-2's does
(``ops/ssd.py``): it is walked token by token.  What the kernels arrange
is that the walk never leaves the chip's fast memory and that every
token's work is whole vector registers: a grid step takes ``_TIME_BLOCK``
tokens of ``_CHANNEL_BLOCK`` channels, channels along the lanes and the
state's ``n`` along the sublanes (a ``[16, 512]`` float32 state is eight
registers), and the state rides a VMEM scratch from one time block to
the next (grid ``batch x channel blocks x time blocks``, the last axis
sequential).  Inside a block a ``fori_loop`` takes ``_GROUP`` tokens a
turn, unrolled, so loads and stores are whole tiles.

``B_t`` and ``C_t`` have to stand along the sublanes, the same for every
lane.  They come from a matmul with a token a row; turning each row into
a column inside the kernel is the cross-lane unit's work, token by token.
XLA writes them out once a call instead, as ``[seq * state, 128]`` with
every lane holding the value (33 MB each in bfloat16 at 8192 tokens),
and the kernels read ``[16, 128]`` tiles of that.  It is the larger part
of what the calls read and is hidden behind the arithmetic: the
recurrence, not the memory, bounds both kernels.

Under differentiation the forward also writes the state at each time
block's start (``[batch, seq / 128, state, channels]`` float32: 20 MiB a
layer at 8192 tokens, 5120 channels of 16), which with ``y`` is all that
is kept (``kept_mib``).  The backward walks the time blocks in reverse:
in each it runs the recurrence forward again from the kept state with
every ``h_t`` into a VMEM scratch (4 MiB), then walks the block's tokens
backwards with the state's gradient in registers.  ``dA`` adds up in an
output block that stays in VMEM over the whole time axis; ``dB`` and
``dC`` are sums over channels, which a block's 512 channels reach in two
steps: the lane groups are added, and the 128 lanes that are left are
summed by the MXU against a matrix of ones, with the result a token and
state a lane.  XLA adds the channel blocks' shares.

Precision: ``dt``, ``A``, the decays, the state and its gradient are
float32 whatever ``u`` is.  ``y``, ``du`` take ``u``'s dtype; the one
matmul's operands (the lane sums of ``dB`` and ``dC``) take ``u``'s dtype
and accumulate in float32, full precision where ``u`` is float32.

Off the TPU the same kernels run through the Pallas interpreter at any
shape (``flash_attention._interpret_for_backend``, looked up at call
time).  On the chip a shape the tiles cannot take is refused by name
(``_check_tiles``).  The calls sit behind an inner ``jax.jit``, so the
layers of a model lower each kernel once.  Any ``seq`` is taken: the
wrapper pads it to whole time blocks with ``dt = 0``, which leaves the
state as it is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes
from . import flash_attention

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))   # a @ b.T

_TIME_BLOCK = 128      # tokens a grid step; a kept state each
_CHANNEL_BLOCK = 512   # channels a grid step, along the lanes
_GROUP = 16            # tokens a loop turn: one bfloat16 tile's rows
_LANES = 128
_VMEM_LIMIT = 16 * 2 ** 20


def _blocks(seq: int, channels: int):
    """``(time block, padded seq, channel block, lanes)`` for a shape."""
    tb = min(_TIME_BLOCK, -(-seq // _GROUP) * _GROUP)
    lanes = _LANES if channels % _LANES == 0 else channels
    cb = lanes
    while cb * 2 <= _CHANNEL_BLOCK and channels % (cb * 2) == 0:
        cb *= 2
    return tb, -(-seq // tb) * tb, cb, lanes


def _check_tiles(channels: int, state: int):
    """What the compiled kernels need of a shape, with its numbers."""
    if channels % _LANES:
        raise ValueError(
            f"selective_scan: channels={channels} is not a multiple of "
            f"{_LANES}, the lanes a channel block is cut into on the TPU")
    if state % 8 or (_GROUP * state) % _LANES:
        raise ValueError(
            f"selective_scan: state={state} has to be a multiple of 8 (the "
            f"sublanes it lies along), and {_GROUP} tokens of it a "
            f"multiple of {_LANES} lanes")


def kept_mib(batch: int, seq: int, channels: int, state: int) -> float:
    """MiB of time-block-start states one call keeps for its backward."""
    tb, padded, _, _ = _blocks(seq, channels)
    return batch * (padded // tb) * state * channels * 4 / 2 ** 20


def selective_scan(u, dt, A, B, C, D):
    """``u`` [batch, seq, channels]; ``dt`` like ``u`` (positive: after
    its softplus); ``A`` [channels, state] (negative); ``B``, ``C``
    [batch, seq, state]; ``D`` [channels].  Returns ``y`` like ``u``."""
    b, s, c = u.shape
    n = A.shape[1]
    if dt.shape != u.shape or A.shape[0] != c or D.shape != (c,) or (
            B.shape != (b, s, n) or C.shape != (b, s, n)):
        raise ValueError(
            f"selective_scan: u {u.shape}, dt {dt.shape}, A {A.shape}, "
            f"B {B.shape}, C {C.shape}, D {D.shape} do not belong together")
    interpret = flash_attention._interpret_for_backend(jax.default_backend())
    if not interpret:
        _check_tiles(c, n)
    with jax.named_scope(scopes.SELECTIVE_SCAN):
        return _scan(u, dt, A, B, C, D, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(u, dt, A, B, C, D, interpret):
    y, _ = _forward(u, dt, A, B, C, D, interpret, False)
    return y


def _scan_fwd(u, dt, A, B, C, D, interpret):
    y, states = _forward(u, dt, A, B, C, D, interpret, True)
    # both, or a rematerialised block reruns the call: the backward reads
    # the states, the gate's recompute (and a layer that is handed the
    # scan's output) reads y
    y = checkpoint_name(y, scopes.SSCAN_OUT)
    states = checkpoint_name(states, scopes.SSCAN_STATES)
    return y, (u, dt, A, B, C, D, states)


def _scan_bwd(interpret, res, dy):
    return _backward(*res, dy, interpret)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _layouts(u, dt, A, B, C, padded, lanes):
    """What both kernels read: ``u`` and ``dt`` padded to whole time
    blocks (``dt = 0`` keeps the state), ``A`` with the state along the
    sublanes, and ``B`` and ``C`` a token and state a row, every lane
    holding the value."""
    b, s, _ = u.shape
    n = A.shape[1]
    pad = lambda t: jnp.pad(t, ((0, 0), (0, padded - s), (0, 0)))

    def spread(t):
        t = pad(t.astype(u.dtype))
        return jnp.broadcast_to(t[..., None], (b, padded, n, lanes)).reshape(
            b, padded * n, lanes)

    return (pad(u), pad(dt.astype(_F32)), A.astype(_F32).T, spread(B),
            spread(C))


def _wide(tile, reps: int):
    """A ``[state, lanes]`` tile over a channel block's lane groups."""
    return tile if reps == 1 else jnp.concatenate([tile] * reps, axis=1)


def _rows(acc, row, k: int):
    """Row ``k`` of the ``_GROUP`` rows in ``acc`` (two halves of eight,
    so that a pick touches one tile's registers) set to ``row``."""
    half = acc[k // 8]
    at = lax.broadcasted_iota(jnp.int32, half.shape, 0) == k % 8
    picked = jnp.where(at, jnp.broadcast_to(row, half.shape), half)
    return tuple(picked if i == k // 8 else h for i, h in enumerate(acc))


def _no_rows(cb: int):
    return (jnp.zeros((8, cb), _F32),) * (_GROUP // 8)


def _fwd_kernel(u_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest,
                n, save_states):
    st_ref = rest[0] if save_states else None
    h_scr = rest[-1]
    tb, cb = u_ref.shape[1:]
    reps = cb // b_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        h_scr[...] = jnp.zeros(h_scr.shape, _F32)

    if save_states:
        st_ref[0, 0] = h_scr[...]
    a, d = a_ref[...], d_ref[...]

    def group(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        r0 = pl.multiple_of(g * (_GROUP * n), _GROUP * n)
        u = u_ref[0, pl.ds(t0, _GROUP), :].astype(_F32)
        dt = dt_ref[0, pl.ds(t0, _GROUP), :]
        bx = b_ref[0, pl.ds(r0, _GROUP * n), :].astype(_F32)
        cx = c_ref[0, pl.ds(r0, _GROUP * n), :].astype(_F32)
        du = dt * u
        read = _no_rows(cb)
        for k in range(_GROUP):
            state = slice(k * n, (k + 1) * n)
            h = (jnp.exp(dt[k:k + 1] * a) * h
                 + du[k:k + 1] * _wide(bx[state], reps))
            read = _rows(read, jnp.sum(_wide(cx[state], reps) * h, axis=0,
                                       keepdims=True), k)
        y = jnp.concatenate(read, axis=0) + d * u
        y_ref[0, pl.ds(t0, _GROUP), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = lax.fori_loop(0, tb // _GROUP, group, h_scr[...])


def _bwd_kernel(u_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref, st_ref,
                du_ref, ddt_ref, da_ref, db_ref, dc_ref,
                g_scr, hs_scr, eb_scr, ec_scr, *, n):
    tb, cb = u_ref.shape[1:]
    lanes = b_ref.shape[-1]
    reps = cb // lanes
    dtype = u_ref.dtype
    exact = lax.Precision.HIGHEST if dtype == _F32 else None

    @pl.when(pl.program_id(2) == 0)          # the sequence's last block
    def _last_block():
        g_scr[...] = jnp.zeros(g_scr.shape, _F32)
        da_ref[0] = jnp.zeros(da_ref.shape[1:], _F32)

    a, d = a_ref[...], d_ref[...]

    # the block's states again, from the kept one: hs[t + 1] = h_t
    hs_scr[pl.ds(0, n), :] = st_ref[0, 0]

    def again(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        r0 = pl.multiple_of(g * (_GROUP * n), _GROUP * n)
        dt = dt_ref[0, pl.ds(t0, _GROUP), :]
        du = dt * u_ref[0, pl.ds(t0, _GROUP), :].astype(_F32)
        bx = b_ref[0, pl.ds(r0, _GROUP * n), :].astype(_F32)
        for k in range(_GROUP):
            h = (jnp.exp(dt[k:k + 1] * a) * h
                 + du[k:k + 1] * _wide(bx[k * n:(k + 1) * n], reps))
            hs_scr[pl.ds(pl.multiple_of(r0 + (k + 1) * n, n), n), :] = h
        return h

    lax.fori_loop(0, tb // _GROUP, again, st_ref[0, 0])

    def fold(t):
        """[state, cb] -> [state, lanes]: the lane groups added."""
        return sum(t[:, j * lanes:(j + 1) * lanes] for j in range(reps))

    groups = tb // _GROUP
    ones = jnp.ones((8, lanes), dtype)
    group_row = lax.broadcasted_iota(jnp.int32, (groups, _GROUP * n), 0)

    def group(i, carry):
        gst, d_a, d_b, d_c = carry
        g = groups - 1 - i
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        r0 = pl.multiple_of(g * (_GROUP * n), _GROUP * n)
        u = u_ref[0, pl.ds(t0, _GROUP), :].astype(_F32)
        dt = dt_ref[0, pl.ds(t0, _GROUP), :]
        dy = dy_ref[0, pl.ds(t0, _GROUP), :].astype(_F32)
        bx = b_ref[0, pl.ds(r0, _GROUP * n), :].astype(_F32)
        cx = c_ref[0, pl.ds(r0, _GROUP * n), :].astype(_F32)
        du = dt * u
        d_du, d_dt = _no_rows(cb), _no_rows(cb)
        h_t = hs_scr[pl.ds(pl.multiple_of(r0 + _GROUP * n, n), n), :]
        for k in reversed(range(_GROUP)):
            state = slice(k * n, (k + 1) * n)
            h_before = hs_scr[pl.ds(pl.multiple_of(r0 + k * n, n), n), :]
            gst = gst + _wide(cx[state], reps) * dy[k:k + 1]
            ec_scr[state, :] = fold(h_t * dy[k:k + 1])
            eb_scr[state, :] = fold(gst * du[k:k + 1])
            d_du = _rows(d_du, jnp.sum(gst * _wide(bx[state], reps), axis=0,
                                       keepdims=True), k)
            # through the decay: d/d(dt A) of exp(dt A) h_before
            gst = gst * jnp.exp(dt[k:k + 1] * a)
            through = gst * h_before
            d_a = d_a + through * dt[k:k + 1]
            d_dt = _rows(d_dt, jnp.sum(through * a, axis=0, keepdims=True),
                         k)
            h_t = h_before
        d_du = jnp.concatenate(d_du, axis=0)
        du_ref[0, pl.ds(t0, _GROUP), :] = (d_du * dt + d * dy).astype(
            du_ref.dtype)
        ddt_ref[0, pl.ds(t0, _GROUP), :] = (
            jnp.concatenate(d_dt, axis=0) + d_du * u)

        def over_lanes(scr):
            """[tokens x state, lanes] -> its lane sums, a token and
            state a lane, through the MXU."""
            return lax.dot_general(
                ones, scr[...].astype(dtype), _NT, precision=exact,
                preferred_element_type=_F32)[:1]

        d_b = jnp.where(group_row == g, over_lanes(eb_scr), d_b)
        d_c = jnp.where(group_row == g, over_lanes(ec_scr), d_c)
        return gst, d_a, d_b, d_c

    none = jnp.zeros((groups, _GROUP * n), _F32)
    gst, d_a, d_b, d_c = lax.fori_loop(
        0, groups, group, (g_scr[...], jnp.zeros((n, cb), _F32), none, none))
    g_scr[...] = gst
    da_ref[0] += d_a
    db_ref[0, 0] = d_b
    dc_ref[0, 0] = d_c


def _specs(n, tb, cb, lanes, nt, reverse):
    """The block specs of what both kernels stream over the grid (batch,
    channel blocks, time blocks); ``reverse`` walks time from the end."""
    at = (lambda ti: nt - 1 - ti) if reverse else (lambda ti: ti)
    return dict(
        wide=pl.BlockSpec((1, tb, cb), lambda bi, ci, ti: (bi, at(ti), ci)),
        a=pl.BlockSpec((n, cb), lambda bi, ci, ti: (0, ci)),
        d=pl.BlockSpec((1, cb), lambda bi, ci, ti: (0, ci)),
        spread=pl.BlockSpec((1, tb * n, lanes),
                            lambda bi, ci, ti: (bi, at(ti), 0)),
        saved=pl.BlockSpec((1, 1, n, cb),
                           lambda bi, ci, ti: (bi, at(ti), 0, ci)),
        da=pl.BlockSpec((1, n, cb), lambda bi, ci, ti: (bi, 0, ci)),
        narrow=pl.BlockSpec((1, 1, tb // _GROUP, _GROUP * n),
                            lambda bi, ci, ti: (bi, ci, at(ti), 0)))


_STREAMED = ("wide", "wide", "a", "d", "spread", "spread")
_PARAMS = dict(
    # the state crosses the time axis
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("interpret", "save_states"))
def _forward(u, dt, A, B, C, D, interpret, save_states):
    b, s, c = u.shape
    n = A.shape[1]
    tb, padded, cb, lanes = _blocks(s, c)
    nt = padded // tb
    spec = _specs(n, tb, cb, lanes, nt, reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, padded, c), u.dtype)]
    out_specs = [spec["wide"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nt, n, c), _F32))
        out_specs.append(spec["saved"])
    u_, dt_, a_, b_, c_ = _layouts(u, dt, A, B, C, padded, lanes)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, save_states=save_states),
        grid=(b, c // cb, nt),
        in_specs=[spec[name] for name in _STREAMED],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, cb), _F32)],       # the state
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="sscan_fwd",
    )(u_, dt_, a_, D.astype(_F32)[None], b_, c_)
    return out[0][:, :s], (out[1] if save_states else None)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(u, dt, A, B, C, D, states, dy, interpret):
    b, s, c = u.shape
    n = A.shape[1]
    tb, padded, cb, lanes = _blocks(s, c)
    nt, ncb = padded // tb, c // cb
    spec = _specs(n, tb, cb, lanes, nt, reverse=True)
    u_, dt_, a_, b_, c_ = _layouts(u, dt, A, B, C, padded, lanes)
    dy_ = jnp.pad(dy.astype(u.dtype), ((0, 0), (0, padded - s), (0, 0)))
    narrow = jax.ShapeDtypeStruct((b, ncb, padded // _GROUP, _GROUP * n),
                                  _F32)
    du, ddt, dA, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n),
        grid=(b, ncb, nt),
        in_specs=[spec[name] for name in _STREAMED + ("wide", "saved")],
        out_specs=[spec[name] for name in ("wide", "wide", "da", "narrow",
                                           "narrow")],
        out_shape=[jax.ShapeDtypeStruct((b, padded, c), u.dtype),
                   jax.ShapeDtypeStruct((b, padded, c), _F32),
                   jax.ShapeDtypeStruct((b, n, c), _F32), narrow, narrow],
        scratch_shapes=[
            pltpu.VMEM((n, cb), _F32),                # the state's gradient
            pltpu.VMEM(((tb + 1) * n, cb), _F32),     # the block's states
            pltpu.VMEM((_GROUP * n, lanes), _F32),    # dB before lane sums
            pltpu.VMEM((_GROUP * n, lanes), _F32),    # dC before lane sums
        ],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret,
        name="sscan_bwd",
    )(u_, dt_, a_, D.astype(_F32)[None], b_, c_, dy_, states)
    over_blocks = lambda t, like: t.sum(axis=1).reshape(b, padded, n)[
        :, :s].astype(like.dtype)
    dD = jnp.sum(dy.astype(_F32) * u.astype(_F32), axis=(0, 1))
    return (du[:, :s], ddt[:, :s].astype(dt.dtype),
            dA.sum(axis=0).T.astype(A.dtype), over_blocks(dB, B),
            over_blocks(dC, C), dD.astype(D.dtype))
