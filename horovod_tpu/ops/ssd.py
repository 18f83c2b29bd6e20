"""The Mamba-2 selective state-space scan, chunked (state-space duality),
as two Pallas TPU kernels under a ``custom_vjp``.

Per head, with a state ``S`` of ``head_dim x state`` that starts at zero::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

computed without a loop over single tokens and without a tensor of
sequence x sequence: the sequence is cut into chunks of ``chunk`` tokens.
Inside a chunk the recurrence unrolls into a masked ``chunk x chunk``
product (``y_i = sum_{j<=i} (C_i . B_j) exp(a_j+1..i) dt_j x_j``: matmuls,
the MXU's work); across chunks only the state at each chunk's end is
carried, and read out through ``C``.

``ssd_fwd`` (grid batch, chunks, head blocks; the chunk axis sequential)
makes a chunk's ``C B^T`` once a group and, per head, the decay
``exp(a_i - a_j)`` under the causal mask and their product, as VMEM
tiles that feed the MXU and are never written; the states of all heads
ride a float32 VMEM scratch from chunk to chunk.  Under differentiation
it also writes each chunk's starting state (heads x head_dim x state
float32 a chunk: 64 MiB a layer at 8192 tokens, 64 heads of 64, state
128, chunk 256), which is all the backward keeps.  ``ssd_bwd`` walks the
chunks once in reverse with the state's gradient in the same kind of
scratch, forms every tile again from ``x``, ``dt``, ``B`` and ``C``, and
sums ``dB`` and ``dC`` over a group's heads in VMEM.  Nothing of
``[chunks, heads, chunk, chunk]`` goes through HBM in either direction.

The gradient of the log-decays needs no tile either: with ``a`` the
log-decay cumulated over the whole sequence, ``dL/da_i = dy_i . (y_i -
D x_i) - u_i . du_i`` (``u = dt * x``), two sums over ``head_dim`` a
token, and ``d(dt * A)`` is its cumulated sum from the sequence's end,
taken outside the kernel on ``[seq, heads]``.

Precision: ``dt``, ``A``, the cumulated log-decays, the decays and the
states are float32 whatever ``x`` is; the operands of the matmuls take
``x``'s dtype and accumulate in float32 (the two sums over ``head_dim``
that cancel nothing are such matmuls, against a matrix of ones); the
mask is ``-inf`` before the ``exp``.  No tile is transposed and no
per-token factor goes through the cross-lane unit: the kernels get the
cumulated log-decays with a token a lane (a decay's columns) and, with
``dt``, with a token a sublane, spread over a row's lanes by the MXU
(``_layouts``).

Off the TPU (the CPU test mesh) the same kernels run through the Pallas
interpreter at any shape; which one is taken from
``flash_attention._interpret_for_backend``, looked up through that
module at call time.  On the chip a shape the tiles cannot take is
refused by name with its numbers (``_check_tiles``).  The calls sit
behind an inner ``jax.jit``: the layers of a model share one shape, so
each kernel is traced and lowered once a program, not once a layer.

On a TPU v5e at 1 x 8192 tokens, 64 heads of 64, state 128, chunk 256,
bfloat16 (chip runs of PR 31): forward 0.84 ms a call, backward 1.85,
0.41 and 0.90 us a head and chunk; the XLA matmul form this file held
before took 1.8 and 4.7 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import scopes
from . import flash_attention

_F32 = jnp.float32
# dot_general numbers: a @ b.T (contract the minor dimension of both) and
# a.T @ b (contract the rows of both).
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# The VMEM both calls state (``vmem_limit_bytes``): a v5e's default scoped
# limit, which the cell's shape fits (``_vmem_bytes`` counts 10.2 MiB).
# What a call asks above that is taken from what XLA may hold in VMEM for
# its own fusions around the call: stating 32 MiB, as the flash backward
# does, made the step of the cell that runs this 9.4 ms longer (479.7
# against 470.3 ms, the feed-forward's backward matmuls; my chip runs,
# PR 31), and 12 MiB read the same as 16.
_VMEM_LIMIT = 16 * 2 ** 20
_HEAD_BLOCK = 8


def _head_block(heads_per_group: int) -> int:
    """Heads a grid step takes: the largest divisor of a group's heads up
    to ``_HEAD_BLOCK`` (a block never straddles two groups)."""
    hb = min(_HEAD_BLOCK, heads_per_group)
    while heads_per_group % hb:
        hb -= 1
    return hb


def _vmem_bytes(heads: int, p: int, n: int, chunk: int, hb: int,
                itemsize: int) -> int:
    """VMEM the backward call holds (the forward holds less), minor
    dimensions padded to the 128 lanes their tiles occupy: the state
    gradients of all heads, the two ``chunk x chunk`` scratch tiles and
    the group's ``dB`` and ``dC``, the streamed blocks (two buffers
    each), and a dozen ``chunk x chunk`` float32 temporaries."""
    lanes = lambda d: -(-d // 128) * 128
    tile = chunk * lanes(chunk) * 4
    wide = chunk * lanes(hb * p) * itemsize       # x, dy, dx
    narrow = chunk * lanes(n) * itemsize          # B, C, dB, dC
    factors = (chunk * lanes(6 * hb) * 2          # a and dt in parts,
               + 2 * hb * lanes(6 * hb) * lanes(max(128, p)) * 2
               + 8 * lanes(chunk) * 4)            # their picks, a as rows
    sums = 3 * chunk * lanes(hb) * 4 + 8 * lanes(hb * p) * 4
    saved = hb * p * lanes(n) * 4                 # chunk-start states
    return (heads * p * lanes(n) * 4 + 2 * tile + 2 * chunk * lanes(n) * 4
            + 2 * (3 * wide + 4 * narrow + factors + sums + saved)
            + 12 * tile)


def _check_tiles(heads, p, n, chunk, hb, itemsize):
    """What the compiled kernels need of a shape, with its numbers."""
    if chunk % 128:
        raise ValueError(
            f"ssd_scan: chunk={chunk} is not a multiple of 128, the lanes "
            "of a chunk x chunk tile on the TPU")
    if (hb * p) % 128 and hb * p != heads * p:
        raise ValueError(
            f"ssd_scan: a block of {hb} heads of head_dim={p} is "
            f"{hb * p} lanes wide, not a multiple of 128")
    if hb % 8 and hb != heads:
        raise ValueError(
            f"ssd_scan: a block of {hb} heads (heads per group a multiple "
            "of 8, or one block of all heads) is what the log-decays' "
            "sublanes take")
    need = _vmem_bytes(heads, p, n, chunk, hb, itemsize)
    if need > _VMEM_LIMIT:
        raise ValueError(
            f"ssd_scan: heads={heads} x head_dim={p} x state={n} at "
            f"chunk={chunk} needs {need} bytes of VMEM "
            f"({need / 2 ** 20:.1f} MiB), over the {_VMEM_LIMIT} "
            f"({_VMEM_LIMIT // 2 ** 20} MiB) the call states")


def kept_mib(batch: int, seq: int, heads: int, p: int, n: int, chunk: int,
             itemsize: int) -> float:
    """MiB one call keeps for its backward: ``y`` in ``x``'s dtype and a
    float32 state of ``heads x p x n`` at every chunk's start."""
    return batch * heads * p * (seq * itemsize
                                + seq // chunk * n * 4) / 2 ** 20


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
    hb = _head_block(h // g)
    interpret = flash_attention._interpret_for_backend(jax.default_backend())
    if not interpret:
        _check_tiles(h, p, n, chunk, hb, x.dtype.itemsize)
    with jax.named_scope(scopes.SSD_SCAN):
        return _ssd(x, dt, A, B, C, D, chunk, hb, bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ssd(x, dt, A, B, C, D, chunk, hb, interpret):
    y, _ = _forward(x, dt, A, B, C, D, chunk, hb, interpret, False)
    return y.reshape(x.shape)


def _ssd_fwd(x, dt, A, B, C, D, chunk, hb, interpret):
    y, states = _forward(x, dt, A, B, C, D, chunk, hb, interpret, True)
    # both, or a rematerialised block reruns the call: the backward reads
    # the states, the gated norm's recompute reads y.  y as the kernel
    # wrote it, heads folded into lanes: kept as [.., heads, head_dim] it
    # costs a copy and a convert a layer (64 of 128 lanes; 1.2 ms a
    # layer at 8192 x 64 x 64, my chip runs, PR 33)
    y = checkpoint_name(y, scopes.SSD_OUT)
    states = checkpoint_name(states, scopes.SSD_STATES)
    return y.reshape(x.shape), (x, dt, A, B, C, D, states)


def _ssd_bwd(chunk, hb, interpret, res, dy):
    return _backward(*res, dy, chunk, hb, interpret)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def _three_bfloat16(v):
    """A float32 array as three bfloat16 arrays that add up to it
    exactly, cut by bits (a cast down and up again is one the TPU
    compiler may drop)."""
    def top(t):
        bits = lax.bitcast_convert_type(t, jnp.uint32)
        return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), _F32)

    hi = top(v)
    mid = top(v - hi)
    return [t.astype(jnp.bfloat16) for t in (hi, mid, v - hi - mid)]


def _layouts(x, dt, A, B, C, chunk, hb):
    """What both kernels read, as XLA hands it over: ``x`` with heads
    folded into lanes; ``B`` and ``C`` as [batch, groups, seq, state]; the
    log-decay ``a`` cumulated from each chunk's start as [batch, heads,
    seq] (a token a lane); ``a`` and ``dt`` a token a sublane, each as
    three bfloat16 parts, a head block's 6 x hb columns side by side in
    128 lanes, with the 0/1 matrices that pick one head's ``a`` or ``dt``
    out of them and spread it over the lanes; and every chunk's whole
    log-decay and decay, flat over (batch, chunk, head), for the scalar
    memory.

    A per-token factor has to reach every lane of its token's row.  As a
    [chunk, 1] column it gets there through the cross-lane unit, 32
    vregs a factor, head and chunk, and that unit then bounds both
    kernels (8355 of its slots in a forward step of 5106 bundles; my
    compile for a v5e, PR 31); through the MXU, which has the room, it
    is one pass, and exact: the three parts are bfloat16 and their sum
    is float32."""
    b, s, h, p = x.shape
    dt = dt.astype(_F32)
    a = jnp.cumsum((dt * A.astype(_F32)).reshape(b, s // chunk, chunk, h),
                   axis=2).reshape(b, s, h)
    by_block = lambda t: t.reshape(b, s, h // hb, hb).transpose(0, 2, 1, 3)
    by_group = lambda t: t.astype(x.dtype).transpose(0, 2, 1, 3)
    parts = jnp.concatenate(
        [by_block(t) for v in (a, dt) for t in _three_bfloat16(v)], axis=-1)
    parts = jnp.pad(parts, ((0, 0),) * 3 + ((0, -6 * hb % 128),))
    pick = np.zeros((2 * hb, parts.shape[-1], _pick_lanes(chunk, p)),
                    np.float32)
    for which in range(2):                                # a, then dt
        for k in range(hb):
            for part in range(3):
                pick[which * hb + k, (3 * which + part) * hb + k] = 1.0
    log_ends = a[:, chunk - 1::chunk].reshape(-1)
    return (log_ends, jnp.exp(log_ends), x.reshape(b, s, h * p), parts,
            jnp.asarray(pick, jnp.bfloat16), a.transpose(0, 2, 1),
            by_group(B), by_group(C))


def _pick_lanes(chunk, p):
    """Lanes a picked factor is spread over: one vreg's 128 where the
    chunk is a multiple of that (the tile takes it once per 128 of its
    columns), else the chunk's own; never fewer than ``head_dim``."""
    return max(128 if chunk % 128 == 0 else chunk, p)


def _factors(parts_ref, pick_ref, arow_ref, k, hb, log_end, p):
    """Head ``k``'s per-token factors, every lane of a token's row
    holding the token's: the decay tile ``exp(a_i - a_j)`` for ``j <= i``
    [chunk, chunk], and ``dt``, ``exp(a)`` and the decay to the chunk's
    end ``exp(a_end - a)`` [chunk, p].  The tile is zero above the
    diagonal by a -inf before the exp, not a zero after it: there the
    difference is positive and its exp may overflow."""
    parts = parts_ref[0, 0]
    chunk = parts.shape[0]
    a = jnp.dot(parts, pick_ref[k], preferred_element_type=_F32)
    dt = jnp.dot(parts, pick_ref[hb + k, :, :p],
                 preferred_element_type=_F32)
    rows = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    across = a if a.shape[1] >= chunk else jnp.tile(
        a, (1, chunk // a.shape[1]))
    seg = across[:, :chunk] - arow_ref[0, k:k + 1, :]
    decay = jnp.exp(jnp.where(rows >= cols, seg, -jnp.inf))
    return decay, dt, jnp.exp(a[:, :p]), jnp.exp(log_end - a[:, :p])


def _first_head(hb, reverse):
    """Where a grid step's first head lies in the flat (batch, chunk,
    head) scalars."""
    ci, nc = pl.program_id(1), pl.num_programs(1)
    chunk = nc - 1 - ci if reverse else ci
    return ((pl.program_id(0) * nc + chunk) * pl.num_programs(2)
            + pl.program_id(2)) * hb


def _fwd_kernel(d_ref, lend_ref, end_ref, x_ref, parts_ref, pick_ref,
                arow_ref, b_ref, c_ref, y_ref, *rest, hb, p, group_blocks,
                save_states):
    st_ref = rest[0] if save_states else None
    s_scr, g_scr = rest[-2:]
    ci, hi = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    first = _first_head(hb, reverse=False)
    bb, cb = b_ref[0, 0], c_ref[0, 0]

    @pl.when(ci == 0)
    def _first_chunk():
        s_scr[hi] = jnp.zeros(s_scr.shape[1:], _F32)

    @pl.when(hi % group_blocks == 0)
    def _first_of_group():
        g_scr[...] = lax.dot_general(cb, bb, _NT,
                                     preferred_element_type=_F32)

    for k in range(hb):
        lanes = slice(k * p, (k + 1) * p)
        decay, dt, ea, to_end = _factors(parts_ref, pick_ref, arow_ref, k,
                                         hb, lend_ref[first + k], p)
        state = s_scr[hi, k]                              # [p, n]
        if save_states:
            st_ref[0, 0, k] = state
        xf = x_ref[0, :, lanes].astype(_F32)
        u = dt * xf
        off = lax.dot_general(cb, state.astype(dtype), _NT,
                              preferred_element_type=_F32)
        y = (jnp.dot((g_scr[...] * decay).astype(dtype), u.astype(dtype),
                     preferred_element_type=_F32)
             + ea * off + d_ref[hi * hb + k] * xf)
        y_ref[0, :, lanes] = y.astype(dtype)
        added = lax.dot_general((to_end * u).astype(dtype), bb, _TN,
                                preferred_element_type=_F32)
        s_scr[hi, k] = end_ref[first + k] * state + added


def _bwd_kernel(d_ref, lend_ref, end_ref, x_ref, parts_ref, pick_ref,
                arow_ref, b_ref, c_ref, dy_ref, st_ref, dx_ref, q_ref, r_ref,
                t_ref, dd_ref, db_ref, dc_ref, ds_scr, g_scr, dg_scr, db_scr,
                dc_scr, *, hb, p, group_blocks):
    ci, hi = pl.program_id(1), pl.program_id(2)   # ci counts from the end
    dtype = x_ref.dtype
    chunk = x_ref.shape[1]
    first = _first_head(hb, reverse=True)
    bb, cb = b_ref[0, 0], c_ref[0, 0]

    @pl.when(ci == 0)
    def _last_chunk():
        ds_scr[hi] = jnp.zeros(ds_scr.shape[1:], _F32)

    @pl.when(hi % group_blocks == 0)
    def _first_of_group():
        g_scr[...] = lax.dot_general(cb, bb, _NT,
                                     preferred_element_type=_F32)
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)
        dc_scr[...] = jnp.zeros_like(dc_scr)

    head = lax.broadcasted_iota(jnp.int32, (chunk, hb), 1)
    last_row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1
    ones = jnp.ones((p, 128), dtype)
    sums = [jnp.zeros((chunk, hb), _F32) for _ in range(3)]
    # the group's sums are carried as values and meet their scratch once
    # a step
    dg = jnp.zeros_like(dg_scr)
    db = jnp.zeros_like(db_scr)
    dc = jnp.zeros_like(dc_scr)
    for k in range(hb):
        lanes = slice(k * p, (k + 1) * p)
        end = end_ref[first + k]
        decay, dt, ea, to_end = _factors(parts_ref, pick_ref, arow_ref, k,
                                         hb, lend_ref[first + k], p)
        m = (g_scr[...] * decay).astype(dtype)
        xf = x_ref[0, :, lanes].astype(_F32)
        dyk = dy_ref[0, :, lanes]
        dyf = dyk.astype(_F32)
        u = dt * xf
        ub = u.astype(dtype)
        saved = st_ref[0, 0, k]                           # [p, n] float32
        state = saved.astype(dtype)
        dstate = ds_scr[hi, k]                            # of the chunk's end
        dstate_b = dstate.astype(dtype)
        # the forward's y without the skip term, float32
        y = (jnp.dot(m, ub, preferred_element_type=_F32)
             + ea * lax.dot_general(cb, state, _NT,
                                    preferred_element_type=_F32))
        dg += decay * lax.dot_general(dyk, ub, _NT,
                                      preferred_element_type=_F32)
        du_tile = lax.dot_general(m, dyk, _TN, preferred_element_type=_F32)
        du_state = to_end * lax.dot_general(bb, dstate_b, _NT,
                                            preferred_element_type=_F32)
        du = du_tile + du_state
        dx_ref[0, :, lanes] = (dt * du
                               + d_ref[hi * hb + k] * dyf).astype(dtype)
        dd_ref[0, 0, :, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        # The log-decay's gradient, a token a row.  Through the tile a
        # token gets what its row weighs less what its column weighs:
        # summed in float32 from the matmuls' own operands, so that the
        # two cancel over the chunk as they do in exact arithmetic (with
        # u in float32 on one side they did not, and the gradient stood
        # 18.5 % from the reference's; my chip run, PR 31).  The two sums
        # that cancel nothing are matmuls with a matrix of ones.
        uf = ub.astype(_F32)
        through_end = end * jnp.sum(saved * dstate)
        r = (jnp.sum(dyf * y - uf * du_tile, axis=-1, keepdims=True)
             + jnp.where(last_row, through_end, 0.0))
        t = jnp.dot((uf * du_state).astype(dtype), ones,
                    preferred_element_type=_F32)[:, :hb]
        q = jnp.dot((du * xf).astype(dtype), ones,
                    preferred_element_type=_F32)[:, :hb]
        sums = [jnp.where(head == k, part, total)
                for total, part in zip(sums, (q, r, t))]
        read = (ea * dyf).astype(dtype)                   # dy through exp(a)
        dc += jnp.dot(read, state, preferred_element_type=_F32)
        db += jnp.dot((to_end * u).astype(dtype), dstate_b,
                      preferred_element_type=_F32)
        ds_scr[hi, k] = end * dstate + lax.dot_general(
            read, cb, _TN, preferred_element_type=_F32)
    for out_ref, total in zip((q_ref, r_ref, t_ref), sums):
        out_ref[0, 0] = total
    dg_scr[...] += dg
    db_scr[...] += db
    dc_scr[...] += dc

    @pl.when(hi % group_blocks == group_blocks - 1)
    def _last_of_group():
        dg_b = dg_scr[...].astype(dtype)
        dc_ref[0, 0] = (dc_scr[...] + jnp.dot(
            dg_b, bb, preferred_element_type=_F32)).astype(dc_ref.dtype)
        db_ref[0, 0] = (db_scr[...] + lax.dot_general(
            dg_b, cb, _TN, preferred_element_type=_F32)).astype(db_ref.dtype)


def _specs(b, s, h, p, g, n, chunk, hb, reverse):
    """The grid (batch, chunks, head blocks) and the block specs of what
    both kernels stream over it; ``reverse`` walks the chunks from the
    end."""
    nc, nhb = s // chunk, h // hb
    group_blocks = nhb // g
    lanes = -(-6 * hb // 128) * 128
    at = (lambda ci: nc - 1 - ci) if reverse else (lambda ci: ci)
    block = lambda shape, index: pl.BlockSpec(
        shape, lambda bi, ci, hi: index(bi, at(ci), hi))
    return (nc, nhb, group_blocks), dict(
        scalars=pl.BlockSpec(memory_space=pltpu.SMEM),
        wide=block((1, chunk, hb * p), lambda bi, ci, hi: (bi, ci, hi)),
        column=block((1, 1, chunk, hb), lambda bi, ci, hi: (bi, hi, ci, 0)),
        parts=block((1, 1, chunk, lanes), lambda bi, ci, hi: (bi, hi, ci, 0)),
        pick=block((2 * hb, lanes, _pick_lanes(chunk, p)),
                   lambda bi, ci, hi: (0, 0, 0)),
        row=block((1, hb, chunk), lambda bi, ci, hi: (bi, hi, ci)),
        narrow=block((1, 1, chunk, n),
                     lambda bi, ci, hi: (bi, hi // group_blocks, ci, 0)),
        saved=block((1, 1, hb, p, n), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        skips=block((1, 1, 1, hb * p), lambda bi, ci, hi: (bi, ci, 0, hi)))


_STREAMED = ("scalars", "scalars", "scalars", "wide", "parts", "pick", "row",
             "narrow", "narrow")


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "interpret",
                                             "save_states"))
def _forward(x, dt, A, B, C, D, chunk, hb, interpret, save_states):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    (nc, nhb, group_blocks), spec = _specs(b, s, h, p, g, n, chunk, hb,
                                           reverse=False)
    out_shape = [jax.ShapeDtypeStruct((b, s, h * p), x.dtype)]
    out_specs = [spec["wide"]]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct((b, nc, h, p, n), _F32))
        out_specs.append(spec["saved"])
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p,
                          group_blocks=group_blocks,
                          save_states=save_states),
        grid=(b, nc, nhb),
        in_specs=[spec[name] for name in _STREAMED],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((nhb, hb, p, n), _F32),   # every head's state
            pltpu.VMEM((chunk, chunk), _F32),    # a group's C B^T
        ],
        compiler_params=pltpu.CompilerParams(
            # the states cross the chunk axis, the scores the blocks
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ssd_fwd",
    )(D.astype(_F32), *_layouts(x, dt, A, B, C, chunk, hb))
    return out[0], (out[1] if save_states else None)


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "interpret"))
def _backward(x, dt, A, B, C, D, states, dy, chunk, hb, interpret):
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    (nc, nhb, group_blocks), spec = _specs(b, s, h, p, g, n, chunk, hb,
                                           reverse=True)
    columns = jax.ShapeDtypeStruct((b, nhb, s, hb), _F32)
    groups = jax.ShapeDtypeStruct((b, g, s, n), x.dtype)
    dx, q, r, t, dd, dB, dC = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p,
                          group_blocks=group_blocks),
        grid=(b, nc, nhb),
        in_specs=[spec[name] for name in _STREAMED + ("wide", "saved")],
        out_specs=[spec[name] for name in (
            "wide", "column", "column", "column", "skips", "narrow",
            "narrow")],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * p), x.dtype),
                   columns, columns, columns,
                   jax.ShapeDtypeStruct((b, nc, 1, h * p), _F32),
                   groups, groups],
        scratch_shapes=[
            pltpu.VMEM((nhb, hb, p, n), _F32),   # every state's gradient
            pltpu.VMEM((chunk, chunk), _F32),    # a group's C B^T
            pltpu.VMEM((chunk, chunk), _F32),    # and its gradient
            pltpu.VMEM((chunk, n), _F32),        # dB of the group
            pltpu.VMEM((chunk, n), _F32),        # dC of the group
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="ssd_bwd",
    )(D.astype(_F32), *_layouts(x, dt, A, B, C, chunk, hb),
      dy.reshape(b, s, h * p), states)
    q, r, t = (v.transpose(0, 2, 1, 3).reshape(b, nc, chunk, h)
               for v in (q, r, t))
    # d(dt * A) of token k: what the chunk's tokens from k on weigh
    # through their own decays (r, with the chunk's whole decay on
    # its last token) and what the tokens before k send to the
    # chunk's end (t)
    d_log = (jnp.cumsum(r[:, :, ::-1], axis=2)[:, :, ::-1]
             + jnp.cumsum(t, axis=2) - t).reshape(b, s, h)
    d_dt = q.reshape(b, s, h) + A.astype(_F32) * d_log
    dA = jnp.sum(dt.astype(_F32) * d_log, axis=(0, 1))
    dD = dd.reshape(b * nc, h, p).sum(axis=(0, 2))
    from_group = lambda t, like: t.transpose(0, 2, 1, 3).astype(
        like.dtype)
    return (dx.reshape(b, s, h, p), d_dt.astype(dt.dtype),
            dA.astype(A.dtype), from_group(dB, B), from_group(dC, C),
            dD.astype(D.dtype))
