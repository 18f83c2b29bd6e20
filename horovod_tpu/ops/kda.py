"""Kimi Delta Attention's recurrence (arXiv:2510.26692): a gated delta
rule with a decay per channel of the key, chunked, under a ``custom_vjp``.

Per head, with a state ``S`` of ``d_k x d_v`` that starts at zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

computed without a loop over single tokens.  The sequence is cut into
chunks of ``chunk`` tokens.  With ``G_i`` the log-decays cumulated from
the chunk's start and ``S`` the state there, the pseudo-values ``u~_i =
beta_i (v_i - (Diag(alpha_i) S_{i-1})^T k_i)`` of a chunk solve a unit
lower triangular system::

    A~_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)        (j < i)
    T  = (I + A~)^-1 Diag(beta)
    W  = T (K * exp(G));  U = T V;  U~ = U - W S
    o_i = S^T (q_i * exp(G_i)) + sum_{j<=i} [sum_d q_id k_jd
                                       exp(G_id - G_jd)] u~_j
    S' = Diag(exp(G_C)) S + sum_j (k_j * exp(G_C - G_j)) u~_j^T

``(I + A~)^-1`` is ``(I + N)(I + N^2)(I + N^4) ...`` with ``N = -A~``
(``N^chunk = 0``): ``log2 chunk`` squarings, matmuls.

**The decays.**  ``exp(-G_j)`` alone overflows (``g`` reaches -50 a token,
``G`` -3200 over a chunk of 64); only differences ``G_i - G_j <= 0`` are
safe.  The two Gram matrices are therefore made in sub-blocks of
``SUB_BLOCK`` tokens: a pair of tokens in the same sub-block gets its
``exp(G_i - G_j)`` directly, a channel at a time (``[sub, sub, d_k]`` a
sub-block, masked to ``j <= i`` by a ``-inf`` BEFORE the ``exp``); a pair
in two sub-blocks factorises through the later one's first row ``R``:
``exp(G_i - R) * exp(R - G_j)``, both exponents ``<= 0``, so the products
over ``d_k`` are matmuls.  Nothing ever exponentiates a positive number.

**What runs.**  Where :func:`plan` gives tiles for the call's shape, two
Pallas kernels, ``kda_fwd`` and ``kda_bwd`` (PR 54), on the grid (batch,
blocks of ``HEAD_BLOCK`` heads, groups of ``states_every`` chunks), the
last axis sequential: the state (forward) and the state's gradient
(backward, the groups in reverse) stay in a float32 VMEM scratch from
group to group, transposed (``[d_v, d_k]``: a decay a channel is a
multiply along the lanes).  The BlockSpecs read ``q``, ``k``, ``v``,
``g`` and write ``o`` and the gradients as ``[batch, seq, heads x d]``
blocks of a group's rows and a block's heads, so nothing is moved into a
group-major layout and nothing of ``[chunks, heads, chunk, chunk]``
goes through HBM.  A program walks its group's chunks; the heads of a
block are written out one after another in the loop's body, independent
chains for Mosaic to interleave.  The Gram matrices follow ``_gram``'s
rule with the same sub-blocks: a pair of tokens in two sub-blocks
through the later one's first row (matmuls), a pair inside a sub-block a
distance at a time (rows rolled by ``t``, ``exp(G_i - G_{i-t})``, a sum
along the lanes, ``SUB_BLOCK - 1`` distances).  The backward loads its
group's kept state, runs the forward's body over the group keeping each
chunk's state, ``(I + A~)^-1``, both Gram matrices, ``u~`` and ``W`` in
VMEM, then walks the chunks back; the log-decays' gradient is products
of arrays it already holds (for ``A_ij = sum_d a_id k_jd exp(G_id -
G_jd)``, ``dG_id`` gets ``a_id dL/da_id`` and ``dG_jd`` loses ``k_jd
dL/dk_jd`` of that term) and ``dg`` its cumulated sum from the chunk's
end, a triangular matmul.  Off the TPU the kernels run through the Pallas
interpreter, every loop that is written out for Mosaic a ``fori_loop``
there (``_loop``).

Elsewhere (on the chip: a head size off the 128 lanes, a chunk under 16
rows, a group past the VMEM the calls state) both directions are the
same chunk algebra as XLA compiles it (PR 51), which is also what the
kernels are tested against.  The forward is a ``lax.scan`` over the
groups: the Gram matrices, ``T``, ``W`` and ``U`` of a group's chunks at
once, then the state through the group's chunks; the backward walks the
groups in reverse with the state's gradient as the carry and
differentiates one group's algebra at a time from its kept state.

Either way the forward keeps the state at each group's start (``[batch,
groups, heads, d_k, d_v]`` float32: 128 MiB a layer at 16 384 tokens,
32 heads of 128, chunk 64, a state every fourth chunk) and ``o``, so
nothing of ``[seq, heads, d_k, d_v]`` is ever held.

Precision: ``g``, ``beta``, the cumulated log-decays, the decays, the
Gram matrices, ``T`` and the states are float32 whatever ``q``, ``k`` and
``v`` are (the Gram products and the squarings at full precision); the
operands of the other matmuls take ``v``'s dtype and accumulate in
float32: the state is carried, decayed and added to in float32, and
rounded like ``T`` and the read-out's Gram matrix where it enters a
product (``W S``, ``q S``).
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
_FULL = lax.Precision.HIGHEST
# Tokens whose pairwise decays are formed directly: a channel at a time
# (the XLA form), a distance at a time (the kernels).
SUB_BLOCK = 16
# Heads a program takes: independent chains for Mosaic to interleave.
HEAD_BLOCK = 4
# The VMEM both calls state: a v5e's default scoped limit, as
# ``ops/ssd.py`` states it and for its reason (what a call asks above
# that is taken from XLA's own fusions around it).
_VMEM_LIMIT = 16 * 2 ** 20
# dot_general numbers: a @ b, a @ b.T and a.T @ b.
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def group_chunks(chunks: int, states_every: int) -> int:
    """Chunks a group holds: the largest divisor of the sequence's
    ``chunks`` up to ``states_every``."""
    n = max(1, min(states_every, chunks))
    while chunks % n:
        n -= 1
    return n


def kept_mib(batch: int, seq: int, heads: int, d_k: int, d_v: int,
             chunk: int, states_every: int, itemsize: int) -> float:
    """What one call keeps for its backward beside its inputs: a float32
    state a group and ``o`` in ``v``'s dtype."""
    groups = seq // chunk // group_chunks(seq // chunk, states_every)
    return (batch * groups * heads * d_k * d_v * 4
            + batch * seq * heads * d_v * itemsize) / 2 ** 20


def _head_block(heads: int) -> int:
    """Heads a program takes: the largest divisor of ``heads`` up to
    ``HEAD_BLOCK``."""
    hb = min(HEAD_BLOCK, heads)
    while heads % hb:
        hb -= 1
    return hb


def _vmem_bytes(n: int, chunk: int, hb: int, d_k: int, d_v: int,
                itemsize: int) -> int:
    """VMEM the backward call holds (the forward holds less), minor
    dimensions padded to the 128 lanes their tiles occupy: the streamed
    blocks (two buffers each), what the program keeps of its group's
    chunks between its walk forward and its walk back, the states'
    gradients, and three dozen ``[chunk, 128]`` float32 temporaries a
    head."""
    lanes = lambda d: -(-d // 128) * 128
    rows = n * chunk
    wide_k, wide_v = rows * lanes(hb * d_k), rows * lanes(hb * d_v)
    streamed = ((2 * wide_k + 2 * wide_v) * itemsize + wide_k * 4   # in
                + (2 * wide_k + wide_v) * itemsize + wide_k * 4     # out
                + 2 * n * -(-hb // 8) * 8 * lanes(chunk) * 4
                + hb * d_k * lanes(d_v) * 4)
    kept = hb * ((n + 1) * d_v * lanes(d_k) * 4
                 + n * (2 * chunk * lanes(chunk) * 4
                        + chunk * (lanes(chunk) + lanes(d_k) + lanes(d_v))
                        * itemsize))
    state = hb * d_v * lanes(d_k) * 4
    temporaries = hb * 36 * chunk * lanes(max(d_k, d_v, chunk)) * 4
    return 2 * streamed + kept + state + temporaries


def plan(seq: int, heads: int, d_k: int, d_v: int, chunk: int,
         states_every: int, itemsize: int):
    """``(heads a program, interpreted)`` for the kernels, or ``None``
    where the XLA form runs: compiled, a head must be whole 128-lane
    tiles in keys and in values, a chunk whole 16-row tiles, and a
    program's group fit the VMEM the calls state.  Through the
    interpreter (off the TPU) every shape is taken."""
    interpret = flash_attention._interpret_for_backend(jax.default_backend())
    hb = _head_block(heads)
    if interpret:
        return hb, True
    n = group_chunks(seq // chunk, states_every)
    if d_k % 128 or d_v % 128 or chunk % 16:
        return None
    while hb > 1 and (heads % hb or _vmem_bytes(
            n, chunk, hb, d_k, d_v, itemsize) > _VMEM_LIMIT):
        hb -= 1
    if _vmem_bytes(n, chunk, hb, d_k, d_v, itemsize) > _VMEM_LIMIT:
        return None
    return hb, False


def kda(q, k, v, g, beta, *, chunk: int = 64, states_every: int = 4):
    """``q``, ``k`` [batch, seq, heads, d_k] (``k`` of unit norm a head,
    ``q`` scaled); ``v`` [batch, seq, heads, d_v]; ``g`` [batch, seq,
    heads, d_k], the log-decay a channel (``<= 0``); ``beta`` [batch,
    seq, heads] in [0, 1].  Returns ``o`` like ``v``.  ``seq`` must be a
    multiple of ``chunk``; a state is kept every ``states_every`` chunks
    (fewer where that does not divide the chunks).  The kernels take the
    call where :func:`plan` gives tiles for its shape, the XLA form
    elsewhere."""
    b, s, h, dk = q.shape
    if s % chunk:
        raise ValueError(
            f"kda: seq={s} is not a multiple of chunk={chunk}")
    if chunk & (chunk - 1):
        raise ValueError(
            f"kda: chunk={chunk} is no power of two: the triangular "
            f"inverse is taken by squarings")
    if k.shape != q.shape or g.shape != q.shape or v.shape[:3] != (b, s, h) \
            or beta.shape != (b, s, h):
        raise ValueError(
            f"kda: q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, "
            f"beta {beta.shape} do not agree")
    n = group_chunks(s // chunk, states_every)
    tiles = plan(s, h, dk, v.shape[-1], chunk, states_every,
                 v.dtype.itemsize)
    with jax.named_scope(scopes.KDA_SCAN):
        return _kda(q, k, v, g.astype(_F32), beta.astype(_F32), chunk, n,
                    tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, chunk, n, tiles):
    return _kda_fwd(q, k, v, g, beta, chunk, n, tiles)[0]


def _kda_fwd(q, k, v, g, beta, chunk, n, tiles):
    if tiles is None:
        o, states = _forward(q, k, v, g, beta, chunk, n)
    else:
        o, states = _kernel_forward(q, k, v, g, beta, chunk, n, *tiles)
    # both, or a rematerialised block reruns the rule: the backward reads
    # the states, the gated norm's recompute reads o
    o = checkpoint_name(o, scopes.KDA_OUT)
    states = checkpoint_name(states, scopes.KDA_STATES)
    return o.reshape(v.shape), (q, k, v, g, beta, states)


def _kda_bwd(chunk, n, tiles, res, do):
    if tiles is None:
        return _backward(*res, do, chunk, n)
    return _kernel_backward(*res, do, chunk, n, *tiles)


_kda.defvjp(_kda_fwd, _kda_bwd)


def _by_group(t, chunk, n):
    """``[batch, seq, heads, ...]`` as ``[groups, batch, heads, n, chunk,
    ...]``: the scan's leading axis, then what the matmuls batch over."""
    b, s, h = t.shape[:3]
    t = t.reshape(b, s // (chunk * n), n, chunk, h, *t.shape[3:])
    return jnp.moveaxis(t, (1, 4), (0, 2))


def _from_groups(t, like):
    """The inverse of :func:`_by_group`, in ``like``'s shape and dtype."""
    return jnp.moveaxis(t, (0, 2), (1, 4)).reshape(like.shape).astype(
        like.dtype)


def _gram(qf, kf, G, sub):
    """``sum_d a_id k_jd exp(G_id - G_jd)`` for ``j <= i`` and zero above,
    for ``a = q`` and for ``a = k``: two ``[.., chunk, chunk]`` float32
    matrices from ``qf``, ``kf``, ``G`` ``[.., chunk, d_k]`` float32."""
    lead, (c, d) = G.shape[:-2], G.shape[-2:]
    blocks = c // sub
    blk = lambda t: t.reshape(*lead, blocks, sub, d)
    Gb, qb, kb = blk(G), blk(qf), blk(kf)
    # the same sub-block: the decays themselves, [.., blocks, i, j, d]
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]
    ke = kb[..., None, :, :] * jnp.exp(
        jnp.where((j <= i)[..., None], diff, -jnp.inf))
    same = [jnp.sum(a[..., :, None, :] * ke, axis=-1) for a in (qb, kb)]
    # an earlier sub-block: through the later one's first row
    first = Gb[..., 0, :]                                 # [.., blocks, d]
    lead_decay = jnp.exp(Gb - first[..., None, :])
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(blocks) * sub)[:, None])      # [blocks, chunk]
    kh = kf[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], first[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                        # [.., blocks, c, d]
    eye = jnp.eye(blocks, dtype=_F32)
    out = []
    for a, diag in zip((qb, kb), same):
        earlier = jnp.einsum("...isd,...icd->...isc", a * lead_decay, kh,
                             precision=_FULL)
        diag = diag[..., :, :, None, :] * eye[:, None, :, None]
        out.append(earlier.reshape(*lead, c, c)
                   + diag.reshape(*lead, c, c))
    return out


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` strictly lower triangular ``[.., c, c]``:
    with ``N = -a``, ``(I + N)(I + N^2)(I + N^4) ...`` until the power
    passes ``c`` (``N^c = 0``)."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=a.dtype)
    mm = functools.partial(jnp.matmul, precision=_FULL)
    power = -a
    inverse = eye + power
    reach = 2
    while reach < c:
        power = mm(power, power)
        inverse = mm(inverse, eye + power)
        reach *= 2
    return inverse


def _group(S, q, k, v, g, beta):
    """One group of ``n`` chunks from the state ``S`` [batch, heads, d_k,
    d_v] at its start: ``q``, ``k``, ``g`` [batch, heads, n, chunk, d_k],
    ``v`` [.., d_v], ``beta`` [batch, heads, n, chunk].  Returns the
    state at the group's end and ``o`` [batch, heads, n, chunk, d_v]
    float32."""
    dtype = v.dtype
    precision = _FULL if dtype == _F32 else None
    mm = lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), precision=precision,
        preferred_element_type=_F32)
    n, c = q.shape[2], q.shape[3]
    G = jnp.cumsum(g, axis=3)
    qf, kf = q.astype(_F32), k.astype(_F32)
    a_qk, a_kk = _gram(qf, kf, G, min(SUB_BLOCK, c))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a_kk = jnp.where(strict, a_kk, 0.0) * beta[..., :, None]
    T = _unit_lower_inverse(a_kk) * beta[..., None, :]
    decay = jnp.exp(G)                                    # from the start
    W = mm("...ij,...jd->...id", T, kf * decay)
    U = mm("...ij,...jd->...id", T, v)
    q_in = qf * decay
    total = G[..., -1:, :]                                # the chunk's whole
    k_out = kf * jnp.exp(total - G)
    outs = []
    for ci in range(n):
        at = lambda t: t[:, :, ci]
        u = at(U) - mm("bhid,bhde->bhie", at(W), S)
        outs.append(mm("bhid,bhde->bhie", at(q_in), S)
                    + mm("bhij,bhje->bhie", at(a_qk), u))
        S = (jnp.exp(at(total))[..., 0, :, None] * S
             + mm("bhjd,bhje->bhde", at(k_out), u))
    return S, jnp.stack(outs, axis=2)


@functools.partial(jax.jit, static_argnames=("chunk", "n"))
def _forward(q, k, v, g, beta, chunk, n):
    b, s, h, dk = q.shape
    dv = v.shape[-1]

    def step(S, xs):
        S_next, o = _group(S, *xs)
        return S_next, (S, o.astype(v.dtype))

    xs = tuple(_by_group(t, chunk, n) for t in (q, k, v, g, beta))
    _, (states, o) = lax.scan(step, jnp.zeros((b, h, dk, dv), _F32), xs)
    # o [groups, batch, heads, n, chunk, d_v]; the states by batch first
    return _from_groups(o, v), jnp.moveaxis(states, 0, 1)


@functools.partial(jax.jit, static_argnames=("chunk", "n"))
def _backward(q, k, v, g, beta, states, do, chunk, n):
    def step(dS, xs):
        S, do_g, *inputs = xs
        _, pullback = jax.vjp(_group, S, *inputs)
        dS_prev, *grads = pullback((dS, do_g.astype(_F32)))
        return dS_prev, tuple(grads)

    inputs = (q, k, v, g, beta)
    xs = (jnp.moveaxis(states, 1, 0), _by_group(do, chunk, n),
          *(_by_group(t, chunk, n) for t in inputs))
    _, grads = lax.scan(step, jnp.zeros_like(states[:, 0]), xs, reverse=True)
    return tuple(_from_groups(dt, t) for dt, t in zip(grads, inputs))


# ---------------------------------------------------------------------
# The kernels: the same algebra a head and chunk at a time on VMEM
# values, the state transposed (``[d_v, d_k]``: a decay a channel is a
# multiply along the lanes, and a sum over ``d_v`` a sum over sublanes).
# ---------------------------------------------------------------------

def _full(a, b, dims=_NN):
    return lax.dot_general(a, b, dims, precision=_FULL,
                           preferred_element_type=_F32)


def _mm(dtype):
    """The products ``_group`` takes in ``v``'s dtype: both operands
    rounded to it, float32 out."""
    precision = _FULL if dtype == _F32 else None
    return lambda a, b, dims=_NN: lax.dot_general(
        a.astype(dtype), b.astype(dtype), dims, precision=precision,
        preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _roll(t, shift, compiled):
    """Row ``i`` gets row ``i - shift``, around the ends."""
    shift %= t.shape[0]
    return pltpu.roll(t, shift, 0) if compiled else jnp.roll(t, shift, 0)


def _column(row):
    """``[1, chunk]`` as ``[chunk, 1]`` (a token a lane as a token a
    sublane) through the diagonal of a ``[chunk, chunk]`` tile: exact."""
    c = row.shape[-1]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), row, 0.0),
                   axis=-1, keepdims=True)


def _row(column):
    """The inverse of :func:`_column`."""
    c = column.shape[0]
    return jnp.sum(jnp.where(_iota((c, c), 0) == _iota((c, c), 1), column,
                             0.0), axis=0, keepdims=True)


def _loop(count, compiled, body, carry=0, first=0):
    """``carry = body(i, carry)`` for ``i`` from ``first`` up to ``count``.
    Compiled, written out: one block of straight code whose independent
    chains Mosaic interleaves, every offset and shift a number it knows.
    Through the interpreter a ``fori_loop``, whose body XLA's CPU
    backend compiles once."""
    if not compiled:
        return lax.fori_loop(first, count, body, carry)
    for i in range(first, count):
        carry = body(i, carry)
    return carry


def _gram_factors(kf, G):
    """The decays of a pair of tokens in two sub-blocks, every exponent
    ``<= 0``, for ``kf``, ``G`` [chunk, d_k] float32: ``lead`` and, a
    sub-block from the second on, ``(kf * tail, tail)``; the pair's
    decay is ``lead_i * tail_j``, through the first row of ``i``'s
    sub-block."""
    c = G.shape[0]
    sub = min(SUB_BLOCK, c)
    row = _iota((c, 1), 0)
    first = jnp.broadcast_to(G[:1], G.shape)
    for p in range(1, c // sub):
        first = jnp.where(row >= p * sub, G[p * sub:p * sub + 1], first)
    far = []
    for p in range(1, c // sub):
        tail = jnp.exp(jnp.where(row < p * sub, G[p * sub:p * sub + 1] - G,
                                 -jnp.inf))
        far.append((kf * tail, tail))
    return jnp.exp(G - first), far


def _near(kf, G, t, compiled):
    """A token and the one ``t`` before it in its sub-block: ``decay_i =
    exp(G_i - G_{i-t})`` (zero where ``i - t`` is another sub-block's)
    and ``k_{i-t} * decay_i``."""
    c = G.shape[0]
    inside = (_iota((c, 1), 0) & (min(SUB_BLOCK, c) - 1)) >= t
    decay = jnp.exp(jnp.where(inside, G - _roll(G, t, compiled), -jnp.inf))
    return _roll(kf, t, compiled) * decay, decay


def _gram_kernel(qf, kf, G, factors, compiled):
    """``_gram``'s two matrices ``[chunk, chunk]``: ``a_qk`` lower with
    its diagonal, ``a_kk`` strictly lower."""
    lead, far = factors
    c = qf.shape[0]
    sub = min(SUB_BLOCK, c)
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    ql, kl = qf * lead, kf * lead
    parts = [jnp.zeros((2 * sub, c), _F32)]
    for p, (rhs, _) in enumerate(far, 1):
        at = slice(p * sub, (p + 1) * sub)
        parts.append(_full(jnp.concatenate([ql[at], kl[at]], axis=0), rhs,
                           _NT))
    a_qk = jnp.concatenate([part[:sub] for part in parts], axis=0)
    a_kk = jnp.concatenate([part[sub:] for part in parts], axis=0)
    along = lambda t: jnp.sum(t, axis=-1, keepdims=True)
    a_qk += jnp.where(rows == cols, along(qf * kf), 0.0)

    def distance(t, grams):
        shifted, _ = _near(kf, G, t, compiled)
        diagonal = cols == rows - t
        return (grams[0] + jnp.where(diagonal, along(qf * shifted), 0.0),
                grams[1] + jnp.where(diagonal, along(kf * shifted), 0.0))

    return _loop(sub, compiled, distance, (a_qk, a_kk), first=1)


def _gram_grad(qf, kf, G, factors, d_qk, d_kk, compiled):
    """The Gram matrices' pullback: ``q``'s gradient, ``k``'s as a row
    of ``a_kk`` and ``k``'s as a column of both, ``[chunk, d_k]`` each,
    from ``d_qk`` (lower with its diagonal) and ``d_kk`` (strictly
    lower).  The log-decays' gradient is made of these three."""
    lead, far = factors
    c, d = qf.shape
    sub = min(SUB_BLOCK, c)
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    d_col = jnp.zeros((c, d), _F32)
    ql, kl = qf * lead, kf * lead
    parts = [jnp.zeros((2 * sub, d), _F32)]
    for p, (rhs, tail) in enumerate(far, 1):
        at = slice(p * sub, (p + 1) * sub)
        d_res = jnp.concatenate([d_qk[at], d_kk[at]], axis=0)
        lhs = jnp.concatenate([ql[at], kl[at]], axis=0)
        parts.append(_full(d_res, rhs))
        d_col += _full(d_res, lhs, _TN) * tail
    d_q = jnp.concatenate([part[:sub] for part in parts], axis=0) * lead
    d_row = jnp.concatenate([part[sub:] for part in parts], axis=0) * lead
    pick = lambda m, t: jnp.sum(jnp.where(cols == rows - t, m, 0.0),
                                axis=-1, keepdims=True)
    on = pick(d_qk, 0)

    def distance(t, grads):
        shifted, decay = _near(kf, G, t, compiled)
        cq, ck = pick(d_qk, t), pick(d_kk, t)
        return (grads[0] + cq * shifted, grads[1] + ck * shifted,
                grads[2] + _roll((cq * qf + ck * kf) * decay, -t, compiled))

    return _loop(sub, compiled, distance,
                 (d_q + on * kf, d_row, d_col + on * qf), first=1)


def _chunk_terms(q, k, g, compiled):
    """What both directions form anew from a chunk's ``q``, ``k``
    [chunk, d_k] and ``g`` float32."""
    c = g.shape[0]
    qf, kf = q.astype(_F32), k.astype(_F32)
    lower = (_iota((c, c), 0) >= _iota((c, c), 1)).astype(_F32)
    G = _full(lower, g)
    total = G[c - 1:c]                                    # the chunk's whole
    decay = jnp.exp(G)
    return dict(qf=qf, kf=kf, G=G, total=total, decay=decay,
                q_in=qf * decay, k_in=kf * decay,
                to_end=jnp.exp(total - G),
                factors=_gram_factors(kf, G))


def _chunk_forward(St, terms, v, beta_row, compiled):
    """One chunk from the state ``St`` [d_v, d_k] at its start, ``beta``
    [1, chunk]: the state at its end, ``o`` [chunk, d_v] float32, and
    what the backward reads again (``M^-1``, ``a_qk``, ``a_kk`` without
    ``beta``, ``u~``, ``W``)."""
    mm = _mm(v.dtype)
    a_qk, a_kk = _gram_kernel(terms["qf"], terms["kf"], terms["G"],
                              terms["factors"], compiled)
    m_inv = _unit_lower_inverse(a_kk * _column(beta_row))
    T = m_inv * beta_row
    W = mm(T, terms["k_in"])
    u = mm(T, v) - mm(W, St, _NT)
    o = mm(terms["q_in"], St, _NT) + mm(a_qk, u)
    St_next = (St * jnp.exp(terms["total"])
               + mm(u, terms["kf"] * terms["to_end"], _TN))
    return St_next, o, (m_inv, a_qk, a_kk, u, W)


def _chunk_backward(dSt, do, St, kept, terms, v, beta_row, compiled):
    """One chunk's pullback from ``do`` [chunk, d_v] and the gradient
    ``dSt`` of the state at its end: the gradient of the state at its
    start, then ``dq``, ``dk``, ``dv``, ``dg`` float32 and ``beta``'s
    gradient [1, chunk] (through ``T``'s columns and through ``A~``'s
    rows)."""
    mm = _mm(v.dtype)
    m_inv, a_qk, a_kk, u, W = kept
    qf, kf, total = terms["qf"], terms["kf"], terms["total"]
    c = qf.shape[0]
    rows, cols = _iota((c, c), 0), _iota((c, c), 1)
    k_out = kf * terms["to_end"]
    end = jnp.exp(total)
    T = m_inv * beta_row
    d_u = mm(a_qk, do, _TN) + mm(k_out, dSt, _NT)
    d_qk = jnp.where(rows >= cols, mm(do, u, _NT), 0.0)
    d_q_in = mm(do, St)
    d_W = -mm(d_u, St)
    d_k_out = mm(u, dSt)
    dSt_prev = (mm(do, terms["q_in"], _TN) + dSt * end
                - mm(d_u, W, _TN))
    d_T = mm(d_W, terms["k_in"], _NT) + mm(d_u, v, _NT)
    d_k_in = mm(T, d_W, _TN)
    d_v = mm(T, d_u, _TN)
    d_a = jnp.where(rows > cols, -_full(
        _full(m_inv, d_T * beta_row, _TN), m_inv, _NT), 0.0)
    d_beta = (jnp.sum(d_T * m_inv, axis=0, keepdims=True)
              + _row(jnp.sum(d_a * a_kk, axis=-1, keepdims=True)))
    d_q, d_row, d_col = _gram_grad(qf, kf, terms["G"], terms["factors"],
                                   d_qk, d_a * _column(beta_row), compiled)
    through_end = k_out * d_k_out
    dG = (qf * d_q + kf * (d_row - d_col) + d_q_in * terms["q_in"]
          + d_k_in * terms["k_in"] - through_end)
    at_end = (jnp.sum(through_end, axis=0, keepdims=True)
              + end * jnp.sum(St * dSt, axis=0, keepdims=True))
    dG += jnp.where(_iota((c, 1), 0) == c - 1, at_end, 0.0)
    upper = (rows <= cols).astype(_F32)
    return (dSt_prev, d_q + d_q_in * terms["decay"],
            d_row + d_col + d_k_in * terms["decay"]
            + d_k_out * terms["to_end"], d_v, _full(upper, dG), d_beta)


def _operands(refs, ci, h, chunk, compiled):
    """Chunk ``ci`` of head ``h`` of a program's blocks: its rows, its
    lanes in keys and in values, its terms, ``v`` and ``beta``."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    hb = beta_ref.shape[3]
    dk, dv = g_ref.shape[-1] // hb, v_ref.shape[-1] // hb
    at = pl.ds(pl.multiple_of(ci * chunk, chunk), chunk)
    keys, values = pl.ds(h * dk, dk), pl.ds(h * dv, dv)
    terms = _chunk_terms(q_ref[0, at, keys], k_ref[0, at, keys],
                         g_ref[0, at, keys], compiled)
    return (at, keys, values, terms, v_ref[0, at, values],
            beta_ref[0, 0, ci, pl.ds(h, 1), :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref, s_scr,
                *, hb, n, chunk, compiled):
    streamed = (q_ref, k_ref, v_ref, g_ref, beta_ref)

    @pl.when(pl.program_id(2) == 0)
    def _first_group():
        s_scr[...] = jnp.zeros_like(s_scr)

    def keep(h, carry):
        st_ref[0, 0, h] = s_scr[h].T
        return carry

    _loop(hb, compiled, keep)

    def one_chunk(ci, carry):
        def head(h, carry):
            at, _, values, terms, v, beta = _operands(streamed, ci, h, chunk,
                                                      compiled)
            s_scr[h], o, _ = _chunk_forward(s_scr[h], terms, v, beta,
                                            compiled)
            o_ref[0, at, values] = o.astype(o_ref.dtype)
            return carry

        return _loop(hb, compiled, head, carry)

    lax.fori_loop(0, n, one_chunk, 0)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, st_ref, dq_ref,
                dk_ref, dv_ref, dg_ref, dbeta_ref, ds_scr, s_scr, minv_scr,
                aqk_scr, akk_scr, u_scr, w_scr, *, hb, n, chunk, compiled):
    streamed = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    kept_scr = (minv_scr, aqk_scr, akk_scr, u_scr, w_scr)

    @pl.when(pl.program_id(2) == 0)
    def _last_group():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def start(h, carry):
        s_scr[h, 0] = st_ref[0, 0, h].T
        return carry

    _loop(hb, compiled, start)

    def walk_forward(ci, carry):
        def head(h, carry):
            *_, terms, v, beta = _operands(streamed, ci, h, chunk, compiled)
            s_scr[h, ci + 1], _, kept = _chunk_forward(
                s_scr[h, ci], terms, v, beta, compiled)
            for scr, value in zip(kept_scr, kept):
                scr[h, ci] = value.astype(scr.dtype)
            return carry

        return _loop(hb, compiled, head, carry)

    lax.fori_loop(0, n, walk_forward, 0)

    def walk_back(step, carry):
        ci = n - 1 - step

        def head(h, carry):
            at, keys, values, terms, v, beta = _operands(streamed, ci, h,
                                                         chunk, compiled)
            ds_scr[h], d_q, d_k, d_v, d_g, d_beta = _chunk_backward(
                ds_scr[h], do_ref[0, at, values], s_scr[h, ci],
                tuple(scr[h, ci] for scr in kept_scr), terms, v, beta,
                compiled)
            dq_ref[0, at, keys] = d_q.astype(dq_ref.dtype)
            dk_ref[0, at, keys] = d_k.astype(dk_ref.dtype)
            dv_ref[0, at, values] = d_v.astype(dv_ref.dtype)
            dg_ref[0, at, keys] = d_g
            dbeta_ref[0, 0, ci, pl.ds(h, 1), :] = d_beta
            return carry

        return _loop(hb, compiled, head, carry)

    lax.fori_loop(0, n, walk_back, 0)


def _specs(b, s, h, dk, dv, chunk, n, hb, reverse):
    """The grid (batch, head blocks, groups) and the block specs of what
    both kernels stream over it; ``reverse`` walks the groups from the
    end."""
    groups = s // (chunk * n)
    at = (lambda gi: groups - 1 - gi) if reverse else (lambda gi: gi)
    rows = n * chunk
    return (b, h // hb, groups), dict(
        keys=pl.BlockSpec((1, rows, hb * dk),
                          lambda bi, hi, gi: (bi, at(gi), hi)),
        values=pl.BlockSpec((1, rows, hb * dv),
                            lambda bi, hi, gi: (bi, at(gi), hi)),
        beta=pl.BlockSpec((1, 1, n, hb, chunk),
                          lambda bi, hi, gi: (bi, hi, at(gi), 0, 0)),
        states=pl.BlockSpec((1, 1, hb, dk, dv),
                            lambda bi, hi, gi: (bi, at(gi), hi, 0, 0)))


def _layouts(q, k, v, g, beta, chunk, hb):
    """What both kernels read: heads folded into lanes, and ``beta`` a
    token a lane, ``[batch, head blocks, chunks, hb, chunk]`` (a
    ``[seq, hb]`` block would be padded to 128 lanes in HBM, 32 times
    its size; a chunk's column is made from its row in VMEM)."""
    b, s, h = beta.shape
    fold = lambda t: t.reshape(b, s, -1)
    rows = beta.reshape(b, s // chunk, chunk, h // hb, hb).transpose(
        0, 3, 1, 4, 2)
    return fold(q), fold(k), fold(v), fold(g), rows


_STREAMED = ("keys", "keys", "values", "keys", "beta")


@functools.partial(jax.jit, static_argnames=("chunk", "n", "hb",
                                             "interpret"))
def _kernel_forward(q, k, v, g, beta, chunk, n, hb, interpret):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    grid, spec = _specs(b, s, h, dk, dv, chunk, n, hb, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, n=n, chunk=chunk,
                          compiled=not interpret),
        grid=grid,
        in_specs=[spec[name] for name in _STREAMED],
        out_specs=[spec["values"], spec["states"]],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, grid[2], h, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), _F32)],   # the states
        compiler_params=pltpu.CompilerParams(
            # the states cross the groups
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kda_fwd",
    )(*_layouts(q, k, v, g, beta, chunk, hb))


@functools.partial(jax.jit, static_argnames=("chunk", "n", "hb",
                                             "interpret"))
def _kernel_backward(q, k, v, g, beta, states, do, chunk, n, hb, interpret):
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    grid, spec = _specs(b, s, h, dk, dv, chunk, n, hb, reverse=True)
    keys = lambda dtype: jax.ShapeDtypeStruct((b, s, h * dk), dtype)
    dq, dk_, dv_, dg, d_beta = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, n=n, chunk=chunk,
                          compiled=not interpret),
        grid=grid,
        in_specs=[spec[name] for name in _STREAMED + ("values", "states")],
        out_specs=[spec[name] for name in _STREAMED],
        out_shape=[keys(q.dtype), keys(k.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dv), v.dtype), keys(_F32),
                   jax.ShapeDtypeStruct((b, h // hb, s // chunk, hb, chunk),
                                        _F32)],
        scratch_shapes=[
            pltpu.VMEM((hb, dv, dk), _F32),            # the states' gradient
            pltpu.VMEM((hb, n + 1, dv, dk), _F32),     # each chunk's state,
            pltpu.VMEM((hb, n, chunk, chunk), _F32),   # M^-1,
            pltpu.VMEM((hb, n, chunk, chunk), v.dtype),    # a_qk,
            pltpu.VMEM((hb, n, chunk, chunk), _F32),   # a_kk,
            pltpu.VMEM((hb, n, chunk, dv), v.dtype),   # u~
            pltpu.VMEM((hb, n, chunk, dk), v.dtype),   # and W
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="kda_bwd",
    )(*_layouts(q, k, v, g, beta, chunk, hb), do.reshape(b, s, h * dv),
      states)
    # the inverse of _layouts' transpose
    d_beta = d_beta.transpose(0, 2, 4, 1, 3).reshape(b, s, h)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), d_beta)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = 64,
                     states_every: int = 4):
    """The gated delta rule whose decay is ONE number a head and token
    (Gated DeltaNet, arXiv:2412.06464), with fewer key heads than value
    heads: ``q``, ``k`` [batch, seq, key heads, d_k] (``k`` of unit norm
    a head, ``q`` scaled); ``v`` [batch, seq, heads, d_v], ``heads`` a
    multiple of the key heads, value head ``i`` reading key head ``i //
    (heads // key heads)``; ``g`` [batch, seq, heads], the log-decay
    (``<= 0``); ``beta`` [batch, seq, heads] in [0, 1].  Per value head
    ``S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``.  Returns ``o`` like ``v``.

    Computed by :func:`kda`'s rule, exactly: equal decays in all of a
    head's channels are the scalar rule.  ``g`` is spread over the
    ``d_k`` channels (float32 ``[batch, seq, heads, d_k]``: 256 MiB a
    layer at 16 384 tokens and 32 heads of 128) and ``q`` and ``k`` over
    the value heads (128 MiB each there), under the scope ``gdn_spread``
    inside ``gdn_scan``, which holds the rule itself; their gradients are
    the sums over what was spread.  Stands below the kernels so that no
    line of theirs moves: both rules lower to the same ``kda_fwd`` and
    ``kda_bwd``."""
    b, s, hk, dk = q.shape
    h = v.shape[2]
    if s % chunk or chunk & (chunk - 1):
        raise ValueError(
            f"gated_delta_rule: seq={s} must be a multiple of chunk="
            f"{chunk}, a power of two")
    if k.shape != q.shape or h % hk or v.shape[:2] != (b, s) \
            or g.shape != (b, s, h) or beta.shape != (b, s, h):
        raise ValueError(
            f"gated_delta_rule: q {q.shape}, k {k.shape}, v {v.shape}, "
            f"g {g.shape}, beta {beta.shape} do not agree")
    n = group_chunks(s // chunk, states_every)
    tiles = plan(s, h, dk, v.shape[-1], chunk, states_every,
                 v.dtype.itemsize)
    with jax.named_scope(scopes.GDN_SCAN):
        with jax.named_scope(scopes.GDN_SPREAD):
            if h != hk:
                q, k = (jnp.repeat(t, h // hk, axis=2) for t in (q, k))
            channels = jnp.broadcast_to(g.astype(_F32)[..., None],
                                        (b, s, h, dk))
        return _kda(q, k, v, channels, beta.astype(_F32), chunk, n, tiles)
