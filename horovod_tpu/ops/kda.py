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

**What runs.**  Both directions are the chunk algebra as XLA compiles it
(PR 51; a Pallas kernel for either, with the state in a VMEM scratch
across a sequential chunk axis as ``ops/ssd.py`` has it, is ROADMAP
queue A's).  The forward is a ``lax.scan`` over groups of
``states_every`` chunks: the Gram matrices, ``T``, ``W`` and ``U`` of a
group's chunks at once, then the state through the group's chunks.  It
keeps the state at each group's start (``[batch, groups, heads, d_k,
d_v]`` float32: 128 MiB a layer at 16 384 tokens, 32 heads of 128, chunk
64, a state every fourth chunk) and ``o``; the backward walks the groups
in reverse with the state's gradient as the carry and differentiates one
group's algebra at a time from its kept state, so nothing of ``[seq,
heads, d_k, d_v]`` and nothing of ``[chunks, heads, chunk, chunk]`` for
the whole sequence is ever held.

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

from .. import scopes

_F32 = jnp.float32
_FULL = lax.Precision.HIGHEST
# Tokens whose pairwise decays are formed directly, a channel at a time.
SUB_BLOCK = 16


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


def kda(q, k, v, g, beta, *, chunk: int = 64, states_every: int = 4):
    """``q``, ``k`` [batch, seq, heads, d_k] (``k`` of unit norm a head,
    ``q`` scaled); ``v`` [batch, seq, heads, d_v]; ``g`` [batch, seq,
    heads, d_k], the log-decay a channel (``<= 0``); ``beta`` [batch,
    seq, heads] in [0, 1].  Returns ``o`` like ``v``.  ``seq`` must be a
    multiple of ``chunk``; a state is kept every ``states_every`` chunks
    (fewer where that does not divide the chunks)."""
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
    with jax.named_scope(scopes.KDA_SCAN):
        return _kda(q, k, v, g.astype(_F32), beta.astype(_F32), chunk, n)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, chunk, n):
    return _forward(q, k, v, g, beta, chunk, n)[0]


def _kda_fwd(q, k, v, g, beta, chunk, n):
    o, states = _forward(q, k, v, g, beta, chunk, n)
    # both, or a rematerialised block reruns the rule: the backward reads
    # the states, the gated norm's recompute reads o
    o = checkpoint_name(o, scopes.KDA_OUT)
    states = checkpoint_name(states, scopes.KDA_STATES)
    return o, (q, k, v, g, beta, states)


def _kda_bwd(chunk, n, res, do):
    return _backward(*res, do, chunk, n)


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
