"""What the flash kernels' tests compare against and ask with: the
backward as a plain scan over K tiles (the oracle the Pallas backward is
pinned to; it lived in ``horovod_tpu/ops/flash_attention.py`` until
PR 47 and no program called it), and a call's ``FlashPlan`` from folded
operands or from loose sizes, and the tile pairs a mask keeps."""

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.ops import flash_attention as fa


def flash_bwd_blockwise(q, k, v, o, lse, do, causal, scale, bk,
                        window=None):
    """Blockwise flash backward (pure JAX scan over K tiles) on folded
    operands ``[Z, S, D]``, one kv row a query row.  ``v`` and ``do`` may
    be wider or narrower than ``q`` and ``k``: dv comes back at the
    values' width."""
    z, s, d = q.shape
    nk = s // bk
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    dof, of = do.astype(jnp.float32), o.astype(jnp.float32)
    delta = (dof * of).sum(-1)  # [Z,S]
    q_pos = jnp.arange(s)

    def body(dq, j):
        kb = lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)
        vb = lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
        st = jnp.einsum("zqd,zkd->zqk", qf, kb) * scale
        p = jnp.exp(st - lse[..., None])  # exact softmax: exp(s-m)/l
        if causal:
            k_pos = j * bk + jnp.arange(bk)
            p = jnp.where(k_pos[None, :] > q_pos[:, None], 0.0, p)
            if window is not None:
                p = jnp.where(
                    k_pos[None, :] < q_pos[:, None] - (window - 1),
                    0.0, p,
                )
        dp = jnp.einsum("zqd,zkd->zqk", dof, vb)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("zqk,zkd->zqd", ds, kb) * scale
        dk_j = jnp.einsum("zqk,zqd->zkd", ds, qf) * scale
        dv_j = jnp.einsum("zqk,zqd->zkd", p, dof)
        return dq, (dk_j, dv_j)

    dq, (dks, dvs) = lax.scan(
        body, jnp.zeros_like(qf), jnp.arange(nk)
    )
    # stacked [nk, Z, bk, D] -> [Z, S, D], D the keys' or the values'
    unfold = lambda t: t.transpose(1, 0, 2, 3).reshape(z, s, t.shape[-1])
    return (
        dq.astype(q.dtype),
        unfold(dks).astype(k.dtype),
        unfold(dvs).astype(v.dtype),
    )


def live_pairs(seq, bq, bk, window=None, causal=True):
    """The (Q tile, K tile) pairs a mask keeps, Q tile major, from the
    distances ``query - key`` between two tiles: every whole number from
    ``first query - last key`` to ``last query - first key``.  A causal
    query sees the keys at distances 0 to ``window - 1`` (no window: to
    the sequence's start); a pair is live if one of its distances is
    among them."""
    reach = seq if window is None else window
    pairs = []
    for i in range(seq // bq):
        for j in range(seq // bk):
            nearest = i * bq - ((j + 1) * bk - 1)
            farthest = (i + 1) * bq - 1 - j * bk
            if not causal or (farthest >= 0 and nearest <= reach - 1):
                pairs.append((i, j))
    return pairs


def folded_plan(q, k, v, causal, bq, bk, h=1, hkv=1, window=None):
    """The plan of the call whose folded operands ``[batch * heads, S,
    D]`` these are, as ``flash_attention`` makes it before it folds."""
    unfolded = lambda x, heads: jax.ShapeDtypeStruct(
        (x.shape[0] // heads, x.shape[1], heads, x.shape[2]), x.dtype)
    return fa.flash_plan(unfolded(q, h), unfolded(k, hkv), unfolded(v, hkv),
                         causal=causal, block_q=bq, block_k=bk,
                         window=window)


def plan_of(seq, d, group=1, itemsize=2, block_q=512, block_k=256,
            value_dim=None, *, causal=True, window=None, rows=1):
    """The plan of a call from its sizes alone: ``rows`` kv rows of
    ``seq`` keys at head size ``d``, ``group`` query heads each, values
    ``value_dim`` wide (``None``: as wide as the keys)."""
    dtype = {2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    shape = lambda heads, width: jax.ShapeDtypeStruct(
        (1, seq, heads, width), dtype)
    return fa.flash_plan(
        shape(rows * group, d), shape(rows, d),
        shape(rows, d if value_dim is None else value_dim),
        causal=causal, block_q=block_q, block_k=block_k, window=window)


def force_form(monkeypatch, form):
    """Every plan made from here on takes the one-kernel backward in the
    named form whatever the shape says, stating the limit a small shape
    states."""
    made = fa.flash_plan
    monkeypatch.setattr(fa, "flash_plan", lambda *a, **kw: dataclasses.replace(
        made(*a, **kw), bwd_form=form,
        bwd_vmem_bytes=fa._FUSED_BWD_VMEM_LIMIT))


def traced_calls(monkeypatch):
    """A list that receives, for every plan made from here on, the pair
    ``((q, k, v shapes), plan)``: the calls a model traces, in order
    (each makes its plan twice: ``_attend_schedule`` for its gauges,
    ``flash_attention`` for its kernels)."""
    calls, made = [], fa.flash_plan

    def spy(q, k, v, **kw):
        calls.append(((q.shape, k.shape, v.shape), made(q, k, v, **kw)))
        return calls[-1][1]

    monkeypatch.setattr(fa, "flash_plan", spy)
    return calls
