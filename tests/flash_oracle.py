"""What the flash kernels' tests compare against and ask with: the
backward as a plain scan over K tiles (the oracle the Pallas backward is
pinned to; it lived in ``horovod_tpu/ops/flash_attention.py`` until
PR 47 and no program called it), and a call's ``FlashPlan`` from folded
operands or from loose sizes, and the tile pairs a mask keeps; and what
the files of flash tests share (``tests/test_flash_*.py``): seeded
operands, the limits that force a backward's form, and the
``pallas_call``s a program holds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
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


def qkv(b=2, s=64, h=4, d=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d), dtype) * 0.3
    return mk(), mk(), mk()


def out_and_grads(f, weight, *operands):
    """``f``'s output and the gradients of its ``weight``ed float32 sum in
    its three operands, from one trace."""
    return jax.jit(lambda *a: (f(*a), jax.grad(
        lambda *b: (f(*b).astype(jnp.float32) * weight).sum(),
        argnums=(0, 1, 2))(*a)))(*operands)


def grouped_blockwise(q, k, v, o, lse, do, causal, scale, bk, window, h,
                      hkv):
    """`flash_bwd_blockwise` knows no grouped heads: give every query
    head its own copy of its kv row and fold dk and dv back (each at its
    own width: the values' need not be the keys')."""
    z, s, _ = q.shape
    b, group = z // h, h // hkv
    f32 = jnp.float32
    rep = lambda t: jnp.repeat(
        t.astype(f32).reshape(b, hkv, 1, s, t.shape[-1]), group, 2
    ).reshape(z, s, t.shape[-1])
    dq, dk, dv = flash_bwd_blockwise(q.astype(f32), rep(k), rep(v), o, lse,
                                     do, causal, scale, bk, window=window)
    fold = lambda t: t.reshape(b, hkv, group, s, t.shape[-1]).sum(2).reshape(
        -1, s, t.shape[-1])
    return dq, fold(dk), fold(dv)


def vmem_limits(monkeypatch, limit, ceiling=None):
    """The VMEM a one-kernel backward may state, as the shape gate reads
    it: ``limit`` for the forms in their order and ``ceiling`` for the
    smaller count above it (``None``: no room above the limit, so 0
    leaves the two passes alone)."""
    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_LIMIT", limit)
    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_CEILING",
                        limit if ceiling is None else ceiling)


def pallas_calls(jaxpr, what=lambda params: params["name"]):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield what(eqn.params)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(sub, what)


# the backward's ``pallas_call``s by name, as ``pallas_calls`` lists them
ONE_KERNEL = ["flash_bwd_dkdv"]
TWO_PASSES = ["flash_bwd_dkdv", "flash_bwd_dq"]


def force(monkeypatch, backward):
    """Take the named backward whatever the shape says; its kernels' names."""
    if backward == "two_passes":
        vmem_limits(monkeypatch, 0)
    elif backward == "dq_resident":
        force_form(monkeypatch, backward)
    return TWO_PASSES if backward == "two_passes" else ONE_KERNEL


def stated_vmem(eqn_params):
    """The ``vmem_limit_bytes`` a ``pallas_call`` states (``None``: the
    compiler's default)."""
    return eqn_params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes


def forward_call(eqn_params):
    """What a ``flash_fwd`` ``pallas_call`` holds of K and V: the rows of
    their blocks (the whole kv row where it is resident, ``block_k``
    where tiles stream) and the VMEM the call states."""
    k_block, v_block = eqn_params["grid_mapping"].block_mappings[1:3]
    rows = {int(m.block_shape[1].block_size) for m in (k_block, v_block)}
    assert len(rows) == 1 and k_block.pipeline_mode is None
    return rows.pop(), stated_vmem(eqn_params)


# --------------------------- the grids walk the live tiles alone (PR 49)
# A head's live (Q tile, K tile) pairs come from a table the kernels
# prefetch to SMEM, not from two grid axes and a predicate.  Tiles 32 x 16
# over 96 keys (3 x 6 a head): no mask, the causal half, and windows under
# both tiles, of a K tile exactly, and a multiple of neither.
WALK_SEQ, WALK_BQ, WALK_BK, WALK_D = 96, 32, 16, 16
WALK_MASKS = [
    ("noncausal", False, None),
    ("causal", True, None),
    ("window_8_under_the_tiles", True, 8),
    ("window_16_a_k_tile", True, 16),
    ("window_20_no_multiple", True, 20),
]
