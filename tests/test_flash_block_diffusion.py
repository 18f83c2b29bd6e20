"""The flash kernels under the block-diffusion training mask
(``flash_attention(..., block_diffusion=B)``: a noised copy of ``L`` rows,
then the clean one, in blocks of ``B``): forward, ``lse``, ``dq``, ``dk``
and ``dv`` against plain attention under the dense mask, in every
backward form and with the forward streamed; the table of live tiles
against the rectangle to the bit, and against the tiles the dense mask
touches; the plan of the benchmark's cell; and the causal and window
plans of the cells that were there, field for field as the parent made
them.  The Pallas interpreter on the CPU, at 2 x 256 rows."""

from __future__ import annotations

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import local_attention
from horovod_tpu.parallel.ring_attention import block_diffusion_mask

B, ROWS, HEADS, KV_HEADS, D = 2, 512, 4, 2, 64
BQ, BK = 128, 64
FORMS = ["dkdv_resident", "dq_resident", "two_passes"]
TOLERANCE = {jnp.float32: 3e-5, jnp.bfloat16: 4e-2}


def _inputs(dtype, seed=55):
    rng = np.random.RandomState(seed)
    mk = lambda heads: jnp.asarray(
        rng.randn(B, ROWS, heads, D) * 0.7, dtype)
    return mk(HEADS), mk(KV_HEADS), mk(KV_HEADS), mk(HEADS)


def _fold(x):
    return x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], x.shape[3])


def _plan(q, k, v, block, **changed):
    plan = fa.flash_plan(q, k, v, block_diffusion=block, block_q=BQ,
                         block_k=BK)
    assert (plan.mask, plan.block, plan.causal, plan.window) == (
        "block_diffusion", block, False, None)
    assert (plan.bwd_form, plan.fwd_kv_resident) == ("dkdv_resident", True)
    if changed.get("bwd_form") == "two_passes":
        changed["bwd_vmem_bytes"] = 0
    return dataclasses.replace(plan, **changed)


def _kernels(plan, q, k, v, do):
    """o, lse, dq, dk, dv of the kernels under ``plan``, folded."""
    scale = D ** -0.5

    @jax.jit
    def run(q, k, v, do):
        o, lse = fa._flash_fwd_kernel(q, k, v, plan, scale, True)
        return (o, lse) + tuple(fa._flash_bwd_pallas(
            q, k, v, o, lse, do, plan, scale, True))

    return run(*(_fold(x) for x in (q, k, v, do)))


def _dense(q, k, v, do, block):
    """The same five from plain float32 attention under the dense mask."""
    q, k, v, do = (x.astype(jnp.float32) for x in (q, k, v, do))
    rep = lambda x: jnp.repeat(x, HEADS // KV_HEADS, axis=2)
    out, vjp = jax.vjp(
        lambda q, k, v: local_attention(q, rep(k), rep(v),
                                        block_diffusion=block), q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, rep(k)) * D ** -0.5
    lse = jax.nn.logsumexp(jnp.where(
        block_diffusion_mask(ROWS, block), scores, -jnp.inf), axis=-1)
    return (_fold(out), lse.reshape(-1, ROWS)) + tuple(
        _fold(g) for g in vjp(do))


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("block", [4, 32])
def test_kernels_match_plain_attention_under_the_dense_mask(
        block, dtype, form):
    """Grouped heads (4 over 2), each backward form forced as the plan
    states it: every output within the standing tolerance of plain
    attention under ``block_diffusion_mask``."""
    q, k, v, do = _inputs(dtype)
    got = _kernels(_plan(q, k, v, block, bwd_form=form), q, k, v, do)
    want = _dense(q, k, v, do, block)
    for name, a, w in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.shape == w.shape, name
        err = np.abs(np.asarray(a, np.float32) - np.asarray(w)).max()
        scale = max(1.0, float(np.abs(np.asarray(w)).max()))
        assert err <= TOLERANCE[dtype] * scale, (name, err)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("block", [4, 32])
def test_the_table_walk_is_the_rectangle_to_the_bit(block, dtype, form):
    """The grids over the table of live tiles against the same kernels
    over the whole rectangle under the traced tile rule
    (``live_tiles=None``), and the streamed forward against the resident
    one: a dead step adds nothing, so all five are equal to the bit."""
    q, k, v, do = _inputs(dtype, seed=56)
    plan = _plan(q, k, v, block, bwd_form=form)
    assert plan.live_tiles is not None
    assert plan.tiles_grid == plan.tiles_live == \
        B * HEADS * len(plan.live_tiles) < plan.tiles_mask
    table = _kernels(plan, q, k, v, do)
    others = {"the rectangle": dataclasses.replace(plan, live_tiles=None)}
    if form == FORMS[0]:    # the forward knows no backward form
        others["the streamed forward"] = dataclasses.replace(
            plan, fwd_kv_resident=False)
    for which, other in others.items():
        for name, a, r in zip(("o", "lse", "dq", "dk", "dv"), table,
                              _kernels(other, q, k, v, do)):
            assert a.dtype == r.dtype and a.shape == r.shape, name
            assert np.asarray(a).tobytes() == np.asarray(r).tobytes(), (
                f"{name}: the table against {which}")


def test_flash_attention_differentiates_under_jit():
    """The public call, grouped heads, under ``jit`` and ``grad``."""
    q, k, v, do = _inputs(jnp.float32, seed=57)
    rep = lambda x: jnp.repeat(x, HEADS // KV_HEADS, axis=2)
    ours = jax.jit(jax.grad(lambda q, k, v: (fa.flash_attention(
        q, k, v, block_diffusion=8, block_q=BQ, block_k=BK) * do).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    plain = jax.grad(lambda q, k, v: (local_attention(
        q, rep(k), rep(v), block_diffusion=8) * do).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(ours, plain):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=3e-5, rtol=3e-5)


# (rows, block_q, block_k, B): tiles larger and smaller than a block
_TABLES = [(512, 128, 64, 4), (512, 128, 64, 32), (256, 32, 16, 32),
           (256, 16, 32, 64), (128, 64, 64, 1), (64, 8, 8, 16)]


@pytest.mark.parametrize("rows,bq,bk,block", _TABLES)
def test_live_tiles_are_the_tiles_the_dense_mask_touches(rows, bq, bk,
                                                         block):
    """``flash_plan``'s table, Q tile major, is exactly the tiles that
    hold a visible pair of ``block_diffusion_mask``; no tile straddles
    the two copies; the traced rule says the same of every tile."""
    sh = jax.ShapeDtypeStruct((1, rows, 2, 16), jnp.float32)
    plan = fa.flash_plan(sh, sh, sh, block_diffusion=block, block_q=bq,
                         block_k=bk)
    assert (plan.block_q, plan.block_k) == (bq, bk)
    assert (rows // 2) % bq == (rows // 2) % bk == 0
    mask = np.asarray(block_diffusion_mask(rows, block))
    touched = mask.reshape(rows // bq, bq, rows // bk, bk).any(axis=(1, 3))
    assert list(plan.live_tiles) == [tuple(t) for t in np.argwhere(touched)]
    i, j = jnp.meshgrid(jnp.arange(rows // bq), jnp.arange(rows // bk),
                        indexing="ij")
    traced = jax.jit(lambda i, j: fa._tile_live(
        i, j, bq, bk, False, None, (rows // 2, block)))(i, j)
    np.testing.assert_array_equal(np.asarray(traced), touched)
    assert mask.any(axis=1).all()       # every row sees its own block


@pytest.mark.parametrize("length,bq,bk", [(8192, 512, 256), (2048, 256, 128),
                                          (1024, 512, 512)])
def test_live_tiles_follow_the_closed_form(length, bq, bk):
    """Blocks of 4 under tiles no smaller: with ``r = bq / bk`` K tiles a
    Q tile and ``n = L / bq`` Q tiles a copy, ``n r`` noised-to-noised
    tiles and ``r n (n + 1) / 2`` each of noised-to-clean and
    clean-to-clean: ``r n (n + 2)``."""
    sh = jax.ShapeDtypeStruct((1, 2 * length, 1, 128), jnp.bfloat16)
    plan = fa.flash_plan(sh, sh, sh, block_diffusion=4, block_q=bq,
                         block_k=bk)
    r, n = bq // bk, length // bq
    assert len(plan.live_tiles) == r * n * (n + 2)


def test_the_plan_of_the_benchmark_cell():
    """``sdar_train_s8192_bd4``: 16 384 rows, 32 query heads over 4
    key/value heads of 128 in bfloat16: 576 live tiles of 2048 a head
    (272 clean to clean, 272 noised to clean, 32 noised to noised), the
    table walked, the forward resident, the backward one kernel with dk
    and dv resident stating 37 MiB as SmallThinker's."""
    sh = lambda heads: jax.ShapeDtypeStruct((1, 16384, heads, 128),
                                            jnp.bfloat16)
    plan = fa.flash_plan(sh(32), sh(4), sh(4), block_diffusion=4)
    assert (plan.mask, plan.block, plan.block_q, plan.block_k) == (
        "block_diffusion", 4, 512, 256)
    live = plan.live_tiles
    assert len(live) == 576 and plan.tiles_mask == 32 * 2048
    assert plan.tiles_grid == plan.tiles_live == 32 * 576
    half_q, half_k = 16, 32
    kinds = [(i >= half_q, j >= half_k) for i, j in live]
    assert (kinds.count((False, False)), kinds.count((False, True)),
            kinds.count((True, True)), kinds.count((True, False))) == (
                32, 272, 272, 0)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes, plan.bwd_form,
            plan.bwd_vmem_bytes, plan.bwd_kernels) == (
                True, 19 * 2 ** 20, "dkdv_resident", 37 * 2 ** 20, 1)


def test_what_the_mask_refuses():
    sh = lambda rows: jax.ShapeDtypeStruct((1, rows, 2, 16), jnp.float32)
    for rows, kwargs in ((64, dict(block_diffusion=4, causal=True)),
                         (64, dict(block_diffusion=3)),
                         (64, dict(block_diffusion=0)),
                         (66, dict(block_diffusion=4)),
                         (24, dict(block_diffusion=8))):
        with pytest.raises(ValueError, match="block_diffusion"):
            fa.flash_plan(sh(rows), sh(rows), sh(rows), **kwargs)
    with pytest.raises(ValueError, match="block_diffusion"):
        local_attention(*(jnp.zeros((1, 8, 1, 4)),) * 3, causal=True,
                        block_diffusion=2)


# The attention calls of the ten cells that were there (batch, rows,
# heads, key/value heads, key width, value width, window), and every field
# of their plans as the PARENT's ``flash_plan`` made it (commit 652d64e;
# the table as its length and a digest): the third mask changed none.
_PARENT_PLANS = {
    "gpt2m": ((8, 1024, 16, 16, 64, 64, None), dict(
        block_q=512, block_k=256, tiles_live=768, tiles_mask=1024,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(6, "378c5b0b9393"))),
    "granite": ((1, 8192, 32, 8, 64, 64, None), dict(
        block_q=512, block_k=256, tiles_live=8704, tiles_mask=16384,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(272, "21eb59861768"))),
    "glm": ((1, 8192, 20, 20, 256, 256, None), dict(
        block_q=512, block_k=256, tiles_live=5440, tiles_mask=10240,
        fwd_kv_resident=True, fwd_vmem_bytes=20971520,
        bwd_form="dq_resident", bwd_vmem_bytes=33554432,
        live_tiles=(272, "21eb59861768"))),
    "trinity_window": ((1, 8192, 32, 4, 128, 128, 2048), dict(
        block_q=512, block_k=256, tiles_live=4480, tiles_mask=16384,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(140, "c59c0c312261"))),
    "trinity_full": ((1, 8192, 32, 4, 128, 128, None), dict(
        block_q=512, block_k=256, tiles_live=8704, tiles_mask=16384,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(272, "21eb59861768"))),
    "phi_window": ((1, 8192, 40, 20, 64, 128, 512), dict(
        block_q=512, block_k=256, tiles_live=2480, tiles_mask=20480,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(62, "1d3bf771141c"))),
    "phi_full": ((1, 8192, 40, 20, 64, 128, None), dict(
        block_q=512, block_k=256, tiles_live=10880, tiles_mask=20480,
        fwd_kv_resident=True, fwd_vmem_bytes=0, bwd_form="dkdv_resident",
        bwd_vmem_bytes=33554432, live_tiles=(272, "21eb59861768"))),
    "smallthinker_window": ((1, 16384, 28, 4, 128, 128, 4096), dict(
        block_q=512, block_k=256, tiles_live=14112, tiles_mask=57344,
        fwd_kv_resident=True, fwd_vmem_bytes=19922944,
        bwd_form="dkdv_resident", bwd_vmem_bytes=38797312,
        live_tiles=(504, "175400e40068"))),
    "smallthinker_full": ((1, 16384, 28, 4, 128, 128, None), dict(
        block_q=512, block_k=256, tiles_live=29568, tiles_mask=57344,
        fwd_kv_resident=True, fwd_vmem_bytes=19922944,
        bwd_form="dkdv_resident", bwd_vmem_bytes=38797312,
        live_tiles=(1056, "24bf6ecc3dee"))),
    "lfm2": ((1, 32768, 32, 8, 64, 64, None), dict(
        block_q=512, block_k=256, tiles_live=133120, tiles_mask=262144,
        fwd_kv_resident=False, fwd_vmem_bytes=0, bwd_form="dq_resident",
        bwd_vmem_bytes=38797312, live_tiles=(4160, "d824af981ac9"))),
    "kimilin": ((1, 16384, 32, 32, 192, 128, None), dict(
        block_q=512, block_k=256, tiles_live=33792, tiles_mask=65536,
        fwd_kv_resident=True, fwd_vmem_bytes=28311552,
        bwd_form="dq_resident", bwd_vmem_bytes=33554432,
        live_tiles=(1056, "24bf6ecc3dee"))),
}
_NEW_FIELDS = {"mask", "block"}


@pytest.mark.parametrize("cell", sorted(_PARENT_PLANS))
def test_causal_and_window_plans_are_the_parents_field_for_field(cell):
    (b, rows, h, hkv, d, dv, window), parents = _PARENT_PLANS[cell]
    sh = lambda heads, width: jax.ShapeDtypeStruct(
        (b, rows, heads, width), jnp.bfloat16)
    plan = fa.flash_plan(sh(h, d), sh(hkv, d), sh(hkv, dv), causal=True,
                         window=window)
    made = dataclasses.asdict(plan)
    live = made.pop("live_tiles")
    made["live_tiles"] = (len(live), hashlib.sha256(
        repr(tuple(live)).encode()).hexdigest()[:12])
    assert {f.name for f in dataclasses.fields(plan)} == (
        set(parents) | {"heads", "kv_heads", "causal", "window",
                        "tiles_grid"} | _NEW_FIELDS)
    assert (made.pop("mask"), made.pop("block")) == (
        "window" if window else "causal", None)
    assert made == dict(parents, heads=h, kv_heads=hkv, causal=True,
                        window=window, tiles_grid=parents["tiles_live"])
