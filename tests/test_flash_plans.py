"""What a flash call's plan says and what the program then holds: the
backward's and the forward's form by shape, the VMEM counts at the cells'
shapes, the gauges, and the tables of live tiles.  Traced or counted, not
run: the cheap cases.  (Moved whole from ``tests/test_flash_attention.py``.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_oracle import (ONE_KERNEL, TWO_PASSES, WALK_BK, WALK_BQ,
                          WALK_MASKS, WALK_SEQ, forward_call, live_pairs,
                          pallas_calls, plan_of, qkv, stated_vmem,
                          traced_calls, vmem_limits)
from horovod_tpu.models import gpt
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel import local_attention


# (id, q shape [B,S,H,D], kv heads, dtype, scale, the backward's form)
_GATE_CASES = [
    ("gpt2m_train_8x1024x16x64", (8, 1024, 16, 64), 16, jnp.bfloat16, None,
     "dkdv_resident"),
    ("granite4hm_1x8192x32on8x64", (1, 8192, 32, 64), 8, jnp.bfloat16,
     0.015625, "dkdv_resident"),
    ("trinitym_1x8192x32on4x128", (1, 8192, 32, 128), 4, jnp.bfloat16, None,
     "dkdv_resident"),
    ("longest_kv_row_8192x128", (1, 8192, 4, 128), 2, jnp.bfloat16, None,
     "dkdv_resident"),
    # past a kv row's dk and dv, the row's dq: 4 MiB at head size 64
    ("dq_fits_at_16384x64", (1, 16384, 4, 64), 4, jnp.bfloat16, None,
     "dq_resident"),
    ("over_the_budget_131072x128", (1, 131072, 4, 128), 2, jnp.bfloat16,
     None, "two_passes"),
    ("float32_8192x64_fits", (1, 8192, 2, 64), 2, jnp.float32, None,
     "dkdv_resident"),
    ("float32_16384x64_dq_fits", (1, 16384, 2, 64), 2, jnp.float32, None,
     "dq_resident"),
    # head size 256 (latent attention: 192 + 64 query and key channels,
    # values of 256): a kv row's dk and dv accumulators are four times
    # head size 64's and end at 4096 keys; glm47f_train_s8192's 8192 keep
    # dq resident (8 MiB), which ends at 26624 keys
    ("head_256_4096_keys_fit", (1, 4096, 20, 256), 20, jnp.bfloat16, None,
     "dkdv_resident"),
    ("glm47f_1x8192x20x256", (1, 8192, 20, 256), 20, jnp.bfloat16, None,
     "dq_resident"),
    ("head_256_longest_dq_26624", (1, 26624, 2, 256), 2, jnp.bfloat16, None,
     "dq_resident"),
    # past 32 MiB in both forms the smaller count decides, up to 48 MiB
    # (PR 44; these two ran the two passes before it): 32.5 MiB of dq
    # here, 37.5 MiB of dk and dv for eight query heads on one
    ("head_256_first_past_the_limit_27136", (1, 27136, 2, 256), 2,
     jnp.bfloat16, None, "dq_resident"),
    ("head_256_grouped_8_on_1_8192", (1, 8192, 8, 256), 1, jnp.bfloat16,
     None, "dkdv_resident"),
    # smallthinker_train_s16384: seven query heads a key/value head, a
    # kv row's dk and dv 36.25 MiB (its dq 60.5)
    ("smallthinker_1x16384x28on4x128", (1, 16384, 28, 128), 4, jnp.bfloat16,
     None, "dkdv_resident"),
    # the longest rows under the ceiling, 47.25 and 48 MiB, and the first
    # past it
    ("longest_under_the_ceiling_22016x128", (1, 22016, 7, 128), 1,
     jnp.bfloat16, None, "dkdv_resident"),
    ("first_over_the_ceiling_22528x128", (1, 22528, 7, 128), 1,
     jnp.bfloat16, None, "two_passes"),
    ("head_256_longest_dq_under_the_ceiling_43008", (1, 43008, 2, 256), 2,
     jnp.bfloat16, None, "dq_resident"),
    ("head_256_first_over_the_ceiling_43520", (1, 43520, 2, 256), 2,
     jnp.bfloat16, None, "two_passes"),
]


@pytest.mark.parametrize(
    "shape,kv_heads,dtype,scale,form", [c[1:] for c in _GATE_CASES],
    ids=[c[0] for c in _GATE_CASES],
)
def test_backward_path_follows_the_shape(shape, kv_heads, dtype, scale, form):
    """Which backward runs is read from the ``pallas_call`` names in the
    differentiated jaxpr, as a device trace would read it: one kernel
    (under the name ``flash_bwd_dkdv``) at every benchmark shape and up
    to the ceiling of what a call may state, ``flash_bwd_dq`` beside it
    only above; and the plan of the same call says the same."""
    from horovod_tpu.ops.flash_attention import flash_plan

    b, s, h, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), dtype)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               interpret=True).astype(jnp.float32).sum()

    assert flash_plan(q, kv, kv, causal=True).bwd_form == form
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv)
    assert list(pallas_calls(jaxpr.jaxpr)) == ["flash_fwd"] + (
        TWO_PASSES if form == "two_passes" else ONE_KERNEL)


# (id, the limit and the ceiling the gate reads, form, gauge
# flash.bwd_kernels, whether the plan keeps dq resident and the MiB it
# states, the backward's names and grids in a layer: a row of heads by the
# 6 live tiles of the 2 x 4 a head's mask holds) at 64 keys of 16
# channels in
# float32 and 32 x 16 tiles, where dk and dv resident count 334 KiB and dq
# resident 204: the limit as it stands; one that only dq fits; none, with
# the ceiling as it stands (the smaller count, stated itself: a whole
# MiB); none and no ceiling
_DQ_FITS = "what dq resident counts"
_GAUGE_CASES = [
    ("dkdv_resident", None, None, "dkdv_resident", 1, 0, 32,
     [("flash_bwd_dkdv", (8, 6))]),
    ("dq_resident", _DQ_FITS, None, "dq_resident", 1, 1, 1,
     [("flash_bwd_dkdv", (8, 6))]),
    ("over_the_limit_the_smaller_count", 0, 48 * 2 ** 20, "dq_resident",
     1, 1, 1, [("flash_bwd_dkdv", (8, 6))]),
    ("two_passes", 0, 0, "two_passes", 2, 0, 0,
     [("flash_bwd_dkdv", (8, 6)), ("flash_bwd_dq", (8, 6))]),
]


@pytest.mark.parametrize(
    "limit,ceiling,form,kernels,dq_resident,vmem_mib,backward",
    [c[1:] for c in _GAUGE_CASES], ids=[c[0] for c in _GAUGE_CASES])
def test_the_gauges_say_which_backward_the_step_holds(
        monkeypatch, limit, ceiling, form, kernels, dq_resident, vmem_mib,
        backward):
    """``flash.bwd_kernels``, set while a two-layer model is traced, and
    the plan of the traced calls (its form, the VMEM it states, the
    value width it was made for) against the ``pallas_call`` names, grids
    and stated VMEM of the model's differentiated jaxpr, at a shape on
    each side of the gates (the limit patched to what a form holds, or
    to nothing): gauge and kernel read one record, the call's
    ``FlashPlan``."""
    from horovod_tpu.obs.registry import get_registry
    from horovod_tpu.ops import flash_attention as fa

    if limit == _DQ_FITS:
        limit = fa._dq_resident_bwd_vmem_bytes(64, 16, 32, 16, 4, 1)
    if limit is not None:
        vmem_limits(monkeypatch, limit, ceiling)
    assert plan_of(64, 16, 1, 4, 32, 16).bwd_form == form
    model = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                vocab_size=512, max_len=64, dtype=jnp.float32,
                flash_block_q=32, flash_block_k=16)
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 512, (2, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    gauge = get_registry().gauge("flash.bwd_kernels", layer_type="attention")
    gauge.set(-1)
    qkv = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.float32)
    of_the_shape = fa.flash_plan(qkv, qkv, qkv, causal=True, block_q=32,
                                 block_k=16)
    traced = traced_calls(monkeypatch)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.apply(p, toks).sum()))(params)
    calls = list(pallas_calls(
        jaxpr.jaxpr, lambda p: (p["name"], tuple(p["grid_mapping"].grid))))
    assert calls == [("flash_fwd", (8, 6))] * 2 + backward * 2
    # one plan, made twice a layer: for the gauges and for the kernels
    (shapes, plan), = set(traced)
    assert len(traced) == 4
    assert [gauge.value, plan.bwd_kernels, plan.bwd_form == "dq_resident",
            -(-plan.bwd_vmem_bytes // 2 ** 20)] == [
                kernels, kernels, bool(dq_resident), vmem_mib]
    # the plan holds what the one kernel states, the two passes nothing
    stated = [limit for name, limit in pallas_calls(
        jaxpr.jaxpr, lambda p: (p["name"], stated_vmem(p)))
        if name != "flash_fwd"]
    assert stated == ([None] * 4 if form == "two_passes" else
                      [plan.bwd_vmem_bytes] * 2)
    assert all(-(-b // 2 ** 20) == vmem_mib for b in stated if b)
    # values as wide as keys, the head size, 64 / 4
    assert shapes == ((2, 64, 4, 16),) * 3 and plan == of_the_shape
    assert sum(name != "flash_fwd" for name, _ in calls) == 2 * kernels


def test_flash_attention_refuses_k_and_v_of_different_rows():
    """The width is v's own; batch, sequence and key/value head count are
    not."""
    q, k, v = qkv(h=4, d=16)
    with pytest.raises(ValueError, match="matching in batch, sequence"):
        flash_attention(q, k[:, :, :2], v)
    with pytest.raises(ValueError, match="matching in batch, sequence"):
        flash_attention(q, k, v[:, :32])
    with pytest.raises(ValueError, match="head_dim must match"):
        flash_attention(q, k[..., :8], v)


# (id, keys, head size, value width, group, the form, the Q-outermost
# count, the K-outermost count) in bfloat16 at 512 x 256 tiles: the
# benchmark cells' shapes read the bytes they read before the counts took
# a value width (PR 38's tree), and 8192 x (64, 128), the Phi cell's
# one-pass differential call, pads both widths to 128 lanes and reads
# 8192 x 64's count.
_COUNT_CASES = [
    ("gpt2m_1024x64", 1024, 64, None, 1, "dkdv_resident", 6422528,
     4980736),
    ("granite4hm_8192x64", 8192, 64, None, 4, "dkdv_resident", 21102592,
     13107200),
    ("trinitym_8192x128", 8192, 128, None, 8, "dkdv_resident", 21233664,
     38273024),
    ("glm47f_8192x256", 8192, 256, None, 1, "dq_resident", 39321600,
     14680064),
    ("phi4mf_8192x64_values_128", 8192, 64, 128, 2, "dkdv_resident",
     21102592, 8912896),
]


@pytest.mark.parametrize("seq,d,dv,group,form,q_outer,k_outer",
                         [c[1:] for c in _COUNT_CASES],
                         ids=[c[0] for c in _COUNT_CASES])
def test_vmem_counts_at_the_cells_shapes(seq, d, dv, group, form, q_outer,
                                         k_outer):
    from horovod_tpu.ops import flash_attention as fa

    assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, dv) == q_outer
    assert fa._dq_resident_bwd_vmem_bytes(seq, d, 512, 256, 2, group,
                                          dv) == k_outer
    assert plan_of(seq, d, group, 2, value_dim=dv).bwd_form == form
    if dv is None:
        # a value width that is the head size changes nothing
        assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, d) == q_outer
        assert fa._dq_resident_bwd_vmem_bytes(seq, d, 512, 256, 2, group,
                                              d) == k_outer
        assert plan_of(seq, d, group, 2) == plan_of(
            seq, d, group, 2, 512, 256, d)
    else:
        # the dk and dv halves each at their own padded lanes: values of
        # 512 put 8192 keys over the Q-outermost form's limit, and dq,
        # 64 wide, stays resident under the K tiles
        assert fa._fused_bwd_vmem_bytes(seq, d, 512, 256, 2, 512) \
            > fa._FUSED_BWD_VMEM_LIMIT > q_outer
        assert plan_of(seq, d, group, 2,
                       value_dim=512).bwd_form == "dq_resident"


def _kernel_signature(eqn_params):
    """What a ``pallas_call`` holds that the chip would see: its name and
    grid, the kernel's block and scratch refs, its outputs and the VMEM
    it states."""
    return (eqn_params["name"], tuple(eqn_params["grid_mapping"].grid),
            [str(v.aval) for v in eqn_params["jaxpr"].invars],
            [str(a) for a in eqn_params["out_avals"]],
            stated_vmem(eqn_params))


def test_a_call_with_values_as_wide_as_keys_is_the_program_it_was():
    """granite's call (32 query over 8 key/value heads of 64 at 8192
    tokens, bfloat16): the ``pallas_call``s of the differentiated jaxpr,
    listed as PR 38's tree made them but for the forward's K and V
    blocks, whole kv rows since PR 46, and for the grids, which since
    PR 49 walk a head's 272 live tiles of 16 x 32 from a table of three
    int32 columns in SMEM, the calls' first operands.  The five cells
    that send ``dv == d`` run this program; only the value width of a
    call that has one moves a block, a scratch buffer or an output."""
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16)

    def calls(v):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=True
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, kv, v)
        return list(pallas_calls(jaxpr.jaxpr, _kernel_signature))

    bf = lambda *shape: "Ref{bfloat16[%s]}" % ",".join(map(str, shape))
    stat = "Ref{float32[1,1,1,512]}"
    vmem = lambda *shape: "Ref<vmem>{float32[%s]}" % ",".join(map(str, shape))
    arr = lambda *shape: "bfloat16[%s]" % ",".join(map(str, shape))
    table = ["Ref<smem>{int32[272]}"] * 3

    def listed(dv):
        return [
            ("flash_fwd", (32, 272),
             table + [bf(1, 512, 64), bf(1, 8192, 64), bf(1, 8192, dv),
              bf(1, 512, dv), stat,
              vmem(dv, 512), vmem(1, 512), vmem(1, 512)],
             [arr(32, 8192, dv), "float32[32,16,1,512]"], None),
            ("flash_bwd_dkdv", (32, 272),
             table + [bf(1, 512, 64), bf(1, 256, 64), bf(1, 256, dv),
              bf(1, 512, dv), stat, stat,
              bf(1, 512, 64), bf(1, 8192, 64), bf(1, 8192, dv),
              vmem(64, 512), vmem(8192, 64), vmem(8192, dv)],
             [arr(32, 8192, 64), arr(8, 8192, 64), arr(8, 8192, dv)],
             32 * 2 ** 20),
        ]

    assert calls(kv) == listed(64)
    wide = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    assert calls(wide) == listed(128)


# (id, q shape [B,S,H,D], kv heads, value width, the backward's form, the
# MiB it states): the attention call of every flash cell.  The first five
# serve seven cells and read what PR 43's tree read, form and stated
# limit (32 MiB, the module's constant); the sixth left the two passes in
# PR 44 and states its own count.
_CELL_CALLS = [
    ("gpt2m_train_s1024_and_dp4", (8, 1024, 16, 64), 16, 64,
     "dkdv_resident", 32),
    ("granite4hm_train_s8192", (1, 8192, 32, 64), 8, 64, "dkdv_resident",
     32),
    ("glm47f_train_s8192", (1, 8192, 20, 256), 20, 256, "dq_resident", 32),
    ("trinitym_train_s8192", (1, 8192, 32, 128), 4, 128, "dkdv_resident",
     32),
    ("phi4mf_train_s8192", (1, 8192, 40, 64), 20, 128, "dkdv_resident", 32),
    ("smallthinker_train_s16384", (1, 16384, 28, 128), 4, 128,
     "dkdv_resident", 37),
]


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window_512"])
@pytest.mark.parametrize("shape,kv_heads,dv,form,mib",
                         [c[1:] for c in _CELL_CALLS],
                         ids=[c[0] for c in _CELL_CALLS])
def test_every_cells_call_keeps_its_form_and_the_vmem_it_states(
        shape, kv_heads, dv, form, mib, window):
    """The one backward ``pallas_call`` of each cell's attention shape,
    full and banded, read from the differentiated jaxpr: its grid says
    the form (Q tile outermost ``(z, live)``, a head's live tiles; K
    tile outermost ``(z_kv, live * group)``) and its params the
    ``vmem_limit_bytes``.  A call that fit 32 MiB before PR 44 states
    those 32 MiB still: the table changes the grid and adds the
    prefetched columns, never the VMEM a call states."""
    from horovod_tpu.ops import flash_attention as fa

    b, s, h, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, kv_heads, dv), jnp.bfloat16)
    group, live = h // kv_heads, len(live_pairs(s, 512, 256, window))
    plan = fa.flash_plan(q, k, v, causal=True, window=window)
    assert (plan.bwd_form, plan.bwd_vmem_bytes) == (form, mib * 2 ** 20)
    assert (plan.tiles_grid, plan.tiles_mask) == (
        b * h * live, b * h * (s // 512) * (s // 256))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: flash_attention(
            *a, causal=True, window=window, interpret=True
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    calls = list(pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid), stated_vmem(p))))
    grid = ((b * h, live) if form == "dkdv_resident"
            else (b * kv_heads, live * group))
    assert calls == [("flash_fwd", (b * h, live),
                      plan.fwd_vmem_bytes or None),
                     ("flash_bwd_dkdv", grid, mib * 2 ** 20)]


# (id, keys, head size, value width, itemsize, resident, the MiB the call
# states, the bytes counted) at 512 x 256 tiles: every flash cell's
# forward holds its kv row resident; the two whose rows are 16 MiB in
# their two buffers (GLM's 8192 keys of 256, SmallThinker's 16384 of 128)
# pass the compiler's default scoped limit and state their count, the
# others state nothing.  The TPU compiler asks 18.12 and 17.31 MiB for
# those two (sandbox compiles for a described v5e, PR 46).
_FWD_COUNT_CASES = [
    ("gpt2m_1024x64", 1024, 64, 64, 2, True, 0, 3342336),
    ("granite4hm_8192x64", 8192, 64, 64, 2, True, 0, 10682368),
    ("glm47f_8192x256", 8192, 256, 256, 2, True, 20, 20512768),
    ("trinitym_8192x128", 8192, 128, 128, 2, True, 0, 10813440),
    ("phi4mf_8192x64_values_128", 8192, 64, 128, 2, True, 0, 10813440),
    ("smallthinker_16384x128", 16384, 128, 128, 2, True, 19, 19202048),
    # the first row that states a limit, the longest that stays resident
    # and the first whose tiles stream, at head sizes 128 and 256 and in
    # float32
    ("last_that_states_nothing_13824x128", 13824, 128, 128, 2, True, 0,
     16580608),
    ("first_that_states_its_count_14336x128", 14336, 128, 128, 2, True, 17,
     17104896),
    ("longest_resident_row_30208x128", 30208, 128, 128, 2, True, 32,
     33357824),
    ("first_streamed_row_30720x128", 30720, 128, 128, 2, False, 0,
     33882112),
    ("longest_resident_row_14336x256", 14336, 256, 256, 2, True, 32,
     33095680),
    ("first_streamed_row_14848x256", 14848, 256, 256, 2, False, 0,
     34144256),
    ("float32_longest_resident_row_14848x128", 14848, 128, 128, 4, True, 32,
     33357824),
    ("float32_first_streamed_row_15360x128", 15360, 128, 128, 4, False, 0,
     34406400),
]


@pytest.mark.parametrize("seq,d,dv,itemsize,resident,mib,count",
                         [c[1:] for c in _FWD_COUNT_CASES],
                         ids=[c[0] for c in _FWD_COUNT_CASES])
def test_forward_plan_at_the_cells_shapes(seq, d, dv, itemsize, resident,
                                          mib, count):
    from horovod_tpu.ops import flash_attention as fa

    assert fa._fwd_resident_vmem_bytes(seq, d, dv, 512, 256,
                                       itemsize) == count
    plan = plan_of(seq, d, 1, itemsize, value_dim=dv)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (
        resident, mib * 2 ** 20)
    assert plan == plan_of(seq, d, 1, itemsize, 512, 256, dv)
    # the rule: resident wherever the count fits the limit the backward's
    # forms share, a stated MiB only past the default scoped limit
    assert resident == (count <= fa._FUSED_BWD_VMEM_LIMIT)
    assert (mib > 0) == (fa._DEFAULT_SCOPED_VMEM < count
                         <= fa._FUSED_BWD_VMEM_LIMIT)
    if mib:
        assert 0 <= mib * 2 ** 20 - count < 2 ** 20


# (id, the limit and the default scoped limit the gate reads, the
# forward's plan at the shape, the same of the traced calls' plan as
# resident 1 / 0 and whole MiB, the rows of the K and V blocks) at 64
# keys of 16 channels in float32 and
# 32 x 16 tiles, where the resident forward counts 264 KiB: the limits as
# they stand; a default the count passes (the count stated, a whole MiB);
# no room
_FWD_GAUGE_CASES = [
    ("resident", None, None, (True, 0), 1, 0, 64),
    ("resident_stating_its_count", None, 0, (True, 2 ** 20), 1, 1, 64),
    ("streamed", 0, None, (False, 0), 0, 0, 16),
]


@pytest.mark.parametrize("limit,default,plan,resident,vmem_mib,rows",
                         [c[1:] for c in _FWD_GAUGE_CASES],
                         ids=[c[0] for c in _FWD_GAUGE_CASES])
def test_the_gauges_say_which_forward_the_step_holds(
        monkeypatch, limit, default, plan, resident, vmem_mib, rows):
    """The plan of the calls a two-layer model traces (whether the
    forward holds a kv row resident, the VMEM it states) against the K
    and V blocks and the stated VMEM of the ``flash_fwd`` calls in the
    model's jaxpr, on each side of the forward's gates: whoever asks and
    the kernel read one record, the call's ``FlashPlan``."""
    from horovod_tpu.ops import flash_attention as fa

    if default is not None:
        monkeypatch.setattr(fa, "_DEFAULT_SCOPED_VMEM", default)
    if limit is not None:
        vmem_limits(monkeypatch, limit)
    at_the_shape = plan_of(64, 16, 1, 4, 32, 16)
    assert (at_the_shape.fwd_kv_resident, at_the_shape.fwd_vmem_bytes) == plan
    model = gpt("nano", num_layers=2, num_heads=4, emb_dim=64,
                vocab_size=512, max_len=64, dtype=jnp.float32,
                flash_block_q=32, flash_block_k=16)
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 512, (2, 64)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)
    asked = traced_calls(monkeypatch)
    jaxpr = jax.make_jaxpr(lambda p: model.apply(p, toks))(params)
    calls = list(pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid)) + forward_call(p)))
    # the grid walks the 6 live tiles of a head's 2 x 4 in either form
    assert calls == [("flash_fwd", (8, 6), rows, plan[1] or None)] * 2
    (_, traced), = set(asked)
    assert len(asked) == 4
    assert [int(traced.fwd_kv_resident),
            traced.fwd_vmem_bytes // 2 ** 20] == [resident, vmem_mib]


def test_local_attention_refuses_a_window_it_cannot_mean():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="causal"):
        local_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="window >= 1"):
        local_attention(q, k, v, causal=True, window=0)


def _dense_live_pairs(seq, bq, bk, causal, window):
    """A mask's live tile pairs from the positions themselves."""
    qp, kp = np.arange(seq)[:, None], np.arange(seq)[None, :]
    sees = np.ones((seq, seq), bool)
    if causal:
        sees = kp <= qp
        if window is not None:
            sees &= kp >= qp - (window - 1)
    tiles = sees.reshape(seq // bq, bq, seq // bk, bk).any((1, 3))
    return [(int(i), int(j)) for i, j in zip(*np.nonzero(tiles))]


# (id, S, block_q, block_k, causal, window, query heads a key/value head)
_TABLE_CASES = [
    (mask + "_group_%d" % group, WALK_SEQ, WALK_BQ, WALK_BK, causal,
     window, group)
    for mask, causal, window in WALK_MASKS for group in (1, 3)
] + [
    ("window_72_of_256_tiles_64x32_group_8", 256, 64, 32, True, 72, 8),
    ("s_is_window_plus_1_group_8", 128, 64, 32, True, 127, 8),
    ("tiles_16x16_window_of_one_tile", 64, 16, 16, True, 16, 2),
    ("k_tiles_wider_than_q_tiles", 64, 8, 32, True, 20, 2),
    # the cells' calls at 512 x 256: Trinity's band, Phi's, SmallThinker's
    # band and triangle, LFM2's triangle
    ("trinitym_8192_window_2048_group_8", 8192, 512, 256, True, 2048, 8),
    ("phi4mf_8192_window_512_group_2", 8192, 512, 256, True, 512, 2),
    ("smallthinker_16384_window_4096_group_7", 16384, 512, 256, True, 4096,
     7),
    ("smallthinker_16384_full_group_7", 16384, 512, 256, True, None, 7),
    ("lfm2_32768_full_group_4", 32768, 512, 256, True, None, 4),
]


@pytest.mark.parametrize("seq,bq,bk,causal,window,group",
                         [c[1:] for c in _TABLE_CASES],
                         ids=[c[0] for c in _TABLE_CASES])
def test_the_table_holds_the_live_tiles_once_in_walk_order(
        seq, bq, bk, causal, window, group):
    """The plan's table is exactly the pairs the mask keeps (from the
    positions themselves at small sizes, from the tiles' distances at the
    cells'), each once, Q tile major with K tiles ascending: the order
    the rectangle walked them; ``len(table) * batch * heads`` is
    ``tiles_live`` and ``tiles_grid``, the rectangle ``tiles_mask``; and
    the Q-major columns mark each Q row's first and last live tile."""
    from horovod_tpu.ops import flash_attention as fa

    plan = plan_of(seq, 64, group, 2, bq, bk, causal=causal, window=window,
                   rows=2)
    pairs = live_pairs(seq, bq, bk, window, causal)
    if seq <= 256:
        assert pairs == _dense_live_pairs(seq, bq, bk, causal, window)
    assert list(plan.live_tiles) == pairs == sorted(set(pairs))
    assert all(fa._tile_live(i, j, bq, bk, causal, plan.window)
               for i, j in pairs)
    heads = 2 * group
    assert plan.tiles_live == plan.tiles_grid == heads * len(pairs)
    assert plan.tiles_mask == heads * (seq // bq) * (seq // bk)
    qi, kj, edges = fa._q_major_table(plan.live_tiles)
    assert list(zip(qi.tolist(), kj.tolist())) == pairs
    assert qi.dtype == kj.dtype == edges.dtype == np.int32
    for t, (i, j) in enumerate(pairs):
        row = [jj for ii, jj in pairs if ii == i] if seq <= 256 else None
        first = t == 0 or pairs[t - 1][0] != i
        last = t == len(pairs) - 1 or pairs[t + 1][0] != i
        assert edges[t] == first + 2 * last, (t, i, j)
        if row:
            assert (first, last) == (j == row[0], j == row[-1])


@pytest.mark.parametrize("seq,bq,bk,causal,window,group",
                         [c[1:] for c in _TABLE_CASES],
                         ids=[c[0] for c in _TABLE_CASES])
def test_the_k_major_table_writes_each_dq_block_at_its_last_live_k_tile(
        seq, bq, bk, causal, window, group):
    """The K-outermost kernel's table: for each K tile in turn, for each
    query head of the group, the Q tiles that see it, Q tiles ascending
    (the order the rectangle walked them, so dk and dv sum in the
    parent's order).  dk and dv's accumulators open on a K tile's first
    step and close on its last; a ``(g, i)`` pair's dq opens on its first
    live K tile and is written on its LAST (the rectangle wrote at ``j ==
    nk - 1``, which under a mask most pairs never reach live); and dq's
    block index, the pair written next, holds still up to each write and
    moves right after it, so a block is one run of steps and goes to HBM
    once."""
    from horovod_tpu.ops import flash_attention as fa

    nq = seq // bq
    plan = plan_of(seq, 64, group, 2, bq, bk, causal=causal, window=window)
    pairs = set(plan.live_tiles)
    kj, qg, qi, edges, fg, fi = fa._k_major_table(plan.live_tiles, nq, group)
    steps = list(zip(kj.tolist(), qg.tolist(), qi.tolist()))
    assert steps == [(j, g, i) for j in range(seq // bk)
                     for g in range(group)
                     for i in range(nq) if (i, j) in pairs]
    assert len(steps) == group * len(pairs) == len(set(steps))
    first_j, last_j = {}, {}
    for i, j in sorted(pairs):
        first_j.setdefault(i, j)
        last_j[i] = j
    total = len(steps)
    for t, (j, g, i) in enumerate(steps):
        assert edges[t] == (
            (t == 0 or steps[t - 1][0] != j)
            + 2 * (t == total - 1 or steps[t + 1][0] != j)
            + 4 * (j == first_j[i]) + 8 * (j == last_j[i])), (t, j, g, i)
    closing = [t for t in range(total) if edges[t] & 8]
    assert sorted((steps[t][1], steps[t][2]) for t in closing) == [
        (g, i) for g in range(group) for i in range(nq)]
    assert closing[-1] == total - 1
    blocks = list(zip(fg.tolist(), fi.tolist()))
    start = 0
    for t in closing:   # each write ends the run of its own block index
        assert set(blocks[start:t + 1]) == {steps[t][1:]}, t
        start = t + 1


def test_a_table_past_the_smem_limit_keeps_the_rectangle(monkeypatch):
    """The plan says from the shape whether the call's largest table (the
    forward's three columns, the K-outermost backward's six a query head
    of the group) fits ``_TILE_TABLE_SMEM_LIMIT``: every cell's call does,
    LFM2's 16 640 steps the largest at 390 KiB; 131 072 keys in two
    passes do not, and keep the rectangle, as any call does with the
    limit at nothing."""
    from horovod_tpu.ops import flash_attention as fa

    assert fa._TILE_TABLE_SMEM_LIMIT == 512 * 2 ** 10
    lfm2 = plan_of(32768, 64, 4, rows=8)
    assert lfm2.bwd_form == "dq_resident"
    assert 4 * fa._k_major_table(lfm2.live_tiles, 64, 4).size == 399_360
    for seq, d, group, window in [(1024, 64, 1, None), (8192, 64, 4, None),
                                  (8192, 256, 1, None), (8192, 128, 8, 2048),
                                  (8192, 64, 2, 512), (16384, 128, 7, 4096),
                                  (16384, 128, 7, None)]:
        plan = plan_of(seq, d, group, window=window)
        assert plan.live_tiles and plan.tiles_grid == plan.tiles_live
    long = plan_of(131072, 128, 2)
    assert (long.bwd_form, long.live_tiles) == ("two_passes", None)
    assert long.tiles_grid == long.tiles_mask == 2 * 256 * 512
    assert long.tiles_live == 2 * 65792
    monkeypatch.setattr(fa, "_TILE_TABLE_SMEM_LIMIT", 0)
    small = plan_of(64, 16, 1, 4, 32, 16)
    assert (small.live_tiles, small.tiles_live, small.tiles_grid,
            small.tiles_mask) == (None, 6, 8, 8)
    q, k, v = qkv(b=1, s=64, h=2, d=16)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *a: flash_attention(
        *a, causal=True, block_q=32, block_k=16).sum(),
        argnums=(0, 1, 2)))(q, k, v)
    assert list(pallas_calls(jaxpr.jaxpr, lambda p: (
        p["name"], tuple(p["grid_mapping"].grid),
        p["grid_mapping"].num_index_operands))) == [
            ("flash_fwd", (2, 8), 0), ("flash_bwd_dkdv", (2, 8), 0)]
