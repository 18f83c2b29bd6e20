"""Chip-compiler tests: the main path's Pallas kernels, and the gradient
plane's bucketed step, compiled by the TPU's own compiler for a described
(not attached) v5e at real widths.

The ONE file with such tests.  The topology is described inside a
module-scoped fixture — never at import, in a ``skipif`` or a
``parametrize`` argument — because only one process may hold the TPU
library: under pytest-xdist every worker imports this file, and only the
worker that runs it may load the library.  Each case compiles in the
test's own process, in about two seconds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns and
    compiles again): keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The 2x2 as one flat data-parallel mesh, as ``hvd.mesh("flat")``
    makes it on the chip."""
    import numpy as np
    from jax.sharding import Mesh

    import horovod_tpu as hvd

    return Mesh(np.asarray(topo.devices, dtype=object).reshape(4),
                (hvd.DP_AXIS,))


# (id, q shape [B,S,H,D], kv heads, dtype, causal, window)
_SHAPES = [
    ("gpt_small", (8, 1024, 12, 64), 12, jnp.bfloat16, True, None),
    ("head_dim_128", (2, 4096, 16, 128), 16, jnp.bfloat16, True, None),
    ("gqa_12_to_4", (8, 1024, 12, 64), 4, jnp.bfloat16, True, None),
    ("window_512_at_2048", (8, 2048, 12, 64), 12, jnp.bfloat16, True, 512),
    ("fp32", (8, 1024, 12, 64), 12, jnp.float32, True, None),
]


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize(
    "shape,kv_heads,dtype,causal,window",
    [s[1:] for s in _SHAPES], ids=[s[0] for s in _SHAPES],
)
def test_flash_attention_compiles_for_v5e(one_chip, shape, kv_heads, dtype,
                                          causal, window, direction):
    """interpret=False: the kernel the chip would run, default 512x256
    tiles, forward and backward, as a tpu_custom_call."""
    b, s, _, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), dtype, sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=False)

    if direction == "forward":
        fn = attend
    else:
        def fn(q, k, v):
            return jax.grad(
                lambda *a: attend(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


# granite-4.0-h-micro's two mixers at the published widths and the
# benchmark cell's 1 x 8192 tokens (benchmark/workloads/
# granite4hm_train_s8192.json): 32 query heads over 8 K/V heads of 64 with
# the stated scale 1/64 through the flash kernel's grouped path, and the
# Mamba-2 scan of 64 heads of 64, state 128, in 32 chunks of 256.
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_granite_attention_compiles_for_v5e(one_chip, direction):
    q = jax.ShapeDtypeStruct((1, 8192, 32, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=0.015625,
                               interpret=False)

    def backward(q, k, v):
        return jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    fn = attend if direction == "forward" else backward
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


# (id, q shape [B,S,H,D], kv heads, dtype, scale, window, one kernel?)
_BACKWARD_PATHS = [
    ("gpt2m_train_128x1024x64", (8, 1024, 16, 64), 16, jnp.bfloat16, None,
     None, True),
    ("granite4hm_32on8x8192x64", (1, 8192, 32, 64), 8, jnp.bfloat16,
     0.015625, None, True),
    # the longest rows the shape gate admits: what it counts must cover
    # what the compiler asks for inside the limit the call states
    ("longest_fp32_8192x64", (1, 8192, 8, 64), 8, jnp.float32, None, None,
     True),
    ("longest_fp32_8192x128", (1, 8192, 4, 128), 2, jnp.float32, None,
     None, True),
    ("longest_fp32_4096x256_window", (1, 4096, 2, 256), 2, jnp.float32,
     None, 1024, True),
    # with a kv row's dk and dv resident this one asks for 32.63 MiB; its
    # dq is 4 MiB, so the K-outermost kernel takes it
    ("dq_resident_16384x64", (1, 16384, 8, 64), 8, jnp.bfloat16, None,
     None, True),
    # latent attention's shape in glm47f_train_s8192: head size 256 puts
    # a kv row's dk and dv accumulators at 32 MiB and its dq at 8
    ("glm47f_1x8192x20x256", (1, 8192, 20, 256), 20, jnp.bfloat16, None,
     None, True),
    ("head_256_fits_4096_keys", (1, 4096, 20, 256), 20, jnp.bfloat16, None,
     None, True),
    # the longest rows whose dq the gate admits, counted to 32 MiB exactly
    # and to 31.5, and the first shapes past them
    ("longest_dq_26624x256", (1, 26624, 2, 256), 2, jnp.bfloat16, None,
     None, True),
    ("longest_fp32_dq_24064x256_window", (1, 24064, 2, 256), 2, jnp.float32,
     None, 1024, True),
    # past 32 MiB in both forms (PR 44; the two passes before it): the
    # smaller count, stated itself, 33 MiB of dq and 38 of dk and dv
    ("first_past_the_limit_27136x256", (1, 27136, 2, 256), 2, jnp.bfloat16,
     None, None, True),
    ("grouped_8_on_1_8192x256", (1, 8192, 8, 256), 1, jnp.bfloat16, None,
     None, True),
    # smallthinker_train_s16384's call, full and banded: 37 MiB stated
    ("smallthinker_28on4x16384x128", (1, 16384, 28, 128), 4, jnp.bfloat16,
     None, None, True),
    ("smallthinker_28on4x16384x128_window", (1, 16384, 28, 128), 4,
     jnp.bfloat16, None, 4096, True),
    # the longest rows under the 48 MiB ceiling in either form and in
    # float32, and the first past it
    ("longest_under_the_ceiling_22016x128", (1, 22016, 7, 128), 1,
     jnp.bfloat16, None, None, True),
    ("longest_fp32_under_the_ceiling_14336x128", (1, 14336, 7, 128), 1,
     jnp.float32, None, None, True),
    ("longest_dq_under_the_ceiling_43008x256", (1, 43008, 2, 256), 2,
     jnp.bfloat16, None, None, True),
    ("two_passes_22528x128", (1, 22528, 7, 128), 1, jnp.bfloat16, None,
     None, False),
    # lfm2_train_s32768's call: a row's dq 36.25 MiB with the K tile
    # outermost, 37 stated (its dk and dv would be 65)
    ("lfm2_32on8x32768x64", (1, 32768, 32, 64), 8, jnp.bfloat16, None,
     None, True),
]


@pytest.mark.parametrize(
    "shape,kv_heads,dtype,scale,window,one_kernel",
    [c[1:] for c in _BACKWARD_PATHS], ids=[c[0] for c in _BACKWARD_PATHS],
)
def test_flash_backward_path_compiles_for_v5e(one_chip, shape, kv_heads,
                                              dtype, scale, window,
                                              one_kernel):
    """The backward as ONE kernel (under the name ``flash_bwd_dkdv``, no
    ``flash_bwd_dq`` beside it) at every benchmark shape and at the
    longest rows the shape gate admits in either form, under the limit
    and under the ceiling above it, compiled inside the
    ``vmem_limit_bytes`` the call states (the TPU compiler refuses a
    kernel that needs more); the two passes above the ceiling."""
    b, s, _, d = shape
    q = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), dtype, sharding=one_chip)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(
                *a, causal=True, scale=scale, window=window,
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(backward).lower(q, kv, kv).compile().as_text()
    assert "flash_bwd_dkdv" in text
    assert ("flash_bwd_dq" not in text) == one_kernel


# (id, q shape [B,S,H,D], kv heads, dtype, window, the rows of the K and V
# blocks, the MiB stated): the forward with a kv row's K and V resident
# (PR 46) at the two cells whose rows pass the default scoped limit and
# state their count, at a cell that states nothing, at the longest rows the
# gate admits, bfloat16 and float32, and at the first row past it, whose
# tiles stream.
_FORWARD_FORMS = [
    ("smallthinker_28on4x16384x128", (1, 16384, 28, 128), 4, jnp.bfloat16,
     None, 16384, 19),
    ("smallthinker_28on4x16384x128_window", (1, 16384, 28, 128), 4,
     jnp.bfloat16, 4096, 16384, 19),
    ("glm47f_20on20x8192x256", (1, 8192, 20, 256), 20, jnp.bfloat16, None,
     8192, 20),
    ("trinitym_32on4x8192x128_window", (1, 8192, 32, 128), 4, jnp.bfloat16,
     2048, 8192, 0),
    ("longest_resident_row_30208x128", (1, 30208, 2, 128), 1, jnp.bfloat16,
     None, 30208, 32),
    ("longest_fp32_resident_row_14848x128_window", (1, 14848, 2, 128), 1,
     jnp.float32, 1024, 14848, 32),
    ("first_streamed_row_30720x128", (1, 30720, 2, 128), 1, jnp.bfloat16,
     None, 256, 0),
    # lfm2_train_s32768's call, the one cell whose forward streams
    ("lfm2_32on8x32768x64_streamed", (1, 32768, 32, 64), 8, jnp.bfloat16,
     None, 256, 0),
]


@pytest.mark.parametrize(
    "shape,kv_heads,dtype,window,rows,mib",
    [c[1:] for c in _FORWARD_FORMS], ids=[c[0] for c in _FORWARD_FORMS])
def test_flash_forward_form_compiles_for_v5e(one_chip, shape, kv_heads,
                                             dtype, window, rows, mib):
    """The forward kernel on folded operands, which stay in HBM (through
    ``flash_attention`` alone in a jit XLA may hand the call ``k`` and
    ``v`` in its own VMEM space, and the compile says nothing of the
    blocks): the ``pallas_call`` holds whole-kv-row K and V blocks
    wherever the call's plan says resident, and the TPU compiler takes
    them inside the scoped VMEM the call states, or inside its default
    where it states none (it refuses a kernel that needs more)."""
    import re

    from horovod_tpu.ops import flash_attention as fa

    b, s, h, d = shape
    unfolded = lambda heads: jax.ShapeDtypeStruct((b, s, heads, d), dtype)
    plan = fa.flash_plan(unfolded(h), unfolded(kv_heads), unfolded(kv_heads),
                         causal=True, window=window)
    assert (plan.fwd_kv_resident, plan.fwd_vmem_bytes) == (
        rows == s, mib * 2 ** 20)
    q = jax.ShapeDtypeStruct((b * h, s, d), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b * kv_heads, s, d), dtype,
                              sharding=one_chip)

    def forward(q, k, v):
        return fa._flash_fwd_kernel(q, k, v, plan, d ** -0.5, False)

    (call,) = [e.params for e in jax.make_jaxpr(forward)(q, kv, kv).eqns
               if e.primitive.name == "pallas_call"]
    for block in call["grid_mapping"].block_mappings[1:3]:
        assert block.block_shape[1].block_size == rows
    text = jax.jit(forward).lower(q, kv, kv).compile().as_text()
    (line,) = [l for l in text.splitlines()
               if "custom-call(" in l and "flash_fwd" in l]
    stated = re.findall(r'"scoped_memory_configs":\[([^\]]*)\]', line)
    assert stated == ([f'{{"memory_space":"1","offset":"0",'
                       f'"size":"{mib * 2 ** 20}"}}'] if mib else [""])


# (id, q shape [B,S,H,D], kv heads, window, the backward's form, a head's
# live tiles, the table's columns forward and backward): the two cells
# ISSUE 49 claims in.  LFM2's K-outermost table is the largest of any
# cell, 6 x 16 640 int32 (390 KiB of SMEM).
_TILE_TABLES = [
    ("lfm2_32on8x32768x64", (1, 32768, 32, 64), 8, None, "dq_resident",
     4160, 3, 6),
    ("smallthinker_28on4x16384x128_window", (1, 16384, 28, 128), 4, 4096,
     "dkdv_resident", 504, 3, 3),
]


@pytest.mark.parametrize(
    "shape,kv_heads,window,form,live,fwd_columns,bwd_columns",
    [c[1:] for c in _TILE_TABLES], ids=[c[0] for c in _TILE_TABLES])
def test_flash_tile_table_compiles_for_v5e(one_chip, shape, kv_heads, window,
                                           form, live, fwd_columns,
                                           bwd_columns):
    """The grids walk a table of the live tiles (PR 49): the forward and
    the one backward kernel of the two longest cells take the table's
    int32 columns as scalar-prefetch operands, their grids are ``(rows,
    steps)`` with the live tiles alone for steps, and the TPU compiler
    takes table and kernel inside the VMEM the call states, which is what
    the plan stated before there was a table."""
    import re

    from horovod_tpu.ops import flash_attention as fa

    b, s, h, d = shape
    group = h // kv_heads
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    plan = fa.flash_plan(q, kv, kv, causal=True, window=window)
    assert (plan.bwd_form, len(plan.live_tiles)) == (form, live)
    assert plan.tiles_grid == plan.tiles_live == b * h * live

    def backward(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(
                *a, causal=True, window=window,
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                mapping = eqn.params["grid_mapping"]
                yield (eqn.params["name"], tuple(mapping.grid),
                       mapping.num_index_operands)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    steps = live if form == "dkdv_resident" else live * group
    assert list(calls(jax.make_jaxpr(backward)(q, kv, kv).jaxpr)) == [
        ("flash_fwd", (b * h, live), fwd_columns),
        ("flash_bwd_dkdv",
         (b * h if form == "dkdv_resident" else b * kv_heads, steps),
         bwd_columns)]
    text = jax.jit(backward).lower(q, kv, kv).compile().as_text()
    for name, columns, tensors, extent, stated in (
            ("flash_fwd", fwd_columns, 3, live, plan.fwd_vmem_bytes),
            ("flash_bwd_dkdv", bwd_columns, 6, steps, plan.bwd_vmem_bytes)):
        (line,) = [l for l in text.splitlines()
                   if "custom-call(" in l and name in l.split("(")[0]]
        # the table's columns before q, k, v (do, lse, delta)
        operands = line.split("custom-call(")[1].split(")")[0].split(", ")
        assert len(operands) == columns + tensors, (name, operands)
        assert f"s32[{extent}]" in text, name
        sizes = re.findall(
            r'"scoped_memory_configs":\[\{[^\]]*"size":"(\d+)"', line)
        # inside a whole program a call that states nothing shows the
        # compiler's default
        assert sizes == [str(stated or fa._DEFAULT_SCOPED_VMEM)], (
            name, sizes)


def test_flash_block_diffusion_mask_compiles_for_v5e(one_chip):
    """``sdar_train_s8192_bd4``'s attention call (16 384 rows: a noised
    copy of 8192 tokens, then the clean one; 32 query heads over 4
    key/value heads of 128; blocks of 4): the forward and the one
    backward kernel under the block-diffusion mask walk a table of 576
    live tiles a head of 2048, the mask's in-tile predicate (block indices
    as a column and a row, shifts and comparisons) lowers, and the TPU
    compiler takes both inside the VMEM the plan states (19 and 37 MiB,
    as SmallThinker's call at the same keys)."""
    import re

    from horovod_tpu.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((1, 16384, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16,
                              sharding=one_chip)
    plan = fa.flash_plan(q, kv, kv, block_diffusion=4)
    assert (plan.mask, plan.bwd_form, len(plan.live_tiles)) == (
        "block_diffusion", "dkdv_resident", 576)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(
                *a, block_diffusion=4,
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(backward).lower(q, kv, kv).compile().as_text()
    for name, stated in (("flash_fwd", plan.fwd_vmem_bytes),
                         ("flash_bwd_dkdv", plan.bwd_vmem_bytes)):
        (line,) = [l for l in text.splitlines()
                   if "custom-call(" in l and name in l.split("(")[0]]
        assert "s32[576]" in text, name
        sizes = re.findall(
            r'"scoped_memory_configs":\[\{[^\]]*"size":"(\d+)"', line)
        assert sizes == [str(stated)], (name, sizes)
    assert (plan.fwd_vmem_bytes, plan.bwd_vmem_bytes) == (
        19 * 2 ** 20, 37 * 2 ** 20)


def test_xing4_latent_flash_call_compiles_for_v5e(one_chip):
    """``xing4_train_s8192``'s attention call as ``mla_mixer`` makes it:
    32 heads with keys of 192 (128 latent-made channels beside 64 rotary
    ones turned by YaRN's tables, the rotary key one vector for all
    heads) over values of 128 at 8192 keys, the softmax scaled by
    ``192 ** -0.5`` times YaRN's ``mscale`` squared: Kimi-Linear's head
    geometry at GLM's length.  Forward and backward compile as the
    kernels' own calls, the rotation around them as XLA's."""
    from horovod_tpu.models.transformer import GPT_CONFIGS
    from horovod_tpu.ops.rope import apply_rope_tables, rope_tables

    cfg = GPT_CONFIGS["xing4.0-29b-a4b"]
    assert (cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (32, 192, 128)
    shape = lambda heads, width: jax.ShapeDtypeStruct(
        (1, 8192, heads, width), jnp.bfloat16, sharding=one_chip)

    def attend(q, k_nope, k_rope, v):
        tabs = rope_tables(jnp.arange(8192), 64, cfg.rope_theta,
                           dict(cfg.rope_scaling))
        q = jnp.concatenate(
            [q[..., :128], apply_rope_tables(q[..., 128:], *tabs)], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            apply_rope_tables(k_rope, *tabs), (1, 8192, 32, 64))], axis=-1)
        return flash_attention(q, k, v, causal=True,
                               scale=cfg.attention_scale, interpret=False)

    def backward(*args):
        return jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3))(*args)

    text = jax.jit(backward).lower(
        shape(32, 192), shape(32, 128), shape(1, 64),
        shape(32, 128)).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd" in text


# (id, rows, hidden, held experts, expert width): the whole slot buffer of
# the GLM, Trinity and SmallThinker cells, the LFM2 cell's row bound
_EXPERT_SHAPES = [
    ("glm47f_train_s8192", 32768, 2048, 8, 1536),
    ("trinitym_train_s8192", 65536, 2048, 16, 1024),
    ("smallthinker_train_s16384", 98304, 2560, 16, 768),
    ("lfm2_train_s32768", 32768, 2048, 8, 1536),
]


@pytest.mark.parametrize("rows,hidden,held,width",
                         [case[1:] for case in _EXPERT_SHAPES],
                         ids=[case[0] for case in _EXPERT_SHAPES])
def test_grouped_expert_matmuls_compile_for_v5e(one_chip, rows, hidden, held,
                                                width):
    """The dropless expert layer's grouped feed-forward at each expert
    cell's shape (glm47f_train_s8192: 8192 tokens x 4 choices = 32768
    rows of 2048, 8 held experts of 1536, a ninth group for the rows whose
    expert lives elsewhere), forward and backward: jax's Pallas grouped
    matmul, whose grid follows the group sizes (five calls: the first
    matmul forward, and each matmul's two gradients, ``gmm`` for the rows
    and ``tgmm`` for the weights), and no ``ragged-dot`` beside it.  The
    chip's compiler accepts the tiles ``gmm_tiles`` gives each call: their
    blocks fit the VMEM a kernel that states no limit may use."""
    from horovod_tpu.parallel.moe import grouped_ffn

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((rows, hidden), jnp.bfloat16),
            shape((held, hidden, 2 * width), jnp.float32),
            shape((held, width, hidden), jnp.float32),
            shape((held + 1,), jnp.int32))

    def backward(xs, fc1, fc2, sizes):
        return jax.grad(
            lambda *a: grouped_ffn(*a, sizes, interpret=False).astype(
                jnp.float32).sum(), argnums=(0, 1, 2))(xs, fc1, fc2)

    compiled = jax.jit(backward).lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5
    assert "ragged-dot" not in text and "ragged_dot" not in text
    # the widest temporaries are the [rows, 2 width] buffers, 192 MiB
    # each at GLM's shape, where the limit is 1 GiB: 16 / 3 of them
    widest = rows * 2 * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * widest // 3


def _wide_rows(text, rows):
    """Where a compiled program holds arrays of ``rows`` rows and more
    than one column: ``(outside, sides)``, the shapes outside every
    ``conditional`` and, for each conditional, the shapes inside each of
    its branches (with what the branch calls), as ``{shape: count}``."""
    import re

    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    called = {
        name: set(re.findall(
            r"(?:to_apply|calls|body|condition|true_computation"
            r"|false_computation)=%?([\w.\-]+)", "\n".join(lines)))
        | {c.strip().lstrip("%") for group in re.findall(
            r"branch_computations=\{([^}]*)\}", "\n".join(lines))
           for c in group.split(",")}
        for name, lines in bodies.items()}

    def reach(name, seen):
        if name in bodies and name not in seen:
            seen.add(name)
            for other in called[name]:
                reach(other, seen)
        return seen

    wide = re.compile(r"= \(?((?:bf16|f32|s32|pred)\[%d,\d+\])" % rows)

    def shapes(names):
        found = {}
        for name in names:
            for line in bodies[name]:
                for shape in wide.findall(line):
                    if not shape.endswith(",1]"):      # a gather's indices
                        found[shape] = found.get(shape, 0) + 1
        return found

    sides, inside = [], set()
    for lines in bodies.values():
        for line in lines:
            branches = re.search(r" conditional\(.*branch_computations="
                                 r"\{([^}]*)\}", line)
            if branches:
                reached = [reach(b.strip().lstrip("%"), set())
                           for b in branches.group(1).split(",")]
                sides.append([shapes(r) for r in reached])
                inside |= set().union(*reached)
    return shapes(set(bodies) - inside), sides


@pytest.mark.parametrize("experts,held,top_k,ff,bound", [
    (64, 8, 4, 1536, 8192),      # glm47f_train_s8192: 32768 slots
    (128, 16, 8, 1024, 16384),   # trinitym_train_s8192: 65536 slots
])
def test_bounded_expert_layer_compiles_for_v5e(one_chip, experts, held,
                                               top_k, ff, bound):
    """The dropless expert layer at both cells' shapes, rematerialised,
    forward and backward, with the compiled kernels.  Outside the branch
    nothing has ``n * top_k`` rows, and on the side that stays under the
    row bound nothing has either: the way back to the tokens and the
    tokens' gradient follow the routed rows (``ops/moe_combine.py``), so
    no gather brings rows back to slot order, and every gate, cast,
    select and grouped matmul there is on ``[bound, .]`` buffers.  The
    one exception is no array: the counts compare every slot with every
    group (``moe._counts``) inside a fusion that writes ``held + 1``
    integers, and the ``[n * top_k, held + 1]`` matches are values of its
    loop.  The other side is the whole-buffer computation with the same
    kernels.
    The bounded side calls the Pallas grouped matmul as the layer without
    a bound does: twice forward, twice in the recomputed forward, four
    times backward (``gmm`` by the rows, ``tgmm`` by the matrices; the
    forward calls that ``jax.vjp`` traces there are dropped), and
    ``moe_combine`` for the outputs, for them again and for the tokens'
    gradient, each reading ``[bound, d]`` rows there and ``[n * top_k,
    d]`` on the other side; no computation holds kernels of both
    sides."""
    import re

    from horovod_tpu.parallel import moe

    n, d = 8192, 2048
    assert moe.row_bound(n, top_k, held, experts) == bound

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((n, d), jnp.bfloat16), shape((d, experts), jnp.float32),
            shape((held, d, 2 * ff), jnp.float32),
            shape((held, ff, d), jnp.float32), shape((experts,), jnp.float32))

    def step(x2, router, fc1, fc2, bias):
        def loss(x2, router, fc1, fc2):
            y, _ = moe.routed_experts(x2, router, bias, fc1, fc2,
                                      top_k=top_k, scaling=1.8,
                                      interpret=False)
            return y.astype(jnp.float32).sum()

        return jax.value_and_grad(
            jax.checkpoint(
                loss, policy=jax.checkpoint_policies.nothing_saveable),
            argnums=(0, 1, 2, 3))(x2, router, fc1, fc2)

    text = jax.jit(step).lower(*args).compile().as_text()
    assert "ragged-dot" not in text and "ragged_dot" not in text
    outside, sides = _wide_rows(text, n * top_k)
    matches = {f"{kind}[{n * top_k},{held + 1}]" for kind in ("pred", "s32")}
    assert set(outside) == matches
    # made and used up inside fusions: none is a fusion's operand or result
    assert set(re.findall(
        r"= (?:pred|s32)\[%d,%d\]\S* ([\w\-]+)\(" % (n * top_k, held + 1),
        text)) <= {"compare", "broadcast", "convert", "iota"}
    for under, over in sides:
        assert under == {}, under
    assert any(f"bf16[{n * top_k},{2 * ff}]" in over for _, over in sides)
    # the Pallas calls: which computation holds each, and its rows (a
    # ``tgmm`` gives matrices, [held, ., .]: 0 here; a ``moe_combine``
    # gives tokens: the rows it reads, its last operand's)
    where, combines, name = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
        elif "tpu_custom_call" in line and "/moe_combine/" in line:
            assert "/moe_dispatch/moe_rows_out/" in line   # its scope
            read = re.search(r"bf16\[(\d+),%d\]\{1,0\}\}, frontend" % d, line)
            combines.setdefault(name, []).append(int(read.group(1)))
        elif "tpu_custom_call" in line and "pallas_call" in line:
            rows = re.search(r"= bf16\[(\d+),\d+\]", line)
            where.setdefault(name, []).append(
                int(rows.group(1)) if rows else 0)
    assert "ENTRY" not in where and all(
        bound not in rows or n * top_k not in rows for rows in where.values())
    calls = sorted(rows for side in where.values() for rows in side)
    assert calls.count(bound) == 6 and calls.count(0) == 4
    # the other side: forward (the compiler may merge its two passes:
    # nothing lies between them here), forward again and by the rows
    assert calls.count(n * top_k) in (6, 8) and len(calls) in (16, 18)
    # the way back: forward (merged or twice) and backward a side, each
    # computation's calls on its own side's rows
    assert "ENTRY" not in combines and all(
        len(set(rows)) == 1 for rows in combines.values())
    read = sorted(rows for side in combines.values() for rows in side)
    assert read.count(bound) in (2, 3) and read.count(n * top_k) in (2, 3)
    assert len(read) == read.count(bound) + read.count(n * top_k)


@pytest.mark.parametrize("n,top_k,experts,held,score_rule", [
    (16384, 8, 256, 8, "sigmoid"),           # kimilin_train_s16384
    (16384, 8, 128, 16, "softmax_chosen"),   # sdar_train_s8192_bd4
])
def test_the_router_compiles_without_a_pass_by_the_slots(
        one_chip, n, top_k, experts, held, score_rule):
    """The decision and its gradient at the widest sigmoid cell and the
    widest ``softmax_chosen`` one, 131 072 slots a layer both: the
    compiled program scatters and gathers nothing but, under
    ``softmax_chosen``, the derivative of ``top_k``'s own values (one
    scatter to indices that are unique, which the chip does not take one
    after another: 0.03 ms a layer in the cell's trace), and what the
    dense forms compare (``[slots, bins]`` for the counts, ``[n, k, E]``
    for the sigmoid rule's chosen scores and their gradient) is made and
    used up inside fusions, never an array in HBM (Kimi-Linear's one-hot
    would be 128 MiB a pass in int32)."""
    import re

    from horovod_tpu.parallel import moe

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(x2, router, bias):
        def loss(x2, router):
            routing = moe.routing_decision(
                x2, router, bias if score_rule == "sigmoid" else None,
                top_k=top_k, scaling=1.5, first_held=0, held=held,
                score_rule=score_rule, balance=True)
            return routing.weights.sum() + routing.balance, routing

        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
            x2, router)

    text = jax.jit(step).lower(
        shape((n, 2048), jnp.bfloat16), shape((2048, experts), jnp.float32),
        shape((experts,), jnp.float32)).compile().as_text()
    assert re.findall(r" (scatter|gather)\(", text) == (
        [] if score_rule == "sigmoid" else ["scatter"])
    slots = n * top_k
    dense = "|".join((f"{slots},{held + 1}", f"{slots},{experts}",
                      f"{n},{top_k},{experts}"))
    ops = set(re.findall(
        r"= (?:pred|s32|f32)\[(?:%s)\]\S* ([\w\-]+)\(" % dense, text))
    assert ops >= ({"compare", "select"} if score_rule == "sigmoid"
                   else {"compare"})
    assert ops <= {"compare", "select", "broadcast", "convert", "iota",
                   "bitcast", "reshape"}, ops


# tokens, choices, hidden, held, experts, rows: the widest layers of the
# way back, SDAR's under its row bound and SmallThinker's whole buffer
_COMBINE_SHAPES = {
    "sdar_train_s8192_bd4": (16384, 8, 2048, 16, 128, 32768),
    "smallthinker_whole_buffer": (16384, 6, 2560, 16, 64, 98304),
}


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain_sum"])
@pytest.mark.parametrize("cell", sorted(_COMBINE_SHAPES))
def test_moe_combine_compiles_for_v5e(one_chip, cell, weighted):
    """``moe_combine`` under the plan's tiles at the cell's shape: the
    weighted sum into float32 (the forward pass's) and the plain sum into
    bfloat16 (the tokens' gradient), inside the VMEM the call states."""
    from horovod_tpu.ops import moe_combine

    n, k, d, held, experts, rows = _COMBINE_SHAPES[cell]
    tiles = moe_combine.plan(n, d, held, rows, 2)
    assert tiles is not None

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def back(ys, weights, inverse, held_sizes):
        return moe_combine.combine_rows(
            ys, weights if weighted else None, inverse, held_sizes, k=k,
            tiles=tiles, dtype=jnp.float32 if weighted else jnp.bfloat16)

    text = jax.jit(back).lower(
        shape((rows, d), jnp.bfloat16), shape((n, k), jnp.float32),
        shape((n * k,), jnp.int32), shape((held,), jnp.int32),
    ).compile().as_text()
    assert text.count("tpu_custom_call") == 1 and "moe_combine" in text
    # nothing by the slots: no gather, no scatter, no [n k, d] array
    assert " gather(" not in text and " scatter(" not in text
    assert f"[{n * k},{d}]" not in text or rows == n * k


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``ssd_scan`` takes its interpret mode from the default backend,
    which is the CPU here, through ``flash_attention._interpret_for_
    backend`` (it has no switch of its own): ask for the compiled
    kernels the way ``benchmark/tools/compile_check.py`` does."""
    from horovod_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret_for_backend", lambda backend: False)


def _cell_scan_args(one_chip, heads=64):
    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return (shaped(1, 8192, heads, 64),
            shaped(1, 8192, heads, dtype=jnp.float32),
            shaped(heads, dtype=jnp.float32), shaped(1, 8192, 1, 128),
            shaped(1, 8192, 1, 128), shaped(heads, dtype=jnp.float32))


@pytest.mark.parametrize("direction,kernels,temporaries_mib", [
    ("forward", ["ssd_fwd"], 64), ("backward", ["ssd_fwd", "ssd_bwd"], 320)])
def test_ssd_scan_compiles_for_v5e_without_a_loop(
        one_chip, compiled_kernels, direction, kernels, temporaries_mib):
    """The two Pallas kernels at the cell's shapes, inside the VMEM their
    calls state: no ``while`` in the compiled program (a device trace
    files one under no scope and its body a second time), and nothing of
    ``[chunks, heads, chunk, chunk]`` in HBM (0.5 GiB each in float32:
    the XLA form's backward held 0.30 GiB of temporaries, under a limit
    of 1 GiB).  What the design keeps: forward the 18 MiB of ``a`` and
    ``dt`` laid out for the kernel (0 MiB of temporaries when this was
    written: they are fused into their producers); backward the 64 MiB
    of chunk-start states, the layouts, and in this test, whose ``x`` and
    ``dy`` arrive as ``[seq, heads, 64]`` in tiles of 128 lanes, three
    64 MiB copies that fold heads into lanes (289 MiB in all; in the
    model ``x`` is a slice of a ``[seq, 4352]`` matrix and needs none)."""
    from horovod_tpu.ops.ssd import ssd_scan

    def scan(*a):
        return ssd_scan(*a, 256)

    def backward(*a):
        return jax.grad(lambda *a: scan(*a).astype(jnp.float32).sum(),
                        argnums=tuple(range(6)))(*a)

    compiled = jax.jit(scan if direction == "forward" else backward
                       ).lower(*_cell_scan_args(one_chip)).compile()
    text = compiled.as_text()
    assert " while(" not in text
    for name in ("ssd_fwd", "ssd_bwd"):
        assert (f"/{name}/" in text) == (name in kernels), name
    assert (compiled.memory_analysis().temp_size_in_bytes
            < temporaries_mib * 2 ** 20)


def test_ssd_scan_refuses_a_shape_over_its_vmem(one_chip, compiled_kernels):
    """1024 heads of 64 x 128 float32 state are 32 MiB of scratch alone, and
    the call states 16:
    refused before anything is traced, with the numbers."""
    from horovod_tpu.ops.ssd import ssd_scan

    with pytest.raises(ValueError, match=r"heads=1024 x head_dim=64 x "
                       r"state=128 at chunk=256 needs \d+ bytes of VMEM "
                       r"\(40\.2 MiB\), over the 16777216 \(16 MiB\)"):
        jax.jit(lambda *a: ssd_scan(*a, 256)).lower(
            *_cell_scan_args(one_chip, heads=1024))
    with pytest.raises(ValueError, match="chunk=64 is not a multiple of "
                       "128"):
        jax.jit(lambda *a: ssd_scan(*a, 64)).lower(
            *_cell_scan_args(one_chip))


# phi-4-mini-flash-reasoning's two kernels at the published widths and the
# benchmark cell's 1 x 8192 tokens (benchmark/workloads/
# phi4mf_train_s8192.json): the Mamba-1 selective scan over 5120 channels
# of 16 states, and differential attention's one flash call (40 query
# sub-heads of 64 over 20 rows of keys of 64 and values of 128: the pairs'
# own shape), banded at window 512 (one K tile wide) and full.
@pytest.mark.parametrize("direction,kernels,temporaries_mib", [
    ("forward", ["sscan_fwd"], 96), ("backward", ["sscan_fwd", "sscan_bwd"],
                                     480)])
def test_selective_scan_compiles_for_v5e(one_chip, compiled_kernels,
                                         direction, kernels,
                                         temporaries_mib):
    """Both Pallas kernels inside the VMEM their calls state, no
    ``while`` outside them (the walk over the tokens is inside the
    kernel), and nothing of ``[seq, channels, state]`` in HBM (2.5 GiB in
    float32).  What the design keeps in HBM beside its arguments: ``B``
    and ``C`` spread over 128 lanes (32 MiB each in bfloat16, forward and
    again backward) and, under differentiation, the 20 MiB of states at
    each time block's start; the backward's ``d dt`` is 160 MiB."""
    from horovod_tpu.ops.selective_scan import selective_scan

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (shaped(1, 8192, 5120), shaped(1, 8192, 5120, dtype=jnp.float32),
            shaped(5120, 16, dtype=jnp.float32), shaped(1, 8192, 16),
            shaped(1, 8192, 16), shaped(5120, dtype=jnp.float32))

    def backward(*a):
        return jax.grad(
            lambda *a: selective_scan(*a).astype(jnp.float32).sum(),
            argnums=tuple(range(6)))(*a)

    compiled = jax.jit(selective_scan if direction == "forward"
                       else backward).lower(*args).compile()
    text = compiled.as_text()
    assert " while(" not in text
    for name in ("sscan_fwd", "sscan_bwd"):
        assert (f"/{name}/" in text) == (name in kernels), name
    assert (compiled.memory_analysis().temp_size_in_bytes
            < temporaries_mib * 2 ** 20)


def test_selective_scan_refuses_what_its_tiles_cannot_take(
        one_chip, compiled_kernels):
    from horovod_tpu.ops.selective_scan import selective_scan

    def shaped(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def args(channels, state):
        return (shaped(1, 256, channels), shaped(1, 256, channels),
                shaped(channels, state), shaped(1, 256, state),
                shaped(1, 256, state), shaped(channels))

    with pytest.raises(ValueError, match="channels=192 is not a multiple "
                       "of 128"):
        jax.jit(selective_scan).lower(*args(192, 16))
    with pytest.raises(ValueError, match="state=4 has to be a multiple of "
                       "8"):
        jax.jit(selective_scan).lower(*args(256, 4))


@pytest.mark.parametrize("window", [512, None])
def test_differential_flash_call_compiles_for_v5e(one_chip, window):
    """40 query rows of 64 over 20 key/value rows, the values 128 wide,
    at 8192 tokens: forward and the one-kernel backward with a kv row's
    dk ``[8192, 64]`` and dv ``[8192, 128]`` resident, inside the 32 MiB
    the call states."""
    q = jax.ShapeDtypeStruct((1, 8192, 40, 64), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, 8192, 20, 64), jnp.bfloat16,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 20, 128), jnp.bfloat16,
                             sharding=one_chip)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(
                *a, causal=True, window=window,
                interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(backward).lower(q, k, v).compile().as_text()
    assert "flash_fwd" in text and "flash_bwd_dkdv" in text
    assert "flash_bwd_dq" not in text


# The gradient plane's "proof of overlap" (optim/overlap.py), read from
# the artifact that matters.  XLA:CPU merges the buckets' all-reduces, so
# its text proves nothing either way; this is the TPU compiler's, for the
# described 2x2, at its default options.
def test_lfm2_cell_step_compiles_for_v5e(topo, compiled_kernels):
    """``lfm2_train_s32768``'s whole step (four gated short convolutions
    and a grouped-query attention layer at 32 768 tokens, a 23 552-wide
    dense feed-forward, four expert layers of 131 072 slots, AdamW) as
    the benchmark builds it, for one described chip: the streamed flash
    forward and ONE backward kernel, the grouped matmuls, the conv
    chain's scope forward and backward, and the step inside the chip's
    memory with room for the checks (the issue's rule: under 15 GiB)."""
    import json
    import os
    import sys

    import numpy as np
    from jax.sharding import Mesh

    import horovod_tpu as hvd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import registry

    cell = registry.load_cell("lfm2_train_s32768", root)
    config = cell["config_values"]
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), (hvd.DP_AXIS,))
    built = registry.load_model_builder(config["family"], root).build(
        config, cell["params"], 0, described_mesh=mesh)
    assert built.ran["flash_fwd_kv_resident"] == {"full_attention": False}
    compiled = built.step.lower(*built.state).compile()
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkdv", "gmm", "tgmm"):
        assert kernel in text, kernel
    assert "flash_bwd_dq" not in text
    assert "jvp(GPT)/block0/short_conv/short_conv_filter" in text
    assert "/block4/short_conv/short_conv_filter" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        469_284_992 * 12, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15 * 2 ** 30, json.dumps(total / 2 ** 30)


@pytest.mark.parametrize("width,bucket_bytes", [
    (None, 8 * 1024),         # tests/test_overlap.py's MLP: 5 buckets
    (1024, 4 * 1024 * 1024),  # four 4 MiB weights: 8 buckets, 16 MiB
], ids=["tiny_mlp", "4MiB_buckets"])
def test_tpu_compiler_combines_the_bucket_allreduces(four_chips, width,
                                                     bucket_bytes):
    """What holds today (a finding for ROADMAP A2, PERF.md section 7):
    the ``bucket`` plan asks for one psum per bucket inside the backward,
    and the TPU compiler's all-reduce combiner folds them into ONE
    all-reduce whose operands are the buckets, scheduled after the last
    backward fusion — the same schedule as ``off``.  Nothing overlaps.
    A PR that makes the buckets survive changes these assertions."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.collectives import shard_map_compat
    from horovod_tpu.optim import overlap

    sizes = [width] * 5 if width else [32, 64, 37, 41, 10]

    def init_params():
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        return [{"w": jax.random.normal(k, (a, b)) * 0.1,
                 "b": jnp.zeros(b)}
                for k, a, b in zip(keys, sizes, sizes[1:])]

    def loss_fn(params, x, y):
        h = x
        for i, layer in enumerate(params):
            h = h @ layer["w"] + layer["b"]
            if i < 3:
                h = jax.nn.relu(h)
        return jnp.mean((h - y) ** 2)

    def on_mesh(shape, spec):
        return jax.ShapeDtypeStruct(
            shape.shape, shape.dtype,
            sharding=NamedSharding(four_chips, spec))

    params = jax.eval_shape(init_params)
    x = on_mesh(jax.ShapeDtypeStruct((16, sizes[0]), jnp.float32),
                P(hvd.DP_AXIS))
    y = on_mesh(jax.ShapeDtypeStruct((16, sizes[-1]), jnp.float32),
                P(hvd.DP_AXIS))
    texts, plans = {}, {}
    for mode in ("off", "bucket"):
        plan = overlap.OverlapPlan(
            params, optax.sgd(0.05, momentum=0.9), mode=mode,
            mesh=four_chips, bucket_mb=bucket_bytes / 2 ** 20)
        spec = plan.state_spec()
        step = jax.jit(
            shard_map_compat(
                plan.local_step(loss_fn), mesh=four_chips,
                in_specs=(spec, P(hvd.DP_AXIS), P(hvd.DP_AXIS)),
                out_specs=(spec, P())),
            donate_argnums=(0,))
        state = jax.tree_util.tree_map(
            lambda sp, sub: jax.tree_util.tree_map(
                lambda leaf: on_mesh(leaf, sp), sub),
            spec, jax.eval_shape(plan.init, params),
            is_leaf=lambda v: isinstance(v, P))
        texts[mode] = step.lower(state, x, y).compile().as_text()
        plans[mode] = plan

    n_buckets = len(plans["bucket"].layout.buckets)
    assert n_buckets >= 3  # the plan did ask for separate collectives

    def gradient_allreduces(text):
        """(shape, opcode) of the entry computation's reduce-class
        collectives, the scalar loss's left out, in schedule order."""
        found = []
        for line in overlap._entry_lines(text):
            for op in ("all-reduce-start", "all-reduce", "reduce-scatter"):
                if f" {op}(" in line:
                    shape = line.split(" = ", 1)[1].split(f" {op}(")[0]
                    if not shape.startswith("f32[]"):
                        found.append((shape, op))
        return found

    for mode in ("off", "bucket"):
        reduces = gradient_allreduces(texts[mode])
        assert [op for _, op in reduces] == ["all-reduce"], (mode, reduces)
    # one operand per bucket: combined, not dropped
    combined = gradient_allreduces(texts["bucket"])[0][0]
    assert combined.count("f32[") == n_buckets, combined

    if n_buckets <= 5:
        # inspect_schedule reads the same thing where it can: its
        # pattern stops at the "/*index=5*/" the compiler writes into a
        # tuple shape of more than five elements (PERF.md section 7).
        for mode in ("off", "bucket"):
            rep = overlap.inspect_schedule(texts[mode])
            assert rep.gradient_collectives == 1, (mode, rep.as_dict())
            assert rep.in_backward == 0 and rep.monolithic, rep.as_dict()
