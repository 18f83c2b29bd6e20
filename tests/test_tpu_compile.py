"""Chip-compiler tests: the main path's Pallas kernels, and the gradient
plane's bucketed step, compiled by the TPU's own compiler for a described
(not attached) v5e at real widths.

The file with the fixtures of such tests (three more files import them:
the tier-1 command sets ``ALLOW_MULTIPLE_LIBTPU_LOAD``).  The topology is
described inside a module-scoped fixture — never at import, in a
``skipif`` or a ``parametrize`` argument — because under pytest-xdist
every worker imports this file, and only a worker that runs such tests
may load the TPU library.  Each case compiles in the
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


@pytest.mark.parametrize("seq,groups", [(8192, 1), (16384, 8)])
def test_ssm_chain_kernels_compile_for_v5e(one_chip, compiled_kernels, seq,
                                           groups):
    """The Mamba-2 chains' two kernel pairs (``ops/ssm_chain.py``) at
    ``granite4hm_train_s8192``'s and ``nemotron3n_train_s16384``'s
    shapes (inner 4096, a state of 128, one group and eight), forward
    and backward through the ``custom_vjp``s, inside the VMEM their
    calls state: all four calls in the compiled program and no
    ``while``."""
    from horovod_tpu.ops import ssm_chain

    inner, heads, state = 4096, 64, 128
    width = inner + 2 * groups * state
    tiles = ssm_chain.plan(seq, inner, groups, state, 4)

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def backward(fused, conv_kernel, conv_bias, y, scale):
        def loss(*a):
            x, B, C = ssm_chain.ssm_prep(*a[:3], inner=inner, heads=heads,
                                         groups=groups, tiles=tiles)
            normed = ssm_chain.ssm_norm(a[3], a[0], a[4], groups=groups,
                                        eps=1e-5, tiles=tiles)
            return sum(t.astype(jnp.float32).sum()
                       for t in (x, B, C, normed))

        # the value too: the backward reads the inputs alone, and a
        # gradient by itself would leave the forward calls dead
        return jax.value_and_grad(loss, argnums=tuple(range(5)))(
            fused, conv_kernel, conv_bias, y, scale)

    compiled = jax.jit(backward).lower(
        shaped(1, seq, inner + width + heads),
        shaped(4, width, dtype=jnp.float32),
        shaped(width, dtype=jnp.float32), shaped(1, seq, heads, 64),
        shaped(inner, dtype=jnp.float32)).compile()
    text = compiled.as_text()
    assert " while(" not in text
    for name in ("ssm_prep_fwd", "ssm_prep_bwd", "ssm_norm_fwd",
                 "ssm_norm_bwd"):
        assert f"/{name}/" in text, name
