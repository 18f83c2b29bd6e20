"""Whole steps compiled for a described v5e, without the chip:
``lfm2_train_s32768``'s step as the benchmark builds it (two minutes of the
TPU compiler in one case) and the gradient plane's bucketed step.  The
fixtures are ``tests/test_tpu_compile.py``'s, from which these cases moved
whole; they have a file of their own so that ``--dist loadfile`` starts
the long compile first and beside that file's kernels, not after them."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from test_ssm_chain import _names
from test_tpu_compile import (compiled_kernels, four_chips,  # noqa: F401
                              no_compile_cache, one_chip, topo)


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
    # heads of 64, half a lane tile: the norms and the rotation stay
    # XLA's chain, under the scope (ops/attn_prep.py:plan)
    assert "attn_prep_fwd" not in text and "attn_prep_bwd" not in text
    assert "/block1/attn/attn_prep/" in text
    assert "jvp(GPT)/block0/short_conv/short_conv_filter" in text
    assert "/block4/short_conv/short_conv_filter" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        469_284_992 * 12, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15 * 2 ** 30, json.dumps(total / 2 ** 30)


@pytest.mark.parametrize("cell,layers", [
    ("gpt2m_train_s1024", 0),       # no norms, learned positions
    ("granite4hm_train_s8192", 0),  # no norms, no positions
    ("trinitym_train_s8192", 5),    # four with the rotation, one without
    ("sdar_train_s8192_bd4", 6),
    # one layer, its gate out of the query projection, 1 + w head norms
    # and a quarter of a head rotated: the chain
    ("qwen3next_train_s16384", 0),
])
def test_which_cells_steps_hold_the_attn_prep_kernels(topo, cell, layers):
    """The steps as the benchmark builds them, traced for one described
    chip: a cell whose attention layers have neither a norm over each
    head nor a rotation holds no ``attn_prep`` call; Trinity-Mini's and
    SDAR's hold one forward kernel a layer in the forward pass, a second
    in the rematerialised block's recompute, and one backward, and
    ``attn_prep_kernel_share`` reads 1.0 from the gauges the trace set;
    Qwen3-Next's one attention layer takes the chain and reads 0.0.
    (LFM2's heads of 64 keep the chain: the compiled step above.)"""
    import os
    import sys

    import numpy as np
    from jax.sharding import Mesh

    import horovod_tpu as hvd

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import registry

    loaded = registry.load_cell(cell, root)
    config = loaded["config_values"]
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), (hvd.DP_AXIS,))
    built = registry.load_model_builder(config["family"], root).build(
        config, loaded["params"], 0, described_mesh=mesh)
    names = _names(jax.make_jaxpr(built.step)(*built.state).jaxpr)
    assert names.count("attn_prep_fwd") == 2 * layers
    assert names.count("attn_prep_bwd") == layers
    assert names.count("flash_fwd") > 0
    if config["family"] in ("afmoe", "sdar_moe", "qwen3_next"):
        # (the cells above these set no attn_prep gauge)
        share = registry.load_module(os.path.join(
            root, "benchmark", "metrics",
            "attn_prep_kernel_share.py")).read({})
        assert share == (1.0 if layers else 0.0)


@pytest.mark.parametrize("rows,heads,norms,rotates", [
    (16384, 32, True, True),    # SDAR's layers, Trinity-Mini's sliding
    (8192, 32, True, False),    # Trinity-Mini's full_attention layer
    (16384, 28, False, True),   # SmallThinker's sliding layers
])
def test_attn_prep_kernels_compile_for_v5e(one_chip, rows, heads, norms,
                                           rotates):
    """Both kernels at the cells' shapes (heads over 4 key/value heads
    of 128, bfloat16), inside the VMEM their calls state."""
    from horovod_tpu.ops import attn_prep

    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tiles = attn_prep.plan(rows, heads, 4, 128,
                           norm="rmsnorm" if norms else None,
                           rotates=rotates, flash=True, plain=True)
    call = dict(shape=(heads, 4, 1e-6, *tiles), interpret=False)
    args = (shaped(1, rows, (heads + 8) * 128),
            shaped(2, 128, dtype=jnp.float32) if norms else None,
            (shaped(rows, 128, dtype=jnp.float32),) * 2 if rotates else None)
    major = lambda n: shaped(1, n, rows, 128)
    for lowered in (
            attn_prep._forward.lower(*args, **call),
            attn_prep._backward.lower(*args, major(heads), major(4),
                                      major(4), **call)):
        assert "tpu_custom_call" in lowered.compile().as_text()


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
