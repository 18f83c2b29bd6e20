"""``kimilin_train_s16384`` compiled for a described v5e, without the
chip: the latent flash call at keys of 192 over values of 128, one
layer's chunked gated delta rule, and the cell's step at two of its
layers.  The fixtures are ``tests/test_tpu_compile.py``'s; the tests have
a file of their own so that ``--dist loadfile`` starts these five
minutes of TPU compiles beside that file's and not after them."""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops.kda import kda

from test_tpu_compile import (compiled_kernels, no_compile_cache,  # noqa: F401
                              one_chip, topo)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_kimi_latent_flash_call_compiles_for_v5e(one_chip, direction):
    """The cell's latent call: 32 heads with keys of 192 (128 + 64, the
    first head size that is no multiple of 128 and no 64: 192 channels
    occupy 256 lanes) over values of 128 at 16 384 tokens.  Mosaic takes
    the minor dimension of 192 as it stands, forward with a kv row
    resident and ONE backward kernel with a row's dq resident, inside the
    VMEM each states: no padding of q and k to 256 channels is needed."""
    shape = lambda width: jax.ShapeDtypeStruct(
        (1, 16384, 32, width), jnp.bfloat16, sharding=one_chip)
    attend = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False)

    def backward(q, k, v):
        return jax.grad(lambda *a: attend(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    fn = attend if direction == "forward" else backward
    text = jax.jit(fn).lower(shape(192), shape(192),
                             shape(128)).compile().as_text()
    assert "flash_fwd" in text
    if direction == "backward":
        assert "flash_bwd_dkdv" in text and "flash_bwd_dq" not in text


def test_kda_rule_compiles_for_v5e(one_chip, compiled_kernels):
    """One layer's chunked gated delta rule, forward and backward, at the
    cell's shape (1 x 16 384 tokens, 32 heads of 128, bfloat16, chunk 64,
    a state every fourth chunk): ``plan`` gives it the kernel pair at
    four heads a program, Mosaic takes both inside the 16 MiB they state,
    and the compiled program holds ``kda_fwd`` and ``kda_bwd`` under the
    rule's scope and no loop.  Its temporaries are the copies between
    ``[seq, heads, 128]`` arguments and the kernels' ``[seq, heads x
    128]`` (which the step, whose neighbours are kernels too, does not
    make), far from a ``[seq, heads, 128, 128]`` array (32 GiB)."""
    def shaped(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert kda_ops.plan(16384, 32, 128, 128, 64, 4, 2) == (4, False)
    wide = (1, 16384, 32, 128)
    args = (shaped(*wide), shaped(*wide), shaped(*wide),
            shaped(*wide, dtype=jnp.float32),
            shaped(1, 16384, 32, dtype=jnp.float32))
    rule = lambda *a: kda(*a, chunk=64, states_every=4)
    compiled = jax.jit(jax.grad(
        lambda *t: rule(*t).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "while" not in text
    assert "jvp(kda_scan)/jit(_kernel_forward)/kda_fwd" in text
    assert "transpose(jvp(kda_scan))/jit(_kernel_backward)/kda_bwd" in text
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < 3000 * 2 ** 20, temporaries / 2 ** 20


def test_kimi_cell_step_compiles_for_v5e_at_two_layers(topo,
                                                       compiled_kernels):
    """The cell's step as the benchmark builds it, for one described
    chip, at the published widths and 16 384 tokens, cut to TWO of its
    five layers (the KDA layer with the dense feed-forward and the latent
    layer with the routed experts and the shared one; the whole step
    takes the TPU compiler five minutes here, and
    ``benchmark/tools/compile_check.py kimilin_train_s16384`` is how it
    is compiled by hand: 12.79 GiB, PERF.md section 4): the flash forward
    and ONE backward kernel at keys of 192 over values of 128, the
    grouped matmuls, the rule's scope forward and backward as the two
    kernels of ``ops/kda.py`` with its float32 chain beside it as the two
    of ``ops/kda_prep.py`` (forward, recomputed forward and
    ``transpose(...)``; the gauges count the layer), and the two layers'
    share of the memory."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import registry

    cell = registry.load_cell("kimilin_train_s16384", root)
    config = cell["config_values"]
    params = {**cell["params"], "overrides": {
        "num_layers": 2, "layer_types": ("kda", "mla")}}
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), (hvd.DP_AXIS,))
    built = registry.load_model_builder(config["family"], root).build(
        config, params, 0, described_mesh=mesh)
    assert built.ran["flash_fwd_kv_resident"] == {"attention": True}
    compiled = built.step.lower(*built.state).compile()
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dkdv", "gmm", "tgmm"):
        assert kernel in text, kernel
    assert "flash_bwd_dq" not in text
    for scope in ("jvp(GPT)/block0/kda/kda_prep", "/block0/kda/kda_scan/",
                  "transpose(jvp(GPT))/jvp(GPT)/checkpoint/block0/kda/"
                  "kda_scan/", "/block1/attn/mla_proj"):
        assert scope in text, scope
    for name in ("/jvp(GPT)/block0/kda/kda_prep/jit(_forward)/kda_prep_fwd",
                 "transpose(jvp(GPT))/jvp(GPT)/checkpoint/"
                 "rematted_computation/block0/kda/kda_prep/jit(_forward)/"
                 "kda_prep_fwd",
                 "transpose(jvp(GPT))/jvp(GPT)/checkpoint/block0/kda/"
                 "kda_prep/jit(_backward)/kda_prep_bwd",
                 "/jvp(GPT)/block0/kda/kda_scan/jit(_kernel_forward)/"
                 "kda_fwd",
                 "transpose(jvp(GPT))/jvp(GPT)/checkpoint/block0/kda/"
                 "kda_scan/jit(_kernel_backward)/kda_bwd"):
        assert name in text, name
    from horovod_tpu.obs.registry import get_registry

    assert get_registry().gauge("kda.prep_kernel_layers").value == 1
    assert get_registry().gauge("kda.kernel_layers").value == 1
    assert get_registry().gauge("kda.layers").value == 1
    mem = compiled.memory_analysis()
    # layer 1 of the cell, the latent layer, table, head and final norm
    assert mem.argument_size_in_bytes == pytest.approx(
        (103_219_872 + 93_410_304 + 2 * 47_185_920 + 2304) * 12, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 11 * 2 ** 30, total / 2 ** 30
