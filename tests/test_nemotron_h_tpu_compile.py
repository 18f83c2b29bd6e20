"""``nemotron3n_train_s16384`` compiled for a described v5e, without the
chip: the cell's whole step as the benchmark builds it.  The fixtures
are ``tests/test_tpu_compile.py``'s; the test has a file of its own so
that ``--dist loadfile`` starts its minutes of the TPU compiler beside
that file's and not after them."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest
from jax.sharding import Mesh

import horovod_tpu as hvd

from test_tpu_compile import (compiled_kernels, no_compile_cache,  # noqa: F401
                              topo)


def test_nemotron_cell_step_compiles_for_v5e(topo, compiled_kernels):
    """``nemotron3n_train_s16384``'s whole step (nine layers of one half
    each at 16 384 tokens: four Mamba-2 scans over 8 groups of 8 heads at
    a chunk of 128, a grouped-query attention layer of 32 query heads
    over 2 key/value heads, four layers of 8 held ungated experts beside
    a shared expert of 3712; AdamW) as the benchmark builds it, for one
    described chip: the scan's two kernels at a head block of one group,
    the flash kernels, the grouped matmuls on a first matrix without a
    gate, each layer under the scope of its one half and the gated norm
    inside ``ssm``, and the step inside the chip's memory with room for
    the checks (under 15 GiB)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import registry

    cell = registry.load_cell("nemotron3n_train_s16384", root)
    config = cell["config_values"]
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), (hvd.DP_AXIS,))
    built = registry.load_model_builder(config["family"], root).build(
        config, cell["params"], 0, described_mesh=mesh)
    compiled = built.step.lower(*built.state).compile()
    text = compiled.as_text()
    for kernel in ("ssd_fwd", "ssd_bwd", "flash_fwd", "flash_bwd_dkdv",
                   "gmm", "tgmm"):
        assert kernel in text, kernel
    assert "jvp(GPT)/block0/ssm/ssm_norm" in text
    assert "/block7/ssm/ssd_scan" in text and "/block5/attn/" in text
    assert "/block8/mlp/moe_shared" in text
    # a layer of one half: no mixer scope in an expert layer, no
    # feed-forward's in a mixer layer
    for absent in ("/block0/mlp", "/block5/mlp", "/block1/ssm",
                   "/block1/attn", "/block8/attn"):
        assert absent not in text, absent
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        666_962_944 * 12, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15 * 2 ** 30, json.dumps(total / 2 ** 30)
