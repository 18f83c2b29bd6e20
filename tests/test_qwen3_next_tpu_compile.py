"""``qwen3next_train_s16384`` compiled for a described v5e, without the
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


def test_qwen3_next_cell_step_compiles_for_v5e(topo, compiled_kernels):
    """``qwen3next_train_s16384``'s whole step (four layers at 16 384
    tokens: three Gated DeltaNet layers whose scalar-decay rule enters
    the channel-decay kernels at 32 heads of 128, a gated attention
    layer of 16 query heads over 2 key/value heads of 256 whose backward
    is the two passes, four layers of 32 held experts of 512 behind a
    router of 512 outputs beside the gated shared expert; AdamW) as the
    benchmark builds it, for one described chip: the rule's two kernels
    under ``gdn_scan``, the flash kernels, the grouped matmuls, the
    mixers' scopes, and the step inside the chip's memory with room for
    the checks (under 15 GiB)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.harness import registry

    cell = registry.load_cell("qwen3next_train_s16384", root)
    config = cell["config_values"]
    mesh = Mesh(np.asarray(topo.devices[:1], dtype=object), (hvd.DP_AXIS,))
    built = registry.load_model_builder(config["family"], root).build(
        config, cell["params"], 0, described_mesh=mesh)
    compiled = built.step.lower(*built.state).compile()
    text = compiled.as_text()
    for kernel in ("kda_fwd", "kda_bwd", "flash_fwd", "flash_bwd_dkdv",
                   "flash_bwd_dq", "gmm", "tgmm"):
        assert kernel in text, kernel
    assert "jvp(GPT)/block0/gdn/gdn_prep" in text
    assert "/block2/gdn/gdn_scan" in text and "/block3/attn/" in text
    assert "/block3/attn/attn_gate" in text
    assert "/block0/mlp/moe_shared" in text
    for absent in ("/block0/attn", "/block3/gdn", "kda_scan", "kda_prep",
                   "attn_prep_fwd"):
        assert absent not in text, absent
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(
        625_667_136 * 12, rel=0.01)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 15 * 2 ** 30, json.dumps(total / 2 ** 30)
