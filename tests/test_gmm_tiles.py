"""The grouped matmuls' tiles (``parallel/moe.py:gmm_tiles``): a rule of
each call's own ``k`` and ``n`` that divides them, from shapes alone, and
jax's kernel itself under those tiles in Pallas' interpreter against the
``lax.ragged_dot`` stand-in."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.transformer import GPT_CONFIGS
from horovod_tpu.parallel import moe

# cell: (configuration, hidden, expert width)
CELLS = {
    "glm47f_train_s8192": ("glm-4.7-flash", 2048, 1536),
    "trinitym_train_s8192": ("trinity-mini", 2048, 1024),
    "smallthinker_train_s16384": ("smallthinker-21ba3b-instruct", 2560, 768),
    "lfm2_train_s32768": ("lfm2-24b-a2b", 2048, 1536),
}
CALLS = ("gate_up", "down", "down_rows_grad", "gate_up_rows_grad",
         "down_weights_grad", "gate_up_weights_grad")


def _parent_tiles(calls):
    """What the calls got before PR 50: ``(512, 1024, 1024)`` cut to the
    forward call's ``k`` and ``n``, handed to both its gradients too."""
    gate_up, down = ((512, min(1024, k), min(1024, n))
                     for k, n, _ in calls[:2])
    return [gate_up, down, down, gate_up, down, gate_up]


@pytest.mark.parametrize("call", range(6), ids=CALLS)
@pytest.mark.parametrize("cell", CELLS)
def test_a_calls_tiles_divide_its_own_k_and_n(cell, call):
    name, hidden, width = CELLS[cell]
    cfg = GPT_CONFIGS[name]
    assert (cfg.emb_dim, cfg.routed_width) == (hidden, width)
    k, n, weights_out = moe.ffn_calls(hidden, width)[call]
    tm, tk, tn = moe.gmm_tiles(98304, k, n, 2, weights_out)
    assert tm == moe.GMM_ROW_TILE == 512
    assert tk % 128 == 0 and tn % 128 == 0
    assert k % tk == 0 and n % tn == 0
    blocks = 4 * (tm * tk + tk * tn + tm * tn) + 4 * (
        tk if weights_out else tm) * tn
    assert blocks <= moe.GMM_VMEM_BYTES == 12 * 2 ** 20
    if cell == "trinitym_train_s8192":
        assert (tm, tk, tn) == (512, 1024, 1024)      # as before PR 50


@pytest.mark.parametrize("cell", CELLS)
def test_the_tiles_multiply_no_padding(cell):
    _, hidden, width = CELLS[cell]
    assert moe.ffn_tile_fill(hidden, width, jnp.bfloat16) == 1.0


@pytest.mark.parametrize("cell,fill", [
    ("glm47f_train_s8192", 0.90), ("trinitym_train_s8192", 1.0),
    ("smallthinker_train_s16384", 0.66), ("lfm2_train_s32768", 0.90)])
def test_what_the_parents_pairs_filled(cell, fill):
    """ISSUE 50's table: 1024 cut to the forward call's dimensions
    executes 13.6U for SmallThinker's 9U, 10U for GLM's and LFM2's 9U."""
    _, hidden, width = CELLS[cell]
    calls = moe.ffn_calls(hidden, width)
    assert moe.tile_fill(calls, _parent_tiles(calls)) == pytest.approx(
        fill, abs=0.005)


def test_the_rule_reads_shapes_and_the_item_size_alone():
    """No tile is wider than its dimension, a width no multiple of 128
    divides gets one block (up to 1024, as before PR 50), float32
    operands get smaller blocks, and few rows cut the row tile alone."""
    assert moe.gmm_tiles(512, 96, 320, 2) == (512, 96, 320)
    assert moe.gmm_tiles(512, 5000, 1280, 2) == (512, 1024, 1280)
    assert moe.gmm_tiles(64, 2560, 1536, 2) == (64, 1280, 1536)
    assert moe.gmm_tiles(98304, 2560, 1536, 2) == (512, 2560, 512)
    assert moe.gmm_tiles(98304, 2560, 1536, 4) == (512, 640, 768)
    assert moe.gmm_tiles(98304, 2560, 1536, 2, True) == (512, 1280, 768)


@pytest.fixture
def interpreted(monkeypatch):
    """jax's kernels in Pallas' interpreter: the tests' CPU has no
    Mosaic.  Steered here, not by an option of the program."""
    from functools import partial

    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    monkeypatch.setattr(gmm, "gmm", partial(gmm.gmm, interpret=True))
    monkeypatch.setattr(gmm, "tgmm", partial(gmm.tgmm, interpret=True))
    monkeypatch.setattr(moe, "GMM_ROW_TILE", 16)


@pytest.mark.parametrize("vmem,tiles", [
    (moe.GMM_VMEM_BYTES, (16, 640, 384)), (2 ** 18, (16, 128, 128))],
    ids=["one_block", "five_by_three_blocks"])
def test_the_kernel_under_the_rules_tiles_matches_the_stand_in(
        interpreted, monkeypatch, vmem, tiles):
    """``k`` 640 and ``n`` 384, which no power of two divides, three
    groups (one empty) and the ownerless tail: the matmul, and both its
    gradients with the tiles of their own dimensions."""
    monkeypatch.setattr(moe, "GMM_VMEM_BYTES", vmem)
    rows, k, n = 96, 640, 384
    assert moe.gmm_tiles(rows, k, n, 4) == tiles
    sizes = jnp.asarray([40, 0, 27, 29], jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    lhs = jax.random.normal(keys[0], (rows, k), jnp.float32)
    rhs = jax.random.normal(keys[1], (3, k, n), jnp.float32) / k ** 0.5
    grad = jax.random.normal(keys[2], (rows, n), jnp.float32)

    ours = moe._gmm(lhs, rhs, sizes, False)
    np.testing.assert_allclose(ours, moe._gmm(lhs, rhs, sizes, True),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(ours[67:]).any()           # the ownerless tail
    d_lhs, d_rhs = moe._gmm_bwd(lhs, rhs, sizes, False, grad)
    want_lhs, want_rhs = moe._gmm_bwd(lhs, rhs, sizes, True, grad)
    np.testing.assert_allclose(d_lhs, want_lhs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(d_rhs, want_rhs, rtol=1e-5, atol=2e-5)
