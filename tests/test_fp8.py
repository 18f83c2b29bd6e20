"""fp8 activation-storage numerics contract (VERDICT r3 weak #2).

``dtype="fp8"`` of the step builders (bf16 compute, e4m3 activation
storage between ResNet blocks) changes the loss contract, so the opt-in path needs a
convergence-sanity assertion, reference-style: on a fixed seed, a short
training run under fp8 must track the bf16 run's loss within a stated
tolerance — and must actually train (loss decreases).

Tolerance contract (documented in docs/performance.md):
- step-1 loss (identical params, pure forward numerics): within 2% of bf16
- every later step (trajectories compound the rounding): within 15% + 0.05
- both runs strictly decrease loss over the 6 steps
The run is deterministic (fixed data/init seeds, single CPU-mesh process),
so these are regression bounds, not statistical ones.
"""

from __future__ import annotations

import numpy as np
import pytest

pytestmark = pytest.mark.full


def _short_train(dtype: str, steps: int = 6) -> list:
    from horovod_tpu.testing.steps import build_step

    step, state, static = build_step(
        "resnet18", dtype, batch_size=2, image_size=32
    )
    carry, const = state[:3], state[3:]
    losses = []
    for _ in range(steps):
        *carry, loss = step(*carry, *const)
        losses.append(float(loss))
    return losses


def test_fp8_tracks_bf16_loss():
    losses_bf16 = _short_train("bf16")
    losses_fp8 = _short_train("fp8")
    # both runs actually train
    assert losses_bf16[-1] < losses_bf16[0]
    assert losses_fp8[-1] < losses_fp8[0]
    # step 1: same params on both runs, so the gap is pure e4m3
    # activation-storage rounding in the forward pass — tight bound
    assert abs(losses_fp8[0] - losses_bf16[0]) <= 0.02 * abs(losses_bf16[0]), (
        f"fp8 forward numerics off: {losses_fp8[0]} vs {losses_bf16[0]}"
    )
    # later steps: trajectories compound the rounding — loose bound
    for b, f in zip(losses_bf16[1:], losses_fp8[1:]):
        assert np.isfinite(f)
        assert abs(f - b) <= 0.15 * abs(b) + 0.05, (
            f"fp8 loss {f} diverged from bf16 loss {b} "
            f"(series fp8={losses_fp8}, bf16={losses_bf16})"
        )


def _short_gpt_train(dtype: str, steps: int = 6) -> list:
    from horovod_tpu.testing.steps import build_gpt_step

    step, state, static = build_gpt_step(
        "nano", dtype, batch_size=2, seq_len=64, attention="reference"
    )
    *carry, const = state
    losses = []
    for _ in range(steps):
        *carry, loss = step(*carry, const)
        losses.append(float(loss))
    return losses


def test_gpt_fp8_tracks_bf16_loss():
    """The transformer act-storage path (attention context, branch
    deltas, gelu intermediate at e4m3 — models/transformer.py act_store)
    under the same contract as the ResNet path: step-1 within 2%, later
    steps within 15% + 0.05, both runs strictly decrease."""
    losses_bf16 = _short_gpt_train("bf16")
    losses_fp8 = _short_gpt_train("fp8")
    assert losses_bf16[-1] < losses_bf16[0]
    assert losses_fp8[-1] < losses_fp8[0]
    assert abs(losses_fp8[0] - losses_bf16[0]) <= 0.02 * abs(losses_bf16[0]), (
        f"gpt fp8 forward numerics off: {losses_fp8[0]} vs {losses_bf16[0]}"
    )
    for b, f in zip(losses_bf16[1:], losses_fp8[1:]):
        assert np.isfinite(f)
        assert abs(f - b) <= 0.15 * abs(b) + 0.05, (
            f"gpt fp8 loss {f} diverged from bf16 loss {b} "
            f"(series fp8={losses_fp8}, bf16={losses_bf16})"
        )


def test_moe_expert_ffn_act_store():
    """The MoE leg of fp8 act storage: the expert gelu intermediate
    quantizes through the same e4m3 round-trip (the combination
    --moe-experts + --dtype fp8 must not silently run bf16 experts)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.parallel.moe import init_moe_params, moe_mlp

    x = jnp.asarray(
        np.random.RandomState(0).randn(2, 8, 16), jnp.float32
    )
    params = init_moe_params(jax.random.PRNGKey(0), 16, 64, 4)
    y_bf16, _ = moe_mlp(x, params, top_k=2, dtype=jnp.float32)
    y_fp8, _ = moe_mlp(x, params, top_k=2, dtype=jnp.float32,
                       act_store_dtype=jnp.float8_e4m3fn)
    assert np.isfinite(np.asarray(y_fp8)).all()
    # quantization must actually change the values (the knob is live)...
    assert not np.allclose(np.asarray(y_fp8), np.asarray(y_bf16))
    # ...but only by e4m3 rounding of the gelu intermediate
    np.testing.assert_allclose(
        np.asarray(y_fp8), np.asarray(y_bf16), atol=0.15, rtol=0.15
    )
