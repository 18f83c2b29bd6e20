"""Model zoo tests, the GPT cases: a rematerialised block against a kept
one, grouped-query heads, the position guards.  (Moved whole from
``tests/test_models.py``.)"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import remat_cases  # noqa: E402


def _traced(mixer):
    """``jax.jit`` for the float32 models.  The reference attention's
    nano is bfloat16, where the compiler fuses the recomputed block
    otherwise than the kept one and the pair differs by 2.4e-4 at
    ``atol=1e-6``: that pair runs op by op, where both sides round alike."""
    return (lambda f: f) if mixer == "reference" else jax.jit


@functools.lru_cache(maxsize=None)
def _no_remat(mixer):
    loss, params = remat_cases.build(mixer)
    return _traced(mixer)(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("policy", remat_cases.POLICIES)
@pytest.mark.parametrize("mixer", sorted(remat_cases.MIXERS))
def test_gpt_remat_matches_no_remat(mixer, policy):
    """cfg.remat=True is a pure memory/compute trade, whatever the
    blocks' mixer (the reference attention, the flash kernels, latent
    attention with a prediction module, Mamba-2) and whichever policy
    says what else a block keeps beside its kernels' outputs: loss AND
    gradients must match the non-remat model on the same params."""
    import jax
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import remat_cases

    l0, g0 = _no_remat(mixer)
    rematted, params = remat_cases.build(mixer, remat=True, policy=policy)
    l1, g1 = _traced(mixer)(jax.value_and_grad(rematted))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1,
    )


def test_gpt_gqa_trains():
    """num_kv_heads < num_heads (GQA): model builds, the qkv projection
    shrinks accordingly, flash and reference impls agree."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.transformer import gpt

    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 1024, size=(2, 32)), jnp.int32
    )
    import pytest
    with pytest.raises(ValueError, match="multiple of num_kv_heads"):
        gpt("nano", num_kv_heads=3)  # 4 % 3 != 0 -> fail at config time
    with pytest.raises(ValueError, match="multiple of num_kv_heads"):
        gpt("nano", num_kv_heads=0)
    flash = gpt("nano", num_kv_heads=2, dtype=jnp.float32)  # 4 q, 2 kv heads
    ref = gpt("nano", num_kv_heads=2, dtype=jnp.float32,
              attention_impl="reference")
    params = jax.jit(flash.init)(jax.random.PRNGKey(0), tokens)
    # qkv projection: emb + 2 * kv_dim = 128 + 2*64 = 256 (not 3*128)
    assert params["params"]["block0"]["qkv"]["kernel"].shape == (128, 256)

    def loss(model, p):
        logits = model.apply(p, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens
        ).mean()

    lf, gf = jax.jit(jax.value_and_grad(lambda p: loss(flash, p)))(params)
    lr, gr = jax.jit(jax.value_and_grad(lambda p: loss(ref, p)))(params)
    np.testing.assert_allclose(float(lf), float(lr), rtol=5e-5, atol=5e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4),
        gf, gr,
    )


def test_transformer_position_guards():
    """Layout misuse fails loudly: zigzag without explicit positions
    raises at trace time; an out-of-range learned position poisons the
    output with NaN instead of silently reusing the clamped last row."""
    from horovod_tpu.models.transformer import gpt

    tokens = jnp.zeros((1, 8), jnp.int32)
    zz = gpt("nano", attention_impl="zigzag", sp_axis="sp")
    with pytest.raises(ValueError, match="requires explicit positions"):
        zz.init(jax.random.PRNGKey(0), tokens)

    m = gpt("nano", attention_impl="reference", dtype=jnp.float32)
    params = jax.jit(m.init)(jax.random.PRNGKey(0), tokens)
    bad_positions = jnp.arange(8) + 255  # nano max_len=256 -> 255..262
    out = m.apply(params, tokens, positions=bad_positions)
    assert not np.isfinite(np.asarray(out)).all(), \
        "out-of-range position did not poison the output"
