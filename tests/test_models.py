"""Model zoo tests: shapes, dtypes, trainability, SyncBatchNorm variant."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import remat_cases  # noqa: E402

from horovod_tpu import models  # noqa: E402


def test_convnet_and_mlp_shapes():
    x = jnp.ones((4, 28, 28, 1))
    for model in (models.ConvNet(), models.MLP()):
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (4, 10)


def test_resnet18_forward_backward():
    model = models.ResNet18(num_classes=10)
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.zeros(2, jnp.int32)
        ).mean()

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert np.isfinite(float(loss))
    norms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).max()), grads)
    assert any(v > 0 for v in jax.tree_util.tree_leaves(norms))


def test_resnet50_structure():
    model = models.ResNet50(num_classes=1000)
    x = jnp.ones((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (1, 1000)
    assert out.dtype == jnp.float32  # head in fp32 even under bf16 compute
    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(variables["params"])
    )
    # canonical resnet50 parameter count ~25.5M
    assert 25_000_000 < n_params < 26_000_000, n_params


def test_resnet_bf16_compute_fp32_params():
    model = models.ResNet18(num_classes=10, compute_dtype=jnp.bfloat16)
    x = jnp.ones((1, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    for leaf in jax.tree_util.tree_leaves(variables["params"]):
        assert leaf.dtype == jnp.float32


def test_resnet_s2d_stem_matches_shapes():
    """The space-to-depth stem (MLPerf TPU recipe) is architecturally
    equivalent: same output shape, same downstream stage geometry."""
    x = jnp.ones((2, 64, 64, 3))
    base = models.ResNet18(num_classes=10)
    s2d = models.ResNet18(num_classes=10, s2d_stem=True)
    vb = base.init(jax.random.PRNGKey(0), x, train=False)
    vs = s2d.init(jax.random.PRNGKey(0), x, train=False)
    assert base.apply(vb, x, train=False).shape == (2, 10)
    assert s2d.apply(vs, x, train=False).shape == (2, 10)
    # stem conv consumes the folded 12-channel input at stride 1
    assert vs["params"]["conv_init"]["kernel"].shape == (4, 4, 12, 64)
    # every non-stem layer is unchanged
    for k in vb["params"]:
        if k != "conv_init":
            assert (
                jax.tree_util.tree_map(
                    lambda p: p.shape, vb["params"][k]
                )
                == jax.tree_util.tree_map(
                    lambda p: p.shape, vs["params"][k]
                )
            ), k


def test_resnet_fp8_activation_storage_trains():
    """act_store_dtype=float8_e4m3fn: forward/backward stay finite and
    produce nonzero grads — the lossy storage is numerically viable."""
    model = models.ResNet18(
        num_classes=10,
        compute_dtype=jnp.bfloat16,
        act_store_dtype=jnp.float8_e4m3fn,
    )
    x = jnp.ones((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=True)

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.zeros(2, jnp.int32)
        ).mean()

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert np.isfinite(float(loss))
    assert any(
        float(jnp.abs(g).max()) > 0
        for g in jax.tree_util.tree_leaves(grads)
    )


def test_graft_entry_single_device():
    import __graft_entry__ as g

    fn, example = g.entry()
    out = jax.jit(fn)(*example)
    assert out.shape == (8, 1000)


@pytest.mark.multiprocess
def test_graft_entry_dryrun_multichip():
    import __graft_entry__ as g

    g.dryrun_multichip(8)


@functools.lru_cache(maxsize=None)
def _no_remat(mixer):
    loss, params = remat_cases.build(mixer)
    return jax.value_and_grad(loss)(params)


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
    l1, g1 = jax.value_and_grad(rematted)(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1,
    )


def test_vgg16_forward_backward():
    """VGG-16 (reference headline family, benchmarks.rst:13-14): forward
    shape, fp32 logits from bf16 compute, finite grads; no BN state."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import VGG16

    model = VGG16(num_classes=10)
    x = jnp.ones((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    assert "batch_stats" not in variables
    logits = model.apply(variables, x, train=True)
    assert logits.shape == (2, 10) and logits.dtype == jnp.float32

    def loss_fn(p):
        out = model.apply({"params": p}, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.asarray([1, 2])
        ).mean()

    loss, grads = jax.value_and_grad(loss_fn)(variables["params"])
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves and all(np.all(np.isfinite(g)) for g in leaves)


def test_inception_v3_forward_backward():
    """Inception V3 (the reference's top headline model): canonical branch
    concatenation geometry trains on a small input; BN stats mutate."""
    import jax
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models import InceptionV3

    model = InceptionV3(num_classes=10)
    x = jnp.ones((2, 96, 96, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=True)
    assert "batch_stats" in variables

    def loss_fn(p):
        out, mutated = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.asarray([1, 2])
        ).mean(), mutated["batch_stats"]

    (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        variables["params"]
    )
    assert np.isfinite(float(loss))
    assert jax.tree.leaves(new_stats)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))
    # eval mode runs with frozen stats
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


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
    params = flash.init(jax.random.PRNGKey(0), tokens)
    # qkv projection: emb + 2 * kv_dim = 128 + 2*64 = 256 (not 3*128)
    assert params["params"]["block0"]["qkv"]["kernel"].shape == (128, 256)

    def loss(model, p):
        logits = model.apply(p, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens
        ).mean()

    lf, gf = jax.value_and_grad(lambda p: loss(flash, p))(params)
    lr, gr = jax.value_and_grad(lambda p: loss(ref, p))(params)
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
    params = m.init(jax.random.PRNGKey(0), tokens)
    bad_positions = jnp.arange(8) + 255  # nano max_len=256 -> 255..262
    out = m.apply(params, tokens, positions=bad_positions)
    assert not np.isfinite(np.asarray(out)).all(), \
        "out-of-range position did not poison the output"
