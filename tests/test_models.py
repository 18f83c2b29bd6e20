"""Model zoo tests, the vision models: shapes, dtypes, trainability,
SyncBatchNorm variant.  (The GPT cases are in ``tests/test_models_gpt.py``,
the graft entry's in ``tests/test_graft_entry.py``.)"""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu import models


def test_convnet_and_mlp_shapes():
    x = jnp.ones((4, 28, 28, 1))
    for model in (models.ConvNet(), models.MLP()):
        params = jax.jit(model.init)(jax.random.PRNGKey(0), x)
        out = jax.jit(model.apply)(params, x)
        assert out.shape == (4, 10)


def _init(model, x, train):
    """The model's variables, traced once and not run op by op."""
    return jax.jit(lambda key: model.init(key, x, train=train))(
        jax.random.PRNGKey(0))


def test_resnet18_forward_backward():
    model = models.ResNet18(num_classes=10)
    x = jnp.ones((2, 32, 32, 3))
    variables = _init(model, x, train=True)

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.zeros(2, jnp.int32)
        ).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    assert np.isfinite(float(loss))
    norms = jax.tree_util.tree_map(lambda g: float(jnp.abs(g).max()), grads)
    assert any(v > 0 for v in jax.tree_util.tree_leaves(norms))


def test_resnet50_structure():
    model = models.ResNet50(num_classes=1000)
    x = jnp.ones((1, 64, 64, 3))
    variables = _init(model, x, train=False)
    out = jax.jit(lambda v: model.apply(v, x, train=False))(variables)
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
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), x, train=False))
    for leaf in jax.tree_util.tree_leaves(variables["params"]):
        assert leaf.dtype == jnp.float32


def test_resnet_s2d_stem_matches_shapes():
    """The space-to-depth stem (MLPerf TPU recipe) is architecturally
    equivalent: same output shape, same downstream stage geometry."""
    x = jnp.ones((2, 64, 64, 3))
    base = models.ResNet18(num_classes=10)
    s2d = models.ResNet18(num_classes=10, s2d_stem=True)
    vb = _init(base, x, train=False)
    vs = _init(s2d, x, train=False)
    for model, v in ((base, vb), (s2d, vs)):
        assert jax.jit(lambda v: model.apply(v, x, train=False))(
            v).shape == (2, 10)
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
    variables = _init(model, x, train=True)

    def loss_fn(p):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.zeros(2, jnp.int32)
        ).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
    assert np.isfinite(float(loss))
    assert any(
        float(jnp.abs(g).max()) > 0
        for g in jax.tree_util.tree_leaves(grads)
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
    variables = _init(model, x, train=True)
    assert "batch_stats" not in variables
    logits = jax.jit(lambda v: model.apply(v, x, train=True))(variables)
    assert logits.shape == (2, 10) and logits.dtype == jnp.float32

    def loss_fn(p):
        out = model.apply({"params": p}, x, train=True)
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.asarray([1, 2])
        ).mean()

    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables["params"])
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
    variables = _init(model, x, train=True)
    assert "batch_stats" in variables

    def loss_fn(p):
        out, mutated = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]},
            x, train=True, mutable=["batch_stats"],
        )
        return optax.softmax_cross_entropy_with_integer_labels(
            out, jnp.asarray([1, 2])
        ).mean(), mutated["batch_stats"]

    (loss, new_stats), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(variables["params"])
    assert np.isfinite(float(loss))
    assert jax.tree.leaves(new_stats)
    assert all(np.all(np.isfinite(g)) for g in jax.tree.leaves(grads))
    # eval mode runs with frozen stats
    out = jax.jit(lambda v: model.apply(v, x, train=False))(variables)
    assert out.shape == (2, 10)
