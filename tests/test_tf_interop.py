"""horovod.tensorflow-compatible interop frontend (reference surface:
test/test_tensorflow.py — op correctness, gradients, DistributedOptimizer,
DistributedGradientTape, IndexedSlices sparse path; single-process
identities here, real 2-process semantics in test_multiprocess.py)."""

import importlib.util

import numpy as np
import pytest

if importlib.util.find_spec("tensorflow") is None:
    pytest.importorskip("tensorflow")  # one skip for the module, as ever

tf = hvd = None  # ``_tensorflow`` binds them: see there


@pytest.fixture(scope="module", autouse=True)
def _tensorflow():
    # TensorFlow is imported here and not at the top: every xdist worker
    # imports every test file, and the twenty seconds its import takes
    # belong to the one worker that runs this file.  Nothing outside a
    # test's body (or a helper a test calls) may name ``tf`` or ``hvd``.
    global tf, hvd
    import tensorflow as tf

    import horovod_tpu.interop.tf as hvd


@pytest.fixture(autouse=True)
def _init():
    # conftest's session fixture owns the framework lifecycle; don't
    # shutdown here or later test files lose the initialized topology.
    hvd.init()
    yield


def test_allreduce_identity_single_process():
    x = tf.reshape(tf.range(6, dtype=tf.float32), (2, 3))
    out = hvd.allreduce(x)
    assert isinstance(out, tf.Tensor)
    np.testing.assert_allclose(out.numpy(), x.numpy())


def test_allreduce_sum_bf16_roundtrip():
    x = tf.ones((8,), dtype=tf.bfloat16)
    out = hvd.allreduce(x, op=hvd.Sum)
    assert out.dtype == tf.bfloat16
    np.testing.assert_allclose(tf.cast(out, tf.float32).numpy(), np.ones(8))


def test_allreduce_inside_tf_function():
    # py_function keeps the engine call graph-safe (reference runs these
    # as TF graph ops, tensorflow/mpi_ops.cc).
    @tf.function
    def fn(x):
        return hvd.allreduce(x, op=hvd.Sum)

    out = fn(tf.constant([1.0, 2.0]))
    np.testing.assert_allclose(out.numpy(), [1.0, 2.0])


def test_allreduce_indexed_slices_allgathers():
    # reference tensorflow/__init__.py:74-89: IndexedSlices -> allgather
    # of values and indices.
    slices = tf.IndexedSlices(
        values=tf.constant([[1.0, 2.0], [3.0, 4.0]]),
        indices=tf.constant([0, 3], dtype=tf.int64),
        dense_shape=tf.constant([5, 2], dtype=tf.int64),
    )
    out = hvd.allreduce(slices, op=hvd.Average)
    assert isinstance(out, tf.IndexedSlices)
    np.testing.assert_allclose(out.values.numpy(), [[1.0, 2.0], [3.0, 4.0]])
    np.testing.assert_array_equal(out.indices.numpy(), [0, 3])


def test_allreduce_grad_is_allreduced():
    x = tf.Variable([1.0, 2.0, 3.0])
    with tf.GradientTape() as tape:
        y = tf.reduce_sum(hvd._allreduce(x, op=hvd.Sum))
    grad = tape.gradient(y, x)
    np.testing.assert_allclose(grad.numpy(), np.ones(3))


def test_allgather_and_grad():
    x = tf.Variable(np.random.randn(2, 3).astype(np.float32))
    with tf.GradientTape() as tape:
        g = hvd.allgather(x)
        loss = tf.reduce_sum(g)
    assert g.shape == (2, 3)
    grad = tape.gradient(loss, x)
    np.testing.assert_allclose(grad.numpy(), np.ones((2, 3)))


def test_broadcast_grad_root():
    x = tf.Variable(np.random.randn(4).astype(np.float32))
    with tf.GradientTape() as tape:
        y = tf.reduce_sum(hvd.broadcast(x, root_rank=0))
    grad = tape.gradient(y, x)
    # rank 0 IS the root in a single-process world: grads arrive summed
    np.testing.assert_allclose(grad.numpy(), np.ones(4))


def test_broadcast_variables_assigns():
    v = tf.Variable([5.0, 6.0])
    hvd.broadcast_variables([v], root_rank=0)
    np.testing.assert_allclose(v.numpy(), [5.0, 6.0])


def test_distributed_gradient_tape():
    x = tf.Variable(3.0)
    with hvd.DistributedGradientTape(tf.GradientTape()) as tape:
        y = x * x
    grad = tape.gradient(y, x)
    np.testing.assert_allclose(float(grad), 6.0)


def test_distributed_keras_optimizer_applies():
    try:
        opt = tf.keras.optimizers.SGD(learning_rate=0.5)
    except Exception:
        pytest.skip("keras optimizers unavailable")
    dopt = hvd.DistributedOptimizer(opt)
    assert type(dopt).__name__.startswith("Distributed")
    v = tf.Variable(2.0)
    dopt.apply_gradients([(tf.constant(1.0), v)])
    np.testing.assert_allclose(float(v), 1.5)


def test_distributed_legacy_optimizer_wrap():
    try:
        base = tf.compat.v1.train.GradientDescentOptimizer(0.1)
    except AttributeError:
        pytest.skip("tf.compat.v1 unavailable")
    dopt = hvd.DistributedOptimizer(base)
    assert dopt.get_slot_names() == base.get_slot_names()


def test_adasum_optimizer_single_process_delta_step():
    """op=Adasum diverts to the delta-reducing wrapper (reference factory
    tensorflow/__init__.py:453-459); world 1 applies the local update.
    A Keras optimizer yields a real Keras subclass so model.compile
    accepts it."""
    v = tf.Variable([1.0, 2.0])
    opt = hvd.DistributedOptimizer(
        tf.keras.optimizers.SGD(learning_rate=0.1), op=hvd.Adasum
    )
    assert type(opt).__name__ == "AdasumSGD"
    assert isinstance(opt, tf.keras.optimizers.SGD)
    opt.apply_gradients([(tf.constant([1.0, 2.0]), v)])
    np.testing.assert_allclose(v.numpy(), [0.9, 1.8], rtol=1e-6)
    # slot-style start buffer exists per variable
    assert len(opt._hvd_starts) == 1


def test_adasum_keras_optimizer_works_in_model_compile():
    """The Adasum wrapper must survive Keras's optimizer validation in
    model.compile + fit (existing user flow, not just apply_gradients)."""
    model = tf.keras.Sequential(
        [tf.keras.Input(shape=(2,)),
         tf.keras.layers.Dense(1, use_bias=False)]
    )
    model.compile(
        optimizer=hvd.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=0.1), op=hvd.Adasum
        ),
        loss="mse",
    )
    x = np.ones((8, 2), np.float32)
    y = np.zeros((8, 1), np.float32)
    hist = model.fit(x, y, epochs=1, batch_size=4, verbose=0)
    assert np.isfinite(hist.history["loss"][0])


def test_allreduce_dtype_dims_grid():
    """Reference test_tensorflow.py pattern: allreduce across dtype x
    dimensionality preserves dtype/shape/values (world 1 identities)."""
    dtypes = [tf.float32, tf.float64, tf.float16, tf.bfloat16,
              tf.int32, tf.int64]
    for dt in dtypes:
        for dim in (1, 2, 3):
            shape = (2,) * dim
            x = tf.cast(
                tf.reshape(tf.range(2 ** dim) % 3, shape), dt
            )
            op = hvd.Sum if not dt.is_floating else hvd.Average
            out = hvd.allreduce(x, op=op)
            assert out.dtype == dt, (dt, dim)
            assert tuple(out.shape) == shape, (dt, dim)
            np.testing.assert_allclose(
                tf.cast(out, tf.float64).numpy(),
                tf.cast(x, tf.float64).numpy(),
            )


def test_compression_fp16_roundtrip():
    x = tf.constant([1.0, 2.0, 3.0])
    c, ctx = hvd.Compression.fp16.compress(x)
    assert c.dtype == tf.float16
    out = hvd.Compression.fp16.decompress(c, ctx)
    assert out.dtype == tf.float32
    np.testing.assert_allclose(out.numpy(), x.numpy())


def test_alltoall_single_process_identity():
    x = tf.constant(np.arange(4, dtype=np.float32))
    out = hvd.alltoall(x)
    np.testing.assert_allclose(out.numpy(), x.numpy())


def test_feature_probes_answer():
    assert hvd.size() >= 1
    assert isinstance(hvd.gloo_built(), bool)
    assert isinstance(hvd.mpi_built(), bool)


# ---------------------------------------------------------------------------
# Keras frontend (reference horovod.tensorflow.keras; VERDICT r2 item 7)
# ---------------------------------------------------------------------------


def _tiny_model(lr=0.1):
    import tensorflow as tf

    import horovod_tpu.interop.tf_keras as hvk

    model = tf.keras.Sequential(
        [tf.keras.Input(shape=(2,)),
         tf.keras.layers.Dense(1, use_bias=False)]
    )
    model.compile(
        optimizer=hvk.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=lr)
        ),
        loss="mse",
    )
    return model


def test_keras_fit_with_callbacks_single_process():
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.interop.tf_keras as hvk

    x = np.random.RandomState(0).randn(32, 2).astype(np.float32)
    y = (x @ np.asarray([[1.0], [2.0]], np.float32)).astype(np.float32)
    model = _tiny_model()
    hist = model.fit(
        x, y, epochs=2, batch_size=8, verbose=0,
        callbacks=[
            hvk.callbacks.BroadcastGlobalVariablesCallback(0),
            hvk.callbacks.MetricAverageCallback(),
            # no steps_per_epoch: must auto-fill from Keras's fit params
            hvk.callbacks.LearningRateWarmupCallback(
                initial_lr=0.1, warmup_epochs=2
            ),
        ],
    )
    assert "loss" in hist.history
    assert hist.history["loss"][-1] < hist.history["loss"][0]
    # warmup ramps toward initial_lr (world==1: multiplier is 1 throughout)
    assert abs(hvk._lr_value(model.optimizer) - 0.1) < 1e-6


def test_keras_lr_schedule_staircase():
    import numpy as np

    import horovod_tpu.interop.tf_keras as hvk

    x = np.zeros((8, 2), np.float32)
    y = np.zeros((8, 1), np.float32)
    model = _tiny_model(lr=1.0)
    cb = hvk.callbacks.LearningRateScheduleCallback(
        initial_lr=1.0, multiplier=lambda epoch: 0.5 ** epoch
    )
    hist = model.fit(x, y, epochs=3, batch_size=8, verbose=0, callbacks=[cb])
    # epoch e runs at lr = 0.5^e; logs record it
    assert hist.history["lr"] == [1.0, 0.5, 0.25]


def test_keras_load_model_rewraps_optimizer(tmp_path):
    import numpy as np

    import horovod_tpu.interop.tf_keras as hvk

    x = np.random.RandomState(0).randn(16, 2).astype(np.float32)
    y = np.zeros((16, 1), np.float32)
    model = _tiny_model()
    model.fit(x, y, epochs=1, batch_size=8, verbose=0)
    path = str(tmp_path / "model.keras")
    model.save(path)
    restored = hvk.load_model(path)
    assert getattr(restored.optimizer, "_hvd_wrapped", False), (
        "load_model must return a model whose optimizer is re-wrapped in "
        "DistributedOptimizer (reference _keras/__init__.py:113-128)"
    )
    restored.fit(x, y, epochs=1, batch_size=8, verbose=0)  # still trains


def test_keras_load_model_restores_adasum_wrap(tmp_path):
    """A model compiled with op=Adasum serializes its optimizer as
    'AdasumSGD'; load_model must deserialize it back into the delta
    wrapper and keep training."""
    import horovod_tpu.interop.tf_keras as hvk

    x = np.random.RandomState(0).randn(16, 2).astype(np.float32)
    y = np.zeros((16, 1), np.float32)
    model = tf.keras.Sequential(
        [tf.keras.Input(shape=(2,)),
         tf.keras.layers.Dense(1, use_bias=False)]
    )
    model.compile(
        optimizer=hvd.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=0.1), op=hvd.Adasum
        ),
        loss="mse",
    )
    model.fit(x, y, epochs=1, batch_size=8, verbose=0)
    path = str(tmp_path / "adasum.keras")
    model.save(path)
    restored = hvk.load_model(path)
    assert type(restored.optimizer).__name__ == "AdasumSGD"
    assert getattr(restored.optimizer, "_hvd_wrapped", False)
    restored.fit(x, y, epochs=1, batch_size=8, verbose=0)


def test_keras_warmup_momentum_correction_restores():
    import numpy as np
    import tensorflow as tf

    import horovod_tpu.interop.tf_keras as hvk

    x = np.zeros((16, 2), np.float32)
    y = np.zeros((16, 1), np.float32)
    model = tf.keras.Sequential(
        [tf.keras.Input(shape=(2,)),
         tf.keras.layers.Dense(1, use_bias=False)]
    )
    model.compile(
        optimizer=hvk.DistributedOptimizer(
            tf.keras.optimizers.SGD(learning_rate=0.1, momentum=0.9)
        ),
        loss="mse",
    )
    model.fit(
        x, y, epochs=2, batch_size=8, verbose=0,
        callbacks=[hvk.callbacks.LearningRateWarmupCallback(
            initial_lr=0.1, warmup_epochs=2
        )],
    )
    # per-batch LR changes temporarily rescale momentum (Goyal et al.
    # correction) and must restore it after every batch
    assert abs(float(model.optimizer.momentum) - 0.9) < 1e-9
