"""The program names its own time (ISSUE 24): kernel and scope names in
the lowered step, the compile log, the program's trace reduction and
the serving rank's profiler hook.  All on the CPU; the real profiler
runs only on the chip, so the serving tests hand the rank a recording.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models.resnet import ResNet18, ResNet50
from horovod_tpu.models.transformer import gpt
from horovod_tpu.obs import profile
from horovod_tpu.obs import trace as obs_trace
from horovod_tpu.obs.registry import get_registry, reset_registry
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.ops.collectives import shard_map_compat
from horovod_tpu.utils import env as envmod
from horovod_tpu.utils.compile_cache import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# names in the lowered step
# ---------------------------------------------------------------------------

def _distributed(local_step, n_args):
    specs = tuple([P()] * (n_args - 1) + [P(hvd.DP_AXIS)])
    return jax.jit(shard_map_compat(
        local_step, mesh=hvd.mesh("flat"), in_specs=specs, out_specs=P()))


@pytest.fixture(scope="module")
def gpt_step():
    """A tiny GPT step as a user writes it, with its example arguments."""
    model = gpt("nano", num_layers=1, vocab_size=256, max_len=64,
                attention_impl="flash")
    tokens = jnp.zeros((8, 64), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    tx = hvd.DistributedOptimizer(optax.adamw(1e-3))

    def loss_fn(p, t):
        logits = model.apply(p, t)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean()

    def local_step(p, o, t):
        loss, grads = jax.value_and_grad(loss_fn)(p, t)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    return _distributed(local_step, 3), (params, tx.init(params), tokens)


@pytest.fixture(scope="module")
def resnet_step():
    model = ResNet18(num_classes=10, num_filters=8)
    images = jnp.zeros((8, 32, 32, 3), jnp.float32)
    labels = jnp.zeros((8,), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), images[:1], train=False)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9))

    def local_step(p, stats, o, x, y):
        def loss_fn(p):
            logits, new = model.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean(), new["batch_stats"]

        (loss, stats2), grads = jax.value_and_grad(loss_fn,
                                                   has_aux=True)(p)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), stats2, o, loss

    specs = (P(), P(), P(), P(hvd.DP_AXIS), P(hvd.DP_AXIS))
    step = jax.jit(shard_map_compat(
        local_step, mesh=hvd.mesh("flat"), in_specs=specs, out_specs=P()))
    p = variables["params"]
    return step, (p, variables["batch_stats"], tx.init(p), images, labels)


def _locations(step, args):
    """Every name stack in the lowered text's locations."""
    text = step.lower(*args).as_text(debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.fixture(scope="module")
def gpt_locations(gpt_step):
    return _locations(*gpt_step)


@pytest.fixture(scope="module")
def resnet_locations(resnet_step):
    return _locations(*resnet_step)


def _has(locations, *path):
    want = "/" + "/".join(path) + "/"
    return any(want in "/" + loc + "/" for loc in locations)


@pytest.mark.parametrize("path", [
    ("grad_allreduce", "allreduce"),   # the reduction inside its packing
    ("optimizer_update",),
    ("block0", "attn", "qkv"),         # a scope names no module: the
    ("block0", "attn", "flash_fwd"),   # flax names sit inside it
    ("block0", "mlp", "fc1"),
    ("embed", "wte"),
    ("head", "lnf"),
])
def test_gpt_step_carries_each_scope(gpt_locations, path):
    assert _has(gpt_locations, *path), path
    # and the backward pass carries the same names, transposed
    if path[0].startswith("block"):
        back = "/".join(path).replace("flash_fwd", "flash_bwd_dkdv")
        assert any(loc.startswith("transpose(") and back in loc
                   for loc in gpt_locations), back


@pytest.mark.parametrize("path", [
    ("grad_allreduce", "allreduce"),
    ("optimizer_update",),
    ("stem", "conv_init"),
    ("stem", "reduce_window_max"),      # the max-pool: the model's own
    ("stage1_block1", "conv1"),         # flax names the stages
    ("stage4_block2", "bn2"),
    ("head", "head"),                   # the scope, then the module
    ("head", "reduce_sum"),             # the global mean under it
])
def test_resnet_step_carries_each_scope(resnet_locations, path):
    assert _has(resnet_locations, *path), path


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def test_step_holds_its_named_pallas_calls(gpt_step):
    """Forward, and the one backward kernel under the name it kept."""
    step, args = gpt_step
    names = _pallas_names(jax.make_jaxpr(step)(*args).jaxpr, [])
    assert sorted(names) == ["flash_bwd_dkdv", "flash_fwd"]


def _paths(tree):
    return sorted("/".join(str(getattr(k, "key", k)) for k in path)
                  for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0])


def test_gpt_parameter_tree_is_letter_for_letter_the_same():
    """No scope became a module: a checkpoint's keys are what they were."""
    model = gpt("nano")  # 3 layers, learned positions
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    block = ["fc1/bias", "fc1/kernel", "fc2/bias", "fc2/kernel",
             "ln1/bias", "ln1/scale", "ln2/bias", "ln2/scale",
             "proj/bias", "proj/kernel", "qkv/bias", "qkv/kernel"]
    want = [f"params/block{i}/{leaf}" for i in range(3) for leaf in block]
    want += ["params/head/kernel", "params/lnf/bias", "params/lnf/scale",
             "params/wpe", "params/wte/embedding"]
    assert _paths(shapes) == sorted(want)


def test_resnet50_parameter_tree_is_letter_for_letter_the_same():
    model = ResNet50(num_classes=1000)
    shapes = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 32, 32, 3)), train=False),
        jax.random.PRNGKey(0))
    want = []
    for kind, leaves in (("params", {"conv": ["kernel"],
                                     "bn": ["bias", "scale"]}),
                         ("batch_stats", {"conv": [], "bn": ["mean", "var"]})):
        def put(module, what):
            want.extend(f"{kind}/{module}/{leaf}" for leaf in leaves[what])
        put("conv_init", "conv")
        put("bn_init", "bn")
        for stage, blocks in enumerate([3, 4, 6, 3], start=1):
            for b in range(1, blocks + 1):
                name = f"stage{stage}_block{b}"
                for i in (1, 2, 3):
                    put(f"{name}/conv{i}", "conv")
                    put(f"{name}/bn{i}", "bn")
                if b == 1:
                    put(f"{name}/proj_conv", "conv")
                    put(f"{name}/proj_bn", "bn")
    want += ["params/head/bias", "params/head/kernel"]
    assert _paths(shapes) == sorted(want)


@pytest.mark.parametrize("vmem_limit, names", [
    (None, ["flash_bwd_dkdv", "flash_fwd"]),           # one backward kernel
    (0, ["flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]),      # two passes
], ids=["one_kernel", "two_passes"])
def test_named_flash_kernels_are_bitwise_the_unnamed_ones(
        monkeypatch, vmem_limit, names):
    """A kernel's name is metadata: forward, dq, dk and dv in interpret
    mode are bit for bit what the unnamed ``pallas_call`` gives, on both
    backward paths."""
    if vmem_limit is not None:
        monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_LIMIT", vmem_limit)
        jax.clear_caches()
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(2, 64, 4, 16), jnp.float32) * 0.3
               for _ in range(3))

    def run():
        def f(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, block_q=16,
                                     block_k=16)
            return (out ** 2).sum(), out

        (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
        return [np.asarray(a) for a in (out, *grads)]

    named = run()
    real = fa.pl.pallas_call
    seen = []

    def unnamed(*args, name=None, **kwargs):
        seen.append(name)
        return real(*args, **kwargs)

    monkeypatch.setattr(fa.pl, "pallas_call", unnamed)
    jax.clear_caches()
    plain = run()
    assert sorted(seen) == names
    for a, b in zip(named, plain):
        assert a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# the reduction, on plain lists
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name, scope", [
    ("jit(local_step)/jvp(GPT)/block3/attn/qkv/dot_general:", "attn"),
    ("jit(local_step)/transpose(jvp(GPT))/block3/mlp/fc1/dot_general:",
     "transpose(mlp)"),
    ("jit(local_step)/grad_allreduce/allreduce/allreduce/psum:",
     "allreduce"),
    ("jit(local_step)/grad_allreduce/allreduce/concatenate:", "allreduce"),
    ("jit(local_step)/grad_allreduce/convert_element_type:",
     "grad_allreduce"),
    ("jit(local_step)/optimizer_update/sqrt:", "optimizer_update"),
    ("jit(_step)/attn/kv_gather/jit(_take)/gather:", "kv_gather"),
    ("jit(_step)/vmap(sample)/jit(sort)/sort:", "sample"),  # under vmap
    ("jit(_step)/jit(_take)/gather:", "unscoped"),  # a transform's name
    ("pool['k']:", "unscoped"),                     # an argument's copy
    ("jit(_step)/sample/argmax:", "sample"),
    ("jit(step)/jvp(ResNet)/stage2_block1/conv2/conv_general_dilated:",
     "conv2"),                         # no program scope: the module's
    ("jit(step)/transpose(jvp(ResNet))/stem/select_and_scatter_add:",
     "transpose(stem)"),
    ("jit(local_step)/add:", "unscoped"),  # the user's apply_updates
    ("", "unscoped"),
])
def test_scope_of_an_operation(op_name, scope):
    assert profile.scope_of(op_name) == scope


def _synthetic(t0_ns=1_000_000.0):
    """One device: two operations that overlap, a gap, an operation, a
    second gap, an operation.  Busy [0,15] [30,40] [60,70] (ms after
    ``t0_ns``), so the window is 70 ms, busy 35 ms, idle 15 + 20 ms."""
    ms = 1e6
    attn = "jit(step)/jvp(GPT)/block0/attn/qkv/dot_general:"
    ops = [
        ["fusion.1", t0_ns, 10 * ms, attn],
        ["kernel:flash_fwd.2", t0_ns + 5 * ms, 10 * ms,
         "jit(step)/jvp(GPT)/block0/attn/flash_fwd/pallas_call:"],
        ["fusion.3", t0_ns + 30 * ms, 10 * ms,
         "jit(step)/transpose(jvp(GPT))/block0/mlp/fc2/dot_general:"],
        ["kernel:flash_fwd.3", t0_ns + 60 * ms, 10 * ms,
         "jit(step)/jvp(GPT)/block1/attn/flash_fwd/pallas_call:"],
    ]
    return {0: ops}


def test_reduction_unions_and_names_the_gaps():
    """Spans are on a wall clock far from the trace's; the marker says
    that trace time 1 ms is wall time 5000 s."""
    wall = 5000.0
    spans = [
        # the whole step covers both gaps; decode_compute (shorter, so
        # the innermost) covers the first gap and 5 ms of the second
        {"trace": "serve.steps", "name": "step", "t0": wall - 0.001,
         "dur": 0.056},
        {"trace": "serve.steps", "name": "decode_compute",
         "t0": wall + 0.010, "dur": 0.035},
        {"trace": "serve.steps", "name": "finish", "t0": wall, "dur": 0.0},
    ]
    out = profile.reduce_trace(_synthetic(), (1_000_000.0, wall), spans)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.070)
    assert out["busy_s"] == pytest.approx(0.035)     # a union: not 0.040
    assert out["idle_s"] == pytest.approx(0.035)
    # gap 1 is [15,30]: all decode_compute.  Gap 2 is [40,60]: decode_
    # compute to 45, then the step span to 55, then nothing.
    assert out["idle_by_span"] == pytest.approx(
        {"decode_compute": 0.020, "step": 0.010, "uncovered": 0.005})
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert out["by_scope"] == pytest.approx(
        {"attn": 0.025, "transpose(mlp)": 0.010})    # attn: [0,15]+[60,70]
    assert out["by_kernel"] == pytest.approx({"flash_fwd": 0.020})
    assert list(out["by_scope"]) == ["attn", "transpose(mlp)"]  # ranked


def test_reduction_without_a_marker_leaves_every_gap_uncovered():
    out = profile.reduce_trace(_synthetic(), None, [
        {"trace": "t", "name": "step", "t0": 0.0, "dur": 1e9}])
    assert out["idle_by_span"] == pytest.approx({"uncovered": 0.035})
    empty = profile.reduce_trace({}, None, [])
    assert empty["devices"] == 0 and empty["busy_s"] == 0.0


def test_reduction_averages_devices():
    ops = _synthetic()
    ops[1] = [["fusion.1", 1_000_000.0, 70e6, ""]]
    out = profile.reduce_trace(ops, None, [])
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(0.070)
    assert out["busy_s"] == pytest.approx((0.035 + 0.070) / 2)
    assert out["per_device"]["1"]["busy_s"] == pytest.approx(0.070)
    assert out["by_scope"]["unscoped"] == pytest.approx(0.070)


@pytest.mark.parametrize("instruction, kernel", [
    ("flash_fwd.2", "flash_fwd"),
    ("flash_bwd_dkdv", "flash_bwd_dkdv"),      # the first of its name
    ("paged_attn_v2.3", "paged_attn_v2"),      # the kernel's own digits stay
    ("k1.10", "k1"),
])
def test_reduction_keys_a_kernel_by_its_name_without_xlas_number(
        instruction, kernel):
    out = profile.reduce_trace(
        {0: [["kernel:" + instruction, 0.0, 5e6, ""],
             ["kernel:k2.1", 5e6, 5e6, ""]]}, None, [])
    assert out["by_kernel"][kernel] == pytest.approx(0.005)
    assert out["by_kernel"]["k2"] == pytest.approx(0.005)
    assert out["ops"] == 2


def test_every_named_scope_of_the_package_is_a_constant_of_scopes():
    """A scope written as a literal at its call site would be missing
    from SCOPES, and the reduction would file it under a module name."""
    from horovod_tpu import scopes

    # the module's other names are the values a rematerialised block
    # keeps (checkpoint_name, not named_scope): no trace holds them
    constants = {v for k, v in vars(scopes).items()
                 if k.isupper() and isinstance(v, str)}
    assert not set(scopes.KERNEL_OUTPUTS) & set(scopes.SCOPES)
    constants -= set(scopes.KERNEL_OUTPUTS)
    assert constants == set(scopes.SCOPES) == set(profile.SCOPES)
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES)
    used = set()
    for folder, _, files in os.walk(os.path.join(ROOT, "horovod_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                text = f.read()
            for arg in re.findall(r"named_scope\(([^)]*)\)", text):
                if arg in ("scope", ""):       # optim._scoped's parameter,
                    continue                   # prose in a docstring
                assert arg.startswith("scopes."), (name, arg)
                used.add(getattr(scopes, arg[len("scopes."):]))
    used.add(scopes.OPTIMIZER_UPDATE)          # passed to optim._scoped
    assert used == constants


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def test_read_xplane_decodes_the_fields_it_needs(tmp_path):
    """A hand-built XSpace: one TPU plane whose operation's scope is a
    metadata stat, one host plane with the clock marker."""
    def entry(key, message):
        return _field(1, key) + _field(2, message)

    def event(md, offset_ps, dur_ps, stats=b""):
        return _field(1, md) + _field(2, offset_ps) + _field(3, dur_ps) \
            + stats

    text = ('%flash_fwd.2 = (bf16[8]) custom-call(bf16[8] %x), '
            'custom_call_target="tpu_custom_call"')
    device = (
        _field(2, "/device:TPU:0")
        + _field(3, _field(2, "XLA Ops") + _field(3, 100)
                 + _field(4, event(7, 2_000_000, 5_000_000))
                 + _field(4, event(8, 9_000_000, 1_000_000)))
        + _field(3, _field(2, "Steps") + _field(4, event(7, 0, 1)))
        + _field(4, entry(7, _field(1, 7) + _field(2, text) + _field(
            5, _field(1, 3) + _field(5, "jit(s)/attn/flash_fwd/pallas_call:"))))
        + _field(4, entry(8, _field(1, 8) + _field(2, "%copy.1 = f32[] copy()")))
        + _field(5, entry(3, _field(1, 3) + _field(2, "tf_op"))))
    host = (
        _field(2, "/host:CPU")
        + _field(3, _field(2, "python3") + _field(3, 50)
                 + _field(4, event(1, 4_000_000, 10, _field(
                     4, _field(1, 9) + _field(4, 1_790_500_000_250_000)))))
        + _field(4, entry(1, _field(1, 1) + _field(2, profile.CLOCK_MARKER)))
        + _field(5, entry(9, _field(1, 9) + _field(2, "wall_us"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(4, "hostname"))
    ops, marker = profile.read_xplane(str(path))
    assert ops == {0: [
        ["kernel:flash_fwd.2", 2100.0, 5000.0,
         "jit(s)/attn/flash_fwd/pallas_call:"],
        ["copy.1", 9100.0, 1000.0, ""]]}
    assert marker == (4050.0, 1790500000.25)


# ---------------------------------------------------------------------------
# the compile log
# ---------------------------------------------------------------------------

def test_compile_log_names_a_fresh_jit_once():
    enable_compile_cache()   # every entry point's call; the CPU is left
    enable_compile_cache()   # without a cache but with the listener

    def scale_and_shift_for_the_log(x):
        return x * 3.0 + 1.0

    f = jax.jit(scale_and_shift_for_the_log)
    t_before = time.perf_counter()
    f(jnp.ones((5,))).block_until_ready()
    mine = [r for r in profile.compile_log()
            if r["program"].endswith("scale_and_shift_for_the_log")]
    assert sorted(r["phase"] for r in mine) == ["backend", "lower", "trace"]
    for r in mine:
        assert r["seconds"] >= 0 and set(r) >= {"program", "phase",
                                                "seconds", "t_end"}
        assert t_before <= r["t_end"] <= time.perf_counter()
    assert all("cache" not in r for r in mine)   # no cache on the CPU
    n = len(profile.compile_log())
    f(jnp.ones((5,))).block_until_ready()        # nothing is built again
    assert len(profile.compile_log()) == n

    def outer_with_jitted_helpers(x):
        return jnp.where(x > 0, jnp.add(x, 1.0), jnp.tanh(x))

    jax.jit(outer_with_jitted_helpers)(jnp.ones((5,))).block_until_ready()
    traced = [r["program"] for r in profile.compile_log()[n:]
              if r["phase"] == "trace"]
    assert traced == ["outer_with_jitted_helpers"]   # not _where, add


def test_compile_log_feeds_the_registry_and_the_span_ring(monkeypatch,
                                                          tmp_path):
    enable_compile_cache()
    reset_registry()
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + os.sep)
    obs_trace.reset_buffer()
    try:
        jax.jit(lambda x: x - 2.0)(jnp.ones((3,))).block_until_ready()
        reg = get_registry()
        for phase in ("trace", "lower", "backend"):
            assert reg.counter("compile.seconds", phase=phase).value > 0
        spans = [s for s in obs_trace.get_buffer().snapshot()
                 if s["trace"] == profile.COMPILE_LANE]
        assert spans and all(s["name"] == "compile" for s in spans)
        assert "<lambda>" in spans[-1]["args"]["program"]
        summary = profile.compile_summary()
        assert summary["seconds"]["backend"] > 0
        assert summary["cache_misses"] == 0
    finally:
        obs_trace.reset_buffer()
        reset_registry()


def test_compile_log_puts_the_cache_verdict_on_the_backend_record():
    log = profile._CompileLog()
    trace_event, backend_event = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration")
    log.on_event("/jax/compilation_cache/cache_misses")
    log.on_duration("/jax/unrelated/duration", 9.0)
    # a helper traced inside the step's trace is the step's time
    log.on_start(trace_event, 0.0, fun_name="step")
    log.on_start(trace_event, 0.0, fun_name="add")
    log.on_duration(trace_event, 0.1, fun_name="add")
    log.on_duration(trace_event, 0.5, fun_name="step")
    log.on_duration(backend_event, 2.0, fun_name="step")
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration(backend_event, 0.1, fun_name="init")
    log.on_duration(backend_event, 0.2, fun_name="small")
    got = [(r["program"], r["phase"], r.get("cache")) for r in log.records]
    assert got == [("step", "trace", None), ("step", "backend", "miss"),
                   ("init", "backend", "hit"), ("small", "backend", None)]
    reset_registry()


# ---------------------------------------------------------------------------
# the serving rank's hook
# ---------------------------------------------------------------------------

def _serve_with(tmp_path, monkeypatch, *, trace: bool):
    """A CPU ServeJob of one rank whose profiler is replaced in the rank
    by a spy and a recording (``ServeJob`` pickles the worker function by
    value, so the rank runs this file's wrapper); 150 busy decode steps,
    so one slice ends."""
    from horovod_tpu.serve import ServeJob, service

    reached = str(tmp_path / "profiler_reached")
    real_worker = service.serve_worker

    def worker(spec):
        import jax as _jax

        from horovod_tpu.obs import profile as _profile

        def spy(*_a, **_kw):
            open(reached, "a").write("jax.profiler\n")
            raise AssertionError("the real profiler runs only on the chip")

        _jax.profiler.start_trace = spy

        class Recording:
            """Hands back _synthetic(), placed at the slice's start."""

            def start(self):
                open(reached, "a").write("hook\n")
                self.wall = time.time()
                return self.wall

            def stop(self):
                return _synthetic(2_000_000.0), (2_000_000.0, self.wall)

        _profile._profiler_backend = Recording
        return real_worker(spec)

    monkeypatch.setattr(service, "serve_worker", worker)
    overrides = dict(num_layers=1, num_heads=2, emb_dim=32, max_len=256,
                     vocab_size=64, dtype=jnp.float32,
                     attention_impl="reference")
    spec = {"size": "nano", "overrides": overrides, "seed": 3,
            "num_slots": 2, "idle_secs": 0.005}
    env = {"JAX_PLATFORMS": "cpu"}
    trace_dir = str(tmp_path / "spans") + os.sep
    if trace:
        env[envmod.TRACE] = trace_dir
    job = ServeJob(spec, np=1, env=env, max_retries=0, timeout=300).start()
    try:
        rids = [job.client.submit([5, 17, 3, 9], max_new_tokens=150)]
        docs = [job.client.result(r, timeout=240) for r in rids]
        results, _ = job.stop()
    finally:
        job.shutdown()
    assert len(docs[0]["tokens"]) == 150
    return results[0], reached, trace_dir


@pytest.mark.multiprocess
def test_serving_loop_never_reaches_the_profiler_when_tracing_is_off(
        tmp_path, monkeypatch):
    monkeypatch.delenv(envmod.TRACE, raising=False)
    summary, reached, _ = _serve_with(tmp_path, monkeypatch, trace=False)
    assert not os.path.exists(reached)
    assert "device_slice" not in summary
    assert summary["compile"]["seconds"]["backend"] > 0


@pytest.mark.multiprocess
def test_serving_rank_emits_device_slices_when_tracing_is_on(
        tmp_path, monkeypatch):
    monkeypatch.delenv(envmod.TRACE, raising=False)
    wall0 = time.time()
    summary, reached, trace_dir = _serve_with(tmp_path, monkeypatch,
                                              trace=True)
    assert open(reached).read().splitlines() == ["hook"]  # one slice,
    sys.path.insert(0, ROOT)                 # and never jax.profiler
    try:
        from benchmark.runners.serve import _read_spans
    finally:
        sys.path.remove(ROOT)
    # the benchmark's reader, schema check and window filter untouched
    spans = _read_spans(trace_dir, wall0, time.time())
    slices = [s for s in spans if s["name"] == "device_slice"]
    assert len(slices) == 1 and slices[0]["trace"] == "serve.steps"
    args = slices[0]["args"]
    assert args["busy_s"] == pytest.approx(0.035, abs=1e-5)
    assert args["window_s"] == pytest.approx(0.070, abs=1e-5)
    assert sum(args["idle_by_span"].values()) == pytest.approx(0.035, abs=1e-5)
    assert set(args["idle_by_span"]) <= {
        "decode_compute", "step", "schedule_broadcast", "stream_publish",
        "prefill", "bookkeeping", "uncovered", "compile"}
    assert args["by_kernel"] == {"flash_fwd": pytest.approx(0.020, abs=1e-5)}
    assert slices[0]["dur"] > 0 and args["step"] >= profile.SLICE_PERIOD
    json.dumps(slices[0])
    assert summary["device_slice"]["busy_s"] == pytest.approx(0.035, abs=1e-5)
    # the first token's instant on the server's clock is the end of the
    # request's prefill span, which also carries ttft_ms: no span of
    # its own doubles it
    assert not [s for s in spans if s["name"] == "first_token"]
    prefills = [s for s in spans if s["name"] == "prefill"
                and s["trace"] != "serve.steps"]
    assert len(prefills) == 1 and prefills[0]["args"]["ttft_ms"] > 0


class _Recording:
    """A profiler that hands back _synthetic() and logs its calls."""

    events: list = []

    def start(self):
        self.events.append("start")
        return time.time()

    def stop(self):
        self.events.append("stop")
        return _synthetic(), None


@pytest.fixture
def recording(monkeypatch, tmp_path):
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + os.sep)
    obs_trace.reset_buffer()
    monkeypatch.setattr(_Recording, "events", [])
    monkeypatch.setattr(profile, "_profiler_backend", _Recording)
    yield _Recording.events
    obs_trace.reset_buffer()


def test_slice_schedule_records_the_last_steps_of_a_period(recording):
    """The last SLICE_STEPS busy steps of every SLICE_PERIOD are one
    slice; an idle step ends a slice early."""
    period, length = profile.SLICE_PERIOD, profile.SLICE_STEPS
    assert length <= 4          # a slice's cost follows its operations
    slices = profile.SliceSchedule("serve.steps")
    for step in range(1, period - length):
        slices.tick(True, 0, step)
    assert recording == [] and slices.last is None
    slices.tick(True, 0, period - length)          # armed after this step
    for step in range(period - length + 1, period):
        slices.tick(True, 0, step)
    assert recording == ["start"]
    slices.tick(True, 0, period)
    assert recording == ["start", "stop"]
    assert slices.last["busy_s"] == pytest.approx(0.035, abs=1e-5)
    assert slices.last["ops"] == 4
    for step in range(period + 1, 2 * period - 1):  # 2 into the next slice
        slices.tick(True, 0, step)
    slices.tick(False, 0, 2 * period - 1)           # the pool drained
    assert recording == ["start", "stop", "start", "stop"]
    emitted = [s for s in obs_trace.get_buffer().snapshot()
               if s["name"] == "device_slice"]
    assert [s["args"]["step"] for s in emitted] == [period, 2 * period - 1]


def test_slice_schedule_survives_a_profiler_that_fails(recording,
                                                       monkeypatch):
    def broken():
        raise RuntimeError("a trace is already running")

    monkeypatch.setattr(profile, "_profiler_backend", broken)
    slices = profile.SliceSchedule("serve.steps")
    for step in range(1, 3 * profile.SLICE_PERIOD):
        slices.tick(True, 0, step)                  # does not raise
    assert slices.failed and recording == []


@pytest.mark.parametrize("rate, low, high", [
    (1.0, 64, 64), (0.25, 6, 28), (0.0, 0, 0)])
def test_slice_schedule_honours_the_spans_sample_rate(recording, rate,
                                                      low, high):
    """At rate r one period in 1/r has a slice, and which one is the
    same on every rank: no clock, no per-rank state decides it."""
    def stopped_at(epoch):
        obs_trace.reset_buffer()
        slices = profile.SliceSchedule("serve.steps", rate)
        for step in range(1, 64 * profile.SLICE_PERIOD + 1):
            slices.tick(True, epoch, step)
        return [s["args"]["step"] for s in
                obs_trace.get_buffer().snapshot()
                if s["name"] == "device_slice"]

    rank0, rank1 = stopped_at(0), stopped_at(0)
    assert rank1 == rank0
    assert low <= len(rank0) <= high
    assert all(step % profile.SLICE_PERIOD == 0 for step in rank0)
    if 0.0 < rate < 1.0:
        assert stopped_at(1) != rank0       # another epoch, other periods
