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
from flash_oracle import plan_of
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


@pytest.mark.parametrize("form, names", [
    ("dkdv_resident", ["flash_bwd_dkdv", "flash_fwd"]),
    # the one kernel with the K tile outermost, under the same name
    ("dq_resident", ["flash_bwd_dkdv", "flash_fwd"]),
    ("two_passes", ["flash_bwd_dkdv", "flash_bwd_dq", "flash_fwd"]),
], ids=["one_kernel", "one_kernel_dq_resident", "two_passes"])
def test_named_flash_kernels_are_bitwise_the_unnamed_ones(
        monkeypatch, form, names):
    """A kernel's name is metadata: forward, dq, dk and dv in interpret
    mode are bit for bit what the unnamed ``pallas_call`` gives, on all
    three backward paths (a limit that a kv row's dq fits and its dk and
    dv do not gives the second, none the third)."""
    limit = {"dkdv_resident": fa._FUSED_BWD_VMEM_LIMIT,
             "dq_resident": fa._dq_resident_bwd_vmem_bytes(
                 64, 16, 16, 16, 4, 1),
             "two_passes": 0}[form]
    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_LIMIT", limit)
    # no room above the limit: the shape is on the path the limit says
    monkeypatch.setattr(fa, "_FUSED_BWD_VMEM_CEILING", limit)
    jax.clear_caches()
    assert plan_of(64, 16, 1, 4, 16, 16).bwd_form == form
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
                if arg in ("scope", ""):       # optim._scoped's parameter
                    continue                   # and block_math's; prose
                assert arg.startswith("scopes."), (name, arg)
                used.add(getattr(scopes, arg[len("scopes."):]))
    used.add(scopes.OPTIMIZER_UPDATE)          # passed to optim._scoped
    # block_math's ``scope``: the mixer's, by layer type, else ``attn``,
    # and the feed-forward half's ``mlp``
    from horovod_tpu.models.transformer import MIXER_SCOPES

    used |= set(MIXER_SCOPES.values()) | {scopes.ATTN, scopes.MLP}
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
# the set-up log (ISSUE 37): intervals, the cache's seconds, hvd.init,
# the nested traces, and the benchmark's seven readers
# ---------------------------------------------------------------------------

TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")
RETRIEVAL_EVENT, SAVED_EVENT = (
    "/jax/compilation_cache/cache_retrieval_time_sec",
    "/jax/compilation_cache/compile_time_saved_sec")


def test_every_record_is_an_interval_on_the_runners_clock():
    enable_compile_cache()

    def doubled_for_the_interval_case(x):
        return x * 2.0

    t_before = time.perf_counter()
    jax.jit(doubled_for_the_interval_case)(jnp.ones((4,))).block_until_ready()
    t_after = time.perf_counter()
    log = profile.compile_log()
    assert log and all(r["t_start"] <= r["t_end"] for r in log)
    mine = [r for r in log
            if r["program"] == "doubled_for_the_interval_case"]
    assert [r["phase"] for r in mine] == ["trace", "lower", "backend"]
    for r in mine:
        assert r["t_end"] - r["t_start"] == pytest.approx(r["seconds"],
                                                          abs=1e-6)
        assert t_before <= r["t_start"] and r["t_end"] <= t_after
    # one after the other, as JAX runs them
    assert mine[0]["t_end"] <= mine[1]["t_start"] + 1e-3
    assert mine[1]["t_end"] <= mine[2]["t_start"] + 1e-3
    assert set(mine[0]["children"]) == {"multiply"}   # x * 2.0
    assert mine[2]["cache_load_s"] == 0.0             # no cache on the CPU


def test_the_caches_seconds_land_on_the_same_threads_backend_record():
    import threading

    log = profile._CompileLog()
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration(SAVED_EVENT, 41.5)
    log.on_duration(RETRIEVAL_EVENT, 1.25)
    # another thread compiles in between: it sees none of it
    other = threading.Thread(target=log.on_duration,
                             args=(BACKEND_EVENT, 3.0),
                             kwargs={"fun_name": "jit(elsewhere)"})
    other.start()
    other.join(timeout=10)
    assert not other.is_alive()
    log.on_duration(TRACE_EVENT, 0.2, fun_name="step")   # not a backend
    log.on_duration(BACKEND_EVENT, 1.5, fun_name="jit(step)")
    log.on_duration(BACKEND_EVENT, 0.5, fun_name="jit(next)")
    got = {r["program"]: r for r in log.records if r["phase"] == "backend"}
    assert got["step"]["cache_load_s"] == 1.25
    assert got["step"]["saved_s"] == 41.5 and got["step"]["cache"] == "hit"
    for name in ("elsewhere", "next"):
        assert got[name]["cache_load_s"] == 0.0
        assert "saved_s" not in got[name] and "cache" not in got[name]
    assert get_registry().counter(
        "compile.seconds", phase="cache_load").value == 1.25
    reset_registry()


def test_a_real_hit_in_the_persistent_cache_carries_its_load(tmp_path):
    """The CPU is left without a cache by ``enable_compile_cache``; this
    case turns one on to see JAX's own events arrive in the order the
    log relies on."""
    from jax.experimental.compilation_cache import compilation_cache

    enable_compile_cache()
    settings = {"jax_compilation_cache_dir": str(tmp_path),
                "jax_persistent_cache_min_compile_time_secs": 0,
                "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {k: getattr(jax.config, k) for k in settings}

    def same_program_twice():
        def matmul_for_the_cache_case(x):
            return jnp.sin(x) @ x
        return jax.jit(matmul_for_the_cache_case)

    try:
        for key, value in settings.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
        x = jnp.ones((32, 32))
        same_program_twice()(x).block_until_ready()
        same_program_twice()(x).block_until_ready()
    finally:
        for key, value in before.items():
            jax.config.update(key, value)
        compilation_cache.reset_cache()
    miss, hit = [r for r in profile.compile_log()
                 if r["program"] == "matmul_for_the_cache_case"
                 and r["phase"] == "backend"]
    assert miss["cache"] == "miss" and miss["cache_load_s"] == 0.0
    assert "saved_s" not in miss
    assert hit["cache"] == "hit" and "saved_s" in hit
    assert 0.0 < hit["cache_load_s"] <= hit["seconds"]


def test_hvd_init_is_one_record_after_the_process_record(tmp_path):
    """A fresh process, as an entry point runs: the listener, then
    ``hvd.init()`` twice."""
    import subprocess

    script = (
        "import json, time\n"
        "from horovod_tpu.utils.compile_cache import enable_compile_cache\n"
        "import horovod_tpu as hvd\n"
        "enable_compile_cache(); enable_compile_cache()\n"
        "hvd.init(); hvd.init()\n"
        "from horovod_tpu.obs import profile\n"
        "print(json.dumps({'log': profile.compile_log(),\n"
        "                  'now': time.perf_counter(),\n"
        "                  'summary': profile.compile_summary()}))\n")
    out = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, timeout=240,
        capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    got = json.loads(out.stdout.strip().splitlines()[-1])
    log = got["log"]
    inits = [r for r in log if r["phase"] == "init"]
    assert len(inits) == 1 and inits[0]["program"] == "hvd.init"
    assert 0.0 < inits[0]["seconds"] == pytest.approx(
        inits[0]["t_end"] - inits[0]["t_start"])
    assert got["summary"]["seconds"]["init"] == pytest.approx(
        inits[0]["seconds"], abs=1e-3)
    if not os.path.exists("/proc/self/stat"):
        assert all(r["phase"] != "process" for r in log)
        return
    assert [r["phase"] for r in log].count("process") == 1
    first = log[0]
    assert first["phase"] == "process" and first["program"] == "process"
    # the interpreter's start and the imports of jax and the package:
    # some tenths of a second at least, and before everything else
    assert 0.1 < first["seconds"] < 120.0
    assert first["t_start"] < first["t_end"] <= inits[0]["t_start"]
    assert got["now"] - first["t_start"] < 240.0


def test_no_process_record_where_the_start_cannot_be_read(monkeypatch):
    assert profile._process_start() is not None or not os.path.exists(
        "/proc/self/stat")
    monkeypatch.setattr(profile, "_COMPILE_LOG", profile._CompileLog(None))
    profile.log_interval("init", "hvd.init", time.perf_counter() - 0.5)
    (only,) = profile.compile_log()
    assert only["phase"] == "init"
    assert only["seconds"] == pytest.approx(0.5, abs=0.05)
    assert _setup_reader("setup_uncovered_s").read(
        {"stamps": [time.perf_counter() + 1.0, time.perf_counter() + 2.0]}
    ) is None


def test_children_of_a_trace_are_its_jitted_helpers_by_name():
    enable_compile_cache()

    @jax.jit
    def helper_called_three_times(x):
        return jnp.tanh(x) * 2.0     # tanh, multiply: a level deeper

    def outer_with_a_thrice_called_helper(x):
        for _ in range(3):
            x = helper_called_three_times(x)
        return x

    n = len(profile.compile_log())
    jax.jit(outer_with_a_thrice_called_helper)(
        jnp.ones((5,))).block_until_ready()
    (traced,) = [r for r in profile.compile_log()[n:]
                 if r["phase"] == "trace"]
    assert traced["program"] == "outer_with_a_thrice_called_helper"
    assert set(traced["children"]) == {"helper_called_three_times"}
    count, seconds = traced["children"]["helper_called_three_times"]
    assert count == 3 and 0.0 < seconds <= traced["seconds"]
    # only a trace has children: lowering is one module a program
    assert all("children" not in r for r in profile.compile_log()[n:]
               if r["phase"] != "trace")


def test_the_sixteen_largest_children_are_kept_and_the_rest_is_other():
    log = profile._CompileLog()
    log.on_start(TRACE_EVENT, 0.0, fun_name="step")
    for i in range(17):
        for _ in range(2):
            log.on_start(TRACE_EVENT, 0.0, fun_name=f"helper{i}")
            # a helper's own helper is the helper's time, not a child
            log.on_start(TRACE_EVENT, 0.0, fun_name="deeper")
            log.on_duration(TRACE_EVENT, 0.001, fun_name="deeper")
            log.on_duration(TRACE_EVENT, 0.01 * (i + 1), fun_name=f"helper{i}")
    # what a lowering traces is the lowering's time
    log.on_duration(TRACE_EVENT, 9.0, fun_name="step")
    log.on_start(LOWER_EVENT, 0.0, fun_name="jit(step)")
    log.on_start(TRACE_EVENT, 0.0, fun_name="traced_by_a_lowering_rule")
    log.on_duration(TRACE_EVENT, 0.1, fun_name="traced_by_a_lowering_rule")
    log.on_duration(LOWER_EVENT, 1.0, fun_name="jit(step)")
    traced, lowered = log.records
    children = traced["children"]
    assert len(children) == profile.CHILDREN_KEPT + 1
    assert "helper0" not in children and "deeper" not in children
    assert children["other"] == [2, pytest.approx(0.02)]
    assert children["helper16"] == [2, pytest.approx(0.34)]
    assert sum(s for _, s in children.values()) == pytest.approx(
        0.02 * sum(range(1, 18)))
    assert traced["seconds"] == 9.0
    assert lowered["phase"] == "lower" and "children" not in lowered
    reset_registry()


def test_compile_summary_carries_the_caches_load_and_init(monkeypatch):
    log = profile._CompileLog(started=1.0)
    monkeypatch.setattr(profile, "_COMPILE_LOG", log)
    profile.log_interval("init", "hvd.init", time.perf_counter() - 0.25)
    log.on_event("/jax/compilation_cache/cache_hits")
    log.on_duration(RETRIEVAL_EVENT, 0.75)
    log.on_duration(BACKEND_EVENT, 1.0, fun_name="jit(step)")
    log.on_duration(BACKEND_EVENT, 2.0, fun_name="jit(init)")
    summary = profile.compile_summary()
    assert summary["seconds"] == {
        "trace": 0.0, "lower": 0.0, "backend": 3.0, "cache_load": 0.75,
        "init": pytest.approx(0.25, abs=0.05)}   # and no "process"
    assert summary["cache_hits"] == 1 and summary["cache_misses"] == 0
    assert [r["phase"] for r in profile.compile_log()][0] == "process"
    reset_registry()


def test_the_log_and_its_children_stay_bounded():
    log = profile._CompileLog()
    for i in range(profile.COMPILE_LOG_CAPACITY + 50):
        log.on_start(TRACE_EVENT, 0.0, fun_name=f"program{i}")
        for j in range(40):
            log.on_start(TRACE_EVENT, 0.0, fun_name=f"helper{j}")
            log.on_duration(TRACE_EVENT, 0.001, fun_name=f"helper{j}")
        log.on_duration(TRACE_EVENT, 0.1, fun_name=f"program{i}")
    assert len(log.records) == profile.COMPILE_LOG_CAPACITY
    assert log.records[-1]["program"] == \
        f"program{profile.COMPILE_LOG_CAPACITY + 49}"
    for r in log.records:
        assert len(r["children"]) <= profile.CHILDREN_KEPT + 1
        assert sum(c for c, _ in r["children"].values()) == 40
    reset_registry()


def test_the_compile_span_lies_where_the_record_lies(monkeypatch, tmp_path):
    """One clock pair lays a record on the span ring's wall clock."""
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + os.sep)
    obs_trace.reset_buffer()
    try:
        log = profile._CompileLog()
        perf, wall = log.clock
        assert abs(wall - time.time()) < 5.0
        log.on_duration(BACKEND_EVENT, 0.5, fun_name="jit(step)")
        (record,) = log.records
        (span,) = [s for s in obs_trace.get_buffer().snapshot()
                   if s["trace"] == profile.COMPILE_LANE]
        assert span["t0"] == pytest.approx(
            wall + record["t_start"] - perf, abs=1e-6)
        assert span["dur"] == pytest.approx(0.5, abs=1e-6)
    finally:
        obs_trace.reset_buffer()
        reset_registry()


def _setup_reader(name):
    sys.path.insert(0, ROOT)
    try:
        from benchmark.harness import registry
    finally:
        sys.path.remove(ROOT)
    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def _recorded(program, phase, t_start, t_end, **extra):
    return {"program": program, "phase": phase, "seconds": t_end - t_start,
            "t_start": t_start, "t_end": t_end, **extra}


# A set-up as the log records it: the window's first stamp at 60.0, one
# step 0.5 s long.  ``warm_up`` overlaps the step's backend phase (a
# second thread): a union counts the overlap once.
SETUP_LOG = [
    _recorded("process", "process", 10.0, 14.0),
    _recorded("hvd.init", "init", 15.0, 16.5),
    _recorded("make_state", "trace", 17.0, 18.0, children={}),
    _recorded("make_state", "lower", 18.0, 18.5),
    _recorded("make_state", "backend", 18.5, 20.5, cache="hit",
              cache_load_s=0.25, saved_s=30.0),
    _recorded("local_step", "trace", 25.0, 33.0, children={}),
    _recorded("local_step", "lower", 33.0, 37.0),
    _recorded("local_step", "backend", 37.0, 40.0, cache="hit",
              cache_load_s=1.5, saved_s=60.0),
    _recorded("warm_up", "backend", 39.0, 41.0, cache_load_s=0.0),
    # after the window's first stamp: the checks' program
    _recorded("program_loss", "trace", 80.0, 89.0, children={}),
    _recorded("program_loss", "backend", 89.0, 99.0, cache="miss",
              cache_load_s=0.0),
]
SETUP_RUN = {"stamps": [60.0, 60.5, 61.0, 61.5]}
# the step is ``local_step`` (15 s of 20.5); what the parent's log holds
# of the same set-up: no intervals, no ``init``, no ``process``
PARENTS_LOG = [{k: v for k, v in r.items()
                if k in ("program", "phase", "seconds", "t_end", "cache")}
               for r in SETUP_LOG if r["phase"] not in ("process", "init")]
# covered: 4 + 1.5 + 3.5 + [25, 41] = 25; from 10.0 to 60.0 - 0.5
SETUP_READINGS = {
    "step_trace_s": (8.0, 8.0), "step_lower_s": (4.0, 4.0),
    "step_backend_s": (3.0, 3.0), "cache_load_s": (1.75, None),
    "state_programs_s": (5.5, None), "hvd_init_s": (1.5, None),
    "setup_uncovered_s": (49.5 - 25.0, None)}


@pytest.mark.parametrize("name", sorted(SETUP_READINGS))
def test_setup_readers_on_a_recorded_log(monkeypatch, name):
    reading, on_the_parent = SETUP_READINGS[name]
    monkeypatch.setattr(profile, "compile_log", lambda: list(SETUP_LOG))
    assert _setup_reader(name).read(SETUP_RUN) == pytest.approx(reading)
    # the accepted readers read what they read before, on both logs
    for log in (SETUP_LOG, PARENTS_LOG):
        monkeypatch.setattr(profile, "compile_log", lambda log=log: list(log))
        assert _setup_reader("compile_trace_lower_s").read(
            SETUP_RUN) == pytest.approx(13.5)
        assert _setup_reader("compile_cache_misses").read(SETUP_RUN) == 0
    got = _setup_reader(name).read(SETUP_RUN)
    assert got == (on_the_parent if on_the_parent is None
                   else pytest.approx(on_the_parent))
    # nothing to read: no stamps (a served run), an empty log
    assert _setup_reader(name).read({"requests": []}) is None
    monkeypatch.setattr(profile, "compile_log", lambda: [])
    assert _setup_reader(name).read(SETUP_RUN) is None


def test_the_setup_readers_count_overlapping_records_once(monkeypatch):
    covered = _setup_reader("setup_uncovered_s").covered
    assert covered([]) == 0.0
    assert covered(SETUP_LOG[:9]) == pytest.approx(25.0)
    nested = [_recorded("a", "trace", 0.0, 10.0),
              _recorded("b", "trace", 2.0, 3.0),
              _recorded("c", "lower", 9.0, 12.0),
              _recorded("d", "backend", 20.0, 21.0)]
    assert covered(nested) == pytest.approx(13.0)
    assert covered([{"t_end": 3.0}]) is None
    # a cold run: every program missed, and the reader says 0, not None
    cold = [dict(r, cache="miss", cache_load_s=0.0) if "cache" in r else r
            for r in SETUP_LOG]
    monkeypatch.setattr(profile, "compile_log", lambda: cold)
    assert _setup_reader("cache_load_s").read(SETUP_RUN) == 0.0
    assert _setup_reader("compile_cache_misses").read(SETUP_RUN) == 2
    # the step is chosen by its seconds, not by its name or its place
    late = [dict(r, program="make_state" if r["program"] == "local_step"
                 else "local_step" if r["program"] == "make_state"
                 else r["program"]) for r in SETUP_LOG]
    monkeypatch.setattr(profile, "compile_log", lambda: late)
    assert _setup_reader("step_trace_s").read(SETUP_RUN) == 8.0
    assert _setup_reader("state_programs_s").read(SETUP_RUN) == 5.5


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
