"""A rematerialised block keeps what its Pallas kernels made
(``scopes.KERNEL_OUTPUTS``): the backward pass's recompute does not run
``flash_fwd`` or ``ssd_fwd`` a second time, whatever ``remat_policy``
says of the block's other values, and the metrics registry says what was
kept.  CPU, Pallas interpreter, two blocks a model
(tests/remat_cases.py)."""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import remat_cases as cases  # noqa: E402

from horovod_tpu import scopes  # noqa: E402
from horovod_tpu.obs.registry import (get_registry,  # noqa: E402
                                      reset_registry)

KERNELS = sorted(m for m, case in cases.MIXERS.items() if case[2])


def calls(mixer, **build):
    """How often the mixer's forward kernel is called in the loss's
    gradient: the ``pallas_call``s of that name, a jitted function's
    counted at each of its call sites."""
    loss, params = cases.build(mixer, **build)
    kernel = cases.MIXERS[mixer][2]

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += eqn.params["name"] == kernel
            else:
                n += sum(count(sub) for sub in jax.core.jaxprs_in_params(
                    eqn.params))
        return n

    return count(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)


def kept():
    """{gauge: {name of the kept value: reading}} of the registry."""
    out = {}
    for metric in get_registry().snapshot():
        if metric["name"].startswith("remat."):
            out.setdefault(metric["name"], {})[
                metric["tags"]["name"]] = metric["value"]
    return out


@pytest.mark.parametrize("mixer", KERNELS)
def test_without_remat_the_forward_kernel_runs_once_a_block(mixer):
    assert calls(mixer) == cases.blocks(mixer)


@pytest.mark.parametrize("policy", cases.POLICIES)
@pytest.mark.parametrize("mixer", KERNELS)
def test_the_recompute_does_not_rerun_the_forward_kernel(mixer, policy):
    """Once a block (the prediction module's included), not twice."""
    assert calls(mixer, remat=True, policy=policy) == cases.blocks(mixer)


# What the blocks keep, from the shapes: [batch x heads, seq, head size]
# and a float32 row a head for flash (nano: 2 x 4 heads of 32; latent
# attention pads nothing: 4 heads of 24 value channels, the query and
# key of 16 + 8); the scan's y [batch, seq, heads x head size] and the
# float32 state of every chunk start [batch, chunks, heads, head size,
# state].
KEPT_BYTES = {
    "flash": {scopes.FLASH_OUT: 8 * 32 * 32 * 4, scopes.FLASH_LSE: 8 * 32 * 4},
    "mla": {scopes.FLASH_OUT: 8 * 32 * 24 * 4, scopes.FLASH_LSE: 8 * 32 * 4},
    "mamba": {scopes.SSD_OUT: 2 * 32 * 4 * 16 * 4,
              scopes.SSD_STATES: 2 * 4 * 4 * 16 * 16 * 4},
}


@pytest.mark.parametrize("policy", cases.POLICIES)
@pytest.mark.parametrize("mixer", KERNELS)
def test_the_gauges_say_what_the_blocks_kept(mixer, policy):
    reset_registry()
    loss, params = cases.build(mixer, remat=True, policy=policy)
    jax.make_jaxpr(jax.grad(loss))(params)
    n = cases.blocks(mixer)
    assert kept() == {
        "remat.kept_values": {name: n for name in KEPT_BYTES[mixer]},
        "remat.kept_mib": {name: n * size / 2 ** 20
                           for name, size in KEPT_BYTES[mixer].items()}}
    reset_registry()


@pytest.mark.parametrize("mixer,remat", [
    ("flash", False), ("mamba", False), ("reference", True)])
def test_the_gauges_say_nothing_where_no_kernel_output_was_kept(mixer,
                                                                remat):
    """No ``remat``: no policy, and the names lower to nothing.  The
    reference attention under ``remat``: no kernel, nothing named."""
    reset_registry()
    loss, params = cases.build(mixer, remat=remat)
    jax.make_jaxpr(jax.grad(loss))(params)
    assert kept() == {}


def test_a_second_trace_starts_its_tally_at_nothing():
    reset_registry()
    loss, params = cases.build("flash", remat=True)
    for _ in range(2):
        jax.make_jaxpr(jax.grad(loss))(params)
    assert kept()["remat.kept_values"] == {scopes.FLASH_OUT: 2,
                                           scopes.FLASH_LSE: 2}
    reset_registry()


# ---- while the chip has room a block keeps its matmuls' outputs too.
# Tier-1 runs on the CPU, whose backend reports no memory: the tests
# replace the reading (``transformer.device_memory``) or the room the
# formula makes of it (``transformer.remat_room``).

import functools  # noqa: E402
import re  # noqa: E402
from unittest import mock  # noqa: E402

import numpy as np  # noqa: E402

from horovod_tpu.models import transformer  # noqa: E402

MIXERS = sorted(cases.MIXERS)
NO_ROOM, ALL_THE_ROOM = (1, 0), (1 << 44, 0)


def matmuls(jaxpr):
    """The ``dot_general``s with no batch dimensions in a jaxpr, those
    of its inner jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            n += not any(eqn.params["dimension_numbers"][1])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += matmuls(sub)
    return n


def grad_jaxpr(mixer, **build):
    loss, params = cases.build(mixer, **build)
    return jax.make_jaxpr(jax.grad(loss))(params)


@functools.lru_cache(maxsize=None)
def matmuls_run(mixer, remat):
    """The matmuls of the mixer's gradient without ``remat``, and with
    it where the blocks keep none of their outputs."""
    with mock.patch.object(transformer, "remat_room", lambda *a: 0):
        return matmuls(grad_jaxpr(
            mixer, remat=remat, policy="nothing_saveable").jaxpr)


def text(jaxpr):
    """A jaxpr as text, the addresses of its policies' closures out."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def gauges():
    """{name{tags' values}: reading} of the registry's ``remat.*``."""
    return {m["name"] + "".join("{%s}" % v for v in m["tags"].values()):
            m["value"] for m in get_registry().snapshot()
            if m["name"].startswith("remat.")}


def greedy(sizes, room):
    """What trace order keeps of ``sizes`` in ``room`` bytes where no
    kernel's outputs are charged: each that still fits."""
    kept = []
    for size in sizes:
        if size <= room:
            kept.append(size)
            room -= size
    return kept


@pytest.mark.parametrize("mixer", MIXERS)
def test_with_room_for_everything_no_matmul_runs_twice(mixer, monkeypatch):
    """As many matmuls as the gradient without ``remat`` holds: each
    block's projections once."""
    monkeypatch.setattr(transformer, "device_memory", lambda: ALL_THE_ROOM)
    assert matmuls(grad_jaxpr(
        mixer, remat=True, policy="nothing_saveable").jaxpr) == (
            matmuls_run(mixer, False)) < matmuls_run(mixer, True)


@pytest.mark.parametrize("policy", cases.POLICIES)
@pytest.mark.parametrize("mixer", MIXERS)
def test_with_no_room_the_gradient_is_todays_jaxpr(mixer, policy,
                                                   monkeypatch):
    """No room, and a backend that reports no memory (the CPU's own
    answer), trace the program of before the rule."""
    assert transformer.device_memory() is None
    todays = text(grad_jaxpr(mixer, remat=True, policy=policy))
    monkeypatch.setattr(transformer, "device_memory", lambda: NO_ROOM)
    assert text(grad_jaxpr(mixer, remat=True, policy=policy)) == todays


@pytest.mark.parametrize("stats", [None, {}, {"bytes_in_use": 7}])
def test_a_backend_without_a_limit_keeps_nothing_more(stats, monkeypatch):
    class Device:
        def memory_stats(self):
            return stats

    monkeypatch.setattr(jax, "local_devices", lambda: [Device()])
    assert transformer.device_memory() is None
    reset_registry()
    jaxpr = grad_jaxpr("flash", remat=True, policy="nothing_saveable")
    assert matmuls(jaxpr.jaxpr) == matmuls_run("flash", True)
    assert set(gauges()) == {
        f"remat.{gauge}{{{name}}}" for name in KEPT_BYTES["flash"]
        for gauge in ("kept_values", "kept_mib")}
    reset_registry()


def test_the_reading_is_the_first_local_devices(monkeypatch):
    class Device:
        def memory_stats(self):
            return {"bytes_limit": 1000, "bytes_in_use": 10,
                    "peak_bytes_in_use": 500}

    monkeypatch.setattr(jax, "local_devices", lambda: [Device(), None])
    assert transformer.device_memory() == (1000, 10)


@pytest.mark.parametrize("mixer,share", [
    ("reference", 0.25), ("flash", 0.25), ("flash", 0.6), ("mla", 0.6),
    ("mamba", 0.25), ("mamba", 0.6)])
def test_with_room_for_part_the_kept_bytes_stay_under_it(mixer, share,
                                                         monkeypatch):
    """A share of what the blocks' matmuls make, beside what their
    kernels keep.  The kernels' outputs are charged first; the matmuls'
    may pass what is left by at most the kernel outputs of the blocks
    that had not run their kernel when a matmul was kept."""
    sizes = cases.matmul_outputs(mixer)
    kernels = cases.blocks(mixer) * sum(KEPT_BYTES.get(mixer, {}).values())
    room = kernels + int(share * sum(map(sum, sizes)))
    monkeypatch.setattr(transformer, "remat_room", lambda *a: room)
    reset_registry()
    jaxpr = grad_jaxpr(mixer, remat=True, policy="nothing_saveable")
    read = gauges()
    kept = read["remat.matmul_kept_mib{1}"] * 2 ** 20
    assert read["remat.room_mib{1}"] == room / 2 ** 20
    assert 0 < kept <= room - kernels + kernels / cases.blocks(mixer)
    assert kept == read["remat.kept_mib{matmul}"] * 2 ** 20
    assert (matmuls_run(mixer, False) < matmuls(jaxpr.jaxpr)
            < matmuls_run(mixer, True))
    reset_registry()


@pytest.mark.parametrize("share", [0.1, 0.3, 0.5, 0.8])
def test_the_room_is_spent_in_trace_order(share, monkeypatch):
    """The reference attention has no kernel: each matmul output that
    still fits, block by block, in the order the block makes them."""
    sizes = sum(cases.matmul_outputs("reference"), [])
    room = int(share * sum(sizes))
    monkeypatch.setattr(transformer, "remat_room", lambda *a: room)
    reset_registry()
    grad_jaxpr("reference", remat=True, policy="nothing_saveable")
    expected = greedy(sizes, room)
    assert gauges() == {
        "remat.traces": 1,
        "remat.room_mib{1}": room / 2 ** 20,
        "remat.eligible_mib{1}": sum(sizes) / 2 ** 20,
        "remat.matmul_kept_mib{1}": sum(expected) / 2 ** 20,
        "remat.kept_values{matmul}": len(expected),
        "remat.kept_mib{matmul}": sum(expected) / 2 ** 20}
    reset_registry()


@pytest.mark.parametrize("mixer", MIXERS)
def test_the_gauges_say_what_was_eligible_and_what_was_kept(mixer,
                                                            monkeypatch):
    """Everything fits: eligible and kept are the blocks' matmul
    outputs, the kernels' gauges read what they read without the rule,
    and a second trace in the process counts under its own number."""
    monkeypatch.setattr(transformer, "device_memory", lambda: ALL_THE_ROOM)
    sizes = sum(cases.matmul_outputs(mixer), [])
    reset_registry()
    for trace in "12":
        grad_jaxpr(mixer, remat=True, policy="nothing_saveable")
        read = gauges()
        assert read[f"remat.eligible_mib{{{trace}}}"] == (
            sum(sizes) / 2 ** 20)
        assert read[f"remat.matmul_kept_mib{{{trace}}}"] == (
            sum(sizes) / 2 ** 20)
        assert read[f"remat.room_mib{{{trace}}}"] > 0
    assert read["remat.traces"] == 2
    assert read["remat.kept_values{matmul}"] == len(sizes)
    n = cases.blocks(mixer)
    for name, size in KEPT_BYTES.get(mixer, {}).items():
        assert read[f"remat.kept_values{{{name}}}"] == n
        assert read[f"remat.kept_mib{{{name}}}"] == n * size / 2 ** 20
    reset_registry()


@pytest.mark.parametrize("room", ["all", "none", "part"])
@pytest.mark.parametrize("mixer", MIXERS)
def test_the_gradients_are_those_without_remat(mixer, room, monkeypatch):
    """A kept value is the value the forward computed: loss and
    gradients match the model without ``remat`` whatever the room (the
    cases of ``test_models_gpt.py::test_gpt_remat_matches_no_remat``)."""
    if room == "part":
        part = sum(map(sum, cases.matmul_outputs(mixer))) // 2
        monkeypatch.setattr(transformer, "remat_room", lambda *a: part)
    else:
        monkeypatch.setattr(
            transformer, "device_memory",
            lambda: ALL_THE_ROOM if room == "all" else NO_ROOM)
    # (the bfloat16 reference model op by op, where both sides round
    # alike: test_models_gpt.py says why)
    traced = (lambda f: f) if mixer == "reference" else jax.jit
    loss, params = cases.build(mixer)
    l0, g0 = traced(jax.value_and_grad(loss))(params)
    rematted, params = cases.build(mixer, remat=True,
                                   policy="nothing_saveable")
    l1, g1 = traced(jax.value_and_grad(rematted))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        g0, g1)


def test_the_formula_of_the_room(monkeypatch):
    """``remat_room`` term by term on a model small enough to do by
    hand: two blocks of width 8, 16 tokens, a vocabulary of 32."""
    cfg = transformer.TransformerConfig(
        num_layers=2, num_heads=2, emb_dim=8, vocab_size=32, max_len=16,
        mlp_ratio=4, dtype=np.float32, remat=True)
    block = {"qkv": {"kernel": np.zeros((8, 24), np.float32),
                     "bias": np.zeros((24,), np.float32)},
             "fc1": {"kernel": np.zeros((8, 32), np.float32)}}
    params = {"block0": block, "block1": block,
              "wte": {"embedding": np.zeros((32, 8), np.float32)}}
    gradients = (2 * (8 * 24 + 24 + 8 * 32) + 32 * 8) * 4
    stream = 2 * 16 * 8 * 4
    head = transformer.REMAT_HEAD_COPIES * 16 * 32 * 4
    widest = transformer.REMAT_BLOCK_COPIES * 16 * (8 + 24 + 8 + 32) * 4
    transient = max(head, widest)
    limit, resident = 10 ** 6, 10 ** 5
    monkeypatch.setattr(transformer, "device_memory",
                        lambda: (limit, resident))
    assert transformer.remat_room(cfg, (1, 16), params) == int(
        transformer.REMAT_CEILING * limit - resident - stream - transient)
    # nothing where the state, its gradients and the working set alone
    # pass the ceiling; nothing to say without a limit or parameters
    full = int((resident + gradients + transient)
               / transformer.REMAT_CEILING)
    monkeypatch.setattr(transformer, "device_memory",
                        lambda: (full - 1, resident))
    assert transformer.remat_room(cfg, (1, 16), params) == 0
    assert transformer.remat_room(cfg, (1, 16), {}) is None
    monkeypatch.setattr(transformer, "device_memory", lambda: None)
    assert transformer.remat_room(cfg, (1, 16), params) is None


def test_the_benchmarks_reader_reads_the_steps_share(monkeypatch):
    """``benchmark/metrics/remat_kept_share.py``: kept over eligible of
    the first trace of the process (the step's), whatever a later trace
    (the checks', with more room) found; None without the gauges."""
    from benchmark.harness import registry as bench

    reader = bench.load_module(os.path.join(
        bench.ROOT, "benchmark", "metrics", "remat_kept_share.py"))
    reset_registry()
    grad_jaxpr("reference", remat=True, policy="nothing_saveable")
    assert reader.read({}) is None
    sizes = sum(cases.matmul_outputs("reference"), [])
    room = sum(sizes) // 3
    monkeypatch.setattr(transformer, "remat_room", lambda *a: room)
    grad_jaxpr("reference", remat=True, policy="nothing_saveable")
    monkeypatch.setattr(transformer, "remat_room", lambda *a: 1 << 40)
    grad_jaxpr("reference", remat=True, policy="nothing_saveable")
    assert gauges()["remat.matmul_kept_mib{2}"] == sum(sizes) / 2 ** 20
    assert reader.read({}) == sum(greedy(sizes, room)) / sum(sizes)
    monkeypatch.setattr(transformer, "remat_room", lambda *a: 0)
    reset_registry()
    grad_jaxpr("reference", remat=True, policy="nothing_saveable")
    assert reader.read({}) == 0.0
    reset_registry()


def test_with_no_room_the_step_lowers_to_todays_text(monkeypatch):
    """Blocks that share their helpers (the routed layers' gathers and
    sorts) lower them once where nothing is apportioned: one policy
    object for every block, as before the rule."""
    def lowered():
        loss, params = cases.build("mla", remat=True,
                                   policy="nothing_saveable")
        return jax.jit(jax.grad(loss)).lower(params).as_text()

    todays = lowered()
    monkeypatch.setattr(transformer, "device_memory", lambda: NO_ROOM)
    assert lowered() == todays
