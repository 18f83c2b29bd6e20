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
