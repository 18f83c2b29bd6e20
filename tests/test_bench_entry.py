"""Entry rules (unit level): which device ``chip_smoke.py`` may land on,
where the compile cache lives, that nothing invents a number for a
device it does not know, and that a chip has one process.

The chip path is exercised by ``chip_smoke.py`` on the chip.
"""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_a_cpu_only_machine():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs" in proc.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Without the program beside it the script has nothing to prove."""
    import shutil

    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "chip_smoke.py")], env=env,
        cwd=str(tmp_path), capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


# ------------------------------------------------------ compile cache

def test_cache_dir_from_the_variable_is_not_set_in_code(tmp_path):
    from horovod_tpu.utils import compile_cache

    placed = str(tmp_path / "placed")
    assert compile_cache.resolve_cache_dir(
        {compile_cache.CACHE_ENV: placed}) == (placed, False)


def test_cache_dir_defaults_to_the_checkout():
    from horovod_tpu.utils import compile_cache

    cache_dir, set_in_code = compile_cache.resolve_cache_dir({})
    assert set_in_code is True
    assert cache_dir == os.path.join(REPO_ROOT, ".jax_cache")


def test_cache_is_left_alone_in_a_process_held_to_the_cpu():
    import jax

    from horovod_tpu.utils import compile_cache

    assert jax.config.jax_platforms == "cpu"  # tests/conftest.py
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == was


# ------------------------------------------- nothing hides the device

def test_unknown_device_kind_raises_and_names_itself():
    from horovod_tpu.obs.profile import MFUProfiler, peak_flops

    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops("TPU v99")
    with pytest.raises(ValueError, match="''"):
        peak_flops("")
    with pytest.raises(ValueError, match="TPU v99"):
        MFUProfiler(1e12, "TPU v99")


def test_flash_attention_raises_on_a_backend_it_does_not_know(monkeypatch):
    """tpu compiles, cpu interprets (the test mode), anything else is an
    error that names the backend — never a silent interpreter run."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import flash_attention as fa

    assert fa._interpret_for_backend("tpu") is False
    assert fa._interpret_for_backend("cpu") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "mystery")
    q = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(RuntimeError, match="mystery"):
        fa.flash_attention(q, q, q)
    # an explicit choice is still honoured there
    out = fa.flash_attention(q, q, q, interpret=True)
    assert out.shape == q.shape


# ------------------------------------------------ one process per chip

def test_launcher_refuses_several_local_slots_on_a_tpu_host(monkeypatch):
    """Slots get no device binding, so -np N>1 on one TPU host would have
    every worker claim every chip: refused, clearly, before any spawn —
    unless the workers are kept off the TPU."""
    from horovod_tpu.run import runner
    from horovod_tpu.run.allocate import allocate, parse_hosts

    monkeypatch.setattr(runner, "_local_tpu_chips", lambda: 4)
    two = allocate(parse_hosts("localhost:2"), 2)
    with pytest.raises(RuntimeError, match="one process at a time"):
        runner.refuse_shared_tpu(two, {})
    with pytest.raises(RuntimeError, match="hvd.mesh"):
        runner.refuse_shared_tpu(two, {"JAX_PLATFORMS": "tpu,cpu"})
    runner.refuse_shared_tpu(two, {"JAX_PLATFORMS": "cpu"})
    runner.refuse_shared_tpu(allocate(parse_hosts("localhost:1"), 1), {})
    monkeypatch.setattr(runner, "_local_tpu_chips", lambda: 0)
    runner.refuse_shared_tpu(two, {})


def test_importing_the_step_builders_initialises_no_backend():
    """``chip_smoke.py``'s phases and the scripts start the backend
    themselves, before they build a step: importing the builders must
    not do it for them."""
    code = (
        "from horovod_tpu.testing import steps\n"
        "assert steps.build_step and steps.build_gpt_step\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
