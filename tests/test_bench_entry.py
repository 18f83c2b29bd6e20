"""bench.py's and chip_smoke.py's entry rules (unit level): which device a
run may land on, where a record may be written, where the compile cache
lives, and that nothing invents a number for a device it does not know.

The end-to-end timing path is exercised by the CPU dry run in CI; the
chip path by ``chip_smoke.py`` on the chip.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys

import pytest

import bench

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- provenance

def test_backend_provenance_without_device_never_imports_jax(monkeypatch):
    """The failure paths and the ``--serve`` parent stamp a record with
    no device in hand: that must not import (let alone initialise) JAX —
    the chip belongs to another process then."""
    import builtins
    import sys as _sys

    monkeypatch.setitem(_sys.modules, "jax", None)
    monkeypatch.delitem(_sys.modules, "jax")
    real_import = builtins.__import__

    def guard(name, *a, **k):
        if name == "jax" or name.startswith("jax."):
            raise AssertionError("backend_provenance() imported jax")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", guard)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    prov = bench.backend_provenance()
    assert prov == {"platform": None, "device_kind": None,
                    "jax_platforms": "cpu"}


def test_backend_provenance_reports_device(monkeypatch):
    import jax

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    prov = bench.backend_provenance(jax.devices()[0])
    assert prov["platform"] == "cpu"
    assert prov["device_kind"]
    assert prov["jax_platforms"] == "cpu"
    # a serving rank reports its device as a dict in its drain summary
    prov = bench.backend_provenance(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    assert prov["platform"] == "tpu"
    assert prov["device_kind"] == "TPU v5 lite"


def test_degraded_record_carries_provenance_stamp(tmp_path, monkeypatch):
    """Every degraded BENCH record embeds the backend-provenance stamp,
    so the perf gate can separate 'ran on CPU' from 'failed on the chip'
    without guessing."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    path = bench.write_degraded_record(
        "step raised", rc=1, phase="warmup",
        record_dir=str(tmp_path),
    )
    doc = json.load(open(path))
    assert doc["degraded"] is True
    prov = doc["provenance"]
    assert set(prov) == {"platform", "device_kind", "jax_platforms"}
    assert prov["jax_platforms"] == "cpu"


# -------------------------------------------------- records stay put

def test_auto_record_writes_only_where_the_variable_points(tmp_path,
                                                          monkeypatch):
    monkeypatch.delenv("HVDTPU_BENCH_RECORD_DIR", raising=False)
    assert bench._auto_record("dry run", rc=0, phase="cpu-dry-run") is None
    monkeypatch.setenv("HVDTPU_BENCH_RECORD_DIR", str(tmp_path))
    path = bench._auto_record("dry run", rc=0, phase="cpu-dry-run")
    assert os.path.dirname(path) == str(tmp_path)
    assert json.load(open(path))["failure_phase"] == "cpu-dry-run"


def _run_script(script, *argv, env_extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("HVDTPU_BENCH_RECORD_DIR", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, script), *argv],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=300,
    )


def test_bench_without_cpu_flag_refuses_a_cpu_only_machine(tmp_path):
    """No --cpu and no chip: an error before anything is built — no
    result line, no record, in the checkout or anywhere asked for."""
    before = set(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json")))
    proc = _run_script(
        "bench.py", env_extra={"HVDTPU_BENCH_RECORD_DIR": str(tmp_path)})
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert proc.stdout.strip() == ""
    assert set(glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json"))) == before
    assert os.listdir(tmp_path) == []


def test_chip_smoke_refuses_a_cpu_only_machine():
    proc = _run_script("chip_smoke.py")
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
    from horovod_tpu.obs import anatomy
    from horovod_tpu.obs.profile import MFUProfiler, peak_flops

    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops("TPU v99")
    with pytest.raises(ValueError, match="''"):
        peak_flops("")
    with pytest.raises(ValueError, match="TPU v99"):
        MFUProfiler(1e12, "TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        anatomy.step_anatomy(10.0, mfu=0.2, device_kind="TPU v99")
    with pytest.raises(ValueError, match="None"):
        anatomy.roofline_verdict(
            mfu=0.2, collective_frac=0.0, flops_per_step=None,
            bytes_per_step=None, device_kind=None)

    class _Dev:
        device_kind = "TPU v99"

    with pytest.raises(ValueError, match="TPU v99"):
        bench.peak_flops_per_chip(_Dev(), "bf16")


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


def test_importing_bench_initialises_no_backend():
    """``bench.py --campaign`` and ``--serve`` parents start the workers
    that need the chip: importing the module (and expanding a campaign)
    must stay config-only."""
    code = (
        "import bench\n"
        "from horovod_tpu.bench import campaign\n"
        "spec = campaign.load_spec('scripts/campaigns/hw_round.json')\n"
        "assert campaign.expand_points(spec)\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=REPO_ROOT, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
