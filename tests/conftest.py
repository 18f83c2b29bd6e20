"""Test harness config.

Mirrors the reference's test strategy (SURVEY.md §4): collective correctness
is tested against a real multi-device world, not mocks.  Where the reference
runs pytest under `mpirun -np 2 -H localhost:2`, we give the single test
process an 8-device virtual CPU mesh (XLA host-platform device count) so
every SPMD collective executes for real.  Launcher/controller logic is
unit-tested in-process, like the reference's test_run.py.

Multi-process tests (true multi-controller JAX over the hvdrun launcher)
live in tests/launcher/ and spawn subprocesses themselves.
"""

import os

# Must be set before jax import anywhere in the test process.  Force CPU even
# when the shell points JAX at a TPU platform: the suite wants a deterministic
# 8-device virtual mesh regardless of attached hardware.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("HVDTPU_TEST_MODE", "1")

import shutil  # noqa: E402
import subprocess  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402

# Build the native engine up front so its test coverage is real on a fresh
# checkout: `make -C cpp` is incremental (no-op when the .so is current)
# and the reference CI likewise bakes the build into every test image
# (docker-compose.test.yml).  Without a toolchain the native-gated tests
# skip with an explicit reason — but never silently on a buildable box.
_repo = Path(__file__).resolve().parent.parent
if shutil.which("make") and shutil.which("g++"):
    _build = subprocess.run(
        ["make", "-C", str(_repo / "cpp")], capture_output=True, text=True
    )
    if _build.returncode != 0:
        raise RuntimeError(
            "native engine build failed — fix cpp/ or remove the toolchain "
            f"to run Python-engine-only:\n{_build.stdout}\n{_build.stderr}"
        )


@pytest.fixture(params=["python", "native"])
def engine_env(request):
    """Run a cross-process test under BOTH eager engines: the pure-Python
    one (runtime/engine.py) and the native C++ one (cpp/hvdtpu via
    runtime/native.py) — same tests, same assertions, mirroring how the
    reference CI crosses its {mpi, gloo} backends (SURVEY.md §4)."""
    if request.param == "native":
        from horovod_tpu.runtime.native import native_available

        if not native_available():
            pytest.skip("native library not built (make -C cpp)")
    return {"HVDTPU_EAGER_ENGINE": request.param}


@pytest.fixture(scope="session", autouse=True)
def _world():
    import horovod_tpu as hvd

    hvd.init()
    assert jax.device_count() == 8, "virtual CPU mesh failed to materialize"
    yield
    hvd.shutdown()


# ``--dist loadfile`` gives a file to one worker, in collection order, and
# the first six files are the six workers' first.  The launcher cases go
# first: ``test_multiprocess.py`` is the longest file, and its 2-process
# worlds all start from one worker.  ``test_analysis.py`` is second because
# two of its cases fork a pool from the test process (``analysis/cli.py``,
# ``jobs > 1``), and a fork from a worker that has already loaded the TPU
# compiler or run a launcher leaves a child waiting on a lock for ever: it
# runs on a fresh worker, as it did while files went out most cases first.
# Then the long files whose names sort late, so that none of them starts
# last and is the run's tail; the rest keep the collection's order.
# (``scripts/tier1_times.py`` on a run's junit file shows the tail.)
LONGEST_FIRST = [
    "test_multiprocess", "test_analysis", "test_smallthinker",
    "test_moe_tpu_compile", "test_step_tpu_compile", "test_models_gpt",
    "test_remat_kernels", "test_testing_steps", "test_pipeline",
    "test_xing4_moe_mla", "test_sdar_moe",
]


def pytest_configure(config):
    # pytest-xdist hands the files out by their count of cases, most first
    # (``--loadscope-reorder``, its default), and the few long cases (a
    # step compiled for the chip, the graft entry) would start last.  Under
    # ``loadfile`` the order is the collection's, which the hook below sets.
    if getattr(config.option, "dist", "no") == "loadfile":
        assert hasattr(config.option, "loadscopereorder"), (
            "pytest-xdist no longer has --loadscope-reorder: see how it "
            "orders the files now, or tier-1's tail comes back")
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.stem, len(rank)))


def assert_trees_equal(got, want):
    """Exact-equality pytree comparison shared by the param-layout
    round-trip tests (pipeline/tensor-parallel unstackers)."""
    import numpy as _np

    jax.tree_util.tree_map(
        lambda g, w: _np.testing.assert_array_equal(
            _np.asarray(g), _np.asarray(w)
        ),
        got, want,
    )
