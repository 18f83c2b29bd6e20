"""Process/topology bootstrap for horovod_tpu.

TPU-native analog of the reference's ``HorovodBasics`` ctypes layer
(reference: horovod/common/basics.py:22-66) and the C init path
(horovod/common/operations.cc:604-650).  Where the reference spawns a
background MPI/Gloo controller thread per process, the TPU build wires up
``jax.distributed`` (the JAX coordination service plays the role of the Gloo
HTTP rendezvous, reference horovod/common/gloo/gloo_context.cc:113-157) and
builds named device meshes over which XLA collectives compile.

Rank semantics
--------------
The reference runs one process per accelerator, so ``rank() == device``.
On TPU one process owns several chips, so the concepts split:

* ``rank()`` / ``size()``            -- process-level (one per host by default).
  This is what the eager per-op engine coordinates over, exactly like the
  reference controller negotiates over MPI ranks.
* ``local_rank()`` / ``local_size()`` -- process index within the host
  (reference: horovod/common/mpi/mpi_controller.cc:25-81 local_comm split).
* ``cross_rank()`` / ``cross_size()`` -- one-process-per-host axis
  (reference Communicator::CROSS, horovod/common/common.h:111-115).
* ``num_devices()`` / ``device_rank()`` -- chip-level; this is the width of
  the data-parallel mesh axis the jit path psums over, and the number that
  matters for scaling efficiency.

Environment contract (set by ``hvdrun``, mirroring HOROVOD_RANK/... set by
the reference launcher, horovod/run/gloo_run.py:143-165):

    HVDTPU_RANK / HVDTPU_SIZE
    HVDTPU_LOCAL_RANK / HVDTPU_LOCAL_SIZE
    HVDTPU_CROSS_RANK / HVDTPU_CROSS_SIZE
    HVDTPU_COORDINATOR        host:port of the jax.distributed coordinator
    HVDTPU_CONTROLLER_PORT    base port for the eager-engine controller mesh
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np

__all__ = [
    "init",
    "shutdown",
    "is_initialized",
    "rank",
    "size",
    "local_rank",
    "local_size",
    "cross_rank",
    "cross_size",
    "num_devices",
    "device_rank",
    "is_homogeneous",
    "slice_id",
    "num_slices",
    "slice_size",
    "slice_of_rank",
    "mesh",
    "global_topology",
    "DP_AXIS",
    "CROSS_AXIS",
    "LOCAL_AXIS",
    "SLICE_AXIS",
]

# Canonical mesh axis names.  DP_AXIS is the flat data-parallel axis every
# collective defaults to (the analog of Communicator::GLOBAL); CROSS/LOCAL
# form the 2D hierarchical mesh (DCN x ICI), the analog of the reference's
# cross/local communicators used by NCCLHierarchicalAllreduce
# (horovod/common/ops/nccl_operations.cc:162-300).
DP_AXIS = "hvd"
CROSS_AXIS = "hvd_cross"
LOCAL_AXIS = "hvd_local"
# Outermost axis of the 3-level (slice, host, chip) multislice mesh:
# collectives over SLICE_AXIS ride DCN, everything inside a slice rides
# ICI (the fabric split NCCLHierarchicalAllreduce reasons about,
# nccl_operations.cc:218-229, mapped onto TPU pods).
SLICE_AXIS = "hvd_slice"


class NotInitializedError(RuntimeError):
    def __init__(self) -> None:
        super().__init__(
            "horovod_tpu has not been initialized; call horovod_tpu.init() first."
        )


@dataclass
class Topology:
    """Static view of the job, fixed at init() (SPMD world is static;
    the reference's dynamic Join story is handled at the op layer)."""

    process_rank: int
    process_count: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int
    devices: Sequence[jax.Device] = field(default_factory=list)
    homogeneous: bool = True
    # Slice partition of the job (ICI within a slice, DCN between):
    # devices split into num_slices contiguous equal groups; slice_id is
    # the group this process's devices live in.  1 slice = single-pod
    # job, every fabric-aware path degenerates to flat.
    num_slices: int = 1
    slice_id: int = 0
    # Whether init() started jax.distributed itself; shutdown() only tears
    # down what it owns (≙ the reference's MPIContextManager negotiating
    # MPI_Init/Finalize ownership, horovod/common/mpi/mpi_context.cc).
    owns_jax_distributed: bool = False

    @property
    def num_devices(self) -> int:
        return len(self.devices)


_state_lock = threading.Lock()
_topology: Optional[Topology] = None
_mesh_cache: dict = {}


def _env_int(name: str, default: int) -> int:
    value = os.environ.get(name)
    return int(value) if value not in (None, "") else default


def resolve_slice_partition(
    world: int,
    proc: int,
    devices: Sequence,
    env: Optional[dict] = None,
) -> tuple:
    """Resolve the slice partition of the job -> ``(num_slices, slice_id)``.

    Priority (each level validated, invalid values downgrade to the next
    with one warning rather than killing the job):

    1. ``HVDTPU_NUM_SLICES``  — forced count of contiguous process blocks.
    2. ``HVDTPU_SLICE_SIZE``  — forced processes-per-slice (the CPU/dev
       simulation knob: a 4-proc world with SLICE_SIZE=2 behaves like
       two 2-host slices, so every multislice code path is testable on
       a laptop).
    3. Platform discovery — ``jax.Device.slice_index`` is populated on
       real multislice TPU deployments; distinct values define slices.
    4. Single slice.

    A forced partition must divide the world evenly (equal slices are
    what make the hierarchical schedule's shard math rank-symmetric).
    Pure function of its inputs so the partition logic is unit-testable
    without re-initializing a topology.
    """
    from .utils.logging import get_logger  # noqa: PLC0415

    log = get_logger("basics")
    e = os.environ if env is None else env

    def _val(name):
        raw = e.get(name)
        try:
            return int(raw) if raw not in (None, "") else 0
        except ValueError:
            log.warning("%s=%r is not an integer; ignoring", name, raw)
            return 0

    # The unit a forced partition divides: processes in a real multi-proc
    # world, devices in a single-process world (where SLICE_SIZE means
    # chips-per-slice — the 8-virtual-device in-process test topology).
    units = world if world > 1 else max(len(devices), 1)
    n = _val("HVDTPU_NUM_SLICES")
    if n <= 0:
        ssize = _val("HVDTPU_SLICE_SIZE")
        if ssize > 0:
            if units % ssize:
                log.warning(
                    "HVDTPU_SLICE_SIZE=%d does not divide the %d-unit "
                    "world; running single-slice", ssize, units,
                )
            else:
                n = units // ssize
    if n > 1:
        if units % n:
            log.warning(
                "forced slice count %d does not divide the %d-unit world; "
                "running single-slice", n, units,
            )
            return 1, 0
        return n, (proc // (world // n)) if world > 1 else 0
    if n == 1:
        return 1, 0
    # Platform discovery: slice_index exists (and differs) only on real
    # multislice TPU deployments.
    try:
        indices = sorted(
            {getattr(d, "slice_index", None) for d in devices} - {None}
        )
    except TypeError:
        indices = []
    if len(indices) > 1:
        mine = sorted(
            {
                getattr(d, "slice_index", None)
                for d in devices
                if getattr(d, "process_index", 0) == proc
            }
            - {None}
        )
        if len(mine) == 1:
            return len(indices), indices.index(mine[0])
        log.warning(
            "process %d spans multiple slices %s; treating the job as "
            "single-slice (hierarchical collectives need slice-aligned "
            "processes)", proc, mine,
        )
    return 1, 0


def init(comm=None) -> Topology:
    """Initialize the framework (reference: horovod_init, operations.cc:663).

    Safe to call more than once (the reference spin-waits on
    initialization_done, operations.cc:646-648; here re-init is a no-op).

    ``comm`` is accepted for API compatibility with the reference's
    sub-communicator init (horovod/common/basics.py:33-65) but only the
    default (whole-world) communicator is supported on TPU, where process
    membership is fixed by the coordination service.
    """
    global _topology
    t_start = time.perf_counter()
    with _state_lock:
        if _topology is not None:
            return _topology
        if comm is not None and comm not in ([], None):
            raise ValueError(
                "horovod_tpu.init(comm=...) sub-communicators are not supported; "
                "the TPU world is defined by the coordination service."
            )

        world = _env_int("HVDTPU_SIZE", 1)
        proc = _env_int("HVDTPU_RANK", 0)
        coordinator = os.environ.get("HVDTPU_COORDINATOR")

        owns_distributed = False
        if world > 1 and not _jax_distributed_active():
            if coordinator is None:
                raise RuntimeError(
                    "HVDTPU_SIZE > 1 but HVDTPU_COORDINATOR is unset; launch with "
                    "hvdrun or set the rendezvous environment explicitly."
                )
            # Multi-process CPU worlds (the test/dev topology, SURVEY.md §4)
            # need a CPU collectives backend; jax's is gloo — the very
            # library the reference uses for its CPU data path.
            platforms = (jax.config.jax_platforms or "").split(",")
            if "cpu" in platforms:
                try:
                    jax.config.update(
                        "jax_cpu_collectives_implementation", "gloo"
                    )
                except Exception:  # already initialized or unknown option
                    pass
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world,
                process_id=proc,
                initialization_timeout=_env_int("HVDTPU_START_TIMEOUT", 300),
            )
            owns_distributed = True

        devices = tuple(jax.devices())
        local_devices = tuple(jax.local_devices())
        # Homogeneity check: the reference allgathers local sizes and flags
        # mixed hosts (mpi_controller.cc:46-81).  Here device counts per
        # process are visible globally through the platform client.
        per_proc = {}
        for d in devices:
            per_proc[d.process_index] = per_proc.get(d.process_index, 0) + 1
        homogeneous = len(set(per_proc.values())) <= 1

        eff_world = world if world > 1 else 1
        eff_proc = proc if world > 1 else 0
        n_slices, slice_i = resolve_slice_partition(
            eff_world, eff_proc, devices
        )
        _topology = Topology(
            process_rank=proc if world > 1 else 0,
            process_count=world if world > 1 else 1,
            local_rank=_env_int("HVDTPU_LOCAL_RANK", 0),
            local_size=_env_int("HVDTPU_LOCAL_SIZE", 1),
            cross_rank=_env_int("HVDTPU_CROSS_RANK", proc if world > 1 else 0),
            cross_size=_env_int("HVDTPU_CROSS_SIZE", world if world > 1 else 1),
            devices=devices,
            homogeneous=homogeneous,
            num_slices=n_slices,
            slice_id=slice_i,
            owns_jax_distributed=owns_distributed,
        )
        del local_devices
        # The hierarchical knob without a multi-slice topology is a
        # no-op; one clear line beats silent downgrade (the flat XLA
        # psum is already torus-optimal within a single slice, so this
        # is a downgrade in name only — but the user should know).
        from .utils import env as envmod  # noqa: PLC0415

        if n_slices < 2 and envmod.env_bool(envmod.HIERARCHICAL_ALLREDUCE):
            from .utils.logging import get_logger  # noqa: PLC0415

            get_logger("basics").warning(
                "--hierarchical-allreduce requested but this topology "
                "has a single slice; flat allreduce is already optimal "
                "on one ICI domain — knob downgraded (force a partition "
                "with HVDTPU_NUM_SLICES/HVDTPU_SLICE_SIZE to test the "
                "two-fabric path)"
            )

    # Arm the observability plane: first registry use installs the
    # HVDTPU_METRICS_DUMP exit hook, so every initialized rank leaves a
    # metrics dump even on the jit-only path that never starts an engine.
    from .obs import get_registry  # noqa: PLC0415

    get_registry().gauge("process.rank").set(
        _topology.process_rank
    )
    # Black box: arm the flight recorder's death-path hooks (excepthook,
    # threading.excepthook, SIGTERM/SIGABRT/SIGUSR1) so a rank killed by
    # a signal — including the launcher's own escalation — still flushes
    # its event ring, the metrics dump and the final live delta.
    from .obs import flightrec as _flightrec  # noqa: PLC0415

    _flightrec.install_death_hooks()
    _flightrec.record(
        "init", name=f"rank{_topology.process_rank}",
        detail=f"world={_topology.process_count}",
    )
    # Live telemetry streaming (obs/stream.py): a no-op unless the
    # launcher exported HVDTPU_LIVE_STATS_SECS + a KV endpoint.
    from .obs import stream as _obs_stream  # noqa: PLC0415

    _obs_stream.maybe_start_from_env()

    # Start the native eager engine NOW in multi-process worlds (reference
    # behavior: InitializeHorovodOnce spawns the background thread at init,
    # operations.cc:604-650).  Every rank's engine must cycle for
    # negotiation and stall inspection to work even when this rank hasn't
    # enqueued anything yet.  Only the native engine starts eagerly — it
    # negotiates over its own TCP mesh; the pure-Python fallback rides jax
    # collectives, which must not run concurrently with main-thread jit
    # collectives, so it stays lazy (started on first eager op).
    if world > 1:
        choice = os.environ.get("HVDTPU_EAGER_ENGINE", "auto").lower()
        if choice != "python":
            from .runtime import native  # noqa: PLC0415

            if choice == "native" or native.native_available():
                from . import _engine_registry  # noqa: PLC0415

                _engine_registry.get_engine()
    # The set-up log's record of this call (obs/profile.py): device
    # discovery, jax.distributed where it was started here, the
    # topology, the hooks.
    from .obs import profile as _profile  # noqa: PLC0415

    _profile.log_interval("init", "hvd.init", t_start)
    return _topology


def _jax_distributed_active() -> bool:
    try:
        from jax._src import distributed  # noqa: PLC0415

        return distributed.global_state.client is not None
    except Exception:  # pragma: no cover - internal layout shift
        return jax.process_count() > 1


def shutdown() -> None:
    """Tear down state (reference: horovod_shutdown, operations.cc:688).

    Stops the eager engine if running; leaves the JAX runtime alive (XLA
    client shutdown is owned by the process, as MPI_Finalize ownership is
    negotiated in the reference's MPIContextManager)."""
    global _topology
    from . import _engine_registry  # noqa: PLC0415

    # Engine teardown happens OUTSIDE the state lock: it joins the
    # background thread (bounded 30 s), and a wedged engine holding
    # _state_lock that long would freeze every concurrent rank()/init()
    # caller behind the teardown (hvdtpu-lint HVDC102).  Ordering is
    # safe: the engine's own shutdown path never reads the topology
    # state this lock guards.
    _engine_registry.shutdown_engine()
    with _state_lock:
        # The jax.distributed coordination service is deliberately left
        # running: rank 0 hosts it, and tearing it down here would kill
        # peers still mid-collective (uneven shutdown is normal — that's
        # what Join is for).  JAX owns its teardown at process exit, like
        # the reference leaves MPI_Finalize to the owning context
        # (mpi/mpi_context.cc MPIContextManager).
        _topology = None
        _mesh_cache.clear()


def is_initialized() -> bool:
    return _topology is not None


def global_topology() -> Topology:
    if _topology is None:
        raise NotInitializedError()
    return _topology


def rank() -> int:
    """Process rank (reference: horovod_rank, operations.cc:696)."""
    return global_topology().process_rank


def size() -> int:
    """Process count (reference: horovod_size, operations.cc:708)."""
    return global_topology().process_count


def local_rank() -> int:
    """Rank within the host (reference: horovod_local_rank, operations.cc:702)."""
    return global_topology().local_rank


def local_size() -> int:
    """Processes on this host (reference: horovod_local_size, operations.cc:714)."""
    return global_topology().local_size


def cross_rank() -> int:
    return global_topology().cross_rank


def cross_size() -> int:
    return global_topology().cross_size


def num_devices() -> int:
    """Total chips in the job == width of the DP mesh axis."""
    return global_topology().num_devices


def device_rank(device: Optional[jax.Device] = None) -> int:
    """Global index of a chip in the DP mesh (first local chip by default)."""
    topo = global_topology()
    if device is None:
        device = jax.local_devices()[0]
    return list(topo.devices).index(device)


def is_homogeneous() -> bool:
    """Reference: horovod_is_homogeneous (operations.cc:720)."""
    return global_topology().homogeneous


def slice_id() -> int:
    """Which slice this process's devices live in (0 on single-slice
    jobs).  Slices are the DCN-connected partitions of a multislice job;
    everything within a slice shares ICI."""
    return global_topology().slice_id


def num_slices() -> int:
    """Number of DCN-connected slices in the job (1 = single-pod)."""
    return global_topology().num_slices


def slice_size() -> int:
    """Ranks per slice (the ``local_size`` of the two-fabric hierarchy:
    the cross-slice phase of hierarchical allreduce carries
    1/slice_size of the bytes).  On the single-process dev topology —
    where the forced partition splits DEVICES, not processes — this is
    chips per slice, and it is always >= 1."""
    topo = global_topology()
    if topo.num_slices <= 1:
        return topo.process_count
    if (
        topo.process_count > 1
        and topo.process_count % topo.num_slices == 0
    ):
        return topo.process_count // topo.num_slices
    if topo.num_devices % topo.num_slices == 0:
        return max(topo.num_devices // topo.num_slices, 1)
    return 1


def slice_of_rank(rank: int) -> int:
    """Slice containing process ``rank`` (contiguous-block partition —
    the single mapping the engine, the straggler tagger and the launcher
    blacklist all share, so a slice-level verdict can never name a
    different slice than the data plane ran on)."""
    topo = global_topology()
    if topo.num_slices <= 1 or topo.process_count % topo.num_slices:
        return 0
    return int(rank) // (topo.process_count // topo.num_slices)


# -- feature probes (reference horovod_mpi_built/_enabled, horovod_gloo_*,
# horovod_nccl_built, horovod_mpi_threads_supported — operations.cc:726-799,
# basics.py:131-210).  The TPU build's transports are XLA collectives and
# the native TCP engine; the reference-named probes answer for migrating
# scripts that gate on them. --


def xla_collectives_built() -> bool:
    """The jit/SPMD data path (≙ nccl_built): always compiled in."""
    return True


def native_engine_built() -> bool:
    """The C++ eager engine (≙ gloo_built): True when the shared library
    is present."""
    from .runtime import native  # noqa: PLC0415

    return native.native_available()


def mpi_built() -> bool:
    """MPI does not exist in the TPU design (coordination is
    jax.distributed); always False, so reference scripts take their gloo
    branch, whose semantics the engine provides."""
    return False


mpi_enabled = mpi_built


def mpi_threads_supported() -> bool:
    """Reference basics.mpi_threads_supported: meaningless without MPI;
    False (scripts use it only to decide multi-comm setups)."""
    return False


def gloo_built() -> bool:
    """≙ reference gloo_built: the engine's TCP data path stands in for
    gloo and is available whenever the package is (native or Python)."""
    return True


gloo_enabled = gloo_built


def nccl_built() -> bool:
    """≙ reference nccl_built: the device collective path here is XLA over
    ICI, reported through xla_collectives_built; NCCL itself: False."""
    return False


def ccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def slice_grid(
    devices: Sequence, num_slices: int, hosts: int
) -> np.ndarray:
    """Reshape a flat device list into the 3-level (slice, host, chip)
    view: contiguous device blocks per slice, contiguous per host within
    it.  ``hosts`` is the number of host groups WITHIN one slice (1 when
    the host level degenerates, e.g. a single-process dev world forced
    into chip-level slices).  Pure function for unit-testability."""
    devices = np.asarray(devices, dtype=object)
    total = devices.size
    if num_slices < 1 or total % num_slices:
        raise ValueError(
            f"cannot partition {total} devices into {num_slices} slices"
        )
    per_slice = total // num_slices
    if hosts < 1 or per_slice % hosts:
        raise ValueError(
            f"cannot split a {per_slice}-device slice over {hosts} hosts"
        )
    return devices.reshape(num_slices, hosts, per_slice // hosts)


def mesh(shape: str = "flat") -> jax.sharding.Mesh:
    """Build (and cache) the named device mesh collectives compile over.

    ``flat``          -> 1D mesh, axis DP_AXIS over every chip.
    ``hierarchical``  -> 2D mesh (CROSS_AXIS=hosts, LOCAL_AXIS=chips/host),
                         the TPU analog of the reference's local/cross
                         communicators (mpi/mpi_context.cc; used by
                         NCCLHierarchicalAllreduce, nccl_operations.cc:162-300).
                         Collectives over LOCAL_AXIS ride ICI; CROSS_AXIS
                         rides DCN.
    ``slice``         -> 3D mesh (SLICE_AXIS=slices, CROSS_AXIS=hosts
                         within a slice, LOCAL_AXIS=chips/host): the full
                         two-fabric view of a multislice job.  SLICE_AXIS
                         collectives ride DCN; the inner two axes ride
                         ICI.  Requires a multi-slice topology (forced
                         via HVDTPU_NUM_SLICES/HVDTPU_SLICE_SIZE on dev
                         worlds, discovered on real multislice TPU).
    """
    topo = global_topology()
    if shape in _mesh_cache:
        return _mesh_cache[shape]
    devices = np.asarray(topo.devices, dtype=object)
    if shape == "flat":
        m = jax.sharding.Mesh(devices, (DP_AXIS,))
    elif shape == "hierarchical":
        hosts = topo.cross_size if topo.process_count > 1 else 1
        if len(devices) % max(hosts, 1) != 0:
            raise ValueError(
                f"cannot build hierarchical mesh: {len(devices)} devices over "
                f"{hosts} hosts is uneven"
            )
        per = len(devices) // max(hosts, 1)
        m = jax.sharding.Mesh(
            devices.reshape(hosts, per), (CROSS_AXIS, LOCAL_AXIS)
        )
    elif shape == "slice":
        if topo.num_slices < 2:
            raise ValueError(
                "mesh('slice') needs a multi-slice topology; force one "
                "with HVDTPU_NUM_SLICES / HVDTPU_SLICE_SIZE on dev worlds"
            )
        hosts = (
            topo.process_count // topo.num_slices
            if topo.process_count > 1
            and topo.process_count % topo.num_slices == 0
            else 1
        )
        m = jax.sharding.Mesh(
            slice_grid(devices, topo.num_slices, hosts),
            (SLICE_AXIS, CROSS_AXIS, LOCAL_AXIS),
        )
    else:
        raise ValueError(f"unknown mesh shape {shape!r}")
    _mesh_cache[shape] = m
    return m
