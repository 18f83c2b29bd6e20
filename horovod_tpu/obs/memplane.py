"""HBM memory plane: per-owner device-memory accounting, compiled
per-program breakdowns, and the OOM black box.

The observability stack explains where every millisecond (obs/trace.py)
and every FLOP (obs/profile.py) goes — this module is the missing
*byte* axis, with three legs:

* **Static accounting** — :func:`parse_memory_analysis` reads XLA's own
  post-compile memory breakdown (``compiled.memory_analysis()``:
  argument / output / temp / alias bytes) shape-tolerantly: the
  attribute-object form, a dict form, a single-element-list form, and
  an executable that exposes nothing at all (``source: unavailable`` —
  never a crash).  :func:`register_program` publishes one breakdown per
  compiled program as ``mem.compiled.*{program=…}`` gauges; the compile
  sites (engine fused allreduce, the overlap train step per mode, the
  slot engine's decode/assign) call it with the executable they just
  built, so per-program memory is a property of the artifact — the
  GSPMD argument: memory scaling is *why* sharding exists, so it must
  be measured per program.
* **Dynamic census** — :func:`census` buckets ``jax.live_arrays()`` by
  logical owner through a lightweight tagging registry
  (:func:`register_owner`: params / optimizer_state / grad_buckets /
  kv_cache suppliers; everything unclaimed is ``other``) and reads the
  backend's ``memory_stats()`` (bytes_in_use / peak / limit —
  None-tolerant: CPU reports nothing and the census says so instead of
  inventing an HBM).  Published as ``mem.{hbm_bytes_in_use,
  hbm_peak_bytes,hbm_limit_bytes,headroom_bytes,live_bytes}`` +
  ``mem.owner_bytes{owner=…}`` gauges; :func:`install_census` arms it
  as a registry collector so every snapshot (the live stream, the exit
  dump) refreshes the numbers for free.  The census is
  host-triggered: it sees the arrays alive *between* dispatches, not
  XLA's transient peak (docs/observability.md states this honestly).
* **OOM black box** — :func:`maybe_record_oom` (hooked into
  ``flightrec.record_exception``, so it fires on every death path that
  records its exception) detects a RESOURCE_EXHAUSTED and drops a
  ``mem.oom`` event carrying the last census and the dominant owner
  into the flight-recorder ring — the PyTorch-flight-recorder idea
  applied to memory: always-on bounded evidence that survives the
  crash, so the post-mortem can say "rank 3 died allocating in
  decode_step; kv_cache held 82% of tagged memory" instead of "OOM
  somewhere".  :func:`alloc_guard` is the ``mem_alloc`` fault point's
  consumer (``action=oom`` raises a backend-shaped RESOURCE_EXHAUSTED)
  so the whole path is deterministically chaos-testable.

KV occupancy (:func:`kv_occupancy`) is the pure math behind
``serve.kv.{allocated_bytes,live_bytes,waste_ratio}``: what the
contiguous fixed-row slot pool reserves for its busy slots vs the
positions actually written — the exact number ROADMAP item 1's paged
attention will attack, measured before it lands so its win is provable.

No jax import at module scope: the launcher imports obs eagerly and
must not pay (or hang on) a backend handshake for it.
"""

from __future__ import annotations

import os
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = [
    "OWNERS",
    "parse_memory_analysis",
    "register_program",
    "program_report",
    "reset_programs",
    "register_owner",
    "reset_owners",
    "census",
    "last_census",
    "install_census",
    "device_memory_stats",
    "dominant_owner",
    "kv_occupancy",
    "memory_record",
    "is_resource_exhausted",
    "resource_exhausted_error",
    "alloc_guard",
    "maybe_record_oom",
    "record_oom",
]

# The owner classes.  Free-form owners are accepted (a future subsystem
# can tag itself without touching this module) but the canonical five
# are what the docs, the digest and the post-mortem verdict talk about.
OWNERS = ("params", "optimizer_state", "grad_buckets", "kv_cache", "other")

# -- module state ------------------------------------------------------------
# REENTRANT locks: record_oom() runs from flightrec.record_exception,
# which excepthook/fatal-signal handlers call — a signal landing while
# the owning thread is mid-census must not self-deadlock the dying rank
# (hvdtpu-lint HVDC103, the PR-4 flush-deadlock class).
_lock = threading.RLock()
_owners: Dict[str, List[Callable]] = {}
_programs: Dict[str, dict] = {}
_last_census: Optional[dict] = None
_census_installed = False


# ---------------------------------------------------------------------------
# static accounting: compiled.memory_analysis()
# ---------------------------------------------------------------------------

# (breakdown key, memory_analysis attribute/dict key) pairs.
_MA_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
    ("alias_bytes", "alias_size_in_bytes"),
    ("generated_code_bytes", "generated_code_size_in_bytes"),
)


def parse_memory_analysis(compiled) -> dict:
    """Version-tolerant read of ``compiled.memory_analysis()``.

    Returns ``{"source": "memory_analysis", "argument_bytes": …,
    "output_bytes": …, "temp_bytes": …, "alias_bytes": …,
    "generated_code_bytes": …, "total_bytes": …}`` where
    ``total_bytes`` is the per-device footprint XLA accounts for one
    execution: arguments + outputs + temporaries, minus the aliased
    (donated) bytes that are counted on both sides.

    Tolerates every per-version shape: the ``CompiledMemoryStats``
    attribute object (jax 0.4.x), a plain dict, a single-element list
    of either, and an executable that exposes no analysis at all —
    those degrade to ``{"source": "unavailable"}``, never an exception
    (the ``flops_from_compiled`` contract, applied to bytes).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {"source": "unavailable"}
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return {"source": "unavailable"}
    out = {"source": "memory_analysis"}
    seen_any = False
    for key, field in _MA_FIELDS:
        if isinstance(ma, dict):
            v = ma.get(field)
        else:
            v = getattr(ma, field, None)
        try:
            v = int(v)
        except (TypeError, ValueError):
            v = None
        if v is not None:
            seen_any = True
            out[key] = v
        else:
            out[key] = 0
    if not seen_any:
        return {"source": "unavailable"}
    out["total_bytes"] = max(
        out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        - out["alias_bytes"], 0,
    )
    return out


def register_program(name: str, compiled=None, *, stats: Optional[dict] = None,
                     registry=None) -> dict:
    """Record one compiled program's memory breakdown and publish it as
    ``mem.compiled.*{program=name}`` gauges.  Call with the executable
    at the compile site (``stats=`` accepts a pre-parsed breakdown — the
    mem gate reuses it).  Re-registration overwrites: a recompile's
    numbers are the current truth.  Never raises — accounting is
    observability, not correctness."""
    try:
        if stats is None:
            stats = parse_memory_analysis(compiled)
        with _lock:
            _programs[name] = dict(stats)
        if stats.get("source") != "memory_analysis":
            return stats
        from .registry import get_registry  # noqa: PLC0415

        reg = registry if registry is not None else get_registry()
        for key, _ in _MA_FIELDS:
            reg.gauge(f"mem.compiled.{key}", program=name).set(
                stats.get(key, 0)
            )
        reg.gauge("mem.compiled.total_bytes", program=name).set(
            stats.get("total_bytes", 0)
        )
        return stats
    except Exception:
        return stats if isinstance(stats, dict) else {"source": "unavailable"}


def program_report() -> Dict[str, dict]:
    """``{program name -> breakdown}`` of everything registered so far
    (what ``memory_record`` embeds)."""
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


def reset_programs() -> None:
    """Drop registered program breakdowns (tests)."""
    with _lock:
        _programs.clear()


# ---------------------------------------------------------------------------
# dynamic census: owner tagging + jax.live_arrays + backend memory_stats
# ---------------------------------------------------------------------------


def register_owner(owner: str, supplier: Callable) -> None:
    """Tag a logical owner of device memory.  ``supplier`` is called at
    census time and returns the owner's CURRENT pytree (or None when
    the owner is gone — dead suppliers are pruned, so register through
    a weakref when the owner's lifetime is shorter than the process:
    ``register_owner("kv_cache", lambda r=weakref.ref(e): (r() or
    _G).cache)``-style).  Suppliers must be cheap: they run on every
    registry snapshot once :func:`install_census` armed the plane."""
    with _lock:
        _owners.setdefault(owner, []).append(supplier)


def reset_owners() -> None:
    """Drop every owner supplier (tests, or a full plane re-arm)."""
    with _lock:
        _owners.clear()


def _device_nbytes(leaf) -> Optional[int]:
    """Bytes this PROCESS's devices hold for one array leaf, computed
    from sharding METADATA only (``sharding.shard_shape`` x addressable
    device count) — a globally-sharded ZeRO buffer counts its local
    1/world, a replicated array counts one logical copy.  Deliberately
    never touches ``addressable_shards[...].data``: reading it mints a
    NEW live jax.Array view over the same buffer, which would make the
    census itself inflate the very ``jax.live_arrays()`` population it
    measures.  None for non-array leaves."""
    n = getattr(leaf, "nbytes", None)
    if n is None:
        return None
    try:
        n = int(n)
    except (TypeError, ValueError):
        return None
    sharding = getattr(leaf, "sharding", None)
    try:
        if sharding is not None and not getattr(
                leaf, "is_fully_replicated", True):
            shard_shape = sharding.shard_shape(leaf.shape)
            count = 1
            for dim in shard_shape:
                count *= int(dim)
            return count * leaf.dtype.itemsize \
                * max(len(sharding.addressable_devices), 1)
    except Exception:
        pass
    return n


def _buffer_key(arr):
    """Identity of an array's underlying device buffer: two jax.Array
    OBJECTS can wrap one buffer (``addressable_shards[...].data`` views,
    ``device_plane._local`` extraction), and counting both would
    double-book the bytes.  Falls back to object identity where the
    pointer is unavailable (multi-device sharded arrays)."""
    try:
        return ("ptr", arr.unsafe_buffer_pointer())
    except Exception:
        return ("id", id(arr))


def device_memory_stats() -> dict:
    """Backend memory stats summed over this process's local devices.
    ``{"source": "memory_stats", "bytes_in_use", "peak_bytes",
    "limit_bytes", "headroom_bytes"}`` — or ``{"source":
    "unavailable"}`` when no device reports (CPU returns None: there is
    no HBM, and pretending host RAM were one would poison every budget
    downstream)."""
    try:
        import jax  # noqa: PLC0415

        devices = jax.local_devices()
    except Exception:
        return {"source": "unavailable"}
    in_use = peak = limit = 0
    seen = False
    for d in devices:
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        seen = True
        in_use += int(ms.get("bytes_in_use", 0) or 0)
        peak += int(ms.get("peak_bytes_in_use", 0) or 0)
        limit += int(ms.get("bytes_limit", 0) or 0)
    if not seen:
        return {"source": "unavailable"}
    out = {
        "source": "memory_stats",
        "bytes_in_use": in_use,
        "peak_bytes": peak,
        "limit_bytes": limit or None,
    }
    out["headroom_bytes"] = (limit - in_use) if limit else None
    return out


def census(*, publish: bool = True, registry=None) -> dict:
    """One owner-bucketed pass over the live device arrays plus the
    backend stats.  Returns (and caches as :func:`last_census`)::

        {"source": "live_arrays" | "unavailable",
         "total_bytes": <sum of live array bytes on this process>,
         "owners": {"params": …, "kv_cache": …, …, "other": …},
         "device": <device_memory_stats()>}

    ``publish=True`` additionally sets the ``mem.*`` gauges.  Owner
    attribution is by object identity: a supplier's leaves ARE the live
    arrays (same Python objects), so no bytes are double-counted and
    everything untagged lands in ``other``."""
    global _last_census
    with _lock:
        suppliers = [(owner, list(fns)) for owner, fns in _owners.items()]
    owners: Dict[str, int] = {}
    claimed: Dict[Tuple, str] = {}
    dead: List[Tuple[str, Callable]] = []
    for owner, fns in suppliers:
        total = 0
        for fn in fns:
            try:
                tree = fn()
            except Exception:
                tree = None
            if tree is None:
                dead.append((owner, fn))
                continue
            try:
                import jax  # noqa: PLC0415

                leaves = jax.tree_util.tree_leaves(tree)
            except Exception:
                leaves = []
            for leaf in leaves:
                b = _device_nbytes(leaf)
                if b is None:
                    continue
                key = _buffer_key(leaf)
                if key in claimed:
                    continue
                claimed[key] = owner
                total += b
        owners[owner] = owners.get(owner, 0) + total
    if dead:
        with _lock:
            for owner, fn in dead:
                fns = _owners.get(owner)
                if fns and fn in fns:
                    fns.remove(fn)
    source = "unavailable"
    total_live = sum(owners.values())
    other = 0
    try:
        import jax  # noqa: PLC0415

        live = jax.live_arrays()
        source = "live_arrays"
    except Exception:
        live = None
    if live is not None:
        total_live = 0
        seen: set = set()
        for arr in live:
            b = _device_nbytes(arr)
            if b is None:
                continue
            key = _buffer_key(arr)
            if key in seen:
                continue  # a second view of a buffer already counted
            seen.add(key)
            total_live += b
            if key not in claimed:
                other += b
    # ADD to (not overwrite) any explicitly-registered "other" supplier:
    # free-form owners are legal, and their claimed bytes must not
    # vanish from every bucket just because they chose this name.
    owners["other"] = owners.get("other", 0) + other
    doc = {
        "source": source,
        "total_bytes": int(total_live),
        "owners": {k: int(v) for k, v in owners.items()},
        "device": device_memory_stats(),
    }
    with _lock:
        _last_census = doc
    if publish:
        _publish_census(doc, registry=registry)
    return doc


def _publish_census(doc: dict, registry=None) -> None:
    try:
        from .registry import get_registry  # noqa: PLC0415

        reg = registry if registry is not None else get_registry()
        reg.gauge("mem.live_bytes").set(doc.get("total_bytes", 0))
        for owner, b in (doc.get("owners") or {}).items():
            reg.gauge("mem.owner_bytes", owner=owner).set(b)
        dev = doc.get("device") or {}
        if dev.get("source") == "memory_stats":
            reg.gauge("mem.hbm_bytes_in_use").set(dev.get("bytes_in_use", 0))
            reg.gauge("mem.hbm_peak_bytes").set(dev.get("peak_bytes", 0))
            if dev.get("limit_bytes"):
                reg.gauge("mem.hbm_limit_bytes").set(dev["limit_bytes"])
                reg.gauge("mem.headroom_bytes").set(
                    dev.get("headroom_bytes") or 0
                )
    except Exception:
        pass  # gauges are observability, not correctness


def last_census() -> Optional[dict]:
    """The most recent :func:`census` result (what the OOM event
    falls back to when a fresh census cannot run inside the handler)."""
    with _lock:
        return dict(_last_census) if _last_census else None


def install_census(registry=None) -> None:
    """Arm the census as a registry collector: every snapshot (the live
    stream's publish round, the exit dump, ``collect_engine_gauges``)
    refreshes the ``mem.*`` gauges.  Idempotent."""
    global _census_installed
    with _lock:
        if _census_installed:
            return
        _census_installed = True
    from .registry import get_registry  # noqa: PLC0415

    reg = registry if registry is not None else get_registry()

    def _collect(r) -> None:
        census(publish=True, registry=r)

    reg.register_collector(_collect)


def reset_census() -> None:
    """Forget the cached census + installed-collector latch (tests;
    the collector itself dies with its registry)."""
    global _last_census, _census_installed
    with _lock:
        _last_census = None
        _census_installed = False


def dominant_owner(doc: Optional[dict] = None) -> Tuple[Optional[str], float]:
    """``(owner, share)`` of the biggest tagged-or-other bucket in a
    census (share of the census total).  ``(None, 0.0)`` on an empty
    census."""
    doc = doc or last_census()
    owners = (doc or {}).get("owners") or {}
    total = sum(owners.values())
    if not total:
        return None, 0.0
    owner = max(sorted(owners), key=lambda k: owners[k])
    return owner, owners[owner] / total


def memory_record() -> dict:
    """The record-embeddable view: one fresh census + every registered
    per-program breakdown.  Safe anywhere (before jax ever
    initialized the census reports ``source: unavailable`` and the
    programs dict is empty)."""
    try:
        c = census(publish=False)
    except Exception:
        c = last_census() or {"source": "unavailable"}
    return {"census": c, "programs": program_report()}


# ---------------------------------------------------------------------------
# KV occupancy: allocated vs live bytes of a contiguous slot pool
# ---------------------------------------------------------------------------


def kv_occupancy(positions: Sequence[int], active_slots: Sequence[int],
                 cache_len: int, bytes_per_position: float,
                 pool_bytes: Optional[int] = None) -> dict:
    """Occupancy of a fixed-row KV slot pool.

    * ``allocated_bytes`` — what the contiguous design reserves for the
      busy slots: slots-in-use x worst-case ``cache_len`` rows.
    * ``live_bytes`` — positions those slots actually wrote:
      ``sum(pos[slot])`` x bytes-per-position.
    * ``waste_ratio`` — ``1 - live/allocated`` (0.0 when idle): the
      tail a short request wastes in a long-cache pool, i.e. the bytes
      paged attention (ROADMAP item 1) reclaims.
    * ``pool_bytes`` — the whole pool's resident footprint (free slots
      included), when the caller knows it.
    """
    slots = sorted(set(int(s) for s in active_slots))
    allocated = len(slots) * int(cache_len) * float(bytes_per_position)
    live = 0.0
    for s in slots:
        pos = int(positions[s]) if 0 <= s < len(positions) else 0
        live += min(max(pos, 0), int(cache_len)) * float(bytes_per_position)
    out = {
        "slots_in_use": len(slots),
        "allocated_bytes": int(allocated),
        "live_bytes": int(live),
        "waste_ratio": (1.0 - live / allocated) if allocated else 0.0,
    }
    if pool_bytes is not None:
        out["pool_bytes"] = int(pool_bytes)
    return out


# ---------------------------------------------------------------------------
# OOM black box
# ---------------------------------------------------------------------------


class ResourceExhaustedError(RuntimeError):
    """Stand-in for the backend's RESOURCE_EXHAUSTED when jaxlib's
    XlaRuntimeError cannot be constructed (stripped environments)."""


def resource_exhausted_error(message: str) -> BaseException:
    """A backend-shaped RESOURCE_EXHAUSTED: the real
    ``jaxlib.xla_extension.XlaRuntimeError`` when available (so
    ``except XlaRuntimeError`` handlers and the OOM detector both treat
    the injected death exactly like a real allocator failure), else the
    local stand-in."""
    if not message.startswith("RESOURCE_EXHAUSTED"):
        message = "RESOURCE_EXHAUSTED: " + message
    try:
        from jaxlib.xla_extension import XlaRuntimeError  # noqa: PLC0415

        return XlaRuntimeError(message)
    except Exception:
        return ResourceExhaustedError(message)


def is_resource_exhausted(exc: BaseException) -> bool:
    """Whether an exception is the backend's out-of-device-memory
    signature: XLA surfaces allocator failures as RuntimeErrors whose
    message leads with RESOURCE_EXHAUSTED (plus jaxlib's
    XlaRuntimeError type), and the injected fault is built to match."""
    if isinstance(exc, ResourceExhaustedError):
        return True
    try:
        return "RESOURCE_EXHAUSTED" in str(exc)
    except Exception:
        return False


def record_oom(where: str = "", exc: Optional[BaseException] = None) -> dict:
    """Drop a ``mem.oom`` event (last census + dominant owner) into the
    flight-recorder ring — the memory half of the black box.  Returns
    the event's parsed fields (tests assert on them)."""
    try:
        doc = census(publish=False)
    except Exception:
        doc = last_census() or {}
    owner, share = dominant_owner(doc)
    owners = (doc or {}).get("owners") or {}
    dev = (doc or {}).get("device") or {}
    fields = {
        "where": where or "?",
        "owner": owner or "?",
        "share": round(share, 4),
        "owner_bytes": owners.get(owner, 0) if owner else 0,
        "total_bytes": (doc or {}).get("total_bytes", 0),
        "in_use": dev.get("bytes_in_use"),
        "limit": dev.get("limit_bytes"),
    }
    detail = " ".join(
        f"{k}={v}" for k, v in fields.items() if v is not None
    )
    try:
        from . import flightrec  # noqa: PLC0415

        flightrec.record("mem.oom", name=where or (owner or ""),
                         detail=detail)
    except Exception:
        pass
    return fields


def maybe_record_oom(exc: BaseException, where: str = "") -> bool:
    """Record the OOM black-box event iff ``exc`` is a
    RESOURCE_EXHAUSTED.  Hooked into ``flightrec.record_exception`` so
    every death path that records its exception gets the memory story
    for free; safe to call redundantly (each call appends one ring
    event — the post-mortem reads the newest)."""
    if not is_resource_exhausted(exc):
        return False
    if getattr(exc, "_hvdtpu_oom_recorded", False):
        # Already black-boxed at the allocation site (alloc_guard) with
        # the PRECISE program name — the generic death-path hook must
        # not append a newer, vaguer event (the post-mortem reads the
        # newest).
        return True
    record_oom(where=where, exc=exc)
    try:
        exc._hvdtpu_oom_recorded = True
    except Exception:
        pass
    return True


def alloc_guard(where: str, *, rank: Optional[int] = None) -> None:
    """The ``mem_alloc`` fault point's consumer: call on an
    allocation-heavy path (the serve decode/prefill steps) so
    ``HVDTPU_FAULT_SPEC=mem_alloc:action=oom`` deterministically raises
    a backend-shaped RESOURCE_EXHAUSTED there — the chaos input the
    whole OOM black-box path (event, post-mortem verdict) is tested
    against.  Near-free when no fault spec is loaded."""
    from ..testing import faults  # noqa: PLC0415

    if not faults.active():
        return
    action = faults.maybe_fail("mem_alloc", rank=rank, name=where)
    if action == "oom":
        err = resource_exhausted_error(
            f"RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            f"in {where} (injected by HVDTPU_FAULT_SPEC mem_alloc)"
        )
        # Black-box NOW, at the allocation site, with the precise
        # program name — the death-path hook sees the marker and keeps
        # this event as the newest memory story.
        record_oom(where=where, exc=err)
        try:
            err._hvdtpu_oom_recorded = True
        except Exception:
            pass
        raise err


# Optional env knob: arming the census at init time for any worker
# (serve_worker and bench arm it explicitly; a training job can opt in
# without code changes).
CENSUS_ENV = "HVDTPU_MEM_CENSUS"


def maybe_install_from_env() -> None:
    """Arm the census collector when ``HVDTPU_MEM_CENSUS=1`` (called
    from worker init paths that already import the obs plane)."""
    if os.environ.get(CENSUS_ENV, "") in ("1", "true", "on", "yes"):
        install_census()


def accounting_armed() -> bool:
    """Whether the memory plane is armed in this process (census
    collector installed, or ``HVDTPU_MEM_CENSUS=1``).  Compile sites
    whose registration costs a real extra compile (the engine's fused
    allreduce AOT probe) consult this so the cost lands only on jobs
    that asked for the plane — bench and the serving worker arm it;
    a bare unit-test engine spin-up stays exactly as cheap as before.
    Sites where the artifact is already in hand (slot engine, overlap,
    bench) register unconditionally: their registration is free."""
    if _census_installed:
        return True
    return os.environ.get(CENSUS_ENV, "") in ("1", "true", "on", "yes")
