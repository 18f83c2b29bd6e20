"""Deterministic, env-driven fault injection.

Chaos testing the elastic subsystem needs real failures — a rank dying
mid-step, a checkpoint write erroring on rank 0 — that are *reproducible*
under ``JAX_PLATFORMS=cpu`` in tier-1.  This registry provides them: the
launcher (or a test) sets ``HVDTPU_FAULT_SPEC`` and the injection points
threaded through production code fire exactly where the spec says, every
run, no timing dependence.

Grammar::

    HVDTPU_FAULT_SPEC := fault ("," fault)*
    fault             := point (":" key "=" value)*
    key               := rank | step | epoch | count | action | code | name

    HVDTPU_FAULT_SPEC="ckpt_write:step=3:rank=0,worker_exit:step=5:rank=2"

* ``point`` — the injection-site name.  Sites wired so far:
  ``ckpt_write`` (checkpoint.py rank-0 write), ``enqueue`` (eager-engine
  enqueue path), ``worker_exit`` (elastic context, once per collective;
  also run/task_fn.py at function start), ``task_fn`` (run/task_fn.py
  before the user function runs), ``shard_write`` (ckpt/sharded.py
  per-rank shard write), ``replica_push`` (ckpt/replica.py peer-replica
  push after each commit), ``trace_flush`` (obs/trace.py span-dump
  path), ``mem_alloc`` (obs/memplane.py alloc_guard on the serve
  decode/prefill paths), ``grad_ready`` (the reduced-gradient landing
  sites — ops/eager.py's blocking allreduce after synchronize, and
  elastic/context.py's KV allreduce after the total is computed; fired
  AFTER the reduction so a corruption lands on one rank's copy of the
  *agreed* result, the silent-data-corruption shape the divergence
  sentinel exists to catch — corrupting before the reduce would spread
  identically to every rank and diverge nothing).
* ``rank`` — only fire on this rank (resolved from the ``rank=`` call
  argument, else ``HVDTPU_RANK``, else ``HVDTPU_ELASTIC_RANK``).  Absent
  means any rank.
* ``step`` — fire when the observed step equals N.  Call sites with a
  natural step (checkpoint saves) pass it explicitly; sites without one
  (enqueue) use the per-point 1-based invocation counter.  Absent means
  the first eligible call.
* ``epoch`` — the rendezvous epoch to fire in, default 0 (``any`` to
  disable the filter).  The default is what keeps chaos runs convergent:
  a respawned worker re-executes the same steps at epoch >= 1 and must
  NOT re-trigger the fault that killed its predecessor.
* ``count`` — times to fire (default 1).
* ``action`` — ``raise`` (default) raises :class:`InjectedFault`;
  ``raise:<ExcName>`` raises that builtin exception instead (e.g.
  ``raise:ValueError``) — the deterministic driver for the excepthook
  dump path; ``exit`` calls ``os._exit(code)``; ``abort`` delivers
  SIGABRT to this process via ``signal.raise_signal`` (no Python
  cleanup, no atexit — but the flight recorder's fatal-signal handler
  still runs, which is exactly the death the signal-dump path is
  chaos-tested against); ``hang`` blocks the calling thread
  forever (daemon threads — heartbeats — keep running: the exact
  signature of a deadlocked training thread, which is what the
  progress-beat staleness policy exists to catch);
  ``delay:<ms>`` sleeps the calling thread for that many milliseconds
  and then CONTINUES (default 1000) — a deterministic straggler, the
  chaos input the live telemetry plane's attribution is tested against;
  ``corrupt_write`` instructs the call site to flip bytes in the data it
  is about to write (the site receives the action name back from
  :func:`maybe_fail` and applies :func:`corrupt_bytes` — a deterministic
  torn/corrupted shard, the chaos input checksum validation is tested
  against); ``drop_replica`` instructs the call site to suppress the
  write entirely (the peer-replica push path — a deterministically
  stale replica); ``trace_drop`` instructs the span-flush path
  (obs/trace.py, point ``trace_flush``) to suppress the next span dump
  on a rank — the deterministic missing-rank input trace-merge's
  degraded handling is chaos-tested against; ``swap_abort`` instructs
  the weight hot-swap path (serve/service.py, point ``swap_commit`` —
  fired after shard prefetch succeeded, before the version flip is
  applied) to ``os._exit`` the rank — the deterministic mid-swap death
  the single-version convergence gate is chaos-tested against;
  ``scale_fail`` instructs the launcher's autoscale grow path (point
  ``scale_admit``) to treat the standby host as refusing admission —
  the deterministic failed-grow input the exponential-backoff policy
  is chaos-tested against; ``oom`` instructs an allocation-heavy call
  site (point ``mem_alloc``, consumed through
  ``obs.memplane.alloc_guard``) to raise a backend-shaped
  RESOURCE_EXHAUSTED — the deterministic out-of-device-memory input
  the OOM black box (``mem.oom`` flight-recorder event + post-mortem
  memory verdict) is chaos-tested against; ``frontend_exit`` instructs
  a front-door ingest pump (serve/frontend.py, point ``frontend_beat``
  — fired at the top of each pump round, with the pump's frontend id
  as the rank and its beat counter as the step) to die abruptly
  mid-stream without draining — the deterministic frontend death the
  heartbeat-takeover chaos gate is tested against; ``flip_bits``
  instructs a ``grad_ready`` site to XOR one exponent bit of one
  element of the reduced gradient it is about to hand back (element
  chosen by ``crc32(rank:step:name)`` — deterministic per rank, step
  and tensor, finite-in/finite-out, the canonical SDC bit flip);
  ``nan_inject`` instructs the same site to overwrite that element
  with NaN (the nonfinite-provenance chaos input).  Both are applied
  by the site via :func:`corrupt_grad`.
  ``worker_exit``/``task_fn`` points default to ``exit``.
* ``code`` — exit code for ``action=exit`` (default 43, distinguishable
  from real crashes in launcher traces).
* ``name`` — only fire when the call site passes a matching ``name=``
  (e.g. a tensor name on the enqueue path).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

__all__ = ["InjectedFault", "maybe_fail", "corrupt_bytes", "corrupt_grad",
           "parse_spec", "reset", "active", "point_count"]

SPEC_ENV = "HVDTPU_FAULT_SPEC"
DEFAULT_EXIT_CODE = 43
_EXIT_POINTS = ("worker_exit", "task_fn")
# Advisory actions only take effect at call sites that consume
# maybe_fail's return value; parse-time validation keeps a spec like
# "ckpt_write:action=corrupt_write" from "firing" as a silent no-op —
# a chaos test built on it would pass vacuously.
_ADVISORY_POINTS = {
    "corrupt_write": ("shard_write",),
    "drop_replica": ("replica_push",),
    "trace_drop": ("trace_flush",),
    "swap_abort": ("swap_commit",),
    "scale_fail": ("scale_admit",),
    "oom": ("mem_alloc",),
    "frontend_exit": ("frontend_beat",),
    "flip_bits": ("grad_ready",),
    "nan_inject": ("grad_ready",),
}


class InjectedFault(RuntimeError):
    """Raised by a fired ``action=raise`` fault; carries the site name."""

    def __init__(self, point: str, detail: str):
        super().__init__(
            f"injected fault at {point!r} ({detail}) — HVDTPU_FAULT_SPEC"
        )
        self.point = point


@dataclass
class FaultSpec:
    point: str
    rank: Optional[int] = None
    step: Optional[int] = None
    epoch: Optional[int] = 0
    count: int = 1
    action: str = "raise"
    code: int = DEFAULT_EXIT_CODE
    delay_ms: int = 1000
    exc_name: Optional[str] = None
    name: Optional[str] = None
    fired: int = field(default=0, compare=False)

    def describe(self) -> str:
        parts = [self.point]
        for k in ("rank", "step", "epoch", "count", "name"):
            v = getattr(self, k)
            if v is not None:
                parts.append(f"{k}={v}")
        return ":".join(parts)


def parse_spec(raw: str) -> List[FaultSpec]:
    """Parse a spec string; raises ``ValueError`` on malformed entries so
    a typo'd spec fails the run loudly instead of silently never firing."""
    specs: List[FaultSpec] = []
    for chunk in raw.split(","):
        chunk = chunk.strip()
        if not chunk:
            continue
        fields = chunk.split(":")
        point = fields[0].strip()
        if not point:
            raise ValueError(f"fault spec entry has no point name: {chunk!r}")
        spec = FaultSpec(point=point)
        if point in _EXIT_POINTS:
            spec.action = "exit"
        for kv in fields[1:]:
            if "=" not in kv:
                # ``action=delay:<ms>`` / ``action=raise:<ExcName>``:
                # the parameter rides as a bare field right after the
                # action (the grammar's separator is ":", so it can't
                # live in the value).
                if spec.action == "delay" and kv.strip().isdigit():
                    spec.delay_ms = int(kv.strip())
                    continue
                if spec.action == "raise" and kv.strip().isidentifier():
                    exc_name = kv.strip()
                    cls = getattr(__import__("builtins"), exc_name, None)
                    if not (isinstance(cls, type)
                            and issubclass(cls, BaseException)):
                        raise ValueError(
                            f"action=raise:{exc_name}: {exc_name!r} is "
                            f"not a builtin exception"
                        )
                    spec.exc_name = exc_name
                    continue
                raise ValueError(
                    f"fault spec field {kv!r} in {chunk!r} is not key=value"
                )
            key, value = (s.strip() for s in kv.split("=", 1))
            if key in ("rank", "step", "count", "code", "delay_ms"):
                setattr(spec, key, int(value))
            elif key == "epoch":
                spec.epoch = None if value in ("any", "*") else int(value)
            elif key == "action":
                if value not in ("raise", "exit", "abort", "hang", "delay",
                                 "corrupt_write", "drop_replica",
                                 "trace_drop", "swap_abort",
                                 "scale_fail", "oom", "frontend_exit",
                                 "flip_bits", "nan_inject"):
                    raise ValueError(f"unknown fault action {value!r}")
                spec.action = value
            elif key == "name":
                spec.name = value
            else:
                raise ValueError(
                    f"unknown fault spec key {key!r} in {chunk!r}"
                )
        allowed = _ADVISORY_POINTS.get(spec.action)
        if allowed is not None and spec.point not in allowed:
            raise ValueError(
                f"action={spec.action} is only implemented at "
                f"{'/'.join(allowed)}, not at point {spec.point!r} — "
                f"it would fire as a silent no-op there"
            )
        specs.append(spec)
    return specs


# Parsed cache, keyed by the raw env string so a test that monkeypatches
# the env (or calls reset()) gets a fresh registry.
_cache_raw: Optional[str] = None
_specs: Dict[str, List[FaultSpec]] = {}
_counters: Dict[str, int] = {}


def reset() -> None:
    """Drop the parsed registry and per-point counters (tests)."""
    global _cache_raw
    _cache_raw = None
    _specs.clear()
    _counters.clear()


def _load() -> Dict[str, List[FaultSpec]]:
    global _cache_raw
    raw = os.environ.get(SPEC_ENV, "")
    if raw != _cache_raw:
        _specs.clear()
        _counters.clear()
        for spec in parse_spec(raw):
            _specs.setdefault(spec.point, []).append(spec)
        _cache_raw = raw
    return _specs


def active() -> bool:
    """True when any fault spec is loaded (cheap hot-path gate)."""
    return bool(_load())


def point_count(point: str) -> int:
    """Current value of a point's 1-based invocation counter (0 before
    the first visit) — lets an advisory site key deterministic payload
    corruption (:func:`corrupt_grad`) on the same step number
    :func:`maybe_fail` just matched."""
    return _counters.get(point, 0)


def _resolve_rank(rank: Optional[int]) -> Optional[int]:
    if rank is not None:
        return rank
    from ..utils.env import resolve_rank  # noqa: PLC0415

    return resolve_rank(None)


def _resolve_epoch() -> int:
    value = os.environ.get("HVDTPU_ELASTIC_EPOCH")
    return int(value) if value not in (None, "") else 0


def corrupt_bytes(data: bytes) -> bytes:
    """Deterministically damage ``data`` (first/middle/last byte flipped)
    — the payload an ``action=corrupt_write`` call site writes instead of
    the real one, so checksum validation has something real to catch."""
    if not data:
        return data
    buf = bytearray(data)
    for i in (0, len(buf) // 2, len(buf) - 1):
        buf[i] ^= 0xFF
    return bytes(buf)


def corrupt_grad(arr, action: str, *, rank: int = 0, step: int = 0,
                 name: Optional[str] = None):
    """Apply a fired ``grad_ready`` advisory action to a reduced
    gradient: damage exactly ONE element, chosen deterministically by
    ``crc32(rank:step:name)`` so a chaos assertion can name the exact
    bucket/tensor it expects to see diverge.

    ``flip_bits`` XORs 0x40 into the element's most-significant byte —
    for floats that is a single exponent-bit flip (the canonical SDC:
    a large, *finite* magnitude change that value-level sanity checks
    miss but a bitwise digest cannot); ``nan_inject`` overwrites the
    element with NaN (integer dtypes fall back to the bit flip).
    Returns a same-dtype copy; the input is never mutated.
    """
    import zlib  # noqa: PLC0415

    import numpy as np  # noqa: PLC0415

    a = np.array(arr, copy=True)
    if a.size == 0:
        return a
    key = f"{rank}:{step}:{name or ''}".encode()
    # CRC32 is linear over GF(2): a one-character key change (e.g. the
    # rank digit) XORs a fixed delta whose low bits can be all-zero, so
    # ``crc % power_of_two_size`` would hit the same slot for every
    # rank.  Avalanche the high bits down before reducing.
    h = zlib.crc32(key)
    h ^= h >> 16
    h = (h * 0x45D9F3B) & 0xFFFFFFFF
    h ^= h >> 16
    pos = h % a.size
    if action == "nan_inject" and np.issubdtype(a.dtype, np.floating):
        a.reshape(-1)[pos] = np.nan
        return a
    if action not in ("flip_bits", "nan_inject"):
        raise ValueError(f"corrupt_grad does not implement {action!r}")
    raw = a.view(np.uint8).reshape(a.size, a.dtype.itemsize)
    # Little-endian: the last byte of each element is the most
    # significant — sign + high exponent bits for IEEE floats.
    raw[pos, -1] ^= 0x40
    return a


def maybe_fail(
    point: str,
    *,
    step: Optional[int] = None,
    rank: Optional[int] = None,
    name: Optional[str] = None,
) -> Optional[str]:
    """Fire any matching fault for ``point``; no-op when none match.

    ``step=None`` uses the per-point invocation counter (1-based) — the
    counter advances on every call whether or not a fault fires, so
    ``step=N`` deterministically means "the Nth visit to this point".

    Returns the fired action name for the *advisory* actions the call
    site must apply itself (``corrupt_write``, ``drop_replica``,
    ``trace_drop``, ``swap_abort``, ``scale_fail``, ``oom``) and
    ``None`` otherwise — existing callers that ignore the return value
    keep their exact semantics.
    """
    specs = _load().get(point)
    counter = None
    if specs is not None or point in _counters:
        counter = _counters[point] = _counters.get(point, 0) + 1
    if not specs:
        return None
    observed_step = step if step is not None else counter
    observed_rank = _resolve_rank(rank)
    observed_epoch = _resolve_epoch()
    for spec in specs:
        if spec.fired >= spec.count:
            continue
        if spec.rank is not None and spec.rank != observed_rank:
            continue
        if spec.step is not None and spec.step != observed_step:
            continue
        if spec.epoch is not None and spec.epoch != observed_epoch:
            continue
        if spec.name is not None and spec.name != name:
            continue
        spec.fired += 1
        # Black-box the injection itself: a chaos run's post-mortem must
        # show the fault firing as an event, not leave the analyzer to
        # infer it from the wreckage.
        from ..obs import flightrec  # noqa: PLC0415

        flightrec.record(
            "fault", name=point,
            detail=f"{spec.action}:{spec.describe()}",
        )
        if spec.action in ("corrupt_write", "drop_replica", "trace_drop",
                           "swap_abort", "scale_fail", "oom",
                           "frontend_exit", "flip_bits", "nan_inject"):
            # Advisory actions: the call site owns the I/O, so the
            # registry can only instruct it — corrupt the payload it is
            # about to write, or skip the push entirely.
            return spec.action
        if spec.action == "delay":
            # A deterministic straggler: stall the calling thread, then
            # proceed normally — the collective completes late, which is
            # exactly the skew signature straggler attribution must name.
            import time  # noqa: PLC0415

            time.sleep(spec.delay_ms / 1000.0)
            return None
        if spec.action == "exit":
            # os._exit, not sys.exit: the injected death must look like a
            # hard crash (no atexit, no finally blocks posting results).
            os._exit(spec.code)
        if spec.action == "abort":
            # raise_signal (not os.abort): os.abort bypasses Python
            # signal handlers, which would defeat the very dump path
            # this action exists to chaos-test.  With the flight
            # recorder's handler installed the rank dumps its ring,
            # then dies by real SIGABRT (no atexit, no finally blocks);
            # without it, it is a plain abort.
            import signal  # noqa: PLC0415

            signal.raise_signal(signal.SIGABRT)
        if spec.action == "hang":
            # Deadlock the CALLING thread only: daemon threads (the KV
            # heartbeat) keep beating, so the process looks alive while
            # its training thread is wedged — reproducing the failure
            # mode the collective-path progress beat detects.  The
            # process dies by external SIGTERM/SIGKILL.
            import threading  # noqa: PLC0415

            while True:
                threading.Event().wait(3600)
        if spec.exc_name is not None:
            cls = getattr(__import__("builtins"), spec.exc_name)
            raise cls(
                f"injected fault at {point!r} ({spec.describe()}) — "
                f"HVDTPU_FAULT_SPEC"
            )
        raise InjectedFault(point, spec.describe())
