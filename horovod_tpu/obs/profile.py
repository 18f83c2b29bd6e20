"""Live MFU profiler: model-FLOPs accounting over measured step time.

The measurement layer shared by every surface of the program that times
a step:

* **Model FLOPs per step** — preferred source: XLA's own post-fusion
  cost analysis of the compiled artifact (:func:`flops_from_compiled`,
  the PR-9 HLO-inspector spirit: a property of the artifact, not a
  hand-derived guess).
* **Device peak FLOP/s** — a small per-platform table
  (:data:`PEAK_FLOPS`, public TPU spec sheets).  The kind ``cpu``
  gets a nominal order-of-magnitude entry marked **estimate-only**: a
  CPU MFU is a trajectory placeholder, never a perf claim, and every
  consumer carries the flag.  Any other unknown kind is an error.
* **Live gauges** — :class:`MFUProfiler` divides FLOPs by measured step
  time and publishes ``perf.mfu``, ``perf.model_tflops``,
  ``perf.step_ms`` (plus ``perf.mfu_estimate`` when the peak is a
  guess) into the metrics registry — so the digest (``mfu 0.31``
  token), ``/metrics``, ``--stats-summary`` see
  the same number, computed once.

Two further measurement layers live here, both fed by names the
program itself puts where the work happens:

* **Set-up log** — :func:`compile_log`: the process's set-up as
  intervals on ``time.perf_counter()``.  One record per outermost
  ``jax.monitoring`` compile event (program, phase ``trace`` / ``lower``
  / ``backend``, seconds, ``t_start`` and ``t_end``; on a trace record
  the jitted helpers traced inside it, summed by name; on the backend
  record the persistent cache's ``hit`` or ``miss``, the seconds its
  load took and the seconds it saved), one for ``hvd.init()`` (phase
  ``init``) and, on Linux, a first one from the process's start to this
  module's import (phase ``process``).
  ``utils/compile_cache.enable_compile_cache()`` registers the listener,
  so every entry point has it before its first compile; the registry
  carries ``compile.seconds{phase}``, ``compile.cache_hits`` and
  ``compile.cache_misses``, and with ``HVDTPU_TRACE`` armed each backend
  compile is a ``compile`` span on the ``compile`` lane of the span ring.
* **Device trace** — :class:`DeviceTrace` (``with
  hvd.obs.profile.device_trace(): ...``): the program's ONLY use of
  ``jax.profiler``.  It records a bounded slice and reduces it
  (:func:`reduce_trace`) to window and busy seconds per device, device
  time by scope (the ``jax.named_scope`` names of horovod_tpu/scopes.py,
  read from each operation's ``op_name``) and by Pallas kernel, and idle
  time by the obs/trace.py span that covers each gap.  The spans are on
  ``time.time()``; a marker annotation carrying that clock's stamp puts
  them on the trace's timeline.  The serving rank arms one slice out of
  every :data:`SLICE_PERIOD` busy steps when ``HVDTPU_TRACE`` is set
  (:class:`SliceSchedule`) and emits each as a ``device_slice`` span.

No jax import at module scope: the launcher imports obs eagerly and
must not initialise a backend for it.
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from ..scopes import SCOPES

__all__ = [
    "SCOPES",
    "compile_log",
    "compile_summary",
    "install_compile_listener",
    "log_interval",
    "DeviceTrace",
    "device_trace",
    "SliceSchedule",
    "reduce_trace",
    "read_xplane",
    "PEAK_FLOPS",
    "CPU_PEAK_ESTIMATE",
    "peak_flops",
    "flops_from_compiled",
    "MFUProfiler",
]

# Peak dense-matmul FLOP/s per chip (bf16 on MXU; fp32 runs at ~1/4 via
# bf16x3 passes or worse), keyed by the string ``jax.Device.device_kind``
# reports.  Source, one page per generation: Google Cloud TPU
# documentation, "System architecture" (cloud.google.com/tpu/docs/v2,
# /v3, /v4, /v5e, /v5p, /v6e), "Peak compute per chip (bf16)".  ONE
# table, so no two surfaces can disagree about a chip's peak.  A kind that is not here is
# an error, never a default.
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5": 459e12,        # v5p
    "TPU v6 lite": 918e12,   # v6e
}

# Order-of-magnitude stand-in for a few AVX cores — good enough to keep
# the MFU pipeline exercised end-to-end on the CPU dev path, useless as
# a perf claim, hence estimate-flagged everywhere it flows.  Reachable
# only for the device kind "cpu".
CPU_PEAK_ESTIMATE = 1e11


def peak_flops(device_kind: str, dtype: str = "bf16"
               ) -> Tuple[float, bool]:
    """``(peak FLOP/s, estimate_flag)`` for a device kind string
    (``jax.Device.device_kind``).  Kinds in :data:`PEAK_FLOPS` are
    authoritative; ``"cpu"`` returns the nominal CPU estimate with the
    flag raised; any other kind raises — an accelerator this table does
    not know must not be handed a CPU's peak."""
    peak = PEAK_FLOPS.get(device_kind)
    if peak is None:
        if device_kind == "cpu":
            return CPU_PEAK_ESTIMATE, True
        raise ValueError(
            f"no peak FLOP/s known for device kind {device_kind!r}; add "
            "it to horovod_tpu.obs.profile.PEAK_FLOPS with its source"
        )
    if dtype == "fp32":
        peak = peak / 4.0
    return peak, False


def flops_from_compiled(compiled) -> Optional[float]:
    """Per-device FLOPs of one execution of a compiled executable, as
    XLA counts them post-fusion (``cost_analysis()``).  Returns None
    when the backend exposes no analysis."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    try:
        v = float(ca.get("flops", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return v if v > 0 else None


class MFUProfiler:
    """Publishes the live perf gauges for one measured step loop.

    ``flops_per_step`` is per-device (XLA's cost analysis is the
    post-SPMD-partitioning per-device module; analytic callers must
    divide by world size themselves).  ``observe(step_secs)`` is cheap
    enough for a serving decode loop: three float divisions and three
    gauge stores."""

    def __init__(self, flops_per_step: Optional[float],
                 device_kind: str, dtype: str = "bf16", *,
                 source: str = "cost_analysis", registry=None):
        from .registry import get_registry  # noqa: PLC0415

        self.flops_per_step = flops_per_step
        self.device_kind = device_kind
        self.peak, self.estimate = peak_flops(device_kind, dtype)
        self.source = source
        self.mfu: Optional[float] = None
        self.step_ms: Optional[float] = None
        reg = registry if registry is not None else get_registry()
        self._g_mfu = reg.gauge("perf.mfu")
        self._g_tflops = reg.gauge("perf.model_tflops")
        self._g_step_ms = reg.gauge("perf.step_ms")
        self._g_estimate = reg.gauge("perf.mfu_estimate")
        self._g_estimate.set(1.0 if self.estimate else 0.0)

    def observe(self, step_secs: float) -> Optional[float]:
        """One measured step (or the mean of a timed window): update
        the gauges, return the MFU (None when FLOPs are unknown)."""
        if step_secs <= 0:
            return self.mfu
        self.step_ms = step_secs * 1e3
        self._g_step_ms.set(self.step_ms)
        if not self.flops_per_step:
            return None
        achieved = self.flops_per_step / step_secs
        self.mfu = achieved / self.peak
        self._g_mfu.set(self.mfu)
        self._g_tflops.set(achieved / 1e12)
        return self.mfu

    def summary(self) -> dict:
        """The record-embeddable view — what BENCH/serve records carry
        so the moment a real TPU answers, item 5's sweep lands real MFU
        numbers with zero new code."""
        out = {
            "mfu": round(self.mfu, 4) if self.mfu is not None else None,
            "model_tflops": (
                round(self.flops_per_step / (self.step_ms / 1e3) / 1e12, 4)
                if self.flops_per_step and self.step_ms else None
            ),
            "step_ms": (round(self.step_ms, 3)
                        if self.step_ms is not None else None),
            "flops_per_step": self.flops_per_step,
            "flops_source": self.source,
            "device": self.device_kind,
            "peak_flops": self.peak,
            "estimate": bool(self.estimate),
        }
        return out


# ---------------------------------------------------------------------------
# Compile phases as a log
# ---------------------------------------------------------------------------

_COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_VERDICTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# What a hit cost and what it saved, as JAX reports them just before the
# backend duration of the program that hit (jax/_src/compiler.py).
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
COMPILE_LOG_CAPACITY = 4096
COMPILE_LANE = "compile"
CHILDREN_KEPT = 16


def _process_start() -> Optional[float]:
    """When this process started, on ``time.perf_counter()``'s clock
    (Linux: CLOCK_MONOTONIC), from field 22 of ``/proc/self/stat``
    (clock ticks since boot).  None where that cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        since_boot = ticks / os.sysconf("SC_CLK_TCK")
        return since_boot - (time.clock_gettime(time.CLOCK_BOOTTIME)
                             - time.perf_counter())
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class _Pending(threading.local):
    """What one thread holds between JAX's events: how deep in nested
    phases it is, the outermost one and its children so far, and what
    the cache has said since the thread's last ``backend`` record."""

    def __init__(self):
        self.depth, self.outermost = 0, None
        self.children, self.cache = {}, {}


class _CompileLog:
    """Bounded in-process log of the set-up's phases, as intervals on
    ``time.perf_counter()``: ``jax.monitoring``'s compile events and the
    program's own records (:func:`log_interval`).  ``clock`` is one
    ``(perf_counter, time.time)`` pair, which lays any record on the
    wall clock of the span ring and of a device trace's host plane.

    JAX reports the persistent cache's verdict, and after a hit the
    seconds the load took and saved, just before the backend-compile
    duration of the same program, on the same thread: they are held per
    thread and land on that backend record, so a miss names its program.

    The phases nest: while ``local_step`` is traced or lowered, every
    jitted helper it calls (``jnp.add``, ``_where``, ...: thousands for
    a 24-layer model) is traced inside it and reports a duration of its
    own.  JAX also reports each phase's START (a scalar event); the
    depth kept from those per thread lets only the outermost phase into
    the log, whose seconds already hold the nested ones.  Of an
    outermost ``trace`` the direct children are summed by name into its
    ``children`` (one dict update an event, only while JAX traces): the
    record's seconds less theirs is the tracing of the model's own
    Python."""

    def __init__(self, started: Optional[float] = None):
        self.records = collections.deque(maxlen=COMPILE_LOG_CAPACITY)
        self.installed = False
        self.clock = (time.perf_counter(), time.time())
        self._pending = _Pending()
        if started is not None and started <= self.clock[0]:
            self.records.append(
                _interval("process", "process", started, self.clock[0]))

    def on_event(self, event: str, **_kw) -> None:
        verdict = _CACHE_VERDICTS.get(event)
        if verdict is not None:
            self._pending.cache["cache"] = verdict

    def on_start(self, event: str, _value, **_kw) -> None:
        phase = _COMPILE_PHASES.get(event)
        if phase is not None:
            pending = self._pending
            if not pending.depth:
                pending.outermost, pending.children = phase, {}
            pending.depth += 1

    def on_duration(self, event: str, seconds: float, **kw) -> None:
        pending = self._pending
        if event in _CACHE_SECONDS:
            pending.cache[_CACHE_SECONDS[event]] = float(seconds)
            return
        phase = _COMPILE_PHASES.get(event)
        if phase is None:
            return
        pending.depth = max(pending.depth - 1, 0)
        if pending.depth:
            if pending.depth == 1 and phase == "trace" == pending.outermost:
                child = pending.children.setdefault(
                    str(kw.get("fun_name", "")), [0, 0.0])
                child[0] += 1
                child[1] += seconds
            return
        from . import trace as obs_trace  # noqa: PLC0415
        from .registry import get_registry  # noqa: PLC0415

        t_end = time.perf_counter()
        seconds = float(seconds)
        # JAX says ``step`` when it traces and ``jit(step)`` when it
        # lowers and compiles: one program, one name.
        program = re.sub(r"^jit\((.*)\)$", r"\1",
                         str(kw.get("fun_name", "")))
        record = _interval(program, phase, t_end - seconds, t_end, seconds)
        reg = get_registry()
        reg.counter("compile.seconds", phase=phase).inc(seconds)
        if phase == "trace":
            record["children"] = _largest(pending.children)
        elif phase == "backend":
            held, pending.cache = pending.cache, {}
            record.update({"cache_load_s": 0.0, **held})
            reg.counter("compile.seconds", phase="cache_load").inc(
                record["cache_load_s"])
            verdict = held.get("cache")
            if verdict is not None:
                reg.counter("compile.cache_hits" if verdict == "hit"
                            else "compile.cache_misses").inc()
            if obs_trace.enabled():
                t1 = self.clock[1] + t_end - self.clock[0]
                obs_trace.add_span(
                    COMPILE_LANE, "compile", t1 - seconds, t1,
                    program=program, cache=verdict)
        self.records.append(record)


def _interval(program: str, phase: str, t_start: float, t_end: float,
              seconds: Optional[float] = None) -> dict:
    return {"program": program, "phase": phase,
            "seconds": t_end - t_start if seconds is None else seconds,
            "t_start": t_start, "t_end": t_end}


def _largest(children: Dict[str, list]) -> Dict[str, list]:
    """The :data:`CHILDREN_KEPT` entries ``{name: [count, seconds]}``
    with the most seconds, the rest summed into ``"other"``."""
    ranked = sorted(children.items(), key=lambda kv: -kv[1][1])
    kept = dict(ranked[:CHILDREN_KEPT])
    if ranked[CHILDREN_KEPT:]:
        rest = kept.setdefault("other", [0, 0.0])
        for _, (count, seconds) in ranked[CHILDREN_KEPT:]:
            rest[0] += count
            rest[1] += seconds
    return kept


# The process's log: its first record runs from the process's start to
# this module's import, where the start can be known.
_COMPILE_LOG = _CompileLog(_process_start())


def install_compile_listener() -> None:
    """Register the set-up log with ``jax.monitoring``, once a
    process.  Called by ``utils/compile_cache.enable_compile_cache()``;
    the listeners run only when JAX traces, lowers or compiles."""
    log = _COMPILE_LOG
    if log.installed:
        return
    import jax.monitoring  # noqa: PLC0415

    jax.monitoring.register_event_listener(log.on_event)
    jax.monitoring.register_scalar_listener(log.on_start)
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    log.clock = (time.perf_counter(), time.time())
    log.installed = True


def log_interval(phase: str, program: str, t_start: float) -> None:
    """One of the program's own set-up phases, from ``t_start`` (on
    ``time.perf_counter()``) to now: ``hvd.init()`` is ``init``."""
    _COMPILE_LOG.records.append(
        _interval(program, phase, t_start, time.perf_counter()))


def compile_log() -> List[dict]:
    """The set-up's records so far, oldest first: ``{"program", "phase",
    "seconds", "t_start", "t_end"}``, the stamps on
    ``time.perf_counter()``.  ``phase`` is ``trace``, ``lower`` or
    ``backend`` for JAX's compile events (``t_start`` is ``t_end`` less
    ``seconds``), ``init`` for ``hvd.init()`` and ``process`` for the
    first record (the process's start to this module's import; Linux
    only).  A trace record has ``children``: ``{name: [count, seconds]}``
    of the jitted helpers traced directly inside it, the sixteen largest
    and ``"other"``.  A backend record has ``cache_load_s``, and where
    the persistent cache was asked ``"cache": "hit" | "miss"`` and after
    a hit ``saved_s``.  The newest :data:`COMPILE_LOG_CAPACITY` records
    are kept."""
    return [dict(r) for r in list(_COMPILE_LOG.records)]


def compile_summary() -> dict:
    """The log in one line, for a drain summary: seconds by phase (and
    ``cache_load``, the part of ``backend`` the cache's loads took), the
    cache's hits and misses, and the programs that missed."""
    seconds = dict.fromkeys(
        (*_COMPILE_PHASES.values(), "cache_load", "init"), 0.0)
    hits, missed = 0, []
    for r in compile_log():
        if r["phase"] in seconds:
            seconds[r["phase"]] += r["seconds"]
        seconds["cache_load"] += r.get("cache_load_s", 0.0)
        if r.get("cache") == "hit":
            hits += 1
        elif r.get("cache") == "miss":
            missed.append(r["program"])
    return {"seconds": {k: round(v, 3) for k, v in seconds.items()},
            "cache_hits": hits, "cache_misses": len(missed),
            "missed": missed[-16:]}


# ---------------------------------------------------------------------------
# The device trace: one profiler hook, reduced in the program
# ---------------------------------------------------------------------------

# The reduction files an operation under the innermost program scope
# (horovod_tpu/scopes.py, the one list every ``jax.named_scope`` of the
# package names itself from) in its ``op_name``; an operation under
# none of them goes under the innermost other name (a flax module's:
# ``wte``, ``stage1_block1``), and one with no name at all under
# ``unscoped``.
UNSCOPED = "unscoped"
UNCOVERED = "uncovered"
CLOCK_MARKER = "hvdtpu_clock"
SLICE_STEPS = 4
SLICE_PERIOD = 128

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HOST_PLANE = "/host:CPU"
_OPS_LINE = "XLA Ops"
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
# Name-stack entries that are JAX's, not a scope: transforms, control
# flow, the jitted function itself.
_NOT_A_SCOPE = re.compile(
    r"^(\w+\(.*\)|shard_map|while|body|cond|branch_\d+_fun|"
    r"custom_vjp_call|custom_jvp_call|checkpoint|remat|pjit|jit)$")

Interval = Tuple[float, float]


@functools.lru_cache(maxsize=8192)  # events repeat a few thousand names
def scope_of(op_name: str) -> str:
    """``jit(step)/transpose(jvp(GPT))/block3/attn/qkv/dot_general:``
    -> ``transpose(attn)``: the innermost program scope of an
    operation's name stack (else the innermost other name), with the
    transposed (backward) part kept apart from the forward one."""
    parts = [p for p in op_name.rstrip(":").split("/") if p][:-1]
    names = [p for p in parts if not _NOT_A_SCOPE.match(p)]
    # under ``vmap`` JAX writes the scope inside the transform's name:
    # ``vmap(sample)``
    bare = [re.sub(r"^(?:\w+\()+([\w.]+)\)+$", r"\1", p) for p in parts]
    scope = next((p for p in reversed(bare) if p in SCOPES),
                 names[-1] if names else UNSCOPED)
    if any(p.startswith("transpose(") for p in parts):
        return f"transpose({scope})"
    return scope


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _cover_segments(spans: Sequence[Tuple[str, float, float]]
                    ) -> List[Tuple[float, float, str]]:
    """The timeline cut at every span boundary, each piece labelled
    with the SHORTEST span that covers it (the innermost, where spans
    nest: ``decode_compute`` inside ``step``).  Pieces no span covers
    are left out."""
    edges = sorted({t for _, a, b in spans for t in (a, b)})
    by_start = sorted(spans, key=lambda s: s[1])
    out, active, nxt = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(by_start) and by_start[nxt][1] <= lo:
            active.append(by_start[nxt])
            nxt += 1
        active = [s for s in active if s[2] > lo]
        if active:
            name = min(active, key=lambda s: s[2] - s[1])[0]
            out.append((lo, hi, name))
    return out


def _idle_by_span(busy: Sequence[Interval],
                  segments: Sequence[Tuple[float, float, str]]
                  ) -> Dict[str, float]:
    """The gaps between busy intervals, split among the labelled
    segments in one sweep; what no segment covers is ``uncovered``."""
    out: Dict[str, float] = {}
    j = 0
    for (_, gap_lo), (gap_hi, _) in zip(busy, busy[1:]):
        left = gap_hi - gap_lo
        while j < len(segments) and segments[j][1] <= gap_lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < gap_hi:
            lo, hi, name = segments[k]
            part = min(hi, gap_hi) - max(lo, gap_lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                left -= part
            k += 1
        if left > 0:
            out[UNCOVERED] = out.get(UNCOVERED, 0.0) + left
    return out


def reduce_trace(ops_by_device: Dict[int, Sequence[Sequence]],
                 marker: Optional[Tuple[float, float]] = None,
                 spans: Sequence[dict] = ()) -> dict:
    """A device trace as plain data -> what a ``device_slice`` carries.

    ``ops_by_device``: device index -> ``[name, start_ns, dur_ns,
    op_name]`` per executed operation (:func:`read_xplane`).
    ``marker``: ``(trace_ns, wall_s)`` of the clock marker — the same
    instant on the trace's timeline and on ``time.time()``.  ``spans``:
    obs/trace.py span documents (``name``, ``t0``, ``dur`` on
    ``time.time()``); without a marker they cannot be placed and every
    gap is ``uncovered``.

    A device's window runs from its first operation's start to its last
    one's end; busy is the UNION of its operations' intervals (two that
    overlap count once), idle the rest, so busy + idle is the window.
    ``by_scope`` and ``by_kernel`` are unions too, over the operations
    of one scope (:func:`scope_of`) or one Pallas kernel (instruction
    name without its number), summed over devices.  Seconds throughout.
    """
    segments: List[Tuple[float, float, str]] = []
    if marker is not None and spans:
        trace_ns, wall_s = marker
        segments = _cover_segments([
            (s["name"], trace_ns + (s["t0"] - wall_s) * 1e9,
             trace_ns + (s["t0"] + s["dur"] - wall_s) * 1e9)
            for s in spans if s["dur"] > 0])
    per_device: Dict[str, dict] = {}
    by_scope: Dict[str, float] = {}
    by_kernel: Dict[str, float] = {}
    idle_by_span: Dict[str, float] = {}
    for index in sorted(ops_by_device):
        ops = ops_by_device[index]
        if not ops:
            continue
        busy = _union((o[1], o[1] + o[2]) for o in ops)
        window = busy[-1][1] - busy[0][0]
        per_device[str(index)] = {"window_s": window / 1e9,
                                  "busy_s": _total(busy) / 1e9}
        scopes: Dict[str, List[Interval]] = {}
        kernels: Dict[str, List[Interval]] = {}
        for name, start, dur, op_name in ops:
            scopes.setdefault(scope_of(op_name or ""), []).append(
                (start, start + dur))
            if name.startswith("kernel:"):
                kernels.setdefault(re.sub(r"\.\d+$", "", name[7:]),
                                   []).append((start, start + dur))
        for found, into in ((scopes, by_scope), (kernels, by_kernel)):
            for key, intervals in found.items():
                into[key] = into.get(key, 0.0) \
                    + _total(_union(intervals)) / 1e9
        for name, ns in _idle_by_span(busy, segments).items():
            idle_by_span[name] = idle_by_span.get(name, 0.0) + ns / 1e9
    n = max(len(per_device), 1)
    window_s = sum(d["window_s"] for d in per_device.values()) / n
    busy_s = sum(d["busy_s"] for d in per_device.values()) / n

    def ranked(named: Dict[str, float], scale: float = 1.0) -> dict:
        return {k: round(v * scale, 6) for k, v in
                sorted(named.items(), key=lambda kv: -kv[1])}

    return {"devices": len(per_device),
            "ops": sum(len(ops) for ops in ops_by_device.values()),
            "window_s": round(window_s, 6), "busy_s": round(busy_s, 6),
            "idle_s": round(window_s - busy_s, 6),
            "per_device": per_device,
            "by_scope": ranked(by_scope), "by_kernel": ranked(by_kernel),
            # per device, like window_s and busy_s: the three add up
            "idle_by_span": ranked(idle_by_span, 1.0 / n)}


# -- reading the profiler's file ----------------------------------------
#
# ``jax.profiler.ProfileData`` gives planes, lines and events, but not an
# event's *metadata* stats, and the scope (XLA's ``op_name``) of a TPU
# operation is one of those (``tf_op``).  The file is a protobuf whose
# schema has been stable for years (tsl/profiler/protobuf/xplane.proto);
# the few fields read here are decoded directly, and every other field
# is skipped by its length.  The generated ``xplane_pb2`` is no way in:
# it ships only inside tensorflow, which this package does not depend
# on and which a rank that holds the chip must not import.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int):
    """``(field number, value)`` of the message in ``buf[lo:hi]``: an
    int for a varint, ``(lo, hi)`` offsets for a length-delimited field,
    raw bytes for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value = buf[i:i + n]
            i += n
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span: Tuple[int, int]):
    key, value = 0, None
    for number, v in _fields(buf, *span):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(buf: bytes, span: Tuple[int, int], stat_names: Dict[int, str]):
    """One XStat -> ``(stat name, value)`` for an integer or a string
    value; a string may be a reference into the plane's stat names."""
    name, value = None, None
    for number, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v)
        elif number in (3, 4):
            value = v
        elif number == 5:
            value = _text(buf, v)
        elif number == 7:
            value = stat_names.get(v)
    return name, value


def _plane(buf: bytes, span: Tuple[int, int]) -> dict:
    plane = {"name": "", "lines": [], "event_md": [], "stat_names": {}}
    for number, v in _fields(buf, *span):
        if number == 2:
            plane["name"] = _text(buf, v)
        elif number == 3:
            plane["lines"].append(v)
        elif number == 4:
            plane["event_md"].append(v)
        elif number == 5:
            key, md = _map_entry(buf, v)
            for n, w in _fields(buf, *md):
                if n == 2:
                    plane["stat_names"][key] = _text(buf, w)
    return plane


def _event_metadata(buf: bytes, plane: dict, want_stat: str
                    ) -> Dict[int, Tuple[str, Optional[str]]]:
    """metadata id -> (event name, the ``want_stat`` stat or None).
    Only that one stat is decoded: a TPU operation's metadata carries a
    dozen others (its HLO text among them)."""
    want = {k for k, n in plane["stat_names"].items() if n == want_stat}
    out = {}
    for entry in plane["event_md"]:
        key, md = _map_entry(buf, entry)
        name, stat = "", None
        for number, v in _fields(buf, *md):
            if number == 2:
                name = _text(buf, v)
            elif number == 5 and want and stat is None:
                # XStat.metadata_id is the stat's first field
                tag, i = _varint(buf, v[0])
                if tag != 8 or _varint(buf, i)[0] in want:
                    stat_name, value = _stat(buf, v, plane["stat_names"])
                    if stat_name == want_stat:
                        stat = value
        out[key] = (name, stat)
    return out


def _line_events(buf: bytes, span: Tuple[int, int],
                 only: Optional[str] = None,
                 ids: Optional[set] = None) -> list:
    """A line's events as ``(metadata id, start_ns, dur_ns, [stat
    spans])``; with ``only``, nothing unless that is the line's name
    (a device plane's other lines are never decoded); with ``ids``,
    only the events of those metadata ids (the host plane holds the
    runtime's own events by the thousand, the marker once)."""
    name, t0_ns, events = "", 0, []
    for number, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            t0_ns = v
        elif number == 4:
            events.append(v)
    if only is not None and name != only:
        return []
    out = []
    for ev in events:
        if ids is not None:
            # XEvent.metadata_id is the event's first field
            tag, i = _varint(buf, ev[0])
            if tag == 8 and _varint(buf, i)[0] not in ids:
                continue
        md, offset_ps, dur_ps, stats = 0, 0, 0, []
        for number, v in _fields(buf, *ev):
            if number == 1:
                md = v
            elif number == 2:
                offset_ps = v
            elif number == 3:
                dur_ps = v
            elif number == 4:
                stats.append(v)
        out.append((md, t0_ns + offset_ps / 1e3, dur_ps / 1e3, stats))
    return out


def read_xplane(path: str) -> Tuple[Dict[int, List[list]],
                                    Optional[Tuple[float, float]]]:
    """An ``.xplane.pb`` -> ``(ops_by_device, marker)`` as
    :func:`reduce_trace` takes them.  Operations are the events of each
    TPU plane's ``XLA Ops`` line, named by the instruction's own name
    (``fusion.21``; a Pallas kernel ``kernel:flash_fwd.2``) with the
    ``op_name`` XLA kept for it; the marker is the
    :data:`CLOCK_MARKER` annotation on the host plane."""
    with open(path, "rb") as f:
        buf = f.read()
    ops: Dict[int, List[list]] = {}
    marker = None
    for number, span in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        plane = _plane(buf, span)
        device = _DEVICE_PLANE.match(plane["name"])
        if device:
            metadata = _event_metadata(buf, plane, "tf_op")
            rows = ops.setdefault(int(device.group(1)), [])
            for line in plane["lines"]:
                for md, start, dur, _stats in _line_events(buf, line,
                                                           _OPS_LINE):
                    text, op_name = metadata.get(md, ("", None))
                    rows.append([_instruction(text), start, dur,
                                 op_name or ""])
        elif plane["name"] == _HOST_PLANE and marker is None:
            metadata = _event_metadata(buf, plane, "")
            ids = {k for k, (n, _) in metadata.items()
                   if n == CLOCK_MARKER}
            for line in plane["lines"] if ids else ():
                for md, start, _dur, stats in _line_events(buf, line,
                                                           ids=ids):
                    if md not in ids:
                        continue
                    for st in stats:
                        stat_name, value = _stat(buf, st,
                                                 plane["stat_names"])
                        if stat_name == "wall_us":
                            marker = (start, float(value) / 1e6)
    return ops, marker


def _instruction(text: str) -> str:
    """``%fusion.21 = (...) fusion(...)`` -> ``fusion.21``; a Pallas
    kernel (a custom call to ``tpu_custom_call``) -> ``kernel:<name>``."""
    name = text.split(" = ", 1)[0].lstrip("%")
    return "kernel:" + name if _PALLAS_TARGET in text else name


# -- the hook -------------------------------------------------------------

class _JaxProfiler:
    """``jax.profiler`` behind two calls, so a test can hand
    :class:`DeviceTrace` a recording instead."""

    def __init__(self):
        self._dir = None

    def start(self) -> float:
        import jax  # noqa: PLC0415

        self._dir = tempfile.mkdtemp(prefix="hvdtpu_slice_")
        jax.profiler.start_trace(self._dir)
        wall = time.time()
        with jax.profiler.TraceAnnotation(CLOCK_MARKER,
                                          wall_us=int(wall * 1e6)):
            pass
        return wall

    def stop(self):
        import jax  # noqa: PLC0415

        try:
            jax.profiler.stop_trace()
            found = sorted(glob.glob(os.path.join(
                self._dir, "plugins", "profile", "*", "*.xplane.pb")))
            return read_xplane(found[-1]) if found else ({}, None)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


def _profiler_backend():
    """The profiler to record with, or None where there is no device to
    trace: on the CPU backend the trace has no device plane, and a rank
    of the test suite must not pay for one."""
    import jax  # noqa: PLC0415

    if jax.default_backend() == "cpu":
        return None
    return _JaxProfiler()


class DeviceTrace:
    """One bounded slice of device trace, reduced in this process::

        with hvd.obs.profile.device_trace(steps=8) as dt:
            for batch in batches:
                state, loss = step(state, batch)
                if dt.step():       # True once `steps` were counted
                    break
        print(dt.result["by_scope"], dt.result["idle_by_span"])

    Leaving the block (or the ``steps``-th ``step()``) stops the
    profiler and sets ``result`` (:func:`reduce_trace` over the slice
    and the span ring's spans that overlap it — those of ``lanes``,
    where given — plus ``t0``/``t1`` on ``time.time()``).  The caller makes the device finish its work
    (``block_until_ready``) before the end, or the tail is cut.  One
    slice at a time per process: starting a second one drops the first.
    On the CPU backend nothing is recorded and ``result`` stays None.
    """

    _active: Optional["DeviceTrace"] = None

    def __init__(self, steps: int = SLICE_STEPS,
                 lanes: Optional[Sequence[str]] = None):
        self.steps = int(steps)
        # Span lanes (trace ids) that may name an idle gap; None = all.
        self.lanes = None if lanes is None else set(lanes)
        self.result: Optional[dict] = None
        self._left = 0
        self._profiler = None
        self._t0 = 0.0

    @property
    def recording(self) -> bool:
        return self._profiler is not None

    def start(self) -> bool:
        """True if a slice is now being recorded."""
        if DeviceTrace._active is not None:
            DeviceTrace._active.stop(discard=True)
        self._profiler = _profiler_backend()
        if self._profiler is None:
            return False
        self._t0 = self._profiler.start()
        self._left = self.steps
        DeviceTrace._active = self
        return True

    def step(self) -> bool:
        """Count one step; True when this was the slice's last (the
        slice is then stopped and reduced)."""
        if self._profiler is None:
            return False
        self._left -= 1
        if self._left > 0:
            return False
        self.stop()
        return True

    def stop(self, discard: bool = False) -> Optional[dict]:
        profiler, self._profiler = self._profiler, None
        if profiler is None:
            return self.result
        DeviceTrace._active = None
        t1 = time.time()
        ops, marker = profiler.stop()
        if discard:
            return None
        t2 = time.time()
        from . import trace as obs_trace  # noqa: PLC0415

        spans = []
        if obs_trace.enabled():
            spans = [s for s in obs_trace.get_buffer().snapshot()
                     if s["t0"] + s["dur"] >= self._t0 and s["t0"] <= t1
                     and s["name"] != "device_slice"
                     and (self.lanes is None or s["trace"] in self.lanes)]
        self.result = reduce_trace(ops, marker, spans)
        # what the slice cost after its last step: stopping the
        # profiler and reading its file, then the reduction
        self.result.update(t0=self._t0, t1=t1, collect_s=round(t2 - t1, 6),
                           reduce_s=round(time.time() - t2, 6))
        return self.result

    def __enter__(self) -> "DeviceTrace":
        self.start()
        return self

    def __exit__(self, *_exc) -> None:
        self.stop()


device_trace = DeviceTrace


class SliceSchedule:
    """The serving rank's use of :class:`DeviceTrace`: of every
    :data:`SLICE_PERIOD` busy steps the last :data:`SLICE_STEPS` are
    recorded (so the first slice starts warm), each slice reduced in
    the rank and emitted as ONE ``device_slice`` span on ``lane`` with
    the reduction in its ``args``.  A slice also ends at the first idle
    step: a bounded slice never waits for traffic.

    A slice is not free: stopping the profiler and reading its file
    hold the loop (``collect_s`` in the span), for a time that follows
    the number of operations recorded (``ops``).  Decode steps are all
    alike, so a slice is a few steps long, and the spans' own sample
    rate (``HVDTPU_TRACE_SAMPLE_RATE``) thins the slices as it thins
    the request lanes: at rate ``r`` one period in ``1/r`` has a slice.
    Which period is a pure function of (epoch, period, rate), and busy
    steps are counted from the schedule every rank of a group obeys, so
    the ranks of a group stop a slice at the same step and pay for it
    together, not one after the other inside each other's collectives.
    """

    def __init__(self, lane: str, rate: float = 1.0):
        self.lane = lane
        self.rate = rate
        self.busy_steps = 0
        self.last: Optional[dict] = None
        self.failed = False
        # Request lanes are left out: a request's queue_wait overlaps
        # other requests' steps and would claim their gaps.
        self._trace = DeviceTrace(SLICE_STEPS, lanes=(lane, COMPILE_LANE))

    def tick(self, busy: bool, epoch: int, step: int) -> None:
        """Once per loop iteration, after the step's spans are in the
        ring.  A profiler that fails (a trace someone else started, a
        full disk) costs the slices, never the serving loop."""
        if self.failed:
            return
        try:
            self._tick(busy, epoch, step)
        except Exception:  # the loop must keep serving
            from ..utils.logging import get_logger  # noqa: PLC0415

            get_logger("obs.profile").exception(
                "device slices disabled: the profiler failed")
            self.failed = True

    def _tick(self, busy: bool, epoch: int, step: int) -> None:
        from . import trace as obs_trace  # noqa: PLC0415

        if self._trace.recording:
            if (self._trace.step() if busy else True):
                self._emit(epoch, step)
        if not busy:
            return
        self.busy_steps += 1
        period, at = divmod(self.busy_steps, SLICE_PERIOD)
        if at == SLICE_PERIOD - SLICE_STEPS and obs_trace.sampled(
                f"device_slice/{epoch}/{period}", self.rate):
            self._trace.start()

    def _emit(self, epoch: int, step: int) -> None:
        from . import trace as obs_trace  # noqa: PLC0415

        result = self._trace.stop()
        if result is None:
            return
        self.last = result
        args = {k: v for k, v in result.items() if k not in ("t0", "t1")}
        obs_trace.add_span(self.lane, "device_slice", result["t0"],
                           result["t1"], epoch=epoch, step=step, **args)
