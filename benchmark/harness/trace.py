"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A trace is first turned into plain data::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns, scope],
                                       ...]}]}]}

(`load_xplane`), so that everything below is arithmetic on lists and can
be checked against a small recording kept with the tests
(`benchmark/tests/data/`).  Nothing here imports the program.

What is read:

* a *device plane* is one whose name starts with ``/device:TPU:``; its
  operations are the events of its ``XLA Ops`` line (one event per
  executed HLO instruction or kernel; nested fusions are not separate
  events there).  The profiler names an event by the instruction's whole
  text (``%fusion.21 = (f32[1024,50257]...) fusion(...)``); `load_xplane`
  keeps the instruction's own name (``fusion.21``) and marks a Pallas
  kernel, which XLA sees as a custom call to ``tpu_custom_call``, as
  ``tpu_custom_call:<name>`` (``tpu_custom_call:block0.3``).  The fourth
  element is the operation's scope as XLA kept it (its ``op_name``:
  ``jit(step)/transpose(jvp(GPT))/block3/attn/qkv/dot_general:``), empty
  for an operation the compiler made itself and in recordings saved
  before the loader kept it;
* the *host plane* is ``/host:CPU``; the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans (``dispatch``, ``wait_loss``)
  are events of that name on one of its thread lines.
"""

from __future__ import annotations

import functools
import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.harness import xplane
from benchmark.harness.stats import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
PALLAS = re.compile(r"^tpu_custom_call:")
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")
UNSCOPED = "unscoped"
# Entries of an operation's scope that are JAX's and not a name someone
# gave: a transform around a function (``jit(step)``, ``jvp(GPT)``,
# ``transpose(jvp(GPT))``), control flow, the partitioner.
NOT_A_NAME = re.compile(
    r"^(\w+\(.*\)|shard_map|while|body|cond|branch_\d+_fun|"
    r"custom_vjp_call|custom_jvp_call|checkpoint|remat|pjit|jit)$")

Interval = Tuple[float, float]


# ------------------------------------------------------------- loading

def find_xplane(trace_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, host_names: Iterable[str] = ()) -> dict:
    """The profiler's file -> the plain structure above.  Of device
    planes only the ``XLA Ops`` line is kept, of the host plane only the
    events named in ``host_names`` (it holds every thread of the
    process)."""
    want = set(host_names)

    def on_device(plane):
        return bool(DEVICE_PLANE.match(plane))

    planes = []
    for plane in xplane.planes(
            path,
            want_plane=lambda p: on_device(p) or p == HOST_PLANE,
            want_line=lambda p, line: line == OPS_LINE or not on_device(p),
            want_event=lambda p, name: on_device(p) or name in want):
        named = op_name if on_device(plane["name"]) else str
        lines = [{"name": line["name"],
                  "events": [[named(name), start, dur, scope]
                             for name, start, dur, scope in line["events"]]}
                 for line in plane["lines"] if line["events"]]
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


def op_code(text: str) -> str:
    """The HLO opcode of an instruction's text: in ``%psum.220 =
    f32[1024,50257]{0,1} all-reduce(%x), ...`` it is ``all-reduce``.
    XLA names an instruction after the JAX primitive where it can, so
    the name alone does not say what runs."""
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:].lstrip()
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    m = re.match(r"([A-Za-z][\w\-]*)\(", rest)
    return m.group(1) if m else ""


def op_name(text: str) -> str:
    """``%fusion.21 = (...) fusion(...)`` -> ``fusion.21``.  A Pallas
    kernel gets the ``tpu_custom_call:`` mark in front, and a collective
    that XLA named otherwise its opcode (``all-reduce:psum.220``)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if PALLAS_TARGET in text:
        return "tpu_custom_call:" + name
    code = op_code(text)
    if COLLECTIVE.match(code) and not name.startswith(code):
        return code + ":" + name
    return name


def stem(name: str) -> str:
    """``fusion.21`` -> ``fusion``, ``tpu_custom_call:block7.3`` ->
    ``tpu_custom_call:block``: operations of one kind under one name."""
    return re.sub(r"[.\d]+$", "", name.split(".")[0]) or name


def save_recording(trace: dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f)


def load_recording(path: str) -> dict:
    """A saved trace; events saved as ``[name, start, dur]``, before the
    loader kept the scope, get an empty one."""
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [e if len(e) > 3 else [*e, ""]
                              for e in line["events"]]
    return trace


# ------------------------------------------------------------ selection

def device_ops(trace: dict) -> Dict[int, List[list]]:
    """Device index -> its operation events, sorted by start."""
    out: Dict[int, List[list]] = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                out[int(m.group(1))] = sorted(line["events"],
                                              key=lambda e: e[1])
    return out


def host_spans(trace: dict, names: Iterable[str]) -> List[list]:
    """The benchmark's own annotations on the host plane, by name."""
    want = set(names)
    found = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            found.extend(e for e in line["events"] if e[0] in want)
    return sorted(found, key=lambda e: e[1])


# ------------------------------------------------------------ arithmetic

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals: Sequence[Interval],
             cover: Sequence[Interval]) -> List[Interval]:
    """The parts of ``intervals`` that ``cover`` does not touch.  Both
    must be unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < b:
            ca, cb = cover[k]
            if ca > cur:
                out.append((cur, ca))
            cur = max(cur, cb)
            if cur >= b:
                break
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def _spans(events: Iterable[list]) -> List[Interval]:
    return [(e[1], e[1] + e[2]) for e in events]


def busy(events: Sequence[list]) -> Tuple[float, float, List[Interval]]:
    """(busy ns, window ns, the busy union) of one device's operations.
    The window runs from the first operation's start to the last one's
    end, so idle time before the first traced step does not count."""
    if not events:
        return 0.0, 0.0, []
    merged = union(_spans(events))
    return total(merged), merged[-1][1] - merged[0][0], merged


def time_by_name(events: Iterable[list], key=lambda name: name
                 ) -> Dict[str, float]:
    """Summed duration (ns) by operation name, or by ``key(name)``."""
    out: Dict[str, float] = {}
    for name, _start, dur, *_scope in events:
        out[key(name)] = out.get(key(name), 0.0) + dur
    return out


def scope_of(event: list) -> str:
    """An event's scope; empty where it was recorded without one."""
    return event[3] if len(event) > 3 else ""


@functools.lru_cache(maxsize=None)  # a step has a few thousand scopes
def scope_names(scope: str) -> Tuple[str, ...]:
    """The names in an operation's scope, outermost first, without JAX's
    own entries and without the primitive it ends in:
    ``jit(step)/transpose(jvp(GPT))/block3/attn/qkv/dot_general:`` ->
    ``("block3", "attn", "qkv")``.  The backward pass of a scope carries
    the same names (under ``transpose(...)``), so forward and backward
    are read together."""
    parts = [p for p in scope.rstrip(":").split("/") if p][:-1]
    return tuple(p for p in parts if not NOT_A_NAME.match(p))


def under(events: Iterable[list], scope: str) -> List[list]:
    """The events traced under ``jax.named_scope(scope)``, at any depth,
    forward or backward."""
    return [e for e in events if scope in scope_names(scope_of(e))]


def scope_ms(run, scope: str) -> Optional[float]:
    """What a ``<scope>_ms`` reader returns: summed device time of the
    operations under ``scope`` per step on one device, from the traced
    window; median over the cell's devices.  None where nothing ran
    under it (a program without the scope, a recording without scopes)."""
    traced = run.get("trace")
    if not traced or not traced["ops"]:
        return None
    value = median([
        sum(e[2] for e in under(ops, scope)) / traced["steps"] / 1e6
        for ops in traced["ops"].values()])
    return value if value > 0 else None


def time_by_scope(events: Iterable[list], known: Iterable[str] = ()
                  ) -> Dict[str, float]:
    """Summed duration (ns) by scope: an operation goes under the
    outermost of the ``known`` scopes in its names (the scopes some
    reader reads), else under its innermost name (a flax module's:
    ``wte``, ``conv1``), else under ``unscoped`` (the compiler's own
    operations, and whatever the program traced under no name)."""
    known = set(known)
    out: Dict[str, float] = {}
    for event in events:
        names = scope_names(scope_of(event))
        key = next((n for n in names if n in known),
                   names[-1] if names else UNSCOPED)
        out[key] = out.get(key, 0.0) + event[2]
    return out


def matching(events: Iterable[list], pattern: "re.Pattern") -> List[list]:
    return [e for e in events if pattern.search(e[0])]


def exposed(events: Sequence[list], pattern: "re.Pattern"
            ) -> Tuple[float, float]:
    """(summed ns of the operations matching ``pattern``, the part of
    their union during which no other operation runs on the device)."""
    mine = [e for e in events if pattern.search(e[0])]
    rest = [e for e in events if not pattern.search(e[0])]
    mine_u = union(_spans(mine))
    alone = subtract(mine_u, union(_spans(rest)))
    return sum(e[2] for e in mine), total(alone)


def idle_gaps(merged_busy: Sequence[Interval], spans: Sequence[list]
              ) -> Dict[str, float]:
    """Idle ns inside the device's window, by what the host was doing:
    each gap between busy intervals is split among the host annotations
    that overlap it, and what none covers is ``between_steps``."""
    out: Dict[str, float] = {}
    by_name: Dict[str, List[Interval]] = {}
    for name, start, dur, *_scope in spans:
        by_name.setdefault(name, []).append((start, start + dur))
    unions = {n: union(v) for n, v in by_name.items()}
    gaps = [(merged_busy[i][1], merged_busy[i + 1][0])
            for i in range(len(merged_busy) - 1)]
    gaps = [g for g in gaps if g[1] > g[0]]
    left = gaps
    for name, cover in unions.items():
        covered = total(gaps) - total(subtract(gaps, cover))
        if covered > 0:
            out[name] = covered
        left = subtract(left, cover)
    rest = total(left)
    if rest > 0:
        out["between_steps"] = out.get("between_steps", 0.0) + rest
    return out


def top(named: Dict[str, float], n: int = 10, scale: float = 1e-9,
        last: str = None) -> List[list]:
    """The ``n`` largest entries as ``[[name, seconds], ...]``; the one
    named ``last`` closes the list whatever its size."""
    items = sorted(named.items(), key=lambda kv: -kv[1])
    closing = [kv for kv in items if kv[0] == last]
    items = [kv for kv in items if kv[0] != last][:n - len(closing)]
    return [[k, v * scale] for k, v in items + closing]


def device_time(ops_by_device: Dict[int, List[list]], spans: Sequence[list],
                scopes: Iterable[str] = ()) -> Optional[dict]:
    """What the last line's ``device`` and ``breakdown`` carry: busy and
    window seconds averaged over the devices, the operations that took
    most time (first device, operations of one kind summed under their
    stem), the same time by scope (``time_by_scope`` with the scopes the
    readers read) and the idle gaps by what the host did."""
    if not ops_by_device:
        return None
    busy_ns, window_ns = [], []
    for ops in ops_by_device.values():
        b, w, _ = busy(ops)
        busy_ns.append(b)
        window_ns.append(w)
    first = ops_by_device[min(ops_by_device)]
    _, _, merged = busy(first)
    return {
        "device": {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
                   "window_s": sum(window_ns) / len(window_ns) / 1e9},
        "breakdown": {"device_ops": top(time_by_name(first, stem)),
                      "device_scopes": top(time_by_scope(first, scopes),
                                           last=UNSCOPED),
                      "idle_gaps": top(idle_gaps(merged, spans))},
        "idle_share_worst": max(
            1.0 - b / w for b, w in zip(busy_ns, window_ns) if w > 0),
    }
