"""Step-time anatomy: where a benchmark's mean step wall time went.

A BENCH record that ships a number without its explanation invites the
r03-r05 failure mode in analysis form: the next reader cannot tell a
comms regression from a host-input stall.  This module decomposes the
measured mean step time into three components that tile it:

* **compute** — the ideal matmul time of the step: model FLOPs (XLA's
  post-fusion ``cost_analysis()``, via obs/profile.py) over the chip's
  peak.  By construction ``compute_ms = MFU x step_ms``, so the
  anatomy and the PR-11 MFU gauge can never disagree.
* **collective_wait** — engine collective overhead per step, from the
  ``engine.cycle_time_ms`` histogram the cycle loop already feeds
  (zero on the world==1 jit path, which never starts the engine).
* **host_gap** — the residual: dispatch gaps, input pipeline, python
  overhead.  Defined as ``step - compute - collective`` (clamped at
  zero), which is what makes the three components tile the step time
  exactly; the raw residual is preserved in ``residual_ms`` so an
  over-estimated compute term is visible rather than papered over.

Beside the split ride a top-K HLO op table (parsed from the compiled
artifact's text) and a **roofline verdict** — compute-/memory-/comms-
bound, judged from the collective fraction, the MFU gauge and the
arithmetic intensity vs the chip's ridge point, with the PR-8 dcn/ici
byte counters printed next to it so a comms verdict names its fabric.

Stdlib-only, no jax import at module scope; :func:`attach_anatomy` is
best-effort by contract — anatomy must never sink the measurement it
explains.
"""

from __future__ import annotations

import re
from typing import List, Optional

from .profile import peak_flops

__all__ = ["step_anatomy", "attach_anatomy", "top_ops_from_compiled",
           "roofline_verdict", "HBM_BANDWIDTH", "CPU_BW_ESTIMATE",
           "COMMS_BOUND_FRAC", "COMPUTE_BOUND_MFU"]

# Peak HBM bandwidth, bytes/sec — only used for the ridge point of the
# roofline verdict.  Same keys and same source pages as obs/profile.py's
# PEAK_FLOPS table ("HBM bandwidth per chip").
HBM_BANDWIDTH = {
    "TPU v2": 700e9,
    "TPU v3": 900e9,
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5": 2765e9,
    "TPU v6 lite": 1640e9,
}
# A few DDR channels; estimate-flagged wherever it flows, like
# profile.CPU_PEAK_ESTIMATE, and like it reachable only for kind "cpu".
CPU_BW_ESTIMATE = 5e10


def _hbm_bandwidth(device_kind) -> tuple:
    """``(bytes/sec, estimate_flag)`` under peak_flops' rule: a known
    kind is authoritative, ``"cpu"`` is an estimate, anything else
    raises."""
    bw = HBM_BANDWIDTH.get(device_kind)
    if bw is not None:
        return bw, False
    if device_kind == "cpu":
        return CPU_BW_ESTIMATE, True
    raise ValueError(
        f"no HBM bandwidth known for device kind {device_kind!r}"
    )

# Verdict thresholds: a step spending over a third of itself waiting on
# collectives is comms-bound whatever the MFU says; an MFU at or above
# 0.4 means the MXUs are the constraint.
COMMS_BOUND_FRAC = 0.35
COMPUTE_BOUND_MFU = 0.4

# opcode right before its '(' operand list, after the '=' — tolerant of
# the shape/layout noise HLO text puts between them.
_OPCODE_RE = re.compile(r"=\s+[^=(]*?([a-z][\w-]*)\(")
# Structural opcodes that say nothing about where time went.
_BORING_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
               "bitcast", "copy", "after-all"}


def _bytes_from_compiled(compiled) -> Optional[float]:
    """``bytes accessed`` from cost_analysis() (None when the artifact
    exposes no analysis, like profile.flops_from_compiled)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    try:
        v = float(ca.get("bytes accessed", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return v if v > 0 else None


def top_ops_from_compiled(compiled, k: int = 8) -> List[dict]:
    """Top-K HLO opcodes by instruction count from the compiled
    artifact's text — which op families dominate the module (fusion
    kinds, collectives, convolutions), not a per-op timing profile.
    Returns [] when the artifact exposes no text."""
    try:
        text = compiled.as_text()
    except Exception:
        return []
    if not isinstance(text, str) or not text:
        return []
    counts: dict = {}
    for line in text.splitlines():
        if "=" not in line:
            continue
        m = _OPCODE_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        if op in _BORING_OPS:
            continue
        counts[op] = counts.get(op, 0) + 1
    top = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [{"op": op, "count": n} for op, n in top]


def roofline_verdict(*, mfu: Optional[float],
                     collective_frac: float,
                     flops_per_step: Optional[float],
                     bytes_per_step: Optional[float],
                     device_kind: str,
                     dtype: str = "bf16") -> dict:
    """compute- / memory- / comms-bound, with the evidence beside the
    word.  Comms wins first (a stalled fabric caps everything else);
    then MFU or arithmetic intensity vs the ridge point decides between
    the MXUs and HBM."""
    peak, peak_estimate = peak_flops(device_kind, dtype)
    bw, bw_estimate = _hbm_bandwidth(device_kind)
    ridge = peak / bw  # FLOPs/byte at which HBM stops being the limit
    intensity = None
    if flops_per_step and bytes_per_step:
        intensity = flops_per_step / bytes_per_step
    if collective_frac > COMMS_BOUND_FRAC:
        verdict = "comms-bound"
        basis = (f"collective wait is {collective_frac:.0%} of the step "
                 f"(> {COMMS_BOUND_FRAC:.0%})")
    elif (mfu is not None and mfu >= COMPUTE_BOUND_MFU) or (
            intensity is not None and intensity >= ridge):
        verdict = "compute-bound"
        if mfu is not None and mfu >= COMPUTE_BOUND_MFU:
            basis = f"MFU {mfu:.2f} >= {COMPUTE_BOUND_MFU}"
        else:
            basis = (f"arithmetic intensity {intensity:.1f} FLOPs/B >= "
                     f"ridge {ridge:.1f}")
    else:
        verdict = "memory-bound"
        basis = ("low MFU with low collective wait"
                 if intensity is None else
                 f"arithmetic intensity {intensity:.1f} FLOPs/B < "
                 f"ridge {ridge:.1f}")
    out = {
        "verdict": verdict,
        "basis": basis,
        "mfu": mfu,
        "collective_frac": round(collective_frac, 4),
        "ridge_flops_per_byte": round(ridge, 2),
        "estimate": bool(peak_estimate or bw_estimate),
    }
    if intensity is not None:
        out["arithmetic_intensity"] = round(intensity, 2)
    return out


def _engine_collective_ms(steps_observed: Optional[int]) -> tuple:
    """(per-step collective-wait ms, source string).  Total engine cycle
    time (the ``engine.cycle_time_ms`` histogram's sum — negotiation +
    wire time for every bucket) amortized over the steps that ran.
    Zero with an explaining source when the engine never started."""
    try:
        from .registry import get_registry  # noqa: PLC0415

        total = 0.0
        count = 0
        for m in get_registry().snapshot():
            if m.get("name") in ("engine.cycle_time_ms",
                                 "engine.negotiation_ms"):
                total += float(m.get("sum") or 0.0)
                count += int(m.get("count") or 0)
        if count == 0:
            return 0.0, "no engine cycles (jit path or world=1)"
        if steps_observed and steps_observed > 0:
            return total / steps_observed, "engine.cycle_time_ms histogram"
        return total, "engine.cycle_time_ms histogram (unamortized)"
    except Exception:
        return 0.0, "registry unavailable"


def step_anatomy(step_ms: float, *,
                 mfu: Optional[float] = None,
                 flops_per_step: Optional[float] = None,
                 device_kind: str,
                 dtype: str = "bf16",
                 compiled=None,
                 steps_observed: Optional[int] = None,
                 gauges: Optional[dict] = None) -> Optional[dict]:
    """Decompose ``step_ms`` into compute / collective_wait / host_gap
    (which tile it by construction) plus the op table and roofline
    verdict.  Returns None only when ``step_ms`` is unusable."""
    if not isinstance(step_ms, (int, float)) or not step_ms > 0:
        return None
    peak, peak_estimate = peak_flops(device_kind, dtype)
    compute_ms = None
    compute_source = None
    if isinstance(mfu, (int, float)) and mfu >= 0:
        # MFU = achieved/peak, so ideal compute time = MFU x wall time:
        # the anatomy reuses the record's own MFU rather than rederiving
        # a number that could disagree with it.
        compute_ms = float(mfu) * step_ms
        compute_source = "mfu x step"
    elif isinstance(flops_per_step, (int, float)) and flops_per_step > 0:
        compute_ms = flops_per_step / peak * 1e3
        compute_source = "flops / peak"
    if compute_ms is None:
        compute_ms = 0.0
        compute_source = "unknown (no MFU, no FLOPs)"
    compute_ms = min(compute_ms, step_ms)
    collective_ms, collective_source = _engine_collective_ms(steps_observed)
    collective_ms = min(collective_ms, step_ms - compute_ms)
    residual_ms = step_ms - compute_ms - collective_ms
    host_gap_ms = max(residual_ms, 0.0)
    components = {
        "compute_ms": round(compute_ms, 4),
        "collective_wait_ms": round(collective_ms, 4),
        "host_gap_ms": round(host_gap_ms, 4),
    }
    tile_pct = (compute_ms + collective_ms + host_gap_ms) / step_ms * 100.0
    out = {
        "step_ms": round(float(step_ms), 4),
        "components_ms": components,
        "components_pct": {
            k.replace("_ms", "_pct"): round(v / step_ms * 100.0, 2)
            for k, v in components.items()
        },
        "tile_pct": round(tile_pct, 2),
        "residual_ms": round(residual_ms, 4),
        "method": {
            "compute": compute_source,
            "collective_wait": collective_source,
            "host_gap": "residual (step - compute - collective)",
            "peak_flops_estimate": bool(peak_estimate),
        },
    }
    bytes_per_step = _bytes_from_compiled(compiled) if compiled else None
    if bytes_per_step is not None:
        out["bytes_per_step"] = bytes_per_step
    roofline = roofline_verdict(
        mfu=float(mfu) if isinstance(mfu, (int, float)) else None,
        collective_frac=collective_ms / step_ms,
        flops_per_step=(float(flops_per_step)
                        if isinstance(flops_per_step, (int, float))
                        else None),
        bytes_per_step=bytes_per_step,
        device_kind=device_kind, dtype=dtype,
    )
    # The PR-8 two-fabric byte counters beside the verdict: a
    # comms-bound verdict should name which fabric carried the bytes.
    for key in ("engine.dcn_bytes", "engine.ici_bytes"):
        v = (gauges or {}).get(key)
        if isinstance(v, (int, float)):
            roofline[key.split(".", 1)[1]] = v
    out["roofline"] = roofline
    if compiled is not None:
        top = top_ops_from_compiled(compiled)
        if top:
            out["top_ops"] = top
    return out


def attach_anatomy(out: dict, **kwargs) -> dict:
    """Embed ``anatomy.*`` into a result payload, best-effort: anatomy
    explains a measurement and must never sink one."""
    try:
        anatomy = step_anatomy(**kwargs)
        if anatomy is not None:
            out["anatomy"] = anatomy
    except Exception:
        pass
    return out
