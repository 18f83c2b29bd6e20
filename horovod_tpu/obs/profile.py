"""Live MFU profiler: model-FLOPs accounting over measured step time.

The ROADMAP's item-5 campaign has machinery but no *measurement layer*:
MFU existed only as a line bench.py computed inline at the end of a
run.  This module is that layer, shared by every surface that times a
step:

* **Model FLOPs per step** — preferred source: XLA's own post-fusion
  cost analysis of the compiled artifact (:func:`flops_from_compiled`,
  the PR-9 HLO-inspector spirit: a property of the artifact, not a
  hand-derived guess).  Fallback when the executable cannot be
  inspected: analytic formulas keyed off the bench model builders
  (:func:`analytic_step_flops` — the 6N + 12·L·s·d transformer rule and
  a per-model conv table), flagged ``source: analytic``.
* **Device peak FLOP/s** — a small per-platform table
  (:data:`PEAK_FLOPS`, public TPU spec sheets).  The kind ``cpu``
  gets a nominal order-of-magnitude entry marked **estimate-only**: a
  CPU MFU is a trajectory placeholder, never a perf claim, and every
  consumer carries the flag.  Any other unknown kind is an error.
* **Live gauges** — :class:`MFUProfiler` divides FLOPs by measured step
  time and publishes ``perf.mfu``, ``perf.model_tflops``,
  ``perf.step_ms`` (plus ``perf.mfu_estimate`` when the peak is a
  guess) into the metrics registry — so the digest (``mfu 0.31``
  token), ``/metrics``, ``--stats-summary`` and every BENCH record see
  the same number, computed once.

No jax import at module scope: the launcher imports obs eagerly and
must not initialise a backend for it.
"""

from __future__ import annotations

from typing import Optional, Tuple

__all__ = [
    "PEAK_FLOPS",
    "CPU_PEAK_ESTIMATE",
    "peak_flops",
    "flops_from_compiled",
    "transformer_step_flops",
    "analytic_step_flops",
    "MFUProfiler",
]

# Peak dense-matmul FLOP/s per chip (bf16 on MXU; fp32 runs at ~1/4 via
# bf16x3 passes or worse), keyed by the string ``jax.Device.device_kind``
# reports.  Source, one page per generation: Google Cloud TPU
# documentation, "System architecture" (cloud.google.com/tpu/docs/v2,
# /v3, /v4, /v5e, /v5p, /v6e), "Peak compute per chip (bf16)".  Shared
# with bench.py — ONE table, so the bench headline and the live gauge
# can never disagree about a chip's peak.  A kind that is not here is
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
    when the backend exposes no analysis — callers fall back to
    :func:`analytic_step_flops`."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    try:
        v = float(ca.get("flops", 0.0))
    except (AttributeError, TypeError, ValueError):
        return None
    return v if v > 0 else None


# -- analytic fallbacks ------------------------------------------------------

def _transformer_param_count(cfg) -> int:
    """Parameter count of models/transformer.py's GPT for a config —
    kept in lockstep with the flax module (wte + learned wpe + per-block
    qkv/proj/mlp/2LN + final LN + untied head)."""
    d = cfg.emb_dim
    kv_dim = cfg.kv_heads * cfg.head_dim
    mlp_hidden = cfg.mlp_ratio * d
    per_block = (
        d * (d + 2 * kv_dim) + (d + 2 * kv_dim)   # qkv (+bias)
        + d * d + d                                # proj
        + d * mlp_hidden + mlp_hidden              # mlp up
        + mlp_hidden * d + d                       # mlp down
        + 4 * d                                    # 2 x LayerNorm
    )
    n = cfg.vocab_size * d + cfg.num_layers * per_block
    n += 2 * d                                     # final LayerNorm
    n += d * cfg.vocab_size                        # untied head
    if cfg.pos_embedding == "learned":
        n += cfg.max_len * d
    return n


def transformer_step_flops(cfg, batch_size: int, seq_len: int,
                           training: bool = True) -> float:
    """Analytic model FLOPs for one step over ``batch_size`` sequences
    of ``seq_len`` tokens: the standard 6N-per-token rule (2N forward,
    4N backward) plus the attention term 12·L·s·d per token (4·s·d
    forward for QKᵀ and AV, tripled for training).  ``training=False``
    gives the forward-only 2N + 4·L·s·d (the decode-step shape)."""
    n = _transformer_param_count(cfg)
    tokens = batch_size * seq_len
    per_tok_mat = (6 if training else 2) * n
    per_tok_attn = (12 if training else 4) * cfg.num_layers * seq_len \
        * cfg.emb_dim
    return float(tokens) * (per_tok_mat + per_tok_attn)


# Forward FLOPs per image at 224x224 (published per-model numbers,
# 2 x MACs); training approximated as 3 x forward.
_CONV_FWD_FLOPS_224 = {
    "resnet18": 3.6e9,
    "resnet50": 8.2e9,
    "resnet101": 15.2e9,
    "vgg16": 31.0e9,
    "vgg19": 39.0e9,
    "inception3": 11.4e9,
}


def analytic_step_flops(model_name: str, batch_size: int,
                        seq_len: Optional[int] = None,
                        image_size: int = 224) -> Optional[float]:
    """Analytic per-step training FLOPs keyed off the bench model
    builders (``bench.py --model`` names).  None for a model the tables
    don't know — the caller then reports no MFU rather than a wrong
    one."""
    if model_name.startswith("gpt-"):
        from ..models.transformer import GPT_CONFIGS  # noqa: PLC0415

        cfg = GPT_CONFIGS.get(model_name[len("gpt-"):])
        if cfg is None or not seq_len:
            return None
        return transformer_step_flops(cfg, batch_size, seq_len)
    fwd = _CONV_FWD_FLOPS_224.get(model_name)
    if fwd is None:
        return None
    scale = (image_size / 224.0) ** 2
    return 3.0 * fwd * scale * batch_size


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
