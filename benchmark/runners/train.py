"""Runner ``train``: one training cell, once.

Set-up (import, ``hvd.init``, state made on the device from the seed,
compile or cache load, warm-up), then a window of ``seconds`` in which
the loop keeps one step in flight: it dispatches step *i*, then blocks
on the loss of step *i-1* and stamps the moment it is ready.  The window
is what lies between the first and the last of those stamps; the one
step still in flight when the loop ends is drained after it, keeps its
loss for the checks, and its delay is a note (``drain_ms``), not part of
the window.  With ``trace`` a short second window of ``trace_steps``
steps runs under the JAX profiler.  After the windows the outputs are
checked.  Returns the observations the metric readers read; computes no
metric itself.
"""

from __future__ import annotations

import math
import os
import shutil
import tempfile
import time

from benchmark.harness import correct, device as dev, registry
from benchmark.harness import trace as tr
from benchmark.harness.peaks import peaks
from benchmark.harness.stats import median

HOST_SPANS = ("dispatch", "wait_loss")
STALL = 3.0  # a gap this many times the median gap is noted as a stall


class BuildCounter:
    """Counts programs traced, lowered, compiled or fetched from the
    compile cache, through ``jax.monitoring``: none may happen inside a
    measured window."""

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if "/compile/" in event or "compilation_cache" in event:
            self.count += 1


def _loop(compiled, carry, const, seconds=None, steps=None):
    """Dispatch step i, wait for the loss of step i-1, stamp.  Ends after
    ``seconds`` or after ``steps`` steps.  Returns (carry, the ready
    stamps taken inside the loop, losses as device scalars, time of the
    first dispatch, seconds the drain took).  The step still in flight at
    the loop's exit is drained: its loss is the last of ``losses``, which
    is therefore one longer than the stamps, and it gets no stamp: the
    host does other things between the loop's exit and that moment, and
    a stamp there made the window read a host stall as the last step."""
    import jax

    stamps, losses = [], []
    t_start = time.perf_counter()
    *carry, pending = compiled(*carry, *const)
    while True:
        with jax.profiler.TraceAnnotation("dispatch"):
            *carry, loss = compiled(*carry, *const)
        with jax.profiler.TraceAnnotation("wait_loss"):
            pending.block_until_ready()
        now = time.perf_counter()
        stamps.append(now)
        losses.append(pending)
        pending = loss
        if seconds is not None and now - t_start >= seconds:
            break
        if steps is not None and len(stamps) + 1 >= steps:
            break
    pending.block_until_ready()
    drain_s = time.perf_counter() - stamps[-1]
    losses.append(pending)
    return carry, stamps, losses, t_start, drain_s


def stalls(stamps, drain_s: float) -> list:
    """The gaps between ready stamps, and the drain, that took more than
    ``STALL`` times the median gap: where, how long, and the host's load
    average when this was written (the end of the window)."""
    if len(stamps) < 3:
        return []
    gaps = [b - a for a, b in zip(stamps, stamps[1:])]
    typical = median(gaps)
    found = [{"where": f"gap {i + 1} of {len(gaps)}", "ms": g * 1e3}
             for i, g in enumerate(gaps) if g > STALL * typical]
    if drain_s > STALL * typical:
        found.append({"where": "drain", "ms": drain_s * 1e3})
    for entry in found:
        entry.update(median_gap_ms=typical * 1e3,
                     host_loadavg=list(os.getloadavg()))
    return found


def _traced(compiled, carry, const, steps: int, keep_raw: str = None):
    """``steps`` steps of the same loop under the profiler; the trace as
    plain data (benchmark/harness/trace.py)."""
    import jax

    out_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(out_dir)
        try:
            carry, *_ = _loop(compiled, carry, const, steps=steps)
        finally:
            jax.profiler.stop_trace()
        raw = tr.find_xplane(out_dir)
        if keep_raw:
            shutil.copy(raw, keep_raw)
        data = tr.load_xplane(raw, host_names=HOST_SPANS)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return carry, data


def run(cell: dict, seed: int, seconds: float, trace: bool, t0: float,
        allow_cpu: bool = False, dump_trace: str = None) -> dict:
    params = cell["params"]
    config = cell["config_values"]
    # refused here, before the backend is touched, if the family's file
    # lacks a function the run needs only after its window
    builder = registry.load_model_builder(config["family"], cell["root"])

    import jax

    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    # A scope is metadata, and JAX leaves metadata out of the persistent
    # cache's key unless told: a step cached by a tree without a scope
    # would come back without it, and its reader would find nothing.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    device = dev.require(*dev.local(), cell["chips"], allow_cpu)
    builds = BuildCounter()
    clock = {"import_and_backend_s": time.perf_counter() - t0}

    t_b = time.perf_counter()
    built = builder.build(config, params, seed)
    jax.block_until_ready(built.state)
    t_c = time.perf_counter()
    compiled = built.step.lower(*built.state).compile()
    compile_s = time.perf_counter() - t_c
    clock.update(state_on_device_s=t_c - t_b, compile_s=compile_s)

    carry = list(built.state[:built.carry_len])
    const = built.state[built.carry_len:]
    for _ in range(params["warmup_steps"]):
        *carry, loss = compiled(*carry, *const)
    loss.block_until_ready()

    clock["warm_up_s"] = time.perf_counter() - t_c - compile_s
    builds_before = builds.count
    carry, stamps, losses, t_start, drain_s = _loop(compiled, carry, const,
                                                    seconds=seconds)
    builds_in_window = builds.count - builds_before
    notes = {"drain_ms": drain_s * 1e3}
    stalled = stalls(stamps, drain_s)
    if stalled:
        notes["stalls"] = stalled
    setup_s = t_start - t0
    hbm_peak = dev.memory_peak_bytes()

    traced = None
    if trace:
        builds_before = builds.count
        carry, data = _traced(
            compiled, carry, const, params["trace_steps"],
            keep_raw=dump_trace and dump_trace + ".xplane.pb")
        builds_in_window += builds.count - builds_before
        if dump_trace:
            tr.save_recording(data, dump_trace)
        traced = {"ops": tr.device_ops(data),
                  "host": tr.host_spans(data, HOST_SPANS),
                  "steps": params["trace_steps"]}

    state = tuple(carry) + tuple(const)
    values = [float(x) for x in losses]
    t_checks = time.perf_counter()
    # The checks run two backward passes beside what is left of the
    # cell; whatever of its state they do not read (the optimizer's, the
    # batch) gives its memory back first.
    variables = built.variables(state)
    read = {id(leaf) for leaf in jax.tree.leaves(variables)}
    for leaf in jax.tree.leaves(state):
        if id(leaf) not in read:
            leaf.delete()
    checks = correct.training(
        losses=values, builds_in_window=builds_in_window,
        variables=variables, sample=built.sample(
            params["reference_items"]),
        program_loss=built.program_loss,
        reference=registry.load_reference(cell["config"], cell["root"]),
        config={**config, **built.ran}, chips=built.chips,
        tolerance=config["reference_tolerance"])
    notes["checks_s"] = time.perf_counter() - t_checks

    run_ = {
        "cell": cell, "config": config, "params": params,
        "device": device, "chips": built.chips,
        "setup_s": setup_s, "compile_s": compile_s,
        "stamps": stamps, "items_per_step": built.items_per_step,
        "hbm_peak_bytes": hbm_peak, "ran": built.ran, "trace": traced,
        "attempted": len(values),
        "failed": sum(1 for v in values if not math.isfinite(v)),
        "checks": checks, "correct": all(c["ok"] for c in checks.values()),
        # The window's peak, not the later checks' (they are the
        # benchmark's own, and the two high-water marks memory_stats()
        # keeps would add up across different moments).
        "memory_peak_bytes": hbm_peak,
        # Model FLOPs per item from shapes (forward + backward, no
        # recompute): times ``train_throughput`` over the chip's peak
        # it is the model FLOPs utilisation, which PERF.md derives.
        "notes": {"setup": clock, **notes,
                  "model_flops_per_item": builder.train_flops_per_item(
                      config, built.ran)},
        "device_time": traced and tr.device_time(
            traced["ops"], traced["host"],
            registry.reader_scopes(cell["root"])),
    }
    if device["platform"] == "tpu":
        run_["peaks"] = peaks(device["kind"])
    return run_
