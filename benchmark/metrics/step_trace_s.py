"""Seconds JAX spent tracing the cell's step program before the measured
window, from the program's set-up log
(``horovod_tpu.obs.profile.compile_log()``; ``compile_trace_lower_s``
says which records count).  **The step** is the program whose
``trace``, ``lower`` and ``backend`` records before the window sum to
the most seconds; the other programs (the state's init, broadcasts, the
warm-up's helpers) are ``state_programs_s``.  ``step_trace_s`` +
``step_lower_s`` + ``step_backend_s`` is what the runner's clock reads
from outside as ``compile_s``."""

from benchmark.harness import registry

PROGRAM_PHASES = ("trace", "lower", "backend")


def split(run):
    """``(the step's records, every other program's)`` before the
    window, or None where there is no log to read."""
    log = registry.sibling_metric(__file__, "compile_trace_lower_s")
    records = [r for r in log.records_before_window(run) or ()
               if r["phase"] in PROGRAM_PHASES]
    if not records:
        return None
    total = {}
    for r in records:
        total[r["program"]] = total.get(r["program"], 0.0) + r["seconds"]
    step = max(total, key=total.get)
    return ([r for r in records if r["program"] == step],
            [r for r in records if r["program"] != step])


def step_seconds(run, phase):
    found = split(run)
    if found is None:
        return None
    return sum(r["seconds"] for r in found[0] if r["phase"] == phase)


def read(run):
    return step_seconds(run, "trace")
