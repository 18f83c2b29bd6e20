"""Seconds the process spent tracing and lowering programs before the
measured window, from the program's own compile log
(``horovod_tpu.obs.profile.compile_log()``: one record per
``jax.monitoring`` compile event, stamped with ``perf_counter`` like the
runner's ready stamps).  It is the part of ``compile_s`` that the
persistent cache cannot save, plus the same for the init and warm-up
programs.  A program without the log, or a run without stamps: None."""


def records_before_window(run):
    """The log's records that ended before the window's first ready
    stamp, or None where there is no log to read."""
    stamps = run.get("stamps")
    if not stamps:
        return None
    try:
        from horovod_tpu.obs import profile
    except ImportError:
        return None
    compile_log = getattr(profile, "compile_log", None)
    if compile_log is None:
        return None
    records = [r for r in compile_log() if r["t_end"] < stamps[0]]
    return records or None


def read(run):
    records = records_before_window(run)
    if records is None:
        return None
    return sum(r["seconds"] for r in records
               if r["phase"] in ("trace", "lower"))
