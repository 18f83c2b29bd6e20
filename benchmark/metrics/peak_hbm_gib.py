"""Peak device memory after the window, on the fullest device: live
buffers (``peak_bytes_in_use``) plus the scratch the runtime reserved for
the step program (``peak_bytes_reserved``), from ``memory_stats()``."""


def read(run):
    peak = run.get("hbm_peak_bytes")
    return peak / 2 ** 30 if peak else None
