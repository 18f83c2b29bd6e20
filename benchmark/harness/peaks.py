"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string JAX reports.  The benchmark's own copy: the
program's table (``horovod_tpu/obs/profile.py``) can change without
moving this yardstick.  A kind that is not here is an error, never a
default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16 and 16 GB of HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises on an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add its "
            f"row, with the source, to benchmark/harness/peaks.py "
            f"(known: {sorted(PEAKS)})"
        ) from None
