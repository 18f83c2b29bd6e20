"""What the process sees of the accelerator, and the refusal to measure
without one."""

from __future__ import annotations


class NoAccelerator(SystemExit):
    """Raised (exit code 3, nothing printed to stdout) when JAX finds no
    TPU or fewer chips than the cell asks for."""

    def __init__(self, message: str):
        super().__init__(3)
        self.message = message


def require(platform: str, kind: str, count: int, chips: int,
            allow_cpu: bool = False) -> dict:
    """The ``device`` object of the last line, or NoAccelerator.

    ``allow_cpu`` exists for ``benchmark/tests`` alone (the command line
    has no way to set it): it lets the control flow be rehearsed at a
    tiny size, and every number of such a run is thrown away."""
    if platform != "tpu" and not allow_cpu:
        raise NoAccelerator(
            f"JAX found platform {platform!r}, not 'tpu': the benchmark "
            "measures nothing without the chip")
    if count < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chip(s), JAX found {count}")
    if count != chips and not allow_cpu:
        raise NoAccelerator(
            f"the cell asks for {chips} chip(s) and JAX found {count}: the "
            "program spans every chip it sees, so this is another cell")
    return {"platform": platform, "kind": kind, "count": count}


def local() -> tuple:
    import jax

    devices = jax.devices()
    return devices[0].platform, devices[0].device_kind, len(devices)


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip: the allocator's high-water mark
    of live buffers plus what it reserved for running programs' scratch
    (on the TPU runtime ``peak_bytes_in_use`` leaves the step program's
    temporaries out; they are ``peak_bytes_reserved``).  0 where the
    backend does not say (the CPU)."""
    import jax

    peaks = [0]
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)
