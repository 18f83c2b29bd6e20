"""90th percentile of the gaps between consecutive ready stamps, over
every step of the window: the stamps the loop takes itself
(``train_throughput`` says which), so every gap is one step waited for
inside the loop and none is the drain after it."""

from benchmark.harness.stats import percentile


def read(run):
    stamps = run.get("stamps")
    if not stamps or len(stamps) < 2:
        return None
    gaps = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    return percentile(gaps, 90.0)
