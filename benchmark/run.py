#!/usr/bin/env python3
"""The benchmark's one command: run one cell once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A new process loads, warms up, measures for ``--seconds`` and prints one
JSON object as the last line of its standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``) and
``device``.  Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits 3.
"""

import time

T0 = time.perf_counter()  # process start, as near as Python can stamp it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def result_line(run: dict, trace: bool) -> dict:
    """The last line, from what a runner observed."""
    from benchmark.harness import registry

    root = run["cell"]["root"]
    kind = "per_layer" if trace else "end_to_end"
    entries = registry.metric_entries(kind, run["cell"]["name"], root)
    metrics = registry.read_metrics(entries, run, root)
    device = dict(run["device"])
    device["memory_peak_bytes"] = run["memory_peak_bytes"]
    line = {"correct": bool(run["correct"]),
            "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device}
    if trace and run.get("device_time"):
        device.update(run["device_time"]["device"])
        line["breakdown"] = run["device_time"]["breakdown"]
    line["checks"] = run["checks"]
    if run.get("notes"):
        line["notes"] = run["notes"]
    return line


def execute(workload: str, seed: int, seconds: float, trace: bool,
            root: str = ROOT, allow_cpu: bool = False, t0: float = None,
            dump_trace: str = None) -> dict:
    """Run one cell once and return its last line.  ``root`` and
    ``allow_cpu`` are for benchmark/tests: the command line sets
    neither, so it cannot measure on the CPU."""
    from benchmark.harness import registry

    cell = registry.load_cell(workload, root)
    runner = registry.load_runner(cell["runner"], root)
    run = runner.run(cell, seed, seconds, trace,
                     T0 if t0 is None else t0, allow_cpu=allow_cpu,
                     dump_trace=dump_trace)
    return result_line(run, trace)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--dump-trace", default=None,
                        help="with --trace 1: keep the reduced trace here "
                             "(.json.gz), for a look by hand")
    args = parser.parse_args(argv)

    from benchmark.harness.device import NoAccelerator

    try:
        line = execute(args.workload, args.seed, args.seconds,
                       bool(args.trace), dump_trace=args.dump_trace)
    except NoAccelerator as exc:
        print(f"benchmark: {exc.message}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
