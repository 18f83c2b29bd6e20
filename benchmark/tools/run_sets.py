#!/usr/bin/env python3
"""Measure a cell the way the builder's contract sets its bounds: two
sets of N runs (default 6), the same seeds in both sets, every run a new
process of the benchmark's own command; then, for each metric, each
set's spread (distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` over the median) and both medians.

    python3 benchmark/tools/run_sets.py --workload <cell> --out <dir> \
        [--runs 6] [--sets 2] [--seconds <run_seconds>] [--trace-runs 1]

Every last line is kept in ``<dir>/<cell>.jsonl``.  This process never
touches JAX: each run needs the chip for itself.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIRST_SEED, SEED_STEP = 2147483659, 1000003  # wider than 31 bits


def spread(values):
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def reference_numbers(line) -> str:
    """What the run's reference checks compared, its checks' seconds and
    any stall it noted: a tolerance is set from these over many seeds."""
    checks, notes = line.get("checks", {}), line.get("notes", {})
    picked = [(name, key) for name, key in (
        ("matches_reference", "abs_diff"),
        ("logprob_matches_reference", "abs_diff_max"),
        ("gradient_matches_reference", "diff_norm_over_reference_norm"))
        if name in checks]
    out = [f"{key}={checks[name][key]:.4g}" for name, key in picked]
    out += [f"{key}={notes[key]:.4g}" for key in ("checks_s", "drain_ms")
            if key in notes]
    if notes.get("stalls"):
        out.append("stalls=" + json.dumps(notes["stalls"]))
    return " ".join(out)


def one_run(command, workload, seed, seconds, trace, extra=()):
    t0 = time.time()
    proc = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   *extra],
        cwd=ROOT, capture_output=True, text=True)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = {"error": proc.stderr[-2000:]}
    line.update(rc=proc.returncode, seed=seed, trace=trace,
                wall_s=time.time() - t0, stderr_tail=proc.stderr[-1500:])
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--out", required=True)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--seconds", type=int, default=None)
    parser.add_argument("--trace-runs", type=int, default=1)
    parser.add_argument("--keep-trace", action="store_true",
                        help="keep each traced run's reduced trace in --out")
    args = parser.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, args.workload + ".jsonl"), "a")
    sets = []
    for k in range(args.sets):
        rows = []
        for i in range(args.runs):
            line = one_run(bench["command"], args.workload,
                           FIRST_SEED + i * SEED_STEP, seconds, 0)
            line["set"] = k
            log.write(json.dumps(line) + "\n")
            log.flush()
            rows.append(line)
            if k == 0 and i == 0 and not (line["rc"] == 0
                                          and line.get("correct")):
                print("# the first run failed: " + json.dumps(line)[:4000])
                return 2
            print(f"# set {k} run {i} rc={line['rc']} "
                  f"correct={line.get('correct')} "
                  + " ".join(f"{n}={m['value']:.6g}" for n, m in
                             line.get("metrics", {}).items())
                  + " " + reference_numbers(line), flush=True)
        sets.append(rows)
    for i in range(args.trace_runs):
        extra = ["--dump-trace", os.path.join(
            args.out, f"{args.workload}.trace{i}.json.gz")] \
            if args.keep_trace else []
        line = one_run(bench["command"], args.workload,
                       FIRST_SEED + i * SEED_STEP, seconds, 1, extra)
        line["set"] = "trace"
        log.write(json.dumps(line) + "\n")
        print("# traced " + json.dumps(line)[:6000], flush=True)
    log.close()

    names = sorted({n for rows in sets for r in rows
                    for n in r.get("metrics", {})})
    summary = {}
    for name in names:
        per_set = []
        for rows in sets:
            # the first run of the first set may have compiled: its
            # set-up is recorded apart, as the driver does
            values = [r["metrics"][name]["value"] for r in rows
                      if name in r.get("metrics", {})]
            per_set.append({"median": statistics.median(values),
                            "spread": spread(values), "values": values})
        summary[name] = per_set
        print(f"{name}: " + "; ".join(
            f"set {k}: median {s['median']:.6g} spread "
            f"{100 * s['spread']:.3f} %" for k, s in enumerate(per_set)),
            flush=True)
    with open(os.path.join(args.out, args.workload + ".summary.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    ok = all(r["rc"] == 0 and r.get("correct") for rows in sets
             for r in rows)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
