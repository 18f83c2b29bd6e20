#!/usr/bin/env python3
"""The one sweep that finds a served cell's knee: the highest offered
rate at which no backlog grows and every request finishes.

    python3 benchmark/tools/sweep_rate.py --workload <served cell> \
        --rates 4,8,12,16,20,24 --seconds 20 --seed 1 --out chiprun_out/sweep

One ``ServeJob`` serves every rate in turn (the pool drains in between),
so the sweep pays set-up once.  Run it once, on the chip, when the cell
is defined; write the table into PERF.md and four fifths of the knee
into the cell's file as ``rate_per_s``.  The benchmark itself never
searches for a rate.

A rate counts as sustained when every request finished before the drain
deadline, the requests of the window's second half waited no longer for
their first token than twice the first half's median plus 100 ms, and at
the window's end no more requests were open than the pool has slots.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def summarize(rate: float, rows: list, seconds: float, slots: int) -> dict:
    from benchmark.harness.stats import median, percentile

    ttft = [(r["first_s"] - r["due_s"]) * 1e3 for r in rows
            if r["first_s"] is not None]
    tpot = [(r["last_s"] - r["first_s"]) / (r["tokens"] - 1) * 1e3
            for r in rows if r["done"] and r["tokens"] > 1]
    half = seconds / 2
    early = [(r["first_s"] - r["due_s"]) * 1e3 for r in rows
             if r["first_s"] is not None and r["due_s"] < half]
    late = [(r["first_s"] - r["due_s"]) * 1e3 for r in rows
            if r["first_s"] is not None and r["due_s"] >= half]
    open_at_end = sum(1 for r in rows if r["due_s"] <= seconds and (
        r["last_s"] is None or not r["done"] or r["last_s"] > seconds))
    finished = sum(1 for r in rows if r["done"])
    out = {
        "rate_per_s": rate, "requests": len(rows), "finished": finished,
        "tokens_per_s": sum(r["tokens"] for r in rows) / max(
            max((r["last_s"] or 0) for r in rows), 1e-9),
        "ttft_p50_ms": median(ttft) if ttft else None,
        "ttft_p95_ms": percentile(ttft, 95) if ttft else None,
        "tpot_p50_ms": median(tpot) if tpot else None,
        "tpot_p95_ms": percentile(tpot, 95) if tpot else None,
        "ttft_p50_first_half_ms": median(early) if early else None,
        "ttft_p50_second_half_ms": median(late) if late else None,
        "open_at_window_end": open_at_end,
        "gen_late_p95_ms": percentile(
            [(r["sent_s"] - r["due_s"]) * 1e3 for r in rows
             if r["sent_s"] is not None], 95),
    }
    out["sustained"] = bool(
        finished == len(rows) and early and late
        and out["ttft_p50_second_half_ms"]
        <= 2 * out["ttft_p50_first_half_ms"] + 100
        and open_at_end <= slots)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True,
                        help="comma-separated requests per second")
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--out", required=True, help="output directory")
    args = parser.parse_args(argv)

    from benchmark.harness import loadgen, registry
    from benchmark.runners import serve
    from horovod_tpu.serve import ServeJob

    cell = registry.load_cell(args.workload)
    params, config = cell["params"], cell["config_values"]
    spec = serve._spec(config, params, args.seed)
    vocab = spec["overrides"]["vocab_size"]
    os.makedirs(args.out, exist_ok=True)
    table = []
    t0 = time.perf_counter()
    job = ServeJob(spec, np=1, max_retries=0,
                   timeout=params["setup_timeout_s"] + 3600).start()
    try:
        serve._warm_up(job, params, vocab, params["setup_timeout_s"])
        print(f"# set-up {time.perf_counter() - t0:.1f} s", flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            requests = loadgen.schedule(
                {**params, "rate_per_s": rate}, args.seed, args.seconds,
                vocab)
            rows = serve.offer_window(job, requests, args.seconds, params,
                                      vocab)
            row = summarize(rate, rows, args.seconds, params["num_slots"])
            table.append(row)
            print(json.dumps(row), flush=True)
            if row["finished"] < row["requests"]:
                break  # the pool no longer drains: higher rates say nothing
        results, _ = job.stop(timeout=params["setup_timeout_s"])
    finally:
        job.shutdown()
    device = results[0]["device"]
    with open(os.path.join(args.out, "sweep.json"), "w") as f:
        json.dump({"workload": args.workload, "seconds": args.seconds,
                   "seed": args.seed, "device": device, "table": table},
                  f, indent=1)
    print(json.dumps({"device": device}))
    return 0 if device["platform"] == "tpu" else 3


if __name__ == "__main__":
    sys.exit(main())
