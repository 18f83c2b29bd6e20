#!/usr/bin/env python3
"""Read, in one process, the two numbers every reference tolerance is set
from (BENCHMARK contract, "How correct is decided"): what sound runs of
the program give over many seeds, and what the controls give, which must
come out as not correct.

    python3 benchmark/tools/probe_correct.py --workload <cell> \
        [--seeds 12] [--seconds 30] [--out <file.jsonl>]

For each seed: the cell's state from the seed, ``--seconds`` of the
cell's own training loop (the step is compiled once), then the reference
comparison on fresh seeded items for the untouched program, for its
weights through fp8 e4m3 (the control of a bfloat16 configuration) and
for each of the family's ``fault_probes``.  One JSON line per seed, and
at the end the largest sound reading and the smallest of each control.
Needs the chip, like a measured run; measures no time.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
FIRST_SEED, SEED_STEP = 2147484673, 999983  # not run_sets.py's seeds
NUMBERS = ("loss_abs", "logprob_abs", "logprob_abs_rms", "grad_rel",
           "grad_norm_rel")


def readings(numbers: dict) -> dict:
    got, want = numbers["loss"]
    return {"loss_abs": abs(got - want), "loss": want,
            "grad_norm": numbers["grad_norm"][1],
            **{k: numbers[k] for k in NUMBERS if k in numbers}}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--seconds", type=float, default=30.0)
    parser.add_argument("--out", default=None)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="rehearse the control flow; no reading counts")
    parser.add_argument("--root", default=ROOT,
                        help="where BENCHMARK.json and benchmark/ are")
    args = parser.parse_args(argv)

    import jax

    from benchmark.harness import correct, device as dev, registry
    from benchmark.runners import train
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cell = registry.load_cell(args.workload, args.root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], args.root)
    enable_compile_cache()
    dev.require(*dev.local(), cell["chips"], args.allow_cpu)
    reference = registry.load_reference(cell["config"], args.root)

    compiled = sides = probes = None
    rows = []
    for i in range(args.seeds):
        seed = FIRST_SEED + i * SEED_STEP
        built = builder.build(config, params, seed)
        if compiled is None:
            compiled = built.step.lower(*built.state).compile()
            merged = {**config, **built.ran}
            sides = correct.reference_sides(built.program_loss, reference,
                                            merged)
            probes = {"fp8_weights": correct.through_fp8}
            if hasattr(builder, "fault_probes"):
                probes.update(builder.fault_probes(config, built.ran))
        carry = list(built.state[:built.carry_len])
        const = built.state[built.carry_len:]
        carry, stamps, *_ = train._loop(compiled, carry, const,
                                        seconds=args.seconds)
        variables = correct.first_device(
            built.variables(tuple(carry) + tuple(const)))
        sample = built.sample(params["reference_items"])
        row = {"seed": seed, "steps": len(stamps) + 1, "sound": readings(
            correct.compare_sides(sides, variables, sample))}
        for name, damage in probes.items():
            row[name] = readings(correct.compare_sides(
                sides, variables, sample, program_variables=damage(
                    variables)))
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        del built, carry, const, variables
    summary = {"cell": args.workload, "seeds": len(rows),
               "device": jax.devices()[0].device_kind}
    for number in NUMBERS:
        sound = [r["sound"][number] for r in rows if number in r["sound"]]
        if not sound:
            continue
        summary[number] = {"sound_max": max(sound), "sound_min": min(sound)}
        for name in probes:
            summary[number][name + "_min"] = min(r[name][number]
                                                 for r in rows)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(summary) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
