#!/usr/bin/env python3
"""Read every control of a cell whose reference takes ``depart``, in one
process: the sound program; its weights through fp8 e4m3 and the
family's ``fault_probes`` (damaged copies of the variables, as
``probe_correct.py`` reads them); and the controls that no damage of the
variables can make, the sound program against the plain reference with
one fault seeded into the REFERENCE's mathematics (its ``depart``
argument; the names are the reference file's ``DEPARTURES``).  Each
control must come out as not correct by the configuration's
``reference_tolerance``: a limit that passes a reference which takes the
memory after the gate, say, does not hold the program to taking it
before.

    python3 benchmark/tools/probe_departures.py --workload <cell> \
        [--seeds 3] [--seconds 30] [--out <file.jsonl>]

For each seed: the cell's state from the seed, ``--seconds`` of its own
training loop (the step is compiled once), then the comparisons on fresh
seeded items; whatever of the state the comparisons do not read (the
optimizer's, the batch) gives its memory back first, as the train runner
does before its checks.  One JSON line per seed, and at the end the
largest sound reading and the smallest of each control.  Needs the chip,
like a measured run; measures no time.
"""

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
FIRST_SEED, SEED_STEP = 2147485711, 999979  # no other tool's seeds


def departed(reference, depart):
    """The reference with ``depart`` seeded into both of its functions."""
    return types.SimpleNamespace(
        loss=lambda c, v, b: reference.loss(c, v, b, depart=depart),
        logprob=lambda c, v, b: reference.logprob(c, v, b, depart=depart))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=3)
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
    from benchmark.tools.probe_correct import NUMBERS, readings
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    cell = registry.load_cell(args.workload, args.root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], args.root)
    enable_compile_cache()
    dev.require(*dev.local(), cell["chips"], args.allow_cpu)
    reference = registry.load_reference(cell["config"], args.root)
    tolerance = config["reference_tolerance"]

    compiled = controls = None
    rows = []
    for i in range(args.seeds):
        seed = FIRST_SEED + i * SEED_STEP
        built = builder.build(config, params, seed)
        if compiled is None:
            compiled = built.step.lower(*built.state).compile()
            merged = {**config, **built.ran}
            sound = correct.reference_sides(built.program_loss, reference,
                                            merged)
            damages = {"fp8_weights": correct.through_fp8}
            if hasattr(builder, "fault_probes"):
                damages.update(builder.fault_probes(config, built.ran))
            # name -> (the two sides, what the program's variables go
            # through first)
            controls = {"sound": (sound, None)}
            controls.update({name: (sound, damage)
                             for name, damage in damages.items()})
            controls.update({
                name: (correct.reference_sides(
                    built.program_loss, departed(reference, name), merged),
                    None) for name in reference.DEPARTURES})
        carry = list(built.state[:built.carry_len])
        const = built.state[built.carry_len:]
        carry, stamps, *_ = train._loop(compiled, carry, const,
                                        seconds=args.seconds)
        state = tuple(carry) + tuple(const)
        variables = built.variables(state)
        read = {id(leaf) for leaf in jax.tree.leaves(variables)}
        for leaf in jax.tree.leaves(state):
            if id(leaf) not in read:
                leaf.delete()
        variables = correct.first_device(variables)
        sample = built.sample(params["reference_items"])
        row = {"seed": seed, "steps": len(stamps) + 1}
        for name, (pair, damage) in controls.items():
            numbers = correct.compare_sides(
                pair, variables, sample,
                program_variables=damage and damage(variables))
            row[name] = readings(numbers)
            row[name]["correct"] = all(
                c["ok"] for c in correct.reference_checks(
                    numbers, tolerance).values())
        rows.append(row)
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
        del built, carry, const, state, variables
    summary = {"cell": args.workload, "seeds": len(rows),
               "device": jax.devices()[0].device_kind,
               "correct": {name: [r[name]["correct"] for r in rows]
                           for name in controls}}
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        # the process's high-water mark: the window's or the checks'
        summary["peak_gib"] = stats["peak_bytes_in_use"] / 2 ** 30
    for number in NUMBERS:
        sound = [r["sound"][number] for r in rows if number in r["sound"]]
        if not sound:
            continue
        summary[number] = {"sound_max": max(sound), "sound_min": min(sound)}
        for name in controls:
            if name != "sound":
                summary[number][name + "_min"] = min(r[name][number]
                                                     for r in rows)
    print(json.dumps(summary), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(json.dumps(summary) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
