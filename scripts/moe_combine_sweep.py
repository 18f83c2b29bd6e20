#!/usr/bin/env python
"""Stand-alone timings of the expert layer's way back to the tokens on
the chip, at the expert cells' shapes: what ``ops/moe_combine.py``'s plan
was chosen from (PERF.md section 6, PR 56).

Three candidates for ``y = sum over a token's held choices of w * row``:
``slots``, the form XLA ran before (one gathered row a slot, ``[k, n,
d]`` in float32, summed: ``moe_combine.combine_slots``); ``kernel``, the
Pallas kernel under every ``--tiles`` x ``--chunks`` (and under the
plan's own pair, tagged); ``scatter``, XLA's scatter-add of the ``R``
weighted rows into a float32 ``[n, d]``.  Each is timed weighted into
float32 (``fwd``: the forward pass's) and unweighted into the rows' dtype
(``bwd_x``: the tokens' gradient); ``bwd_w`` times the weights' gradient
by a gather over the slots and by a scatter of the ``R`` rows.  ``layer``
is what a rematerialised layer runs a step: twice ``fwd``, ``bwd_x`` and
``bwd_w``.  The routing is a real one (``moe.routing_decision`` on random
scores), every array an argument of the timed function.  Needs the chip;
prints one JSON line a timing and appends it to
``chiprun_out/moe_combine_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tokens, choices a token, hidden, held experts, experts, rows computed
# on, and what the held experts' scores are raised by (0.26 draws four
# fifths of the slots to them: a step over the row bound, as three of
# SmallThinker's layers run)
SHAPES = {
    "sdar": (16384, 8, 2048, 16, 128, 32768, 0.0),
    "lfm2": (32768, 4, 2048, 8, 64, 32768, 0.0),
    "smallthinker": (16384, 6, 2560, 16, 64, 49152, 0.0),
    "smallthinker_whole": (16384, 6, 2560, 16, 64, 98304, 0.26),
    "kimilin": (16384, 8, 2304, 8, 256, 8192, 0.0),
    "trinity": (8192, 8, 2048, 16, 128, 16384, 0.0),
    "glm": (8192, 4, 2048, 8, 64, 8192, 0.0),
    "tiny": (256, 2, 128, 2, 8, 512, 0.0),
}


def timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - start) / iters)
    return best * 1e3


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes",
                        default=",".join(s for s in SHAPES if s != "tiny"))
    parser.add_argument("--tiles", default="256,512,1024")
    parser.add_argument("--chunks", default="64,128")
    parser.add_argument("--lanes", default="",
                        help="further widths of the kernel's inner step, "
                        "tried under the plan's pair, e.g. 256,1024")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="the control flow on the CPU (--shapes tiny, "
                        "the kernel interpreted): no timing means anything")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import moe_combine
    from horovod_tpu.parallel import moe

    if jax.default_backend() != "tpu" and not args.rehearse_cpu:
        raise SystemExit("moe_combine_sweep times kernels: it needs the chip")
    os.makedirs("chiprun_out", exist_ok=True)

    def report(**line):
        print(json.dumps(line), flush=True)
        with open("chiprun_out/moe_combine_sweep.jsonl", "a") as out:
            out.write(json.dumps(line) + "\n")

    pairs = [(int(t), int(c)) for t in args.tiles.split(",") if t
             for c in args.chunks.split(",") if c]
    for name in args.shapes.split(","):
        n, k, d, held, experts, rows, raised = SHAPES[name]
        keys = jax.random.split(jax.random.key(0), 4)
        routing = moe.routing_decision(
            jax.random.normal(keys[0], (n, 64)),
            jax.random.normal(keys[1], (64, experts)) / 8,
            jnp.where(jnp.arange(experts) < held, raised, 0.0),
            top_k=k, scaling=1.0, first_held=0, held=held)
        held_sizes = routing.group_sizes[:held]
        live = int(held_sizes.sum())
        assert live <= rows, (name, live, rows)
        # what the grouped matmul leaves: nothing past the held groups
        ys, d_xs = (jnp.where(jnp.arange(rows)[:, None] < live,
                              jax.random.normal(key, (rows, d), jnp.bfloat16),
                              0) for key in keys[2:])
        head = routing.order[:rows]
        products = jax.random.normal(keys[2], (rows,))
        plan = moe_combine.plan(n, d, held, rows, 2)
        line = dict(shape=name, n=n, k=k, d=d, held=held, rows=rows, live=live)

        operands = (routing.inverse, held_sizes, head)

        def scatter(ys, weights, inverse, held_sizes, head, dtype):
            by_row = ys.astype(jnp.float32)
            if weights is not None:
                by_row = by_row * weights.reshape(-1)[head][:, None]
            return jnp.zeros((n, d), jnp.float32).at[head // k].add(
                by_row).astype(dtype)

        def kernel(ys, weights, inverse, held_sizes, head, dtype, tiles):
            return moe_combine.combine_rows(
                ys, weights, inverse, held_sizes, k=k, tiles=tiles,
                dtype=dtype, interpret=args.rehearse_cpu)

        forms = {
            "slots": lambda ys, weights, inverse, held_sizes, head, dtype:
                moe_combine.combine_slots(ys, weights, inverse, k).astype(
                    dtype),
            "scatter": scatter,
            **{f"kernel_{t}x{c}": functools.partial(kernel, tiles=(t, c))
               for t, c in dict.fromkeys([plan or (None, None), *pairs])
               if t is not None and n % t == 0 and rows >= c},
        }
        # the weights' gradient: a gather over the slots, or the rows'
        # products put at their slots
        to_slots = {
            "slots": lambda products, inverse, head: moe_combine.slots(
                products, inverse, k).T,
            "rows": lambda products, inverse, head: jnp.zeros(
                (n * k,), jnp.float32).at[head].set(
                    products, unique_indices=True).reshape(n, k),
        }
        bwd_w = {}
        for form, fn in to_slots.items():
            bwd_w[form] = timed(jax.jit(fn), (products, routing.inverse, head),
                                args.iters)
            report(part="bwd_w", form=form, ms=bwd_w[form], **line)
        reference = jax.jit(functools.partial(
            forms["slots"], dtype=jnp.float32))(
                ys, routing.weights, *operands)
        inner = moe_combine._LANES
        for lanes in [int(w) for w in args.lanes.split(",") if w and plan]:
            forms[f"kernel_%dx%d_lanes{lanes}" % plan] = functools.partial(
                kernel, tiles=plan)
        for form, fn in forms.items():
            tags = ["plan"] if plan and form == "kernel_%dx%d" % plan else []
            # a module constant: read when the call is traced
            moe_combine._LANES = int(form.partition("_lanes")[2] or inner)
            jax.clear_caches()
            try:
                forward = jax.jit(functools.partial(fn, dtype=jnp.float32))
                # float32 sums in another order: rounding alone
                err = float(jnp.abs(forward(ys, routing.weights, *operands)
                                    - reference).max())
                fwd = timed(forward, (ys, routing.weights, *operands),
                            args.iters)
                bwd_x = timed(
                    jax.jit(lambda d_xs, *rest: fn(
                        d_xs, None, *rest, dtype=jnp.bfloat16)),
                    (d_xs, *operands), args.iters)
                by_rows = bwd_w["slots" if form == "slots" else "rows"]
                report(part="rows", form=form, tags=tags, max_err=err,
                       fwd_ms=fwd, bwd_x_ms=bwd_x,
                       layer_ms=2 * fwd + bwd_x + by_rows, **line)
            except Exception as e:  # what the chip's compiler refuses
                report(part="rows", form=form, tags=tags,
                       error=str(e).splitlines()[0][:200], **line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
