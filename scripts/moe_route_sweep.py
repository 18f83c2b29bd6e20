#!/usr/bin/env python
"""Stand-alone timings of the three parts of the dropless router that
once followed the slots, on the chip, at the expert cells' shapes: what
``parallel/moe.py:route`` and ``routing_decision`` were rewritten from
(PERF.md section 6, PR 58).

``counts``: ``group_sizes`` and ``load`` together, by the two
scatter-adds (``scatter``, the form before PR 58), by ``moe._counts``
(``compare``: every slot against every bin, summed over the slots), by
the same with the slots along the lanes (``compare_t``) and by a 0/1
matrix summed on the MXU in float32 (``mxu``).  ``chosen``: the chosen
scores of the sigmoid rule by ``take_along_axis`` (``gather``, before)
and by ``moe._chosen`` (``select``), alone and with the gradient by the
scores; and the ``softmax_chosen`` rule's, ``top_k``'s own values
(``topk_values``, what ``route`` keeps: inside the step the compiler
fuses their derivative, which it does not stand-alone) against the
select after a ``top_k`` that is not differentiated (``topk_select``),
which include the ``top_k``.
``inverse``: the sort's inverse by a scatter (``scatter``, before) and
by ``argsort(order)`` (``argsort``; ``argsort_unstable`` where the sort
need not keep the order of equal keys, of which a permutation has
none).  Every array is an argument of the timed function; integers have
no gradient.  A call from the host costs some 190 us whatever it runs,
more than the new forms take, so a timing is of one program that runs
the form ``--reps`` times over (``lax.map`` over copies of its
operands), divided by that.  Needs the chip; prints one JSON line a
timing, in microseconds a call, and appends it to
``chiprun_out/moe_route_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from moe_combine_sweep import timed as timed_ms  # noqa: E402

# tokens, choices a token, experts, held experts: the seven expert cells,
# then further expert counts at Kimi-Linear's tokens and choices, for the
# crossover of the dense forms with the scatter
CELLS = {
    "sdar": (16384, 8, 128, 16),
    "lfm2": (32768, 4, 64, 8),
    "kimilin": (16384, 8, 256, 8),
    "smallthinker": (16384, 6, 64, 16),
    "trinity": (8192, 8, 128, 16),
    "glm": (8192, 4, 64, 8),
    "xing4": (8192, 4, 64, 8),
}
SHAPES = {**CELLS, "tiny": (256, 2, 8, 2),
          **{f"e{e}": (16384, 8, e, 8) for e in (512, 1024, 2048, 4096)}}


def timed(fn, args, iters, reps):
    """Microseconds a call of ``fn(*args)``, ``reps`` of them in one
    program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    stacked = tuple(jnp.stack([a] * reps) for a in args)
    many = jax.jit(lambda *stacked: lax.map(lambda a: fn(*a), stacked))
    return 1e3 * timed_ms(many, stacked, iters) / reps


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--shapes", default=",".join(CELLS))
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--reps", type=int, default=8)
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="the control flow on the CPU (--shapes tiny): "
                        "no timing means anything")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.parallel import moe

    if jax.default_backend() != "tpu" and not args.rehearse_cpu:
        raise SystemExit("moe_route_sweep times device code: it needs the "
                         "chip")
    os.makedirs("chiprun_out", exist_ok=True)

    def report(**line):
        print(json.dumps(line), flush=True)
        with open("chiprun_out/moe_route_sweep.jsonl", "a") as out:
            out.write(json.dumps(line) + "\n")

    for name in args.shapes.split(","):
        n, k, experts, held = SHAPES[name]
        slots = n * k
        keys = jax.random.split(jax.random.key(0), 3)
        scores = jax.nn.sigmoid(jax.random.normal(keys[0], (n, experts)))
        cotangent = jax.random.normal(keys[1], (n, k))
        _, chosen = lax.top_k(scores, k)
        flat = chosen.reshape(slots)
        key = jnp.where(flat < held, flat, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        line = dict(shape=name, slots=slots, k=k, experts=experts, held=held)

        def scatter_count(values, bins):
            return jnp.zeros((bins,), jnp.int32).at[values].add(1)

        def compare_t(values, bins):
            return (jnp.arange(bins, dtype=values.dtype)[:, None]
                    == values).sum(1, dtype=jnp.int32)

        def mxu(values, bins):
            hot = (values[:, None] == jnp.arange(bins, dtype=values.dtype))
            return jnp.dot(jnp.ones((slots,), jnp.bfloat16),
                           hot.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32
                           ).astype(jnp.int32)

        both = lambda count: lambda key, flat: (
            count(key, held + 1), count(flat, experts))
        time = lambda fn, operands: timed(fn, operands, args.iters,
                                          args.reps)
        reference = both(scatter_count)(key, flat)
        for form, count in (("scatter", scatter_count),
                            ("compare", moe._counts),
                            ("compare_t", compare_t), ("mxu", mxu)):
            got = both(count)(key, flat)
            equal = all(bool((a == b).all()) for a, b in zip(got, reference))
            report(part="counts", form=form, equal=equal,
                   us=time(both(count), (key, flat)),
                   group_sizes_us=time(lambda key: count(key, held + 1),
                                       (key,)),
                   load_us=time(lambda flat: count(flat, experts), (flat,)),
                   **line)

        gather = lambda scores, chosen: jnp.take_along_axis(
            scores, chosen, axis=-1)
        topk_values = lambda scores, chosen: lax.top_k(scores, k)[0]
        topk_select = lambda scores, chosen: moe._chosen(
            scores, lax.top_k(lax.stop_gradient(scores), k)[1])
        grad_of = lambda fn: jax.grad(
            lambda scores, chosen, cotangent: (
                fn(scores, chosen) * cotangent).sum())
        operands = (scores, chosen, cotangent)
        reference = (jax.jit(gather)(scores, chosen),
                     jax.jit(grad_of(gather))(*operands))
        for form, fn in (("gather", gather), ("select", moe._chosen),
                         ("topk_values", topk_values),
                         ("topk_select", topk_select)):
            got = (jax.jit(fn)(scores, chosen),
                   jax.jit(grad_of(fn))(*operands))
            equal = all(bool((a == b).all()) for a, b in zip(got, reference))
            report(part="chosen", form=form, equal=equal,
                   us=time(fn, (scores, chosen)),
                   grad_us=time(grad_of(fn), operands), **line)

        forms = {
            "scatter": lambda order: jnp.zeros_like(order).at[order].set(
                jnp.arange(slots, dtype=jnp.int32)),
            "argsort": lambda order: jnp.argsort(order).astype(jnp.int32),
            "argsort_unstable": lambda order: jnp.argsort(
                order, stable=False).astype(jnp.int32),
        }
        reference = jax.jit(forms["scatter"])(order)
        for form, fn in forms.items():
            report(part="inverse", form=form,
                   equal=bool((jax.jit(fn)(order) == reference).all()),
                   us=time(fn, (order,)), **line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
