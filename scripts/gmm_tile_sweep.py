#!/usr/bin/env python
"""Stand-alone timings of the expert layer's grouped matmuls on the chip,
by tile: what ``parallel/moe.py:gmm_tiles`` was chosen from (PERF.md
section 6, PR 50).

``--mode calls`` times each of the six calls of one gated expert
feed-forward and its gradients (``moe.ffn_calls``) alone, jax's
``megablox`` kernel under every admissible ``(tk, tn)`` (multiples of 128
that divide the call's ``k`` and ``n``, blocks within ``--vmem-mib``) and
under the pair the parent handed it (``min(1024, .)`` of the forward
call's dimensions).  ``--mode ffn`` times ``grouped_ffn`` forward and
backward whole, under the parent's pairs, the rule's, and the largest
divisor up to 1024 a dimension; ``--row-tiles`` repeats the rule's with
other row tiles.  Rows are in expert order, ``--held-rows`` of them
spread unevenly over the held experts, the rest in the ownerless tail.
Needs the chip; prints one JSON line a timing and appends it to
``chiprun_out/gmm_tile_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# rows, hidden, held experts, expert width, rows routed to held experts
SHAPES = {
    "smallthinker": (98304, 2560, 16, 768, 84000),
    "smallthinker_bound": (49152, 2560, 16, 768, 31000),
    "glm": (8192, 2048, 8, 1536, 4096),
    "trinity": (16384, 2048, 16, 1024, 8192),
    "lfm2": (32768, 2048, 8, 1536, 16384),
}


def parent_tiles(calls):
    """The pairs before PR 50: 1024 cut to the forward call's ``k`` and
    ``n``, handed to that matmul's two gradients as well."""
    gate_up, down = ((512, min(1024, k), min(1024, n))
                     for k, n, _ in calls[:2])
    return [gate_up, down, down, gate_up, down, gate_up]


def capped_tiles(calls):
    """A dimension's largest divisor (a multiple of 128) up to 1024."""
    from horovod_tpu.parallel import moe

    best = lambda size: max(t for t in moe._divisors(size) if t <= 1024)
    return [(512, best(k), best(n)) for k, n, _ in calls]


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
    parser.add_argument("--shape", default="smallthinker", choices=SHAPES)
    parser.add_argument("--mode", default="calls", choices=["calls", "ffn"])
    parser.add_argument("--vmem-mib", type=float, default=16.0)
    parser.add_argument("--min-tile", type=int, default=384)
    parser.add_argument("--row-tiles", default="",
                        help="ffn mode: further row tiles, e.g. 256,128")
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    from horovod_tpu.parallel import moe

    if jax.default_backend() != "tpu":
        raise SystemExit("gmm_tile_sweep times kernels: it needs the chip")
    rows, d, held, ff, held_rows = SHAPES[args.shape]
    rng = np.random.default_rng(0)
    sizes = np.floor(rng.dirichlet(np.full(held, 8.0)) * held_rows)
    sizes = jnp.asarray([*sizes, rows - sizes.sum()], jnp.int32)
    normal = lambda i, shape: jax.random.normal(
        jax.random.key(i), shape, jnp.bfloat16)
    calls = moe.ffn_calls(d, ff)
    os.makedirs("chiprun_out", exist_ok=True)

    def report(**line):
        line = dict(shape=args.shape, mode=args.mode, **line)
        print(json.dumps(line), flush=True)
        with open("chiprun_out/gmm_tile_sweep.jsonl", "a") as out:
            out.write(json.dumps(line) + "\n")

    if args.mode == "ffn":
        operands = (normal(0, (rows, d)), normal(1, (held, d, 2 * ff)) * 0.02,
                    normal(2, (held, ff, d)) * 0.02)
        rule = moe.gmm_tiles
        row_tiles = [int(t) for t in args.row_tiles.split(",") if t]
        variants = [("parent", parent_tiles(calls), 512),
                    ("capped_1024", capped_tiles(calls), 512),
                    ("rule", None, 512)]
        variants += [(f"rule_tm{tm}", None, tm) for tm in row_tiles]
        for name, tiles, tm in variants:
            moe.GMM_ROW_TILE = tm
            if tiles is None:
                moe.gmm_tiles = rule
            else:
                table = {call: tile for call, tile in zip(calls, tiles)}
                moe.gmm_tiles = lambda rows, k, n, size, weights_out=False, \
                    t=table: t[(k, n, weights_out)]

            def both(xs, gate_up, down):
                loss = lambda *a: moe.grouped_ffn(*a, sizes).astype(
                    jnp.float32).sum()
                return jax.grad(loss, argnums=(0, 1, 2))(xs, gate_up, down)

            forward = lambda *a: moe.grouped_ffn(*a, sizes)
            try:
                report(variant=name,
                       fwd_ms=timed(jax.jit(forward), operands, args.iters),
                       fwd_bwd_ms=timed(jax.jit(both), operands, args.iters))
            except Exception as e:  # what the chip's compiler refuses
                report(variant=name, error=str(e).splitlines()[0][:200])
        return 0

    vmem = lambda tk, tn, out: moe.gmm_vmem_bytes(512, tk, tn, 2, out) / 2 ** 20

    for index, ((k, n, out), parent) in enumerate(
            zip(calls, parent_tiles(calls)), 1):
        transposed = index in (3, 4)
        if out:
            operands = (normal(0, (rows, k)), normal(1, (rows, n)))
            call = lambda lhs, rhs, tiles: tgmm(
                lhs.swapaxes(0, 1), rhs, sizes, jnp.bfloat16, tiles,
                num_actual_groups=held)
        else:
            operands = (normal(0, (rows, k)), normal(
                1, (held, n, k) if transposed else (held, k, n)))
            call = lambda lhs, rhs, tiles, t=transposed: gmm(
                lhs, rhs, sizes, jnp.bfloat16, tiles, transpose_rhs=t)
        pairs = [(tk, tn) for tk in moe._divisors(k) for tn in moe._divisors(n)
                 if min(tk, tn) >= args.min_tile
                 and vmem(tk, tn, out) <= args.vmem_mib]
        rule = moe.gmm_tiles(rows, k, n, 2, out)[1:]
        for tk, tn in dict.fromkeys([parent[1:], *pairs]):
            tags = [tag for tag, pair in (("parent", parent[1:]),
                                          ("rule", rule)) if pair == (tk, tn)]
            line = dict(call=index, k=k, n=n, tk=tk, tn=tn, tags=tags,
                        blocks_mib=round(vmem(tk, tn, out), 2))
            try:
                fn = jax.jit(lambda lhs, rhs, t=(512, tk, tn): call(lhs, rhs, t))
                report(ms=timed(fn, operands, args.iters), **line)
            except Exception as e:  # what the chip's compiler refuses
                report(error=str(e).splitlines()[0][:200], **line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
