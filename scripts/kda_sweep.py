#!/usr/bin/env python
"""Stand-alone timings of the chunked gated delta rule (``ops/kda.py``)
on the chip, forward alone and forward with backward, at one layer's
shape of the cell ``kimilin_train_s16384`` (1 x 16384 tokens, 32 heads of
128, bfloat16, decays as the model draws them): what the module's
constants were chosen from (PERF.md section 6, PR 51).

Each variant is ``chunk,states_every,sub_block,precision`` (``highest``
or ``high`` for the Gram products and the triangular inverse); the first
is what the others' ``o`` and gradients are held against.  Needs the
chip; prints one JSON line a variant and appends it to
``chiprun_out/kda_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--seq", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=32)
    parser.add_argument("--dim", type=int, default=128)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--variants", nargs="+", default=[
        "64,4,16,highest", "64,8,16,highest", "64,16,16,highest",
        "64,4,8,highest", "64,4,32,highest", "64,4,16,high",
        "32,8,16,highest", "128,2,16,highest"])
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.ops import kda as kda_ops

    b, s, h, d = 1, args.seq, args.heads, args.dim
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    unit = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    shape = (b, s, h, d)
    q = (unit(jax.random.normal(ks[0], shape)) * d ** -0.5).astype(
        jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], shape)).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(ks[2], shape)).astype(jnp.bfloat16)
    # A = exp(A_log) in U(1, 16) a head, dt as Mamba's bias draws it
    a = jax.random.uniform(ks[3], (h, 1), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    g = -a * dt
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, h)))
    w = jax.random.normal(ks[6], shape).astype(jnp.bfloat16)
    inputs = (q, k, v, g, beta)

    def timed(fn):
        jax.block_until_ready(fn(*inputs))
        jax.block_until_ready(fn(*inputs))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*inputs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, out

    base = None
    os.makedirs("chiprun_out", exist_ok=True)
    for variant in args.variants:
        chunk, every, sub, precision = variant.split(",")
        kda_ops.SUB_BLOCK = int(sub)
        kda_ops._FULL = {"highest": lax.Precision.HIGHEST,
                         "high": lax.Precision.HIGH}[precision]
        jax.clear_caches()
        rule = lambda *t: kda_ops.kda(*t, chunk=int(chunk),
                                      states_every=int(every))
        fwd = jax.jit(rule)
        both = jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(rule(*t).astype(jnp.float32)
                               * w.astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4)))
        try:
            fwd_ms, o = timed(fwd)
            both_ms, (_, grads) = timed(both)
        except Exception as e:  # a variant that does not fit or compile
            line = {"variant": variant, "error": str(e)[:300]}
        else:
            got = [o, *grads]
            if base is None:
                base = got
            apart = [float(jnp.linalg.norm((x.astype(jnp.float32)
                                            - y.astype(jnp.float32)).ravel())
                           / jnp.linalg.norm(y.astype(jnp.float32).ravel()))
                     for x, y in zip(got, base)]
            line = {"variant": variant, "fwd_ms": fwd_ms,
                    "fwd_bwd_ms": both_ms,
                    "apart_o_dq_dk_dv_dg_dbeta": apart,
                    "device": jax.devices()[0].device_kind}
        print(json.dumps(line), flush=True)
        with open("chiprun_out/kda_sweep.jsonl", "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
