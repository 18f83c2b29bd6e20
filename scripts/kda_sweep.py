#!/usr/bin/env python
"""Stand-alone timings of Kimi Delta Attention's two halves on the
chip, forward alone and forward with backward, at one layer's shape of
the cell ``kimilin_train_s16384`` (1 x 16384 tokens, 32 heads of 128,
bfloat16, decays as the model draws them): what the modules' constants
were chosen from (PERF.md section 6, PRs 51 and 52).

The rule (``ops/kda.py``; the default): a variant is
``chunk,states_every,sub_block,precision`` for the XLA form (``highest``
or ``high`` for the Gram products and the triangular inverse) or
``kernel,chunk,states_every,head_block,sub_block`` for the kernel pair
(timed like the XLA form and, ``kernel_*_ms``, each call alone on the
``[batch, seq, heads x dim]`` arrays it reads and writes).  The float32
chain in
front of it (``--chain``; ``ops/kda_prep.py``): the variant ``xla`` is
the chain as XLA compiles it (``models/transformer.py:kda_prep_chain``),
any other is ``token_tile,head_block,rows`` for the kernel pair (timed
like the chain and, ``kernel_*_ms``, each call alone).  The
first variant is what the others' outputs and gradients are held
against.  Needs the chip; prints one JSON line a variant and appends it
to ``chiprun_out/kda_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import functools
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
    parser.add_argument("--chain", action="store_true")
    parser.add_argument("--variants", nargs="+")
    args = parser.parse_args()
    if args.variants is None:
        args.variants = [
            "xla", "256,4,64", "512,4,64", "512,8,64", "1024,8,64",
            "512,8,128", "512,8,32", "512,16,64"] if args.chain else [
            "64,4,16,highest", "kernel,64,4,4,16", "kernel,64,4,2,16",
            "kernel,64,4,4,8", "kernel,64,4,4,32", "kernel,64,2,8,16",
            "kernel,64,8,2,16", "64,8,16,highest", "64,4,8,highest",
            "64,4,16,high"]

    import jax
    import jax.numpy as jnp
    from jax import lax

    from horovod_tpu.ops import kda as kda_ops

    b, s, h, d = 1, args.seq, args.heads, args.dim
    if args.chain:
        return sweep_chain(args, b, s, h, d)
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

    timed = functools.partial(_timed, inputs=inputs, iters=args.iters)
    base = None
    plan = kda_ops.plan
    for variant in args.variants:
        kernel = variant.startswith("kernel,")
        if kernel:
            _, chunk, every, hb, sub = variant.split(",")
            kda_ops.HEAD_BLOCK, kda_ops.SUB_BLOCK = int(hb), int(sub)
            kda_ops.plan = plan
        else:
            chunk, every, sub, precision = variant.split(",")
            kda_ops.SUB_BLOCK = int(sub)
            kda_ops._FULL = {"highest": lax.Precision.HIGHEST,
                             "high": lax.Precision.HIGH}[precision]
            kda_ops.plan = lambda *shape: None
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
            line = {"variant": variant, "fwd_ms": fwd_ms,
                    "fwd_bwd_ms": both_ms,
                    "apart_o_dq_dk_dv_dg_dbeta": _apart(got, base),
                    "device": jax.devices()[0].device_kind}
            if kernel:
                line.update(_calls_alone(kda_ops, inputs, w, int(chunk),
                                         int(every), args.iters))
        _report(line)


def _calls_alone(kda_ops, inputs, w, chunk, every, iters):
    """The two calls by themselves, on arrays with the heads already
    folded into lanes (a ``[batch, seq, heads, dim]`` argument costs a
    copy that the step, whose neighbours are kernels too, does not
    pay), in ms and in us a head and chunk."""
    import jax

    b, s, h, d = inputs[0].shape
    tiles = kda_ops.plan(s, h, d, d, chunk, every, inputs[2].dtype.itemsize)
    if tiles is None:
        return {"kernel": None}
    n = kda_ops.group_chunks(s // chunk, every)
    folded = tuple(t.reshape(b, s, -1) for t in inputs[:4]) + (inputs[4],)
    unfold = lambda ts: tuple(t.reshape(b, s, h, d) for t in ts)
    fwd = jax.jit(lambda *t: kda_ops._kernel_forward(
        *unfold(t[:4]), t[4], chunk, n, *tiles))
    fwd_ms, (_, states) = _timed(fwd, folded, iters)
    bwd = jax.jit(lambda *t: tuple(
        x.reshape(b, s, -1) for x in kda_ops._kernel_backward(
            *unfold(t[:4]), t[4], t[5], t[6].reshape(b, s, h, d), chunk, n,
            *tiles)))
    bwd_ms, _ = _timed(bwd, folded + (states, w.reshape(b, s, -1)), iters)
    per = 1e3 / (b * h * (s // chunk))
    return {"kernel": list(tiles), "kernel_fwd_ms": fwd_ms,
            "kernel_bwd_ms": bwd_ms, "fwd_us_head_chunk": fwd_ms * per,
            "bwd_us_head_chunk": bwd_ms * per}


def _timed(fn, inputs, iters):
    import jax

    jax.block_until_ready(fn(*inputs))
    jax.block_until_ready(fn(*inputs))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*inputs)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def _apart(got, base):
    """Each array's distance from the first variant's, over its norm."""
    import jax.numpy as jnp

    flat = lambda t: t.astype(jnp.float32).ravel()
    return [float(jnp.linalg.norm(flat(x) - flat(y))
                  / jnp.linalg.norm(flat(y))) for x, y in zip(got, base)]


def _report(line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_sweep.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def sweep_chain(args, b, s, h, d):
    """The chain alone: ``fused`` and ``decay`` as projections of a
    normed stream would be (unit variance, bfloat16), the filter, bias
    and ``A`` as the model draws them; the cotangents random.  Both
    sides hand over ``[batch, seq, heads, head_dim]``, as the rule takes
    them."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import kda_prep_chain
    from horovod_tpu.ops import kda_prep

    inner, taps = h * d, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    inputs = (
        jax.random.normal(ks[0], (b, s, 3 * inner)).astype(jnp.bfloat16),
        jax.random.uniform(ks[1], (taps, 3 * inner), jnp.float32,
                           -taps ** -0.5, taps ** -0.5),
        jax.random.normal(ks[2], (b, s, inner)).astype(jnp.bfloat16),
        jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
            ks[3], (inner,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))))),
        jnp.log(jax.random.uniform(ks[4], (h,), jnp.float32, 1.0, 16.0)))
    weights = tuple(
        jax.random.normal(k, (b, s, h, d)).astype(dtype)
        for k, dtype in zip(ks[5:], [jnp.bfloat16] * 3 + [jnp.float32]))
    base = None
    for variant in args.variants:
        if variant == "xla":
            chain = kda_prep_chain
        else:
            (kda_prep.TOKEN_TILE, kda_prep.HEAD_BLOCK,
             kda_prep._ROWS) = map(int, variant.split(","))
            tiles = kda_prep.plan(s, h, d, taps)
            chain = functools.partial(kda_prep.kda_prep, tiles=tiles)
        jax.clear_caches()

        def both(*t, chain=chain):
            # forward, then backward from the cotangents as given: no
            # loss in between, whose fusion would be timed with them
            out, pull = jax.vjp(chain, *t[:5])
            return out, pull(tuple(t[5:]))

        try:
            fwd_ms, out = _timed(jax.jit(chain), inputs, args.iters)
            both_ms, (_, grads) = _timed(jax.jit(both), inputs + weights,
                                         args.iters)
        except Exception as e:  # a variant that does not fit or compile
            line = {"variant": variant, "error": str(e)[:300]}
        else:
            got = [*out, *grads]
            if base is None:
                base = got
            line = {"variant": variant, "fwd_ms": fwd_ms,
                    "fwd_bwd_ms": both_ms,
                    "apart_q_k_v_g_dfused_dconv_ddecay_dbias_dalog":
                        _apart(got, base),
                    "device": jax.devices()[0].device_kind}
            if variant != "xla":
                # the two calls alone, on [batch, seq, inner] as they
                # write and read it
                shape = (d, *tiles)
                line["kernel_fwd_ms"], _ = _timed(
                    lambda *t: kda_prep._forward(*t, shape, False), inputs,
                    args.iters)
                line["kernel_bwd_ms"], _ = _timed(
                    lambda *t: kda_prep._backward(*t, shape, False),
                    inputs + tuple(w.reshape(b, s, inner) for w in weights),
                    args.iters)
        _report(line)


if __name__ == "__main__":
    main()
