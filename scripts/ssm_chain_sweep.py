#!/usr/bin/env python
"""End-to-end timings of a Mamba-2 cell's training step on the chip
under variants of the two float32 chains' kernels
(``horovod_tpu/ops/ssm_chain.py``): what the module's constants were
chosen from (PERF.md section 6, PR 62).  The whole step and not the
calls alone: what XLA schedules around a custom call (its prefetches,
the VMEM it leaves the call) is part of a variant's price (PR 31).

A variant is ``xla`` (both chains as XLA compiles them:
``models/transformer.py:ssm_prep_chain`` and ``ssm_norm_chain``),
``prep=xla`` or ``norm=xla`` (that side XLA's, the other the kernels at
the module's constants), or ``token_tile,lane_block,norm_block,vmem_mib``
for both kernel pairs (``norm_block`` in Ki elements of a program's
block).  The step is the benchmark's own (``benchmark/models/*.py``
builds it from the cell's files), compiled anew a variant on the one
state; a variant's reading is the median and the 90th percentile of the
gaps between ``--steps`` steps' ready stamps, as the runner takes them.
``--apart`` first holds the kernel pairs against the chains at one
layer's shape of the cell (random inputs and cotangents, bfloat16):
each output's and each gradient's distance over the chain's norm, and
how many elements differ at all.  Needs the chip; prints one JSON line a
variant and appends it to ``chiprun_out/ssm_chain_sweep.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cell", default="granite4hm_train_s8192")
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--apart", action="store_true")
    parser.add_argument("--variants", nargs="+", default=[
        "xla", "1024,512,512,16", "prep=xla", "norm=xla",
        "512,512,512,16", "2048,512,512,16", "1024,256,512,16",
        "1024,512,256,16", "1024,512,1024,16", "1024,512,512,32"])
    args = parser.parse_args()

    import jax

    from benchmark.harness import registry
    from benchmark.runners.train import _loop
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import ssm_chain

    cell = registry.load_cell(args.cell, ROOT)
    config = cell["config_values"]
    if args.apart:
        _report({"cell": args.cell, **apart(cell)})
    built = registry.load_model_builder(config["family"], ROOT).build(
        config, cell["params"], args.seed)
    carry = list(built.state[:built.carry_len])
    const = built.state[built.carry_len:]
    plan, prep, norm = ssm_chain.plan, ssm_chain.ssm_prep, ssm_chain.ssm_norm
    constants = (ssm_chain.TOKEN_TILE, ssm_chain.LANE_BLOCK,
                 ssm_chain.NORM_BLOCK // 1024, ssm_chain._VMEM_LIMIT // 2 ** 20)

    def chain(fn):
        # a kernel's signature in front of the chain's
        return lambda *a, tiles, **kw: fn(*a, **kw)

    for variant in args.variants:
        ssm_chain.plan, ssm_chain.ssm_prep, ssm_chain.ssm_norm = (
            plan, prep, norm)
        tq, lb, block, vmem = constants
        if variant == "xla":
            ssm_chain.plan = lambda *shape: None
        elif variant == "prep=xla":
            ssm_chain.ssm_prep = chain(transformer.ssm_prep_chain)
        elif variant == "norm=xla":
            ssm_chain.ssm_norm = chain(transformer.ssm_norm_chain)
        else:
            tq, lb, block, vmem = map(int, variant.split(","))
        ssm_chain.TOKEN_TILE, ssm_chain.LANE_BLOCK = tq, lb
        ssm_chain.NORM_BLOCK = block * 1024
        for call in (ssm_chain._PREP_PARAMS, ssm_chain._NORM_PARAMS):
            call["vmem_limit_bytes"] = vmem * 2 ** 20
        jax.clear_caches()
        line = {"cell": args.cell, "variant": variant}
        try:
            compiled = built.step.lower(*carry, *const).compile()
            for _ in range(2):
                *carry, loss = compiled(*carry, *const)
            loss.block_until_ready()
            carry, stamps, losses, _, _ = _loop(compiled, carry, const,
                                                steps=args.steps)
        except Exception as e:  # a variant that does not fit or compile
            line["error"] = str(e)[:300]
        else:
            gaps = sorted((b - a) * 1e3 for a, b in zip(stamps, stamps[1:]))
            line.update(
                step_ms_median=statistics.median(gaps),
                step_ms_p90=gaps[min(len(gaps) - 1, int(0.9 * len(gaps)))],
                loss=float(losses[-1]),
                device=jax.devices()[0].device_kind)
            del compiled
        _report(line)


def _report(line):
    print(json.dumps(line), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssm_chain_sweep.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def apart(cell):
    """The kernel pairs against the chains at one layer's shape of the
    cell: ``fused`` as a projection of a normed stream would be (unit
    variance, bfloat16), the filter, bias and scale as a trained model
    might hold them, ``y`` and the cotangents random."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import ssm_chain

    cfg = transformer.GPT_CONFIGS[cell["config_values"]["program"]["size"]]
    b, s = cell["params"]["per_chip_batch"], cell["params"]["seq_len"]
    inner, heads, groups = cfg.ssm_inner, cfg.ssm_heads, cfg.ssm_groups
    bc = groups * cfg.ssm_state
    width = inner + 2 * bc
    ks = jax.random.split(jax.random.PRNGKey(0), 9)
    bf16 = lambda k, *shape: jax.random.normal(k, shape).astype(jnp.bfloat16)
    args = (bf16(ks[0], b, s, inner + width + heads),
            jax.random.uniform(ks[1], (cfg.ssm_conv, width), jnp.float32,
                               -0.5, 0.5),
            0.1 * jax.random.normal(ks[2], (width,)),
            bf16(ks[3], b, s, heads, inner // heads),
            1.0 + 0.1 * jax.random.normal(ks[4], (inner,)))
    weights = (bf16(ks[5], b, s, heads, inner // heads),
               bf16(ks[6], b, s, groups, bc // groups),
               bf16(ks[7], b, s, groups, bc // groups),
               bf16(ks[8], b, s, inner))
    tiles = ssm_chain.plan(s, inner, groups, cfg.ssm_state, cfg.ssm_conv)

    def both(kernels):
        def fn(fused, conv_kernel, conv_bias, y, norm_scale):
            kw = dict(tiles=tiles) if kernels else {}
            prep = ssm_chain.ssm_prep if kernels else (
                transformer.ssm_prep_chain)
            norm = ssm_chain.ssm_norm if kernels else (
                transformer.ssm_norm_chain)
            return (*prep(fused, conv_kernel, conv_bias, inner=inner,
                          heads=heads, groups=groups, **kw),
                    norm(y, fused, norm_scale, groups=groups,
                         eps=cfg.norm_eps, **kw))

        out, pull = jax.vjp(fn, *args)
        return (*out, *pull(weights))

    got, want = jax.jit(both, static_argnums=0)(True), jax.jit(
        both, static_argnums=0)(False)
    flat = lambda t: t.astype(jnp.float32).ravel()
    names = ("x", "B", "C", "normed", "dfused", "dconv", "dbias", "dy",
             "dscale")
    return {"tiles": list(tiles), "apart": {
        name: [float(jnp.linalg.norm(flat(g) - flat(w))
                     / jnp.linalg.norm(flat(w))),
               int(jnp.sum(flat(g) != flat(w)))]
        for name, g, w in zip(names, got, want)}}


if __name__ == "__main__":
    main()
