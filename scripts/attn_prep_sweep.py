#!/usr/bin/env python
"""End-to-end timings of an attention cell's training step on the chip
under variants of the kernel pair between the fused q/k/v matmul and the
flash kernels (``horovod_tpu/ops/attn_prep.py``): what the module's
constants and ``plan``'s open case (a rotation without norms) were
decided from (PERF.md section 6, PR 63).  The whole step and not the
calls alone: what XLA schedules around a custom call (its prefetches,
the VMEM it leaves the call) is part of a variant's price (PR 31).

A variant is ``xla`` (the chain as XLA compiles it,
``models/transformer.py:attn_prep_chain``: ``plan`` says ``None``) or
``token_tile,heads,vmem_mib`` for the pair.  The step is the benchmark's
own (``benchmark/models/*.py`` builds it from the cell's files),
compiled anew a variant on the one state; a variant's reading is the
median and the 90th percentile of the gaps between ``--steps`` steps'
ready stamps, as the runner takes them, and with ``--trace`` the device
time a step under the scopes ``attn_prep`` and ``attn`` and outside
every scope, from four more steps under the profiler (the benchmark's
own reduction).  ``--apart`` first holds the pair against the chain at
one layer's shape of the cell (random inputs and cotangents, bfloat16):
each output's and each gradient's distance over the chain's norm, and
how many elements differ at all.  Needs the chip; prints one JSON line a
variant and appends it to ``chiprun_out/attn_prep_sweep.jsonl``.
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
    parser.add_argument("--cell", default="sdar_train_s8192_bd4")
    parser.add_argument("--steps", type=int, default=12)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--apart", action="store_true")
    parser.add_argument("--trace", action="store_true")
    parser.add_argument("--variants", nargs="+", default=[
        "xla", "512,4,16", "256,4,16", "1024,4,16", "512,1,16", "512,2,16",
        "512,4,12", "512,4,32"])
    args = parser.parse_args()

    import jax

    from benchmark.harness import registry
    from benchmark.harness import trace as tr
    from benchmark.runners.train import _loop, _traced
    from horovod_tpu.ops import attn_prep

    cell = registry.load_cell(args.cell, ROOT)
    config = cell["config_values"]
    if args.apart:
        _report({"cell": args.cell, **apart(cell)})
    built = registry.load_model_builder(config["family"], ROOT).build(
        config, cell["params"], args.seed)
    carry = list(built.state[:built.carry_len])
    const = built.state[built.carry_len:]
    plan = attn_prep.plan
    constants = (attn_prep.TOKEN_TILE, attn_prep.HEAD_BLOCK,
                 attn_prep._VMEM_LIMIT // 2 ** 20)

    for variant in args.variants:
        attn_prep.plan = plan
        tq, hb, vmem = constants
        if variant == "xla":
            attn_prep.plan = lambda *shape, **call: None
        else:
            tq, hb, vmem = map(int, variant.split(","))
        attn_prep.TOKEN_TILE, attn_prep.HEAD_BLOCK = tq, hb
        attn_prep._PARAMS["vmem_limit_bytes"] = vmem * 2 ** 20
        jax.clear_caches()
        line = {"cell": args.cell, "variant": variant}
        try:
            compiled = built.step.lower(*carry, *const).compile()
            for _ in range(2):
                *carry, loss = compiled(*carry, *const)
            loss.block_until_ready()
            carry, stamps, losses, _, _ = _loop(compiled, carry, const,
                                                steps=args.steps)
            if args.trace:
                carry, data = _traced(compiled, carry, const, 4)
                run = {"trace": {"ops": tr.device_ops(data), "steps": 4}}
                (ops,) = run["trace"]["ops"].values()
                line.update(
                    attn_prep_ms=tr.scope_ms(run, "attn_prep"),
                    attn_ms=tr.scope_ms(run, "attn"),
                    unscoped_ms=sum(
                        e[2] for e in ops
                        if not tr.scope_names(tr.scope_of(e))) / 4 / 1e6)
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
    with open("chiprun_out/attn_prep_sweep.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def apart(cell):
    """The pair against the chain at one layer's shape of the cell (its
    first attention layer with a norm or a rotation): ``fused`` as a
    projection of a normed stream would be (unit variance, bfloat16),
    the scales as a trained model might hold them, the cotangents
    random."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import transformer
    from horovod_tpu.ops import attn_prep
    from horovod_tpu.ops.rope import rope_tables

    cfg = transformer.GPT_CONFIGS[cell["config_values"]["program"]["size"]]
    b, s = cell["params"]["per_chip_batch"], cell["params"]["seq_len"]
    if cfg.block_diffusion is not None:
        s *= 2      # the noised copy beside the clean one
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    kind = next(cfg.layer_type(i) for i in range(cfg.num_layers)
                if cfg.layer_type(i) not in transformer.NOT_ATTENTION_MIXER
                and (cfg.qk_norm or cfg.rotates(cfg.layer_type(i))))
    norms, rotates = cfg.qk_norm, cfg.rotates(kind)
    tiles = attn_prep.plan(s, nh, nkv, hd, norm=cfg.norm if norms else None,
                           rotates=rotates, flash=True, plain=True)
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    bf16 = lambda k, *shape: jax.random.normal(k, shape).astype(jnp.bfloat16)
    args = (bf16(ks[0], b, s, (nh + 2 * nkv) * hd),
            1.0 + 0.1 * jax.random.normal(ks[1], (hd,)),
            1.0 + 0.1 * jax.random.normal(ks[2], (hd,)))
    weights = tuple(bf16(k, b * n, s, hd)
                    for k, n in zip(ks[3:], (nh, nkv, nkv)))
    tables = rope_tables(jnp.arange(s) % cell["params"]["seq_len"], hd,
                         cfg.rope_theta) if rotates else None

    def both(kernels):
        def fn(fused, q_scale, k_scale):
            if kernels:
                return attn_prep.attn_prep(
                    fused, (q_scale, k_scale) if norms else None, tables,
                    heads=nh, kv_heads=nkv, eps=cfg.norm_eps, tiles=tiles)
            norm = lambda scale: (lambda t: nn.RMSNorm(
                epsilon=cfg.norm_eps, dtype=jnp.float32).apply(
                    {"params": {"scale": scale}}, t)) if norms else None
            return tuple(
                t.transpose(0, 2, 1, 3).reshape(-1, s, hd)
                for t in transformer.attn_prep_chain(
                    fused, norm(q_scale), norm(k_scale), tables, heads=nh,
                    kv_heads=nkv, head_dim=hd))

        out, pull = jax.vjp(fn, *args)
        return (*out, *pull(weights))

    got, want = jax.jit(both, static_argnums=0)(True), jax.jit(
        both, static_argnums=0)(False)
    flat = lambda t: t.astype(jnp.float32).ravel()
    names = ("q", "k", "v", "dfused", "dq_scale", "dk_scale")
    return {"layer": kind, "tiles": list(tiles), "apart": {
        name: [float(jnp.linalg.norm(flat(g) - flat(w))
                     / jnp.maximum(jnp.linalg.norm(flat(w)), 1e-30)),
               int(jnp.sum(flat(g) != flat(w)))]
        for name, g, w in zip(names, got, want)}}


if __name__ == "__main__":
    main()
