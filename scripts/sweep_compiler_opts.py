#!/usr/bin/env python
"""Sweep per-compile XLA:TPU compiler options for the ResNet-50 step
of ``horovod_tpu/testing/steps.py``.

Dev tool for the perf push: jit ``compiler_options`` set the TPU
compiler's options per compile, one variant after another in one
process.  Each variant pays a fresh compile; run on an otherwise idle
machine.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_variant(opts, iters=20, warmup=5, batch=128):
    from horovod_tpu.testing.steps import build_step

    step, state, _ = build_step("resnet50", "bf16", batch)
    compiled = step.lower(*state).compile(compiler_options=opts or None)
    params, batch_stats, opt_state, images, labels = state
    for _ in range(warmup):
        params, batch_stats, opt_state, loss = compiled(
            params, batch_stats, opt_state, images, labels
        )
    float(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        params, batch_stats, opt_state, loss = compiled(
            params, batch_stats, opt_state, images, labels
        )
    float(loss)
    return batch * iters / (time.perf_counter() - t0)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("variant", type=int, help="index into VARIANTS")
    args = parser.parse_args()
    VARIANTS = [
        ("baseline", {}),
        ("vmem64m", {"xla_tpu_scoped_vmem_limit_kib": "65536"}),
        ("vmem96m", {"xla_tpu_scoped_vmem_limit_kib": "98304"}),
        ("vmem32m", {"xla_tpu_scoped_vmem_limit_kib": "32768"}),
    ]
    name, opts = VARIANTS[args.variant]
    print(f"{name}: {run_variant(opts):.1f} img/s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
