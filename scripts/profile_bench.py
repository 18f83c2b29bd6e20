#!/usr/bin/env python
"""Where the device's time goes in the bench step, by the program's names.

Builds the step of ``horovod_tpu/testing/steps.py`` (the one
``chip_smoke.py`` smokes), runs ``--iters`` steps of it
under ``horovod_tpu.obs.profile.device_trace`` and prints the reduction
as JSON: window and busy seconds, device time by scope (``attn``,
``mlp``, ``grad_allreduce``, ``optimizer_update``, ...) and by Pallas
kernel (``flash_fwd``; ``flash_bwd_dkdv``, the one backward kernel, which
covers dq, dk and dv; ``flash_bwd_dq`` only where neither a kv row's dk
and dv nor its dq fit the VMEM a one-kernel call may state and the
backward ran as two passes: ``ops/flash_attention.py:flash_plan``),
idle time by span.  Needs the chip: on the CPU backend nothing is recorded.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet101", "resnet18",
                                 "vgg16", "vgg19", "inception3",
                                 "gpt-small", "gpt-medium", "gpt-large"])
    parser.add_argument("--dtype", default="bf16")
    parser.add_argument("--batch-size", type=int, default=None,
                        help="default: 128 resnet, 8 gpt")
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--remat", action="store_true")
    parser.add_argument("--iters", type=int, default=5)
    args = parser.parse_args()

    import jax

    from horovod_tpu.obs.profile import device_trace

    from horovod_tpu.testing.steps import build_gpt_step, build_step

    is_gpt = args.model.startswith("gpt-")
    if args.batch_size is None:
        args.batch_size = 8 if is_gpt else 128
    if is_gpt:
        step, state, _ = build_gpt_step(
            args.model[len("gpt-"):], args.dtype, args.batch_size,
            args.seq_len, remat=args.remat,
        )
        carry, const = list(state[:-1]), list(state[-1:])
    else:
        step, state, _ = build_step(args.model, args.dtype, args.batch_size)
        carry, const = list(state[:3]), list(state[3:])
    # warmup/compile
    for _ in range(3):
        *carry, loss = step(*carry, *const)
    float(loss)
    with device_trace(steps=args.iters) as trace:
        for _ in range(args.iters):
            *carry, loss = step(*carry, *const)
        float(loss)
    if trace.result is None:
        print("no device to trace on backend", jax.default_backend(),
              file=sys.stderr)
        return 3
    trace.result["steps"] = args.iters
    print(json.dumps(trace.result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
