#!/usr/bin/env python
"""One KDA layer with the dense feed-forward at the Kimi-Linear cell's
widths and 16 384 tokens (``kda_grad_probe.py [seq]``; under 1024 a tiny
model, for a rehearsal on the CPU): the loss's gradient in bfloat16 with
the float32 chain in front of the rule as XLA compiles it
(``kda_prep_chain``) and with the kernels (``ops/kda_prep.py``), each
against the same program in float32, whole and a parameter (distance
over the float32 gradient's norm; the parameter's share of it).  What
PERF.md section 6, PR 52, quotes; prints one JSON line a seed."""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

NAME = "kimi-linear-48b-a3b-instruct"
TINY = dict(emb_dim=64, kda_heads=2, kda_head_dim=16, vocab_size=256,
            mlp_ratio=2, kda_chunk=16, num_heads=2, num_kv_heads=2)


def main():
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models.transformer import gpt
    from horovod_tpu.ops import kda_prep

    seq = int(sys.argv[1]) if len(sys.argv) > 1 else 16384
    # the cell's cut of the vocabulary; one layer is KDA over the dense
    # feed-forward (the first layer of the model is the dense one)
    sizes = {"num_layers": 1, "layer_types": ("kda",), "max_len": seq,
             "remat": True, "vocab_size": 20480,
             **(TINY if seq < 1024 else {})}
    tokens = jax.random.randint(jax.random.PRNGKey(7), (1, seq + 1), 0,
                                sizes["vocab_size"])
    plan = kda_prep.plan

    def grads(dtype, kernels, seed):
        kda_prep.plan = plan if kernels else (lambda *shape: None)
        jax.clear_caches()
        model = gpt(NAME, **sizes, dtype=dtype)
        variables = jax.jit(model.init)(jax.random.PRNGKey(seed),
                                        tokens[:, :64])

        def loss(params):
            logits = model.apply({**variables, "params": params},
                                 tokens[:, :-1])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None],
                                        axis=-1).mean()

        value, grad = jax.jit(jax.value_and_grad(loss))(variables["params"])
        return float(value), grad

    def apart(a, b):
        """The leaves' distances, and the whole trees'."""
        leaves = jax.tree_util.tree_map(
            lambda x, y: float(jnp.linalg.norm(
                (x.astype(jnp.float32) - y.astype(jnp.float32)).ravel())),
            a, b)
        return leaves, sum(v * v for v in jax.tree_util.tree_leaves(
            leaves)) ** 0.5

    for seed in (1, 2):
        loss32, g32 = grads(jnp.float32, False, seed)
        loss_chain, g_chain = grads(jnp.bfloat16, False, seed)
        loss_kernel, g_kernel = grads(jnp.bfloat16, True, seed)
        norms, whole = apart(g32, jax.tree_util.tree_map(jnp.zeros_like, g32))
        chain, chain_whole = apart(g_chain, g32)
        kernel, kernel_whole = apart(g_kernel, g32)
        line = {"seed": seed, "loss_f32": loss32, "loss_chain": loss_chain,
                "loss_kernel": loss_kernel, "grad_norm_f32": whole,
                "chain_vs_f32": chain_whole / whole,
                "kernel_vs_f32": kernel_whole / whole,
                "kernel_vs_chain": apart(g_kernel, g_chain)[1] / whole,
                "per_param_chain_kernel_share": {
                    jax.tree_util.keystr(path): [
                        round(c / (n + 1e-30), 5), round(k / (n + 1e-30), 5),
                        round(n / whole, 4)]
                    for (path, n), c, k in zip(
                        jax.tree_util.tree_flatten_with_path(norms)[0],
                        jax.tree_util.tree_leaves(chain),
                        jax.tree_util.tree_leaves(kernel))}}
        print(json.dumps(line), flush=True)
    kda_prep.plan = plan


if __name__ == "__main__":
    main()
