"""The named sizes the benchmark's cells run build the parameter tree
and compute the loss they did before PR 61 edited ``block_math``,
``Block``'s ``feed_forward``, ``parallel/moe.py:grouped_ffn`` and
``mamba_mixer`` under all of them (a layer of one half, experts without
a gate matrix, a norm by group): each size at a small shape on the CPU,
its variables from a fixed seed, against the values the parent commit
(3570c60) gave; Nemotron-3-Nano's, PR 61's own size, against PR 63's
tree (37d1e38) since PR 64 edited ``attention_mixer``, ``_norm`` and the
shared expert under it.  ``RECORDED`` is what this file prints when it is run
as a script with the parent's tree on ``PYTHONPATH``: the SHA-1 of the
sorted ``path:shape:dtype`` lines of every collection ``init`` makes,
and the mean next-token loss on a fixed batch.
"""

import hashlib

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.models.transformer import gpt

SEQ = 32
ROUTED = dict(routed_experts=8, routed_held=2, routed_first_held=4,
              routed_top_k=2, routed_width=32)
SMALL = dict(vocab_size=256, emb_dim=64, max_len=64,
             attention_impl="reference", dtype=jnp.float32)
# named size -> the overrides that make it small (every layer type and
# feed-forward kind of the cell's cut is kept)
SIZES = {
    "medium": dict(num_layers=2, num_heads=4),
    "granite-4.0-h-micro": dict(
        num_layers=3, layer_types=("mamba", "attention", "mamba"),
        num_heads=4, num_kv_heads=2, ssm_heads=4, ssm_head_dim=16,
        ssm_state=16, ssm_chunk=8),
    "glm-4.7-flash": dict(
        num_layers=2, layer_types=("mla",) * 2, num_heads=4, num_kv_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=24, mlp_ratio=3, **ROUTED),
    "trinity-mini": dict(
        num_layers=3, layer_types=("sliding_attention", "full_attention",
                                   "sliding_attention"),
        num_heads=8, num_kv_heads=2, head_size=16, attention_window=8,
        mlp_ratio=3, dense_layers_first=1, embedding_multiplier=8.0,
        **ROUTED),
    "phi-4-mini-flash-reasoning": dict(
        num_layers=6, layer_types=(
            "selective_scan", "sliding_attention", "selective_scan",
            "full_attention", "gmu", "cross_attention"),
        memory_layer=2, shared_kv_layer=3, num_heads=4, num_kv_heads=2,
        attention_window=8, ssm_width=32, ssm_state=8, ssm_dt_rank=4),
    "smallthinker-21ba3b-instruct": dict(
        num_layers=2, layer_types=("full_attention", "sliding_attention"),
        num_heads=7, num_kv_heads=1, head_size=16, attention_window=8,
        **ROUTED),
    "lfm2-24b-a2b": dict(
        num_layers=3, layer_types=("conv", "conv", "full_attention"),
        dense_layers_first=1, num_heads=8, num_kv_heads=2, mlp_width=184,
        **ROUTED),
    "kimi-linear-48b-a3b-instruct": dict(
        num_layers=3, layer_types=("kda", "kda", "mla"),
        dense_layers_first=1, num_heads=4, num_kv_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, kda_heads=4,
        kda_head_dim=16, kda_chunk=16, kda_states_every=2, mlp_ratio=3,
        **ROUTED),
    "sdar-30b-a3b-chat": dict(
        num_layers=2, num_heads=8, num_kv_heads=2, head_size=16, **ROUTED),
    "xing4.0-29b-a4b": dict(
        num_layers=2, layer_types=("mla",) * 2, dense_layers_first=1,
        num_heads=4, num_kv_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        mlp_width=96, mtp_modules=0, attention_scale=24 ** -0.5, **ROUTED),
    # since PR 64, against PR 63's tree (37d1e38): one half a layer, a
    # Mamba-2 mixer in groups, an ungated expert layer, attention
    "nvidia-nemotron-3-nano-30b-a3b-bf16": dict(
        num_layers=4, layer_types=("mamba", "feed_forward", "mamba",
                                   "full_attention"),
        num_heads=4, num_kv_heads=2, head_size=16, ssm_heads=4,
        ssm_head_dim=16, ssm_state=16, ssm_groups=2, ssm_chunk=8,
        mlp_width=32, shared_width=64, **ROUTED),
}
RECORDED = {
    "glm-4.7-flash": ("e364b2e28a5a2ad5c4a1342ede039ae2232f9f4e",
                      6.116734504699707),
    "granite-4.0-h-micro": ("ba0285bbb882601d70cb363bf03747cdda5dd8f2",
                            5.534761428833008),
    "kimi-linear-48b-a3b-instruct": (
        "63dc4abeeb03ef59faebed36b52e8ace6508280c", 6.057533264160156),
    "lfm2-24b-a2b": ("1747d5ac9459773bcac56281dfe61e8b076521ab",
                     5.988839149475098),
    "medium": ("6474d4de9bd533ae5fbdf2f814f1cee68aac9050", 6.06050443649292),
    "nvidia-nemotron-3-nano-30b-a3b-bf16": (
        "87097d85fa9ad76b41db4a2feef7ef647a0e45dc", 6.008303165435791),
    "phi-4-mini-flash-reasoning": (
        "53991843cde173f1b0982470254182844177c029", 6.0701904296875),
    "sdar-30b-a3b-chat": ("29ab2b0838e8a9b141e28a0d11ab8023522ec37e",
                          6.029790878295898),
    "smallthinker-21ba3b-instruct": (
        "b372a7f9b57910aa0f0a2364f52d4344b754bf44", 6.0921549797058105),
    "trinity-mini": ("41c79448baaa33873c8d17bacaa7d58b79e15ae3",
                     6.179281711578369),
    "xing4.0-29b-a4b": ("b4508d42b934f188790cfdde30239202cc570678",
                        6.29837703704834),
}


def measured(size: str):
    """The tree's fingerprint and the loss of ``size`` at its small
    shape."""
    model = gpt(size, **{**SMALL, **SIZES[size]})
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0, 256)
    variables = jax.jit(model.init)(jax.random.PRNGKey(1), tokens[:, :SEQ])
    lines = sorted(
        f"{jax.tree_util.keystr(path)}:{leaf.shape}:{leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(variables))
    tree = hashlib.sha1("\n".join(lines).encode()).hexdigest()

    def loss(variables):
        logits = model.apply(variables, tokens[:, :SEQ])
        labels = tokens[:, 1:]
        if model.cfg.block_diffusion is not None:
            labels = labels[:, :SEQ // 2]  # the noised half's
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    return tree, float(jax.jit(loss)(variables))


@pytest.mark.parametrize("size", sorted(SIZES))
def test_a_named_size_builds_and_computes_what_the_parent_did(size):
    tree, loss = measured(size)
    want_tree, want_loss = RECORDED[size]
    assert tree == want_tree
    assert loss == pytest.approx(want_loss, rel=1e-6)


def test_every_transformer_configuration_of_the_benchmark_is_a_case():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sizes = set()
    for entry in json.load(open(os.path.join(root, "BENCHMARK.json")))[
            "configs"]:
        program = json.load(open(os.path.join(root, entry["file"]))).get(
            "program", {})
        if program.get("factory", "").endswith("transformer.gpt"):
            sizes.add(program["size"])
    # this PR's own size has no parent to be held to
    assert sizes - {"qwen3-next-80b-a3b-instruct"} == set(SIZES)


if __name__ == "__main__":
    for name in sorted(SIZES):
        print(f"    {name!r}: {measured(name)!r},", flush=True)
