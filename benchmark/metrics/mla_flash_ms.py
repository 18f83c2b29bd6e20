"""Device time per step of latent attention's flash kernels, forward
(with the recomputed forward) and backward: ``flash_fwd_ms`` +
``flash_bwd_ms``, the kernels' own names, for a cell whose attention is
not the full multi-head form ``flash_ms`` asserts (head size ``n_embd //
n_head``): here every layer and the prediction module run heads of
``qk_nope_head_dim + qk_rope_head_dim``.  A program without latent
attention, or a run without a trace: None."""

from benchmark.harness import registry


def read(run):
    if "kv_lora_rank" not in run["ran"]:
        return None
    parts = [registry.sibling_metric(__file__, name).read(run)
             for name in ("flash_fwd_ms", "flash_bwd_ms")]
    return None if None in parts else sum(parts)
