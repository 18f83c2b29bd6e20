"""The least time the chip could take for a step's latent-attention
flash calls (the larger of their FLOPs over peak FLOP/s and their bytes
over peak bytes/s, both from shapes: benchmark/harness/flops.py with the
heads latent attention hands the kernels, ``num_attention_heads`` of
``qk_nope_head_dim + qk_rope_head_dim`` = ``v_head_dim``, in every layer
and every prediction module; causal halves, the algorithm's one recompute
of the scores and no other) over the time the kernels took
(``mla_flash_ms``, which holds the recomputed forward too).
``run["notes"]`` gets the bounding side."""

from benchmark.harness import flops, registry


def read(run):
    if "peaks" not in run:
        return None
    took_ms = registry.sibling_metric(__file__, "mla_flash_ms").read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    head = ran["qk_nope_head_dim"] + ran["qk_rope_head_dim"]
    if head != ran["v_head_dim"]:
        return None  # the kernels take one head size
    need_flops, need_bytes = flops.flash_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"],
        heads=ran["num_attention_heads"], seq_len=ran["seq_len"],
        head_dim=head,
        layers=ran["num_hidden_layers"] + ran["num_nextn_predict_layers"])
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["mla_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes}
    return 100.0 * bound_s / (took_ms / 1e3)
