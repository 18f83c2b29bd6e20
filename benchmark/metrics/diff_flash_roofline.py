"""The least time the chip could take for a step's differential flash
calls (the larger of their FLOPs over peak FLOP/s and their bytes over
peak bytes/s, both from shapes: benchmark/harness/diff_attn_flops.py: a
layer's score maps over ``head_dim`` channels and their values over
twice that, over the visible pairs of each layer's mask) over the time
the kernels took (``diff_flash_ms``).  ``run["notes"]`` gets the bounding
side.  The count is the algorithm's: a program that computes each score
map twice to read the two halves of its values reads under what one
pass can."""

from benchmark.harness import diff_attn_flops, flops, registry


def read(run):
    ran = run["ran"]
    if "peaks" not in run or "first_layer_index" not in ran:
        return None
    took_ms = registry.sibling_metric(__file__, "diff_flash_ms").read(run)
    if took_ms is None:
        return None
    windows = [ran["sliding_window"] if kind == "sliding_attention" else None
               for kind in ran["layer_types"] if kind in (
                   "sliding_attention", "full_attention", "cross_attention")]
    need_flops, need_bytes = diff_attn_flops.diff_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        heads=ran["num_attention_heads"],
        kv_heads=ran["num_key_value_heads"], head_dim=ran["head_dim"],
        windows=windows)
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["diff_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": len(windows)}
    return 100.0 * bound_s / (took_ms / 1e3)
