"""The least time the chip could take for a step's windowed flash calls
(the larger of their FLOPs over peak FLOP/s and their bytes over peak
bytes/s, both from shapes: benchmark/harness/window_flops.py, the
visible pairs of a band of ``sliding_window`` keys, query heads grouped
over fewer key/value heads, in every layer ``layer_types`` calls
``sliding_attention``) over the time the kernels took
(``swa_flash_ms``).  ``run["notes"]`` gets the bounding side.  The bound
counts pairs, the kernels compute whole tiles: under 100 % by
construction."""

from benchmark.harness import flops, registry, window_flops


def read(run):
    ran = run["ran"]
    if "peaks" not in run or "sliding_window" not in ran:
        return None
    took_ms = registry.sibling_metric(__file__, "swa_flash_ms").read(run)
    if took_ms is None:
        return None
    layers = list(ran["layer_types"]).count("sliding_attention")
    need_flops, need_bytes = window_flops.swa_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"],
        heads=ran["num_attention_heads"],
        kv_heads=ran["num_key_value_heads"], seq_len=ran["seq_len"],
        head_dim=ran["head_dim"], window=ran["sliding_window"],
        layers=layers)
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["swa_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": layers}
    return 100.0 * bound_s / (took_ms / 1e3)
