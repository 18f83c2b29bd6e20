"""The least time the chip could take for a step's full causal
grouped-head flash calls (the larger of their FLOPs over peak FLOP/s and
their bytes over peak bytes/s, both from shapes:
benchmark/harness/window_flops.py with no window, so ``S (S + 1) / 2``
visible pairs a query head, seven matmuls of ``2 x pairs x head_dim``,
six arrays a query head and six a key/value head, in every layer
``layer_types`` calls ``full_attention``) over the time the kernels took
(``gqa_flash_ms``).  ``run["notes"]`` gets the bounding side.  The bound
counts pairs, the kernels compute whole tiles (the diagonal's are half
masked): under 100 % by construction."""

from benchmark.harness import flops, registry, window_flops


def read(run):
    if "peaks" not in run:
        return None
    gqa = registry.sibling_metric(__file__, "gqa_flash_ms")
    took_ms = gqa.read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    layers = gqa.grouped_full_layers(ran)
    need_flops, need_bytes = window_flops.swa_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"],
        heads=ran["num_attention_heads"],
        kv_heads=ran["num_key_value_heads"], seq_len=ran["seq_len"],
        head_dim=ran["head_dim"], window=None, layers=layers)
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["gqa_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": layers}
    return 100.0 * bound_s / (took_ms / 1e3)
