"""The least time the chip could take for a step's flash-attention calls
(the larger of their FLOPs over peak FLOP/s and their bytes over peak
bytes/s, both from shapes: benchmark/harness/flops.py) over the time the
kernels took (``flash_ms``).  ``run["notes"]`` gets the bounding side."""

from benchmark.harness import flops, registry


def read(run):
    if "peaks" not in run:
        return None
    took_ms = registry.sibling_metric(__file__, "flash_ms").read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    need_flops, need_bytes = flops.flash_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], heads=ran["n_head"],
        seq_len=ran["seq_len"], head_dim=ran["n_embd"] // ran["n_head"],
        layers=ran["n_layer"])
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes}
    return 100.0 * bound_s / (took_ms / 1e3)
