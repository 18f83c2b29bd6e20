"""The least time the chip could take for a step's selective scans (the
larger of their operations over peak FLOP/s and their bytes over peak
bytes/s, both from shapes: benchmark/harness/selective_scan_flops.py)
over the time the kernels took (``sscan_ms``).  ``run["notes"]`` gets the
bounding side.  It reads low by nature: the recurrence is elementwise
work, a channel and state at a time, and the bound is the memory's."""

from benchmark.harness import flops, registry, selective_scan_flops


def read(run):
    ran = run["ran"]
    if "peaks" not in run or "mamba_d_inner" not in ran:
        return None
    took_ms = registry.sibling_metric(__file__, "sscan_ms").read(run)
    if took_ms is None:
        return None
    need_flops, need_bytes = selective_scan_flops.sscan_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        channels=ran["mamba_d_inner"], state=ran["mamba_d_state"],
        layers=list(ran["layer_types"]).count("selective_scan"))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["sscan_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes}
    return 100.0 * bound_s / (took_ms / 1e3)
