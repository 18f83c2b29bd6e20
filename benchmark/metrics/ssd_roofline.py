"""The least time the chip could take for a step's state-space scans
(the larger of their FLOPs over peak FLOP/s and their bytes over peak
bytes/s, both from shapes: benchmark/harness/ssd_flops.py) over the time
the scans took (``ssd_ms``).  ``run["notes"]`` gets the bounding side."""

from benchmark.harness import flops, registry, ssd_flops


def read(run):
    if "peaks" not in run:
        return None
    took_ms = registry.sibling_metric(__file__, "ssd_ms").read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    need_flops, need_bytes = ssd_flops.ssd_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        heads=ran["mamba_n_heads"], head_dim=ran["mamba_d_head"],
        groups=ran["mamba_n_groups"], state=ran["mamba_d_state"],
        chunk=ran["ssd_chunk"],
        layers=list(ran["layer_types"]).count("mamba"))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["ssd_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes}
    return 100.0 * bound_s / (took_ms / 1e3)
