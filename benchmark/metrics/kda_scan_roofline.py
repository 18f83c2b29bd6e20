"""The least time the chip could take for a step's chunked gated delta
rules (the larger of their FLOPs over peak FLOP/s and their bytes over
peak bytes/s, both from shapes: benchmark/harness/kda_flops.py, the
algorithm at the chunk the program's gauge ``kda.chunk`` says it ran, as
the family's builder leaves it under ``ran["kda"]``) over the time the
rules took (``kda_scan_ms``).  ``run["notes"]`` gets the bounding side.
A program whose builder leaves no ``ran["kda"]``: None."""

from benchmark.harness import flops, kda_flops, registry


def read(run):
    ran = run["ran"]
    counted = ran.get("kda")
    if "peaks" not in run or not counted or not counted.get("layers"):
        return None
    took_ms = registry.sibling_metric(__file__, "kda_scan_ms").read(run)
    if took_ms is None:
        return None
    need_flops, need_bytes = kda_flops.kda_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        heads=ran["kda_num_heads"], d_k=ran["kda_head_dim"],
        d_v=ran["kda_head_dim"], chunk=int(counted["chunk"]),
        layers=int(counted["layers"]))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["kda_scan_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": int(counted["layers"]),
        "chunk": int(counted["chunk"])}
    return 100.0 * bound_s / (took_ms / 1e3)
