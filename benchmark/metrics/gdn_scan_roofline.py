"""The least time the chip could take for a step's scalar-decay gated
delta rules (the larger of their FLOPs over peak FLOP/s and their bytes
over peak bytes/s, both from shapes: benchmark/harness/gdn_flops.py, the
scalar-decay algorithm at the chunk the program's gauge ``gdn.chunk``
says it ran, as the family's builder leaves it under ``ran["gdn"]``)
over the time the rules took (``gdn_scan_ms``).  ``run["notes"]`` gets
the bounding side.  A program whose builder leaves no ``ran["gdn"]``:
None."""

from benchmark.harness import flops, gdn_flops, registry


def read(run):
    ran = run["ran"]
    counted = ran.get("gdn")
    if "peaks" not in run or not counted or not counted.get("layers"):
        return None
    took_ms = registry.sibling_metric(__file__, "gdn_scan_ms").read(run)
    if took_ms is None:
        return None
    need_flops, need_bytes = gdn_flops.gdn_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        key_heads=ran["linear_num_key_heads"],
        value_heads=ran["linear_num_value_heads"],
        d_k=ran["linear_key_head_dim"], d_v=ran["linear_value_head_dim"],
        chunk=int(counted["chunk"]), layers=int(counted["layers"]))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["gdn_scan_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": int(counted["layers"]),
        "chunk": int(counted["chunk"])}
    return 100.0 * bound_s / (took_ms / 1e3)
