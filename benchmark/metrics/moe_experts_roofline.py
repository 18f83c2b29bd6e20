"""The least time the chip could take for a step's grouped expert matmuls
(the larger of their FLOPs over peak FLOP/s and their bytes over peak
bytes/s: benchmark/harness/moe_flops.py, from shapes and from the rows
the program's counter says were routed to the held experts in the last
step) over the time they took (``moe_experts_ms``).  ``run["notes"]``
gets the bounding side.  It follows the rows routed: a grouped matmul
that computed every held expert on every row would read ``held`` times
under its share, not over 100 %."""

from benchmark.harness import flops, moe_flops, registry


def read(run):
    counters = run["ran"].get("moe_counters")
    if "peaks" not in run or not counters:
        return None
    took_ms = registry.sibling_metric(__file__, "moe_experts_ms").read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    rows = sum(layer["rows_held"] for layer in counters.values())
    need_flops, need_bytes = moe_flops.experts_train_flops_bytes(
        rows=rows, hidden=ran["hidden_size"],
        width=ran["moe_intermediate_size"], held=ran["n_routed_experts"],
        layers=len(counters))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["moe_experts_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "rows": rows}
    return 100.0 * bound_s / (took_ms / 1e3)
