"""The least time the chip could take for the grouped matmuls of a
step's expert layers whose experts have no gate matrix (the larger of
their FLOPs over peak FLOP/s and their bytes over peak bytes/s:
benchmark/harness/ungated_expert_flops.py, two matrices an expert, from
shapes and from the rows the program's counter says were routed to the
held experts in the last step) over the time they took
(``moe_experts_ms``).  ``run["notes"]`` gets the bounding side.  It
follows the rows routed, so it stays under 100 % by construction.  A
program whose ``ran`` does not say its experts are ungated
(``ran["experts_gated"]`` is not False), or without the counters or a
trace: None."""

from benchmark.harness import flops, registry, ungated_expert_flops


def read(run):
    ran = run["ran"]
    counters = ran.get("moe_counters")
    if ("peaks" not in run or not counters
            or ran.get("experts_gated") is not False):
        return None
    took_ms = registry.sibling_metric(__file__, "moe_experts_ms").read(run)
    if took_ms is None:
        return None
    rows = sum(layer["rows_held"] for layer in counters.values())
    need_flops, need_bytes = ungated_expert_flops.experts_train_flops_bytes(
        rows=rows, hidden=ran["hidden_size"],
        width=ran["moe_intermediate_size"], held=ran["n_routed_experts"],
        layers=len(counters))
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["ungated_experts_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "rows": rows}
    return 100.0 * bound_s / (took_ms / 1e3)
