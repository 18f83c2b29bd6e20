"""Rows routed to the experts held on this chip in the last step, over an
even share of all rows (``tokens x experts a token x held / routed``),
summed over the expert layers: 1.0 is an even share, and the grouped
matmuls' time follows it.  From the program's own counters (collection
``moe_stats``, read from the device state after the window by
``horovod_tpu/parallel/moe.py:publish_stats``), which the family's
builder leaves under ``ran["moe_counters"]``; a program without them:
None."""


def read(run):
    ran = run["ran"]
    counters = ran.get("moe_counters")
    if not counters:
        return None
    routed = sum(layer["rows_held"] for layer in counters.values())
    tokens = ran["global_batch"] // run["chips"] * ran["seq_len"]
    even = (tokens * ran["num_experts_per_tok"] * ran["n_routed_experts"]
            / ran["router_width"])
    return routed / (even * len(counters))
