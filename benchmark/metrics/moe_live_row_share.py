"""Of the rows the expert layers computed on in the last step, the share
that was routed to a held expert: ``rows_held`` of each layer (collection
``moe_stats``, which ``publish_stats`` reads from the final carry and
the family's builder leaves under ``ran["moe_counters"]``) over the rows
its buffers carried, summed over the layers.  A layer's buffers carry
``moe.row_bound{layer}`` rows, and ``moe.slots{layer}`` in a step whose
held rows pass that (the program's own rule,
``horovod_tpu/parallel/moe.py:apply_routing``: ``held_sizes.sum() >
bound``); both gauges are set while the step is traced
(``models/transformer.py:routed``) and read from the program's registry
in this process.  Every dead row costs the gathers, the cast, the gate
and the kernels' select every step: about 0.5 where the bound is two
even shares and the load is even.  A program without the counters or
the gauges: None."""

from benchmark.harness import registry


def read(run):
    counters = run["ran"].get("moe_counters")
    gauges = registry.sibling_metric(
        __file__, "moe_gmm_tile_fill").layer_gauges
    bounds, slots = gauges("moe.row_bound"), gauges("moe.slots")
    if not counters or not bounds or not slots:
        return None
    live = carried = 0
    for layer, entry in counters.items():
        if layer not in bounds or layer not in slots:
            return None
        live += entry["rows_held"]
        carried += (bounds[layer] if entry["rows_held"] <= bounds[layer]
                    else slots[layer])
    return live / carried
