"""Steps, summed over the expert layers and counted since the state was
made (warm-up and window alike), in which a layer's held experts were
routed more rows than its row bound and the layer ran on the whole slot
buffer instead (``horovod_tpu/parallel/moe.py:routed_experts``): 0 says
the bound held in every step the run made.  From the program's own
counters (collection ``moe_stats``, read from the device state after the
window by ``publish_stats``), which the family's builder leaves under
``ran["moe_counters"]``; a program without the counter: None."""


def read(run):
    counters = run["ran"].get("moe_counters")
    if not counters or not all(
            "overflow_steps" in layer for layer in counters.values()):
        return None
    return sum(layer["overflow_steps"] for layer in counters.values())
