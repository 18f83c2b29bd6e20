"""The load-balance loss of the last step, the mean over the expert
layers: ``E x sum_e f_e P_e`` a layer (``f_e`` the share of the step's
slots that chose expert ``e``, ``P_e`` the mean over tokens of the full
softmax of the router's logits), 1.0 at an even load, ``E / held`` where
every token chooses held experts alone.  It is what holds the load even
in a layer whose router has no selection bias, so it says how far the
routed rows (``moe_rows_share``, ``moe_experts_ms``) are from an even
share.  From the program's own counters (collection ``moe_stats``, key
``balance_loss``, read from the device state after the window by
``horovod_tpu/parallel/moe.py:publish_stats``), which the family's
builder leaves under ``ran["moe_counters"]``; a program without the
counter: None."""


def read(run):
    counters = run["ran"].get("moe_counters")
    if not counters or not all(
            "balance_loss" in layer for layer in counters.values()):
        return None
    return (sum(layer["balance_loss"] for layer in counters.values())
            / len(counters))
