"""The least time the chip could take for a step's short-convolution
chains (their bytes over peak bytes/s, from shapes:
benchmark/harness/short_conv_bytes.py; the operations are a handful an
element and never bound it) over the time the chains took
(``short_conv_filter_ms``, which holds a rematerialised block's second
forward too, so the share reads low by that much).  The layers are the
``conv`` entries of ``layer_types``; the program's own count of the same
bytes (gauge ``short_conv.filter_bytes``, under ``ran["short_conv"]``) is
noted beside the bound.  ``run["notes"]`` gets both."""

from benchmark.harness import registry, short_conv_bytes


def read(run):
    ran = run["ran"]
    if "peaks" not in run or "conv" not in ran.get("layer_types", ()):
        return None
    took_ms = registry.sibling_metric(
        __file__, "short_conv_filter_ms").read(run)
    if took_ms is None:
        return None
    need_bytes = short_conv_bytes.filter_train_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        channels=ran["hidden_size"],
        layers=list(ran["layer_types"]).count("conv"))
    bound_s = need_bytes / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["short_conv_filter_roofline_bound"] = {
        "side": "memory", "seconds": bound_s, "bytes": need_bytes,
        "program_counted_bytes": (ran.get("short_conv") or {}).get(
            "filter_bytes")}
    return 100.0 * bound_s / (took_ms / 1e3)
