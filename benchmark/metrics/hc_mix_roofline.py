"""The least time the chip could take for a step's hyper-connection
mixing (its bytes over peak bytes/s, from shapes:
benchmark/harness/hyper_connection_bytes.py; the operations are a few an
element and never bound it) over the time the mixing took
(``hc_mix_ms``).  The sub-layers are two a layer of ``num_hidden_layers``,
the streams ``hc_mult`` of ``hidden_size`` channels in the item size the
builder states (``ran["stream_itemsize"]``); the program's own gauges
(``hc.streams``, ``hc.sublayers``, under ``ran["hyper_connections"]``)
are noted beside the bound.  ``run["notes"]`` gets both.  A program
without hyper-connections: None."""

from benchmark.harness import hyper_connection_bytes, registry


def read(run):
    ran = run["ran"]
    if "peaks" not in run or (ran.get("hc_mult") or 1) < 2:
        return None
    took_ms = registry.sibling_metric(__file__, "hc_mix_ms").read(run)
    if took_ms is None:
        return None
    need_bytes = hyper_connection_bytes.mix_train_bytes(
        batch=ran["global_batch"] // run["chips"], seq_len=ran["seq_len"],
        channels=ran["hidden_size"], streams=ran["hc_mult"],
        sublayers=2 * ran["num_hidden_layers"],
        dtype_bytes=ran["stream_itemsize"])
    bound_s = need_bytes / run["peaks"]["hbm_bytes_per_s"]
    run.setdefault("notes", {})["hc_mix_roofline_bound"] = {
        "side": "memory", "seconds": bound_s, "bytes": need_bytes,
        "program_counted": ran.get("hyper_connections")}
    return 100.0 * bound_s / (took_ms / 1e3)
