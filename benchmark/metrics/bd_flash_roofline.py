"""The least time the chip could take for a step's flash calls under the
block-diffusion mask (the larger of their FLOPs over peak FLOP/s and
their bytes over peak bytes/s, both from shapes:
benchmark/harness/block_diffusion_flops.py, the visible pairs of the
mask's equation at the configuration's block length, query heads grouped
over fewer key/value heads, in every layer) over the time the kernels
took (``bd_flash_ms``).  ``run["notes"]`` gets the bounding side.  The
bound counts pairs, the kernels compute whole tiles: under 100 % by
construction.  A program without the mask: None."""

from benchmark.harness import block_diffusion_flops, flops, registry


def read(run):
    ran = run["ran"]
    if "peaks" not in run or not ran.get("block_length"):
        return None
    took_ms = registry.sibling_metric(__file__, "bd_flash_ms").read(run)
    if took_ms is None:
        return None
    layers = len(ran["layer_types"])
    need_flops, need_bytes = block_diffusion_flops.bd_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"],
        heads=ran["num_attention_heads"],
        kv_heads=ran["num_key_value_heads"], length=ran["seq_len"],
        head_dim=ran["head_dim"], block=ran["block_length"], layers=layers)
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["bd_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": layers}
    return 100.0 * bound_s / (took_ms / 1e3)
