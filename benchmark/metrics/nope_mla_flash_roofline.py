"""The least time the chip could take for a step's latent-attention
flash calls whose keys are wider than their values (the larger of their
FLOPs over peak FLOP/s and their bytes over peak bytes/s, both from
shapes: benchmark/harness/kda_flops.py:unequal_flash_train_flops_bytes,
``S (S + 1) / 2`` visible pairs a head, four matmuls over the key's
channels and three over the value's, in every layer ``layer_types`` calls
``mla``) over the time the kernels took (``nope_mla_flash_ms``).
``run["notes"]`` gets the bounding side.  The bound counts pairs and the
channels the mathematics has; the kernels compute whole tiles and keys of
192 occupy 256 lanes: under 100 % by construction."""

from benchmark.harness import flops, kda_flops, registry


def read(run):
    if "peaks" not in run:
        return None
    nope = registry.sibling_metric(__file__, "nope_mla_flash_ms")
    took_ms = nope.read(run)
    if took_ms is None:
        return None
    ran = run["ran"]
    layers = nope.unequal_latent_layers(ran)
    need_flops, need_bytes = kda_flops.unequal_flash_train_flops_bytes(
        batch=ran["global_batch"] // run["chips"],
        heads=ran["num_attention_heads"], seq_len=ran["seq_len"],
        qk_dim=ran["qk_nope_head_dim"] + ran["qk_rope_head_dim"],
        v_dim=ran["v_head_dim"], layers=layers)
    bound_s, side = flops.roofline_seconds(need_flops, need_bytes,
                                           run["peaks"])
    run.setdefault("notes", {})["nope_mla_flash_roofline_bound"] = {
        "side": side, "seconds": bound_s, "flops": need_flops,
        "bytes": need_bytes, "layers": layers}
    return 100.0 * bound_s / (took_ms / 1e3)
