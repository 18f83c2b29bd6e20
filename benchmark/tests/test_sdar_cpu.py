"""The cell ``sdar_train_s8192_bd4`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 8 query heads over 2
key/value heads of 16, 64 data tokens as 128 rows in blocks of 4, two
layers of 8 experts of width 32 of which 2 are held, 3 a token), its
reference checks with the fp8 control, the family's ``fault_probes`` and
the reference's departures, its model FLOPs and the mask's operations
against hand counts, its readers on a hand-built trace and on a recording
of the cell's own traced step, and its entries in ``BENCHMARK.json``
pinned by name.  Nothing these runs time is a measurement."""

import json
import os

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "sdar_train_s8192_bd4"
CONFIG = "sdar-30b-a3b-chat"
TINY = {"seq_len": 64, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 2, "vocab_size": 256, "emb_dim": 64,
            "num_heads": 8, "num_kv_heads": 2, "head_size": 16,
            "routed_experts": 8, "routed_held": 2, "routed_top_k": 3,
            "routed_width": 32, "max_len": 256}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference; loss, largest log-probability, gradient
# apart): sound 0.0003, 0.028, 0.014; every weight through fp8 0.0058,
# 0.36, 0.150; the last layer's experts silent 0.037, 0.59, 0.47.  The
# limits the cell is held to are in its configuration file, from chip
# runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.003, "logprob_abs": 0.15, "grad_rel": 0.05}
FLOAT32_TOLERANCE = {"loss_abs": 1e-4, "logprob_abs": 1e-3,
                     "grad_rel": 1e-3}
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 3.0, "grad_rel": 0.5}
NEW_READERS = {
    "bd_flash_ms": ("ms", "lower", "device_trace", "Kernels"),
    "bd_flash_roofline": ("%", "higher", "device_trace", "Kernels"),
    "bd_visible_pair_share": ("ratio", "higher", "program_counter",
                              "Kernels"),
    "bd_masked_token_share": ("ratio", "higher", "program_counter",
                              "Step builder"),
    "bd_noise_ms": ("ms", "lower", "device_trace", "Step builder")}
JOINED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "step_trace_s", "step_lower_s", "step_backend_s",
    "cache_load_s", "state_programs_s", "hvd_init_s", "setup_uncovered_s",
    "peak_hbm_gib", "optimizer_ms", "attn_ms", "mlp_ms", "head_ms",
    "flash_fwd_ms", "flash_bwd_ms", "flash_live_tile_share", "moe_route_ms",
    "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
    "moe_overflow_steps", "moe_balance_loss",
    "moe_logits_ms", "moe_topk_ms", "moe_sort_ms", "moe_unsort_ms",
    "moe_rows_in_ms", "moe_rows_out_ms", "moe_cast_ms", "moe_gate_ms",
    "moe_live_row_share", "moe_gmm_tile_fill"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RAN = {"global_batch": 1, "seq_len": 8192, "block_length": 4,
       "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
       "layer_types": ["attention"] * 6}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE, dtype=None):
    root = make_root(tmp_path)
    params = json.loads(json.dumps(TINY))
    if dtype:
        params["overrides"]["dtype"] = dtype
    add_cell(root, "tiny_sdar", CELL, params, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_train_runner_sdar(tmp_path):
    import run as cli

    line = cli.execute("tiny_sdar", seed=2**31 + 11, seconds=1.0,
                       trace=False,
                       root=_tiny_root(tmp_path, LAX_TOLERANCE),
                       allow_cpu=True)
    json.dumps(line)
    # all but loss_falls: at 2 x 64 tokens the 1 / t weights of a fresh
    # draw move the loss by a tenth of itself from step to step (at the
    # cell's 8192 by a hundredth), more than a second's steps lower it
    assert all(check["ok"] for name, check in line["checks"].items()
               if name != "loss_falls"), line["checks"]
    assert set(line["checks"]) == {
        "losses_finite", "loss_falls", "nothing_built_in_window",
        "matches_reference", "logprob_matches_reference",
        "gradient_matches_reference"}
    assert line["checks"]["logprob_matches_reference"]["labels"] == 2 * 64
    assert line["failed"] == 0 and line["attempted"] >= 3
    for name in ("train_throughput", "step_ms_p90", "setup_s"):
        assert line["metrics"][name]["value"] > 0
    assert line["notes"]["model_flops_per_item"] > 0


def _trained(tmp_path, tolerance, dtype=None):
    from benchmark.harness import correct, registry
    from benchmark.runners import train

    root = _tiny_root(tmp_path, tolerance, dtype)
    cell = registry.load_cell("tiny_sdar", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    import numpy as np

    keys = [np.asarray(built.state[3]["key"])]     # the step donates it
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:built.carry_len]),
        built.state[built.carry_len:], steps=8)
    reference = registry.load_reference(cell["config"], root)
    merged = {**config, **built.ran}
    keys.append(np.asarray(carry[3]["key"]))
    variables = built.variables(tuple(carry))
    return {"sides": correct.reference_sides(built.program_loss, reference,
                                             merged),
            "program_loss": built.program_loss, "reference": reference,
            "config": merged, "variables": variables, "ran": built.ran,
            "tolerance": tolerance, "keys": keys,
            "items_per_step": built.items_per_step,
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """The cell's program as it is: bfloat16 compute."""
    return _trained(tmp_path_factory.mktemp("sdar"), TINY_TOLERANCE)


@pytest.fixture(scope="module")
def trained_float32(tmp_path_factory):
    return _trained(tmp_path_factory.mktemp("sdar_float32"),
                    FLOAT32_TOLERANCE, "float32")


def _checks(trained, damage=None, sides=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        sides or trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, trained["tolerance"])


def test_untouched_program_passes_and_counts_its_noise(trained):
    import numpy as np

    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    counted = ran["block_diffusion"]
    # the key moved with the steps, and the last step masked some tokens
    assert not np.array_equal(*trained["keys"])
    assert 0 < counted["masked_tokens"] < trained["items_per_step"]
    run = {"ran": ran, "items_per_step": trained["items_per_step"]}
    assert 0.2 < _reader("bd_masked_token_share").read(run) < 0.8
    assert set(ran["moe_counters"]) == {"block0", "block1"}
    assert _reader("moe_balance_loss").read(run) > 0
    # the sample carries its own fixed draw
    sample = trained["sample"]
    assert set(sample) == {"tokens", "masked", "t"}
    assert int(sample["tokens"].max()) < ran["mask_token_id"]


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, damage=correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


def test_silent_experts_are_not_correct(trained):
    assert set(trained["probes"]) == {"experts_silent"}
    checks = _checks(trained, damage=trained["probes"]["experts_silent"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_ones_the_issue_names(trained):
    assert trained["reference"].DEPARTURES == (
        "causal_mask", "positions_not_repeated", "shifted_labels",
        "weight_dropped", "loss_over_every_row", "blind_to_own_block",
        "block_8")


def test_the_float32_program_is_the_reference_to_rounding(trained_float32):
    checks = _checks(trained_float32)
    assert all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("depart", [
    "causal_mask", "positions_not_repeated", "shifted_labels",
    "weight_dropped", "loss_over_every_row", "blind_to_own_block",
    "block_8"])
def test_a_departed_reference_is_not_correct(trained_float32, depart):
    from benchmark.harness import correct
    from benchmark.tools.probe_departures import departed

    sides = correct.reference_sides(
        trained_float32["program_loss"],
        departed(trained_float32["reference"], depart),
        trained_float32["config"])
    checks = _checks(trained_float32, sides=sides)
    assert not all(c["ok"] for c in checks.values()), (depart, checks)


def test_model_flops_against_a_hand_count():
    """The cell's sizes by hand: per data token two rows through six
    blocks, the mask's visible pairs, the head once."""
    from benchmark.harness import registry

    builder = registry.load_model_builder("sdar_moe", ROOT)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    got = builder.train_flops_per_item(
        config, {"seq_len": 8192, "router_width": 128})
    projections = 2 * (2048 * 5120 + 4096 * 2048)
    routed = 2 * 2048 * 128 + (8 * 16 / 128) * 2 * 3 * 2048 * 768
    scores = 4 * 4096 * (8192 * 4 + 8192 ** 2) / 8192
    forward = 2 * 2048 * 18992 + 6 * (2 * (projections + routed) + scores)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert got == pytest.approx(4.368e9, rel=1e-3)


def test_the_masks_operations_and_bytes_against_a_hand_count():
    from benchmark.harness import block_diffusion_flops as bdf

    assert bdf.visible_pairs(8192, 4) == 67_141_632
    assert bdf.visible_pairs(8, 4) == 8 * 4 + 16 * 1 + 16 * 3
    flops, nbytes = bdf.bd_train_flops_bytes(
        batch=1, heads=32, kv_heads=4, length=8192, head_dim=128, block=4,
        layers=6)
    assert flops == 7 * 2 * 67_141_632 * 128 * 32 * 6
    assert nbytes == 6 * (16384 * 128 * 2) * 36 * 6
    # compute bound: 117 ms of matmuls against 1.1 ms of traffic
    assert flops / 197e12 == pytest.approx(0.1173, rel=1e-3)


def test_the_new_readers_on_a_hand_built_trace():
    """The flash kernels under the mask's scope inside ``attn``, forward
    and backward, the noising at the step's top."""
    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 1e6, "jit(step)/diffusion_noise/threefry2x32:"],
        ["fusion.2", 1e6, 3e6, step + "block0/attn/qkv/dot_general:"],
        ["tpu_custom_call:flash_fwd.1", 4e6, 10e6,
         step + "block0/attn/attn_block_diffusion/flash_fwd/pallas_call:"],
        ["fusion.3", 14e6, 4e6, step + "block0/mlp/moe_experts/gmm:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 20e6, 20e6,
         back + "block0/attn/attn_block_diffusion/flash_bwd_dkdv/"
         "pallas_call:"],
        ["fusion.4", 40e6, 2e6,
         back + "block0/attn/attn_block_diffusion/mul:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 2}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    assert _reader("bd_flash_ms").read(run) == pytest.approx(15.0)
    assert _reader("bd_noise_ms").read(run) == pytest.approx(0.5)
    assert _reader("flash_fwd_ms").read(run) == pytest.approx(5.0)
    assert _reader("attn_ms").read(run) == pytest.approx(17.5)
    share = _reader("bd_flash_roofline").read(run)
    assert share == pytest.approx(100 * 0.11727 / 0.015, rel=1e-3)
    assert run["notes"]["bd_flash_roofline_bound"]["side"] == "compute"
    # a program without the scope, the setting or the counters: nothing
    other = {"trace": {"ops": {0: ops[1:2]}, "steps": 2},
             "ran": {"global_batch": 1}, "chips": 1, "peaks": PEAKS,
             "items_per_step": 8192}
    for name in NEW_READERS:
        assert _reader(name).read(other) is None, name
    counted = {"ran": {"block_diffusion": {
        "visible_pairs": {"attention": 32 * 67_141_632},
        "live_tile_pairs": {"attention": 32 * 576 * 512 * 256},
        "masked_tokens": 4100.0}}, "items_per_step": 8192}
    assert _reader("bd_visible_pair_share").read(counted) == pytest.approx(
        0.8893, abs=1e-4)
    assert _reader("bd_masked_token_share").read(counted) == pytest.approx(
        4100 / 8192)


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to the noising
    and the attention halves of blocks 0 and 1 (``made_from`` in the file
    beside it says how), with what plain sums over names and scopes give
    for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".blocks0_1_attn_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".blocks0_1_attn_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN, layer_types=["attention"] * 2), "chips": 1,
           "peaks": PEAKS}
    events = run["trace"]["ops"][0]
    assert len(events) == expect["events"]
    for name in ("attn_ms", "bd_flash_ms", "flash_fwd_ms", "flash_bwd_ms",
                 "bd_noise_ms", "bd_flash_roofline"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    # every flash kernel of the step ran under the mask's scope, inside
    # attn, forward and backward, the backward as one kernel
    flash = [e for e in events if e[0].startswith("tpu_custom_call:flash")]
    under = tr.under(events, "attn_block_diffusion")
    assert flash and all(e in under for e in flash)
    assert set(map(tuple, under)) <= set(map(tuple, tr.under(events, "attn")))
    assert any("transpose(" in tr.scope_of(e) for e in under)
    names = {e[0].split(".")[0] for e in flash}
    assert names == {"tpu_custom_call:flash_fwd",
                     "tpu_custom_call:flash_bwd_dkdv"}
    assert 0 < _reader("bd_flash_roofline").read(run) < 100


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s8192_bd4_b1",
        "chips": 1, "why": cells[CELL]["why"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
        "config.json")
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name, (unit, better, source, layer) in NEW_READERS.items():
        new = by_name[name]
        assert CELL in new["workloads"], name
        assert (new["unit"], new["better"], new["source"], new["layer"],
                new["moves"]) == (unit, better, source, layer,
                                  "train_throughput"), name
    for name in JOINED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # flash_ms and flash_roofline count full causal multi-head attention;
    # the window's, the latent layers' and the grouped causal readers
    # count other masks; the other readers are other families'
    for name in ("flash_ms", "flash_roofline", "swa_flash_ms",
                 "swa_flash_roofline", "swa_live_tile_share",
                 "gqa_flash_ms", "gqa_flash_roofline", "mla_flash_ms",
                 "mla_proj_ms", "attn_gate_ms", "mtp_ms", "ssm_ms",
                 "allreduce_ms", "kda_ms", "short_conv_ms",
                 # its even share counts seq_len tokens a sequence, and
                 # this layer routes two rows a data token
                 "moe_rows_share"):
        assert CELL not in by_name[name]["workloads"], name
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == {
        "seq_len": 8192, "per_chip_batch": 1, "block_length": 4,
        "noise": {"t_min": 0.001},
        "attention": "flash", "remat": True, "optimizer": "adamw",
        "learning_rate": 0.0001, "warmup_steps": cell["params"][
            "warmup_steps"], "trace_steps": 4, "reference_items": 1}
    assert cell["runner"] == "train" and len(cell["why"]) <= 200
    assert cell["why"] == cells[CELL]["why"]


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_experts"], config["first_held_expert"],
            config["vocab_size"], config["mask_token_id"],
            config["block_length"]) == (16, 0, 151936 // 8, 18991, 4)
    assert config["num_hidden_layers"] in (4, 5, 6)
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    # every number of the catalog's config under the same key
    catalog = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    for key, value in catalog.items():
        assert config[key] == value, key
    for stated in ("block length", "noise", "layout and mask", "labels",
                   "mask token", "balance loss", "packing",
                   "initialisation", "optimizer", "fused q, k and v",
                   "fused gate and up"):
        assert config["assumed"][stated], stated
    for limit in ("loss_abs", "logprob_abs", "grad_rel", "why"):
        assert config["reference_tolerance"][limit], limit
    assert config["parameters"] == {
        "on_this_chip": config["parameters"]["on_this_chip"],
        "whole_model": 30_532_122_624}


def test_the_builder_refuses_a_file_that_differs_from_the_program(tmp_path):
    from benchmark.harness import registry

    root = make_root(tmp_path)
    cell = registry.load_cell(CELL, root)
    config = dict(cell["config_values"], head_dim=64)
    builder = registry.load_model_builder("sdar_moe", root)
    with pytest.raises(ValueError, match="head_dim"):
        builder.build(config, dict(cell["params"], attention="reference"),
                      seed=0)

