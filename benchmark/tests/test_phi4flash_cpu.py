"""The cell ``phi4mf_train_s8192`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (the six-layer layout of the cut,
hidden 64, 8 sub-heads of 8 over 4, inner 128, state 16, ``dt_rank`` 4, a
window of 8 in 32 tokens), its reference checks with the fp8 control, the
family's three ``fault_probes`` and the reference's departures, the two
shape-count modules against hand counts, its readers on a hand-built
trace and on a recording of the cell's own traced step, and its entries
in ``BENCHMARK.json`` pinned by name.  Nothing these runs time is a
measurement."""

import json
import os
import types

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "phi4mf_train_s8192"
CONFIG = "phi-4-mini-flash-reasoning"
KINDS = ["selective_scan", "sliding_attention", "selective_scan",
         "full_attention", "gmu", "cross_attention"]
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "vocab_size": 96, "emb_dim": 64, "num_heads": 8,
            "num_kv_heads": 4, "ssm_width": 128, "ssm_dt_rank": 4,
            "attention_window": 8, "max_len": 64}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference).  The limits the cell is held to are in
# its configuration file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.01, "logprob_abs": 0.12, "grad_rel": 0.08}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 1.0, "grad_rel": 0.5}
NEW_READERS = ["sscan_ms", "sscan_roofline", "mamba1_ms", "gmu_ms",
               "attn_cross_ms", "diff_flash_ms", "diff_flash_roofline",
               "attn_diff_ms"]
SHARED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "peak_hbm_gib",
    "compile_trace_lower_s", "compile_cache_misses", "attn_ms", "mlp_ms",
    "head_ms", "optimizer_ms", "flash_fwd_ms", "flash_bwd_ms",
    "step_trace_s", "step_lower_s", "step_backend_s", "cache_load_s",
    "state_programs_s", "hvd_init_s", "setup_uncovered_s"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE):
    root = make_root(tmp_path)
    add_cell(root, "tiny_phi4", CELL, TINY, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def test_train_runner_phi4flash(tmp_path):
    import run as cli

    line = cli.execute("tiny_phi4", seed=2**31 + 11, seconds=1.0,
                       trace=False,
                       root=_tiny_root(tmp_path, LAX_TOLERANCE),
                       allow_cpu=True)
    json.dumps(line)
    assert line["correct"], line["checks"]
    assert set(line["checks"]) == {
        "losses_finite", "loss_falls", "nothing_built_in_window",
        "matches_reference", "logprob_matches_reference",
        "gradient_matches_reference"}
    assert line["checks"]["logprob_matches_reference"]["labels"] == 2 * 32
    assert line["failed"] == 0 and line["attempted"] >= 3
    for name in ("train_throughput", "step_ms_p90", "setup_s"):
        assert line["metrics"][name]["value"] > 0
    assert line["notes"]["model_flops_per_item"] > 0


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from benchmark.harness import correct, registry
    from benchmark.runners import train

    root = _tiny_root(tmp_path_factory.mktemp("phi4"))
    cell = registry.load_cell("tiny_phi4", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:built.carry_len]),
        built.state[built.carry_len:], steps=8)
    assert float(losses[-1]) < float(losses[0])
    reference = registry.load_reference(cell["config"], root)
    merged = {**config, **built.ran}
    sides = correct.reference_sides(built.program_loss, reference, merged)
    variables = built.variables(tuple(carry))
    return {"sides": sides, "variables": variables, "ran": built.ran,
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran),
            "program_loss": built.program_loss, "reference": reference,
            "config": merged}


def _checks(trained, damage=None, sides=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        sides or trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_and_leaves_its_counts(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    assert ran["shared_readers"] == {"kv": 1, "memory": 1}
    # 2 x 2 sequences' states of 16 x 128 float32 (32 tokens: one block)
    assert ran["sscan_kept_mib"] == 2 * 16 * 128 * 4 / 2 ** 20
    assert (ran["hidden_size"], ran["num_attention_heads"],
            ran["num_key_value_heads"], ran["head_dim"],
            ran["mamba_d_inner"], ran["mamba_dt_rank"],
            ran["sliding_window"], ran["first_layer_index"],
            ran["shared_kv_layer"], ran["memory_layer"]) == (
                64, 8, 4, 8, 128, 4, 8, 14, 3, 2)
    assert ran["layer_types"] == KINDS
    # the reference schedule walks no tiles: nothing counted
    assert set(ran["flash_tiles"]) == {
        "sliding_attention", "full_attention", "cross_attention"}


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("probe", ["last_block_identity", "state_forgets",
                                   "lambda_zero"])
def test_a_damaged_copy_is_not_correct(trained, probe):
    import math

    import numpy as np

    damaged = trained["probes"][probe](trained["variables"])["params"]
    sound = trained["variables"]["params"]
    if probe == "last_block_identity":
        assert float(abs(damaged["block5"]["proj"]["kernel"]).max()) == 0.0
        assert float(abs(damaged["block5"]["fc2"]["kernel"]).max()) == 0.0
        assert float(abs(damaged["block4"]["fc2"]["kernel"]).max()) > 0.0
    elif probe == "state_forgets":
        np.testing.assert_allclose(damaged["block2"]["A_log"],
                                   sound["block2"]["A_log"] + 10.0)
        assert "A_log" not in damaged["block4"]
    else:
        for i in (1, 3, 5):
            blk = damaged[f"block{i}"]
            lam0 = 0.8 - 0.6 * math.exp(-0.3 * (14 + i))
            lam = (math.exp(float(blk["lambda_q1"] @ blk["lambda_k1"]))
                   - math.exp(float(blk["lambda_q2"] @ blk["lambda_k2"]))
                   + lam0)
            assert lam == pytest.approx(0.0, abs=1e-6)
    checks = _checks(trained, trained["probes"][probe])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_references_own(trained):
    assert set(trained["reference"].DEPARTURES) == {
        "lambda_zero", "memory_after_gate", "kv_of_window_layer",
        "window_lifted", "window_in_full_layer"}


@pytest.mark.parametrize("depart", [
    "lambda_zero", "memory_after_gate", "kv_of_window_layer",
    "window_lifted", "window_in_full_layer"])
def test_a_departed_reference_is_not_correct(trained, depart):
    """The sound program against the reference with one fault seeded into
    its mathematics, as ``tools/probe_departures.py`` reads it on the
    chip."""
    from benchmark.harness import correct
    from benchmark.harness.registry import load_module

    tool = load_module(os.path.join(ROOT, "benchmark", "tools",
                                    "probe_departures.py"))
    sides = correct.reference_sides(
        trained["program_loss"],
        tool.departed(trained["reference"], depart), trained["config"])
    checks = _checks(trained, sides=sides)
    assert not all(c["ok"] for c in checks.values()), checks


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_selective_scan_flops_and_bytes_by_hand():
    from benchmark.harness import selective_scan_flops as counts

    # dt A, exp, a h, (dt u) B, +, C h, +
    assert counts.FORWARD_OPS == 7 and counts.BACKWARD_OPS == 14
    assert counts.forward_flops_per_token(5120, 16) == 7 * 5120 * 16
    need_flops, need_bytes = counts.sscan_train_flops_bytes(
        batch=1, seq_len=8192, channels=5120, state=16, layers=2)
    assert need_flops == 21 * 5120 * 16 * 8192 * 2
    # forward u, y (bfloat16), dt (float32), B and C; backward those and
    # dy again, and du, d dt, dB, dC written
    token = ((2 * 5120 * 2 + 5120 * 4 + 2 * 16 * 2)
             + (2 * 5120 * 2 + 5120 * 4 + 2 * 16 * 2)
             + (5120 * 2 + 5120 * 4 + 2 * 16 * 2))
    assert need_bytes == token * 8192 * 2
    # elementwise work against the matmul peak: the memory bounds it
    assert need_bytes / 819e9 > need_flops / 197e12


def test_diff_attn_flops_and_bytes_by_hand():
    from benchmark.harness import diff_attn_flops as counts
    from benchmark.harness.window_flops import visible_pairs

    triangle, band = visible_pairs(8192), visible_pairs(8192, 512)
    assert triangle == 8192 * 8193 // 2 == 33_558_528
    assert band == 512 * 513 // 2 + (8192 - 512) * 512
    # 40 maps, 64 channels of scores and 128 of values a pair
    assert counts.forward_flops(8192, None, 40, 64) == \
        2 * triangle * 40 * (64 + 128)
    need_flops, need_bytes = counts.diff_train_flops_bytes(
        batch=1, seq_len=8192, heads=40, kv_heads=20, head_dim=64,
        windows=[512, None, None])
    # four matmuls over 64 channels and three over 128, seven in all
    assert need_flops == 2 * (band + 2 * triangle) * 40 * (4 * 64 + 3 * 128)
    assert need_bytes == 6 * 8192 * 64 * 2 * (40 + 20) * 3
    assert need_flops / 197e12 > need_bytes / 819e9
    # a window as long as the sequence is the causal triangle
    assert counts.forward_flops(64, 64, 8, 16) == counts.forward_flops(
        64, None, 8, 16)


RAN = {"global_batch": 1, "seq_len": 8192, "num_attention_heads": 40,
       "num_key_value_heads": 20, "head_dim": 64, "sliding_window": 512,
       "mamba_d_inner": 5120, "mamba_d_state": 16, "first_layer_index": 14,
       "layer_types": KINDS}


def test_the_readers_on_a_hand_built_trace():
    from benchmark.harness import diff_attn_flops, selective_scan_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 2e6, step + "block0/ssm/in_proj/dot_general:"],
        ["tpu_custom_call:sscan_fwd.1", 2e6, 2e6,
         step + "block0/ssm/selective_scan/pallas_call:"],
        ["fusion.2", 4e6, 1e6, step + "block1/attn/qkv/dot_general:"],
        ["tpu_custom_call:flash_fwd.1", 5e6, 3e6,
         step + "block1/attn/attn_window/pallas_call:"],
        ["fusion.3", 8e6, 1e6, step + "block1/attn/attn_diff/sub:"],
        ["tpu_custom_call:flash_fwd.2", 9e6, 5e6,
         step + "block3/attn/pallas_call:"],
        ["fusion.4", 14e6, 2e6, step + "block4/gmu/in_proj/dot_general:"],
        ["fusion.5", 16e6, 1e6, step + "block5/attn/attn_cross/concatenate:"],
        ["tpu_custom_call:flash_fwd.3", 17e6, 5e6,
         step + "block5/attn/attn_cross/pallas_call:"],
        ["fusion.6", 22e6, 4e6, step + "block5/mlp/fc1/dot_general:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 40e6, 9e6,
         back + "block5/attn/attn_cross/pallas_call:"],
        ["fusion.7", 49e6, 2e6, back + "block5/attn/attn_diff/mul:"],
        ["fusion.8", 51e6, 3e6, back + "block4/gmu/out_proj/dot_general:"],
        ["tpu_custom_call:flash_bwd_dkdv.2", 54e6, 6e6,
         back + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:sscan_bwd.1", 60e6, 5e6,
         back + "block0/ssm/selective_scan/pallas_call:"],
        ["fusion.9", 65e6, 2e6, back + "block0/ssm/mul:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"sscan_ms": 7.0, "mamba1_ms": 11.0, "gmu_ms": 5.0,
            "attn_cross_ms": 15.0, "diff_flash_ms": 28.0,
            "attn_diff_ms": 3.0, "flash_fwd_ms": 13.0, "flash_bwd_ms": 15.0,
            "attn_ms": 33.0, "mlp_ms": 4.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    need_flops, need_bytes = selective_scan_flops.sscan_train_flops_bytes(
        1, 8192, 5120, 16, 2)
    assert _reader("sscan_roofline").read(run) == pytest.approx(
        100 * (need_bytes / 819e9) / 7e-3)
    assert run["notes"]["sscan_roofline_bound"] == {
        "side": "memory", "seconds": need_bytes / 819e9,
        "flops": need_flops, "bytes": need_bytes}
    need_flops, need_bytes = diff_attn_flops.diff_train_flops_bytes(
        1, 8192, 40, 20, 64, [512, None, None])
    assert _reader("diff_flash_roofline").read(run) == pytest.approx(
        100 * (need_flops / 197e12) / 28e-3)
    assert run["notes"]["diff_flash_roofline_bound"] == {
        "side": "compute", "seconds": need_flops / 197e12,
        "flops": need_flops, "bytes": need_bytes, "layers": 3}
    # a program without the scopes, kernels or sizes (the parent, another
    # family), an untraced run, the CPU: nothing to read, no reader raises
    bare = {"trace": {"ops": {0: [op[:3] + [""] for op in ops
                                  if "sscan" not in op[0]]}, "steps": 1},
            "chips": 1, "peaks": PEAKS,
            "ran": {"global_batch": 1, "seq_len": 8192}}
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None, name
        assert _reader(name).read({**run, "trace": None}) is None, name
    # granite's ``ssm`` scope holds another mixer
    assert _reader("mamba1_ms").read(
        {**run, "ran": {"layer_types": ["mamba", "attention"]}}) is None
    for name in ("sscan_roofline", "diff_flash_roofline"):
        assert _reader(name).read(
            {k: v for k, v in run.items() if k != "peaks"}) is None


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to its mixer
    halves (``made_from`` in the file beside it says how), with what
    plain sums over names and scopes give for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".mixers_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".mixers_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    for name in NEW_READERS + ["attn_ms", "flash_fwd_ms", "flash_bwd_ms"]:
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    assert 0 < expect["sscan_roofline"] < 100
    assert 0 < expect["diff_flash_roofline"] < 100
    assert expect["diff_flash_ms"] == pytest.approx(
        expect["flash_fwd_ms"] + expect["flash_bwd_ms"])
    assert expect["sscan_ms"] < expect["mamba1_ms"]
    assert expect["attn_cross_ms"] + expect["attn_diff_ms"] \
        < expect["attn_ms"]
    ops = run["trace"]["ops"][0]
    scans = [e for e in ops if e[0].startswith("tpu_custom_call:sscan_")]
    assert len(scans) == 2 * 2         # two layers, forward and backward
    cross = [e for e in tr.under(ops, "attn_cross")
             if e[0].startswith("tpu_custom_call:flash_")]
    assert len(cross) == 2             # one layer, one-kernel backward


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s8192_b1",
        "chips": 1, "why": cells[CELL]["why"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
        "blob/main/config.json")
    assert configs[CONFIG]["reduced"] == ["num_hidden_layers", "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_throughput"
        assert by_name[name]["source"] == "device_trace"
    for name in ("sscan_ms", "sscan_roofline", "diff_flash_ms",
                 "diff_flash_roofline"):
        assert by_name[name]["layer"] == "Kernels"
    for name in ("mamba1_ms", "gmu_ms", "attn_cross_ms", "attn_diff_ms"):
        assert by_name[name]["layer"] == "Models"
    for name in ("sscan_roofline", "diff_flash_roofline"):
        assert (by_name[name]["unit"], by_name[name]["better"]) == (
            "%", "higher")
    for name in SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # the readers a test holds to one family's cell, and other families'
    for name in ("ssm_ms", "ssd_ms", "ssd_roofline", "swa_flash_ms",
                 "swa_flash_roofline", "swa_live_tile_share", "flash_ms",
                 "flash_roofline", "attn_gate_ms", "mla_flash_ms",
                 "moe_experts_ms", "mtp_ms", "allreduce_ms"):
        assert CELL not in by_name[name]["workloads"], name
    assert {"gmu", "attn_cross", "attn_diff"} <= set(
        registry.reader_scopes(ROOT))
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == registry.load_cell(
        "granite4hm_train_s8192", ROOT)["params"] == {
        "seq_len": 8192, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}
    assert (cell["runner"], cell["chips"]) == ("train", 1)


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["vocab_size"] == 200064
    assert 8 * config["vocab_size"] == 200064
    # the cut ISSUE 39's rule took: published layers 14-19
    assert config["num_hidden_layers"] == 6
    assert config["layer_types"] == KINDS
    assert "893 728 256" in config["published"]["layers_kept"]
    assert "697 094 272" in config["deployment"]
    assert (config["first_layer_index"], config["shared_kv_layer"],
            config["memory_layer"]) == (14, 3, 2)
    # every number of the catalog's row, under the same key
    for key, value in {
            "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
            "intermediate_size": 10240, "layer_norm_eps": 1e-05,
            "max_position_embeddings": 262144, "mb_per_layer": 2,
            "model_type": "phi4flash", "num_attention_heads": 40,
            "num_key_value_heads": 20, "resid_pdrop": 0,
            "sliding_window": 512, "tie_word_embeddings": True,
            "mlp_bias": False, "lm_head_bias": False}.items():
        assert config[key] == value, key
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_d_inner"],
            config["mamba_dt_rank"], config["head_dim"]) == (
                16, 4, 2, 5120, 160, 64)
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"attribution", "mamba-1 sizes", "differential attention",
            "attention biases", "the memory", "the slots", "the window",
            "no positions", "initialisation", "optimizer",
            "fused matrices"} <= set(config["assumed"])
    assert "pipeline stage" in config["deployment"]
    assert "eight chips" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("phi4flash", ROOT)
    config = {**cell["config_values"], "sliding_window": 1024}
    with pytest.raises(ValueError, match="sliding_window=1024"):
        builder.build(config, cell["params"], seed=0)


def test_model_flops_by_hand():
    from benchmark.harness import registry
    from benchmark.harness.window_flops import visible_pairs

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("phi4flash", ROOT)
    config = cell["config_values"]
    flops = builder.train_flops_per_item(config, {"seq_len": 8192})
    d, w, inner = 2560, 10240, 5120
    ffn = 2 * 3 * d * w
    scan = (2 * d * 2 * inner + 2 * 4 * inner + 2 * inner * 192
            + 2 * 160 * inner + 7 * inner * 16 + 2 * inner * d)
    maps = lambda window: 2 * visible_pairs(8192, window) * 40 * 192 / 8192
    own = 2 * d * 5120 + 2 * d * d
    forward = (6 * ffn + 2 * scan + (2 * d * inner + 2 * inner * d)
               + own + maps(512) + own + maps(None)
               + (2 * d * d + 2 * d * d + maps(None))
               + 2 * d * 25008)
    assert flops == pytest.approx(3 * forward)
    # ISSUE 39 reckons about 49 TF a step of 8192 tokens for eight
    # layers; a memory unit and a cross layer less are 37.6
    assert flops * 8192 == pytest.approx(37.56e12, rel=0.01)
    eight = builder.train_flops_per_item(
        {**config, "layer_types": KINDS + KINDS[4:]}, {"seq_len": 8192})
    assert eight * 8192 == pytest.approx(49e12, rel=0.03)
