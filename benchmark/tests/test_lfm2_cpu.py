"""The cell ``lfm2_train_s32768`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 8 query heads over 2
key/value heads of 8, a dense width of 184, 8 experts of width 32 of
which 2 are held, 2 a token, 32 tokens, the cell's five layers), its
reference checks with the fp8 control, the family's ``fault_probes`` and
the reference's departures, its model FLOPs against a hand count, its new
readers on a hand-built trace and on a recording of the cell's own traced
step, and its entries in ``BENCHMARK.json`` pinned by name.  Nothing
these runs time is a measurement."""

import json
import os
import types

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "lfm2_train_s32768"
CONFIG = "lfm2-24b-a2b"
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 5, "layer_types": KINDS, "dense_layers_first": 1,
            "vocab_size": 256, "emb_dim": 64, "num_heads": 8,
            "num_kv_heads": 2, "mlp_width": 184, "routed_experts": 8,
            "routed_held": 2, "routed_top_k": 2, "routed_width": 32,
            "max_len": 64}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference; three seeds): the sound program's
# gradient 3.6 to 10 % apart and a label's log-probability up to 0.55 (a
# choice of experts is discrete, and at hidden 64 one expert is a large
# part of a token's output); the thinnest damage of the variables,
# experts_silent, 17 to 24 %, fp8 weights 49 to 57 %, the filter without
# its past 127 to 135 %.  The limits the cell is held to are in its
# configuration file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.05, "logprob_abs": 0.9, "grad_rel": 0.14}
# The same program in float32 agrees with the reference to rounding
# (1e-5), so the reference's departures are told from it whatever they
# weigh: the selection bias in the weights reads 1.1 to 5.7 % here, under
# bfloat16's own 3.6 to 10.
FLOAT32_TOLERANCE = {"loss_abs": 1e-3, "logprob_abs": 0.01,
                     "grad_rel": 0.004}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 3.0, "grad_rel": 0.5}
DEPARTURES = ["filter_identity", "filter_sees_next", "gate_b_dropped",
              "gate_c_dropped", "head_norms_dropped", "rope_dropped",
              "bias_in_weights", "weights_unnormalised"]
JOINED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "step_trace_s", "step_lower_s", "step_backend_s",
    "cache_load_s", "state_programs_s", "hvd_init_s", "setup_uncovered_s",
    "peak_hbm_gib", "optimizer_ms", "attn_ms", "mlp_ms", "head_ms",
    "flash_fwd_ms", "flash_bwd_ms", "moe_route_ms", "moe_dispatch_ms",
    "moe_experts_ms", "moe_experts_roofline", "moe_rows_share",
    "moe_overflow_steps"]
NEW_READERS = {
    "short_conv_ms": ("ms", "lower", "Models"),
    "short_conv_filter_ms": ("ms", "lower", "Kernels"),
    "short_conv_filter_roofline": ("%", "higher", "Kernels"),
    "gqa_flash_ms": ("ms", "lower", "Kernels"),
    "gqa_flash_roofline": ("%", "higher", "Kernels")}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE, dtype=None):
    root = make_root(tmp_path)
    params = json.loads(json.dumps(TINY))
    if dtype:
        params["overrides"]["dtype"] = dtype
    add_cell(root, "tiny_lfm2", CELL, params, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_train_runner_lfm2(tmp_path):
    import run as cli

    line = cli.execute("tiny_lfm2", seed=2**31 + 11, seconds=1.0,
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


def _trained(tmp_path, tolerance, dtype=None):
    from benchmark.harness import correct, registry
    from benchmark.runners import train

    root = _tiny_root(tmp_path, tolerance, dtype)
    cell = registry.load_cell("tiny_lfm2", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:built.carry_len]),
        built.state[built.carry_len:], steps=8)
    assert float(losses[-1]) < float(losses[0])
    reference = registry.load_reference(cell["config"], root)
    merged = {**config, **built.ran}
    variables = built.variables(tuple(carry))
    return {"sides": correct.reference_sides(built.program_loss, reference,
                                             merged),
            "program_loss": built.program_loss, "reference": reference,
            "config": merged, "variables": variables, "ran": built.ran,
            "tolerance": tolerance,
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """The cell's program as it is: bfloat16 compute."""
    return _trained(tmp_path_factory.mktemp("lfm2"), TINY_TOLERANCE)


@pytest.fixture(scope="module")
def trained_float32(tmp_path_factory):
    return _trained(tmp_path_factory.mktemp("lfm2_float32"),
                    FLOAT32_TOLERANCE, "float32")


def _checks(trained, damage=None, sides=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        sides or trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, trained["tolerance"])


def test_untouched_program_passes_and_counts_its_rows(trained):
    from benchmark.harness import short_conv_bytes

    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    assert set(trained["variables"]) == {"params", "moe_state"}
    # the four expert layers; layer 0 is dense and counts nothing
    assert set(ran["moe_counters"]) == {f"block{i}" for i in range(1, 5)}
    for entry in ran["moe_counters"].values():
        assert entry["rows_dropped"] == 0
        assert 0 < entry["rows_held"] <= 2 * 32 * 2
    # under the names the readers that are there read their sizes by
    assert (ran["n_routed_experts"], ran["router_width"],
            ran["num_experts_per_tok"], ran["hidden_size"],
            ran["moe_intermediate_size"], ran["num_attention_heads"],
            ran["num_key_value_heads"], ran["head_dim"],
            ran["intermediate_size"], ran["conv_L_cache"]) == (
                2, 8, 2, 64, 32, 8, 2, 8, 184, 3)
    assert ran["layer_types"] == KINDS
    run = {"ran": ran, "chips": 1}
    # 64 tokens x 2 choices x 2 / 8 = 32 rows a layer is an even share
    assert _reader("moe_rows_share").read(run) == pytest.approx(sum(
        e["rows_held"] for e in ran["moe_counters"].values()) / (4 * 32))
    assert _reader("moe_overflow_steps").read(run) == 0
    # the program's own count of its chains' bytes is the harness's
    assert ran["short_conv"] == {
        "layers": 4, "filter_bytes": short_conv_bytes.filter_train_bytes(
            batch=2, seq_len=32, channels=64, layers=4)}
    # the reference schedule walks no tiles and makes no plan
    assert "flash_fwd_kv_resident" not in ran


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


def test_silent_experts_are_not_correct(trained):
    damaged = trained["probes"]["experts_silent"](
        trained["variables"])["params"]
    # the last layer's alone: one expert layer of four has to show
    for i in range(1, 5):
        silent = float(abs(damaged[f"block{i}"]["experts_fc2"]).max()) == 0.0
        assert silent == (i == 4)
        assert float(abs(damaged[f"block{i}"]["experts_fc1"]).max()) > 0.0
    checks = _checks(trained, trained["probes"]["experts_silent"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_a_filter_without_its_past_is_not_correct(trained):
    sound = trained["variables"]["params"]
    damaged = trained["probes"]["filter_past_zero"](
        trained["variables"])["params"]
    for i, kind in enumerate(KINDS):
        if kind != "conv":
            assert "conv_kernel" not in damaged[f"block{i}"]
            continue
        taps = damaged[f"block{i}"]["conv_kernel"]
        assert float(abs(taps[:-1]).max()) == 0.0
        assert (taps[-1] == sound[f"block{i}"]["conv_kernel"][-1]).all()
    checks = _checks(trained, trained["probes"]["filter_past_zero"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_eight_the_issue_names(trained):
    assert list(trained["reference"].DEPARTURES) == DEPARTURES


def test_the_float32_program_is_the_reference_to_rounding(trained_float32):
    checks = _checks(trained_float32)
    assert all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("depart", DEPARTURES)
def test_a_departed_reference_is_not_correct(trained_float32, depart):
    """The sound program against the plain reference with one fault
    seeded into the reference's mathematics."""
    from benchmark.harness import correct

    reference = trained_float32["reference"]
    departed = types.SimpleNamespace(
        loss=lambda c, v, b: reference.loss(c, v, b, depart=depart),
        logprob=lambda c, v, b: reference.logprob(c, v, b, depart=depart))
    sides = correct.reference_sides(trained_float32["program_loss"],
                                    departed, trained_float32["config"])
    checks = _checks(trained_float32, sides=sides)
    assert not all(c["ok"] for c in checks.values()), checks


def test_model_flops_against_a_hand_count():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("lfm2_moe", ROOT)
    config = cell["config_values"]
    ran = {"seq_len": 32768, "router_width": 64}
    flops = builder.train_flops_per_item(config, ran)
    d = 2048
    conv = 2 * (d * 3 * d + d * d)
    attention = 2 * (d * (2048 + 2 * 512) + 2048 * d)
    triangle = 32768 * 32769 // 2
    dense = 2 * 3 * d * 11776
    # four experts a token, an eighth of them held: half an expert of
    # 3 x 2048 x 1536 multiply-adds; the router whole
    routed = 2 * d * 64 + 0.5 * 2 * 3 * d * 1536
    want = 3 * (2 * d * 8192 + 4 * conv + attention
                + 4 * 2048 * triangle / 32768 + dense + 4 * routed)
    assert flops == pytest.approx(want, rel=1e-12)
    assert flops == pytest.approx(1.519e9, rel=0.001)


RAN = {"global_batch": 1, "seq_len": 32768, "num_attention_heads": 32,
       "num_key_value_heads": 8, "head_dim": 64, "hidden_size": 2048,
       "moe_intermediate_size": 1536, "n_routed_experts": 8,
       "router_width": 64, "num_experts_per_tok": 4, "layer_types": KINDS,
       "short_conv": {"layers": 4.0,
                      "filter_bytes": 4.0 * 11 * 32768 * 2048 * 2}}


def test_the_new_readers_on_a_hand_built_trace():
    """A conv block's scope with the chain's inside it, forward and
    backward, beside an attention block's kernels in the streamed and the
    one-kernel form."""
    from benchmark.harness import short_conv_bytes, window_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 3e6, step + "block0/short_conv/in_proj/dot_general:"],
        ["fusion.2", 3e6, 1e6,
         step + "block0/short_conv/short_conv_filter/mul:"],
        ["fusion.3", 4e6, 2e6, step + "block0/short_conv/out_proj/"
         "dot_general:"],
        ["tpu_custom_call:flash_fwd.1", 6e6, 10e6,
         step + "block1/attn/flash_fwd/pallas_call:"],
        ["fusion.4", 16e6, 4e6, step + "block1/mlp/fc1/dot_general:"],
        ["fusion.5", 30e6, 1.5e6,
         back + "block0/short_conv/short_conv_filter/mul:"],
        ["fusion.6", 32e6, 5e6,
         back + "block0/short_conv/in_proj/dot_general:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 40e6, 15e6,
         back + "block1/attn/flash_bwd_dkdv/pallas_call:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"short_conv_ms": 12.5, "short_conv_filter_ms": 2.5,
            "gqa_flash_ms": 25.0, "flash_fwd_ms": 10.0,
            "flash_bwd_ms": 15.0, "attn_ms": 25.0, "mlp_ms": 4.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    need = short_conv_bytes.filter_train_bytes(1, 32768, 2048, 4)
    assert need == 11 * 32768 * 2048 * 2 * 4 == RAN["short_conv"][
        "filter_bytes"]
    assert _reader("short_conv_filter_roofline").read(run) == pytest.approx(
        100 * (need / 819e9) / 2.5e-3)
    assert run["notes"]["short_conv_filter_roofline_bound"][
        "program_counted_bytes"] == need
    flops, nbytes = window_flops.swa_train_flops_bytes(
        1, 32, 8, 32768, 64, None, 1)
    assert flops == 7 * 2 * (32768 * 32769 // 2) * 64 * 32
    assert flops / 197e12 > nbytes / 819e9          # compute bounds it
    assert _reader("gqa_flash_roofline").read(run) == pytest.approx(
        100 * (flops / 197e12) / 25e-3)
    assert run["notes"]["gqa_flash_roofline_bound"]["side"] == "compute"
    # a program without the scopes (the parent, another family): nothing
    # to read, and no reader raises
    bare = {"trace": {"ops": {0: ops[3:5]}, "steps": 1}, "chips": 1,
            "peaks": PEAKS, "ran": {}}
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None, name
    # another family's attention (a window, latent, no grouping): not
    # this reader's
    for other in ({"layer_types": ["sliding_attention", "full_attention"]},
                  {"layer_types": ["mla"]},
                  {"num_key_value_heads": 32}):
        run_other = {**run, "ran": {**RAN, **other}}
        assert _reader("gqa_flash_ms").read(run_other) is None, other
        assert _reader("gqa_flash_roofline").read(run_other) is None
    no_trace = {"ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    for name in NEW_READERS:
        assert _reader(name).read(no_trace) is None, name


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to the attention
    block and the conv block after it (``made_from`` in the file beside
    it says how), with what plain sums over names and scopes give for
    it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".blocks1_2_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".blocks1_2_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    events = run["trace"]["ops"][0]
    assert len(events) == expect["events"]
    for name in ("short_conv_ms", "short_conv_filter_ms", "gqa_flash_ms",
                 "flash_fwd_ms", "flash_bwd_ms", "attn_ms", "mlp_ms",
                 "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    # the chain lies inside the conv block's scope, forward and backward,
    # and outside the attention block's; the backward ran as one kernel
    chain = tr.under(events, "short_conv_filter")
    assert chain and set(map(tuple, chain)) <= set(
        map(tuple, tr.under(events, "short_conv")))
    assert any("transpose(" in tr.scope_of(e) for e in chain)
    assert not [e for e in chain if e in tr.under(events, "attn")]
    names = {e[0].split(".")[0] for e in tr.under(events, "attn")}
    assert {"tpu_custom_call:flash_fwd",
            "tpu_custom_call:flash_bwd_dkdv"} <= names
    assert "tpu_custom_call:flash_bwd_dq" not in names
    # one conv layer's chain of the cell's four, against its bytes
    assert _reader("short_conv_filter_roofline").read(run) == pytest.approx(
        100 * (4 * 11 * 32768 * 2048 * 2 / 819e9)
        / (expect["short_conv_filter_ms"] / 1e3))
    assert 0 < _reader("gqa_flash_roofline").read(run) < 100


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s32768_b1",
        "chips": 1, "why": cells[CELL]["why"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/"
        "config.json")
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name, (unit, better, layer) in NEW_READERS.items():
        new = by_name[name]
        assert new["workloads"] == [CELL] or CELL in new["workloads"], name
        assert (new["unit"], new["better"], new["source"], new["layer"],
                new["moves"]) == (unit, better, "device_trace", layer,
                                  "train_throughput"), name
    for name in JOINED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # flash_ms sums every Pallas call (the grouped matmul is one),
    # flash_roofline asserts as many key heads as query heads; there is
    # no window, gate or balance loss; the other readers are other
    # families'
    for name in ("flash_ms", "flash_roofline", "attn_gate_ms",
                 "swa_flash_ms", "swa_flash_roofline", "swa_live_tile_share",
                 "moe_balance_loss", "mla_flash_ms", "mla_flash_roofline",
                 "mla_proj_ms", "mtp_ms", "ssm_ms", "ssd_ms", "ssd_roofline",
                 "allreduce_ms", "sscan_ms", "diff_flash_ms", "gmu_ms"):
        assert CELL not in by_name[name]["workloads"], name
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == {
        "seq_len": 32768, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}
    assert cell["runner"] == "train" and len(cell["why"]) <= 200
    assert cell["why"] == cells[CELL]["why"]


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["first_held_expert"],
            config["vocab_size"]) == (5, 1, 8, 0, 65536 // 8)
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_dense_layers"],
            published["num_experts"], published["vocab_size"]) == (
                40, 2, 64, 65536)
    assert published["layer_types"] == [
        "full_attention" if i % 4 == 2 else "conv" for i in range(40)]
    # the cut keeps published layer 0 and layers 2-5, one whole period
    assert config["layer_types"] == [published["layer_types"][i]
                                     for i in (0, 2, 3, 4, 5)] == KINDS
    for key, value in {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776, "max_position_embeddings": 128000,
            "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
            "norm_eps": 1e-05, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_experts_per_tok": 4,
            "num_key_value_heads": 8,
            "rope_parameters": {"rope_theta": 1000000,
                                "rope_type": "default"},
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "tie_word_embeddings": True}.items():
        assert config[key] == value, key
    assert config["bias_update_rate"] == 0.01
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"tie_word_embeddings", "block", "in_proj thirds", "head norms",
            "rotary pairing", "dense width", "selection bias", "router",
            "router input", "initialisation", "optimizer"} <= set(
                config["assumed"])
    assert "eight chips" in config["deployment"]
    assert "469 284 992" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    """The published keys of the configuration file against what the
    named size built: a differing width is refused before anything is
    traced."""
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("lfm2_moe", ROOT)
    for key, value in (("intermediate_size", 12288), ("conv_L_cache", 4),
                       ("moe_intermediate_size", 1024)):
        config = {**cell["config_values"], key: value}
        with pytest.raises(ValueError, match=f"{key}={value}"):
            builder.build(config, cell["params"], seed=0)
    config = {**cell["config_values"], "published": {"num_experts": 128}}
    with pytest.raises(ValueError, match="router scores 64 experts"):
        builder.build(config, cell["params"], seed=0)
