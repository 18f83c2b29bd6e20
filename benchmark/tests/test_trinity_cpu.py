"""The cell ``trinitym_train_s8192`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 8 query heads over 2
key/value heads of 16, a window of 8 in 32 tokens, two window layers and
a full one, a dense layer and two expert layers of 16 experts of width 32
of which 4 are held, 3 a token), its reference checks with the fp8
control and the family's three ``fault_probes``, ``window_flops.py``
against a brute-force count, its readers on a hand-built trace and on a
recording of the cell's own traced step, and its entries in
``BENCHMARK.json`` pinned by name.  Nothing these runs time is a
measurement."""

import json
import os

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "trinitym_train_s8192"
KINDS = ["sliding_attention", "sliding_attention", "full_attention"]
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 3, "layer_types": KINDS, "vocab_size": 256,
            "emb_dim": 64, "num_heads": 8, "num_kv_heads": 2,
            "head_size": 16, "attention_window": 8, "mlp_ratio": 3,
            "dense_layers_first": 1, "routed_experts": 16,
            "routed_held": 4, "routed_top_k": 3, "routed_width": 32,
            "max_len": 64, "embedding_multiplier": 8.0}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference): the sound program's gradient 6 to 9 %
# apart, a label's log-probability up to 0.4 (a choice of experts is
# discrete, and at hidden 64 one expert is a large part of a token's
# output).  The controls are told from the sound program by the
# gradient.  The limits the cell is held to are in its configuration
# file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.02, "logprob_abs": 0.9, "grad_rel": 0.15}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 3.0, "grad_rel": 0.5}
NEW_READERS = ["swa_flash_ms", "swa_flash_roofline", "swa_live_tile_share",
               "attn_gate_ms"]
SHARED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "peak_hbm_gib", "optimizer_ms", "attn_ms",
    "mlp_ms", "head_ms", "flash_fwd_ms", "flash_bwd_ms", "moe_route_ms",
    "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
    "moe_rows_share"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE):
    root = make_root(tmp_path)
    add_cell(root, "tiny_trinity", CELL, TINY, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def test_train_runner_trinity(tmp_path):
    import run as cli

    line = cli.execute("tiny_trinity", seed=2**31 + 11, seconds=1.0,
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

    root = _tiny_root(tmp_path_factory.mktemp("trinity"))
    cell = registry.load_cell("tiny_trinity", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:built.carry_len]),
        built.state[built.carry_len:], steps=8)
    assert float(losses[-1]) < float(losses[0])
    sides = correct.reference_sides(
        built.program_loss, registry.load_reference(cell["config"], root),
        {**config, **built.ran})
    variables = built.variables(tuple(carry))
    return {"sides": sides, "variables": variables, "ran": built.ran,
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


def _checks(trained, damage=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_and_counts_its_rows(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    assert set(ran["moe_counters"]) == {"block1", "block2"}
    for entry in ran["moe_counters"].values():
        assert entry["rows_dropped"] == 0
        assert 0 < entry["rows_held"] <= 2 * 32 * 3
    # the names the MoE readers that are there read their sizes by
    assert (ran["n_routed_experts"], ran["router_width"],
            ran["num_experts_per_tok"], ran["hidden_size"],
            ran["moe_intermediate_size"]) == (4, 16, 3, 64, 32)
    share = _reader("moe_rows_share").read({"ran": ran, "chips": 1})
    # 64 tokens x 3 choices x 4 / 16 = 48 rows a layer is an even share
    assert share == pytest.approx(sum(
        e["rows_held"] for e in ran["moe_counters"].values()) / (2 * 48))
    # the reference schedule walks no tiles: nothing counted, no share
    assert _reader("swa_live_tile_share").read(
        {"ran": ran, "chips": 1}) is None


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("probe,zero,kept", [
    ("gate_zero", ("block2", "gate", "kernel"), ("block2", "proj", "kernel")),
    ("experts_silent", ("block2", "experts_fc2"), ("block1", "experts_fc2")),
    ("k_norm_zero", ("block2", "k_norm", "scale"),
     ("block1", "k_norm", "scale")),
])
def test_a_zeroed_leaf_is_not_correct(trained, probe, zero, kept):
    damaged = trained["probes"][probe](trained["variables"])["params"]

    def leaf(path):
        tree = damaged
        for key in path:
            tree = tree[key]
        return float(abs(tree).max())

    assert leaf(zero) == 0.0 and leaf(kept) > 0.0
    checks = _checks(trained, trained["probes"][probe])
    assert not all(c["ok"] for c in checks.values()), checks


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


@pytest.mark.parametrize("seq,window", [
    (1, 1), (7, 1), (7, 3), (7, 7), (7, 9), (64, 16), (33, 32), (8192, 2048),
    (8192, None)])
def test_visible_pairs_against_a_brute_force_count(seq, window):
    from benchmark.harness import window_flops

    if seq > 512:  # the closed form of the band, by rows
        want = sum(min(i + 1, window or seq) for i in range(seq))
    else:
        want = sum(1 for i in range(seq) for j in range(seq)
                   if j <= i and (window is None or i - j < window))
    assert window_flops.visible_pairs(seq, window) == want


def test_window_flops_and_bytes():
    from benchmark.harness import window_flops

    assert window_flops.visible_pairs(8192, 2048) == 14_681_088
    need_flops, need_bytes = window_flops.swa_train_flops_bytes(
        batch=1, heads=32, kv_heads=4, seq_len=8192, head_dim=128,
        window=2048, layers=4)
    assert need_flops == 7 * 2 * 14_681_088 * 128 * 32 * 4
    # six arrays of 8192 x 128 bfloat16 a query head, six a k/v head
    assert need_bytes == 6 * 8192 * 128 * 2 * (32 + 4) * 4
    # a window as long as the sequence is the causal triangle
    assert window_flops.swa_train_flops_bytes(
        1, 2, 2, 64, 16, 64, 1) == window_flops.swa_train_flops_bytes(
        1, 2, 2, 64, 16, None, 1)


RAN = {"global_batch": 1, "seq_len": 8192, "num_attention_heads": 32,
       "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
       "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
       "flash_tiles": {
           "sliding_attention": {"live": 32 * 140.0, "grid": 32 * 512.0},
           "full_attention": {"live": 32 * 272.0, "grid": 32 * 512.0}}}


def test_the_readers_on_a_hand_built_trace():
    from benchmark.harness import window_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 2e6, step + "block1/attn/qkv/dot_general:"],
        ["tpu_custom_call:flash_fwd.1", 2e6, 3e6,
         step + "block1/attn/attn_window/pallas_call:"],
        ["fusion.2", 5e6, 1e6, step + "block1/attn/attn_window/transpose:"],
        ["fusion.3", 6e6, 2e6, step + "block1/attn/attn_gate/gate/"
         "dot_general:"],
        ["fusion.4", 8e6, 1e6, step + "block1/attn/attn_gate/logistic:"],
        ["tpu_custom_call:flash_fwd.2", 9e6, 5e6,
         step + "block4/attn/pallas_call:"],
        ["tpu_custom_call:gmm.1", 14e6, 4e6,
         step + "block1/mlp/moe_experts/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 40e6, 4e6,
         back + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dq.1", 44e6, 2e6,
         back + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dkdv.2", 46e6, 7e6,
         back + "block4/attn/pallas_call:"],
        ["fusion.5", 53e6, 3e6, back + "block1/attn/attn_gate/mul:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"swa_flash_ms": 9.0, "attn_gate_ms": 6.0, "flash_fwd_ms": 8.0,
            "flash_bwd_ms": 13.0, "attn_ms": 30.0, "moe_experts_ms": 4.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    assert _reader("swa_live_tile_share").read(run) == pytest.approx(
        140 / 512)
    need_flops, need_bytes = window_flops.swa_train_flops_bytes(
        1, 32, 4, 8192, 128, 2048, 4)
    assert need_flops / 197e12 > need_bytes / 819e9
    share = _reader("swa_flash_roofline").read(run)
    assert share == pytest.approx(100 * (need_flops / 197e12) / 9e-3)
    assert run["notes"]["swa_flash_roofline_bound"] == {
        "side": "compute", "seconds": need_flops / 197e12,
        "flops": need_flops, "bytes": need_bytes, "layers": 4}
    # a program without the scopes or the counters (the parent, another
    # family), an untraced run, the CPU: nothing to read, no reader raises
    bare = {"trace": {"ops": {0: [op[:3] + [""] for op in ops]},
                      "steps": 1}, "chips": 1, "peaks": PEAKS,
            "ran": {"global_batch": 1, "seq_len": 8192}}
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None, name
        if name != "swa_live_tile_share":
            assert _reader(name).read({**run, "trace": None}) is None, name
    assert _reader("swa_flash_roofline").read(
        {k: v for k, v in run.items() if k != "peaks"}) is None
    assert _reader("swa_live_tile_share").read(
        {**run, "ran": {**RAN, "flash_tiles": {
            "sliding_attention": {"live": 0.0, "grid": 0.0}}}}) is None


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to the attention
    halves of its five blocks (``made_from`` in the file beside it says
    how), with what plain sums over names and scopes give for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".attn_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".attn_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    for name in ("swa_flash_ms", "attn_gate_ms", "flash_fwd_ms",
                 "flash_bwd_ms", "attn_ms", "swa_flash_roofline"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    # the window layers' kernels are four of the five layers' and less
    # than four fifths of their time: a banded call is the cheaper one
    both = expect["flash_fwd_ms"] + expect["flash_bwd_ms"]
    assert 0 < expect["swa_flash_ms"] < 0.8 * both
    assert 0 < expect["swa_flash_roofline"] < 100
    kernels = [e for e in tr.under(run["trace"]["ops"][0], "attn_window")
               if e[0].startswith("tpu_custom_call:flash_")]
    assert len(kernels) == 4 * 2   # forward and one-kernel backward


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "trinity-mini",
        "traffic": "train_s8192_b1", "chips": 1, "why": cells[CELL]["why"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs["trinity-mini"]["file"] == \
        "benchmark/configs/trinity-mini.json"
    assert configs["trinity-mini"]["source"] == (
        "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json")
    assert configs["trinity-mini"]["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_throughput"
    assert by_name["swa_live_tile_share"]["source"] == "program_counter"
    assert by_name["swa_live_tile_share"]["better"] == "higher"
    assert by_name["swa_flash_roofline"]["unit"] == "%"
    assert by_name["attn_gate_ms"]["layer"] == "Models"
    for name in SHARED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # flash_ms sums every Pallas call (the grouped matmul is one),
    # flash_roofline asserts head size n_embd // n_head and no window;
    # the latent-attention, prediction-module and Mamba readers are
    # other families'
    for name in ("flash_ms", "flash_roofline", "mla_flash_ms",
                 "mla_flash_roofline", "mla_proj_ms", "mtp_ms", "ssm_ms",
                 "ssd_ms", "ssd_roofline", "allreduce_ms"):
        assert CELL not in by_name[name]["workloads"], name
    assert {"attn_window", "attn_gate"} <= set(registry.reader_scopes(ROOT))
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == {
        "seq_len": 8192, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini.json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 16, 25024)
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    published = config["published"]
    assert {k: v for k, v in published.items() if k != "layer_types"} == {
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "vocab_size": 200192}
    assert published["layer_types"] == [
        "full_attention" if i % 4 == 3 else "sliding_attention"
        for i in range(32)]
    # the cut keeps published layer 0 and layers 4-7, one whole period
    assert config["layer_types"] == (published["layer_types"][:1]
                                     + published["layer_types"][4:8])
    assert 8 * config["vocab_size"] == 200192
    for key, value in {
            "global_attn_every_n_layers": 4, "head_dim": 128,
            "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 6144, "load_balance_coeff": 0.001,
            "max_position_embeddings": 131072, "model_type": "afmoe",
            "moe_intermediate_size": 1024, "mup_enabled": True,
            "n_group": 1, "num_attention_heads": 32,
            "num_expert_groups": 1, "num_experts_per_tok": 8,
            "num_key_value_heads": 4, "num_limited_groups": 1,
            "num_shared_experts": 1, "rms_norm_eps": 1e-05,
            "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
            "route_scale": 2.826, "score_func": "sigmoid",
            "sliding_window": 2048, "tie_word_embeddings": False,
            "topk_group": 1, "use_grouped_mm": True}.items():
        assert config[key] == value, key
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"output gate", "head norms", "four norms a block",
            "rotary in the window layers only", "embedding multiplier",
            "rotary pairing", "selection bias", "fused q, k and v"} <= set(
                config["assumed"])
    assert "eight chips" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    """The published keys of the configuration file against what the
    named size built: a differing width is refused before anything is
    traced."""
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("afmoe", ROOT)
    config = {**cell["config_values"], "sliding_window": 1024}
    with pytest.raises(ValueError, match="sliding_window=1024"):
        builder.build(config, cell["params"], seed=0)


def test_model_flops_count_the_band_as_a_band():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("afmoe", ROOT)
    config = cell["config_values"]
    ran = {"seq_len": 8192, "router_width": 128}
    flops = builder.train_flops_per_item(config, ran)
    # ISSUE 34: about 2.2 GFLOP a token with the backward
    assert flops == pytest.approx(2.214e9, rel=0.01)
    # with every layer full the scores alone would add (4096.5 - 1792.1
    # keys a token) x 4 x 4096 x 3 a window layer
    full = builder.train_flops_per_item(
        {**config, "layer_types": ["full_attention"] * 5}, ran)
    assert full - flops == pytest.approx(
        4 * 3 * 4 * 4096 * (8193 / 2 - 14_681_088 / 8192))
