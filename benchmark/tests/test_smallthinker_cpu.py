"""The cell ``smallthinker_train_s16384`` on the CPU at a tiny size:
through ``run.py``'s entry with ``overrides`` (hidden 64, 7 query heads
over 1 key/value head of 16, a window of 8 in 32 tokens, one full layer
and three window layers, 8 experts of width 32 of which 2 are held, 3 a
token), its reference checks with the fp8 control, the family's
``fault_probes`` and the reference's departures, its model FLOPs against
a hand count, its readers on a hand-built trace and on a recording of the
cell's own traced step, and its entries in ``BENCHMARK.json`` pinned by
name.  Nothing these runs time is a measurement."""

import json
import os
import types

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "smallthinker_train_s16384"
CONFIG = "smallthinker-21ba3b-instruct"
KINDS = ["full_attention"] + ["sliding_attention"] * 3
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 4, "layer_types": KINDS, "vocab_size": 256,
            "emb_dim": 64, "num_heads": 7, "num_kv_heads": 1,
            "head_size": 16, "attention_window": 8, "routed_experts": 8,
            "routed_held": 2, "routed_top_k": 3, "routed_width": 32,
            "max_len": 64}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference, the token table at the configuration
# file's scale; three seeds): the sound program's gradient 5.5 to 7.3 %
# apart, a label's log-probability up to 0.36 (a choice of experts is
# discrete, and at hidden 64 one expert is a large part of a token's
# output); the thinnest controls, fp8 weights 11.7 to 15.3 % and the
# silu gate 16.9 to 20.2 %.  The controls are told from the sound
# program by the gradient.  The limits the cell is held to are in its
# configuration file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.02, "logprob_abs": 0.9, "grad_rel": 0.095}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 3.0, "grad_rel": 0.5}
JOINED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "step_trace_s", "step_lower_s", "step_backend_s",
    "cache_load_s", "state_programs_s", "hvd_init_s", "setup_uncovered_s",
    "peak_hbm_gib", "optimizer_ms", "attn_ms", "mlp_ms", "head_ms",
    "flash_fwd_ms", "flash_bwd_ms", "moe_route_ms", "moe_dispatch_ms",
    "moe_experts_ms", "moe_experts_roofline", "moe_rows_share",
    "moe_overflow_steps", "swa_flash_ms", "swa_flash_roofline",
    "swa_live_tile_share"]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE):
    root = make_root(tmp_path)
    add_cell(root, "tiny_smallthinker", CELL, TINY, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_train_runner_smallthinker(tmp_path):
    import run as cli

    line = cli.execute("tiny_smallthinker", seed=2**31 + 11, seconds=1.0,
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

    root = _tiny_root(tmp_path_factory.mktemp("smallthinker"))
    cell = registry.load_cell("tiny_smallthinker", root)
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
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


def _checks(trained, damage=None, sides=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        sides or trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_and_counts_its_rows(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    assert set(trained["variables"]) == {"params"}     # no selection bias
    assert set(ran["moe_counters"]) == {f"block{i}" for i in range(4)}
    for entry in ran["moe_counters"].values():
        assert entry["rows_dropped"] == 0
        assert 0 < entry["rows_held"] <= 2 * 32 * 3
        assert 0.5 < entry["balance_loss"] < 8.0
    # under the names the readers that are there read their sizes by
    assert (ran["n_routed_experts"], ran["router_width"],
            ran["num_experts_per_tok"], ran["hidden_size"],
            ran["moe_intermediate_size"], ran["sliding_window"],
            ran["num_attention_heads"], ran["num_key_value_heads"],
            ran["head_dim"]) == (2, 8, 3, 64, 32, 8, 7, 1, 16)
    assert ran["layer_types"] == KINDS
    assert ran["sliding_window_layout"] == ran["rope_layout"] == [0, 1, 1, 1]
    run = {"ran": ran, "chips": 1}
    # 64 tokens x 3 choices x 2 / 8 = 48 rows a layer is an even share
    assert _reader("moe_rows_share").read(run) == pytest.approx(sum(
        e["rows_held"] for e in ran["moe_counters"].values()) / (4 * 48))
    assert _reader("moe_overflow_steps").read(run) == 0
    assert _reader("moe_balance_loss").read(run) == pytest.approx(sum(
        e["balance_loss"] for e in ran["moe_counters"].values()) / 4)
    # the reference schedule walks no tiles: nothing counted, no share
    assert _reader("swa_live_tile_share").read(run) is None


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


def test_silent_experts_are_not_correct(trained):
    damaged = trained["probes"]["experts_silent"](
        trained["variables"])["params"]
    # the last layer's alone: one layer of four has to show
    for i in range(4):
        silent = float(abs(damaged[f"block{i}"]["experts_fc2"]).max()) == 0.0
        assert silent == (i == 3)
        assert float(abs(damaged[f"block{i}"]["experts_fc1"]).max()) > 0.0
    checks = _checks(trained, trained["probes"]["experts_silent"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_five_the_issue_names(trained):
    assert set(trained["reference"].DEPARTURES) == {
        "router_after_attention", "silu_gate", "softmax_over_all",
        "rope_in_full_layer", "window_ignored"}


@pytest.mark.parametrize("depart", [
    "router_after_attention", "silu_gate", "softmax_over_all",
    "rope_in_full_layer", "window_ignored"])
def test_a_departed_reference_is_not_correct(trained, depart):
    """The sound program against the plain reference with one fault
    seeded into the reference's mathematics: the late router changes only
    which experts are chosen, and still fails."""
    from benchmark.harness import correct

    reference = trained["reference"]
    departed = types.SimpleNamespace(
        loss=lambda c, v, b: reference.loss(c, v, b, depart=depart),
        logprob=lambda c, v, b: reference.logprob(c, v, b, depart=depart))
    sides = correct.reference_sides(trained["program_loss"], departed,
                                    trained["config"])
    checks = _checks(trained, sides=sides)
    assert not all(c["ok"] for c in checks.values()), checks


def test_model_flops_against_a_hand_count():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("smallthinker", ROOT)
    config = cell["config_values"]
    ran = {"seq_len": 16384, "router_width": 64,
           "layer_types": builder.layer_types(config)}
    flops = builder.train_flops_per_item(config, ran)
    d, q_dim, kv_dim = 2560, 28 * 128, 4 * 128
    projections = 2 * (d * (q_dim + 2 * kv_dim) + q_dim * d)
    band = 4096 * 4097 // 2 + (16384 - 4096) * 4096       # visible pairs
    triangle = 16384 * 16385 // 2
    # six experts a token, a quarter of them held: 1.5 experts of
    # 3 x 2560 x 768 multiply-adds; the router whole
    routed = 2 * d * 64 + 1.5 * 2 * 3 * d * 768
    want = 3 * (2 * d * config["vocab_size"]
                + 4 * (projections + routed)
                + 4 * q_dim * (triangle + 3 * band) / 16384)
    assert flops == pytest.approx(want, rel=1e-12)
    assert band == 58_722_304 and flops == pytest.approx(2.118e9, rel=0.01)
    # with every layer full the scores alone would add
    full = builder.train_flops_per_item(config, {
        **ran, "layer_types": ["full_attention"] * 4})
    assert full - flops == pytest.approx(
        3 * 3 * 4 * q_dim * (triangle - band) / 16384)


RAN = {"global_batch": 1, "seq_len": 16384, "num_attention_heads": 28,
       "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 4096,
       "hidden_size": 2560, "moe_intermediate_size": 768,
       "n_routed_experts": 16, "router_width": 64, "num_experts_per_tok": 6,
       "layer_types": KINDS,
       "flash_tiles": {
           "sliding_attention": {"live": 28 * 504.0, "grid": 28 * 2048.0},
           "full_attention": {"live": 28 * 1056.0, "grid": 28 * 2048.0}},
       "moe_counters": {
           f"block{i}": {"rows_held": rows, "max_over_mean": 1.5,
                         "rows_dropped": 0, "overflow_steps": over,
                         "balance_loss": balance}
           for i, (rows, over, balance) in enumerate([
               (24576, 0, 1.0), (30000, 2, 1.1), (50000, 5, 1.5),
               (20000, 0, 1.2)])}}


def test_the_readers_on_a_hand_built_trace():
    """The router's scope at the block's top (not inside ``mlp``), the
    balance loss inside it, the two-pass backward's two kernel names under
    the window scope and outside it."""
    from benchmark.harness import moe_flops, window_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 2e6, step + "block1/moe_route/dot_general:"],
        ["fusion.2", 2e6, 1e6, step + "block1/moe_route/moe_balance/"
         "reduce_sum:"],
        ["sort.1", 3e6, 3e6, step + "block1/moe_route/sort:"],
        ["tpu_custom_call:flash_fwd.1", 6e6, 3e6,
         step + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:flash_fwd.2", 9e6, 5e6,
         step + "block0/attn/pallas_call:"],
        ["fusion.3", 14e6, 2e6,
         step + "block1/mlp/jit(_forward)/moe_dispatch/gather:"],
        ["tpu_custom_call:gmm.1", 16e6, 4e6,
         step + "block1/mlp/jit(_forward)/moe_experts/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 40e6, 4e6,
         back + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dq.1", 44e6, 2e6,
         back + "block1/attn/attn_window/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dkdv.2", 46e6, 7e6,
         back + "block0/attn/pallas_call:"],
        ["tpu_custom_call:flash_bwd_dq.2", 53e6, 5e6,
         back + "block0/attn/pallas_call:"],
        ["fusion.4", 58e6, 1e6, back + "block1/moe_route/dot_general:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"moe_route_ms": 7.0, "moe_dispatch_ms": 2.0,
            "moe_experts_ms": 4.0, "mlp_ms": 6.0, "swa_flash_ms": 9.0,
            "flash_fwd_ms": 8.0, "flash_bwd_ms": 18.0, "attn_ms": 26.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    assert _reader("swa_live_tile_share").read(run) == pytest.approx(
        504 / 2048)
    assert _reader("moe_overflow_steps").read(run) == 7
    assert _reader("moe_balance_loss").read(run) == pytest.approx(1.2)
    # an even share is 16384 x 6 x 16 / 64 = 24576 rows a layer
    assert _reader("moe_rows_share").read(run) == pytest.approx(
        124576 / (4 * 24576))
    need_flops, need_bytes = window_flops.swa_train_flops_bytes(
        1, 28, 4, 16384, 128, 4096, 3)
    assert need_flops == 7 * 2 * 58_722_304 * 128 * 28 * 3
    assert _reader("swa_flash_roofline").read(run) == pytest.approx(
        100 * (need_flops / 197e12) / 9e-3)
    # a ReLU is no matmul: the experts' count is the rows' alone
    exp_flops, exp_bytes = moe_flops.experts_train_flops_bytes(
        rows=124576, hidden=2560, width=768, held=16, layers=4)
    assert exp_flops == 3 * 2 * 3 * 2560 * 768 * 124576
    assert _reader("moe_experts_roofline").read(run) == pytest.approx(
        100 * max(exp_flops / 197e12, exp_bytes / 819e9) / 4e-3)
    # a program without the counter (the parent, another family): nothing
    # to read, and the reader does not raise
    reader = _reader("moe_balance_loss")
    assert reader.read({"ran": {}, "chips": 1}) is None
    assert reader.read({"ran": {"moe_counters": {}}, "chips": 1}) is None
    without = {name: {k: v for k, v in entry.items() if k != "balance_loss"}
               for name, entry in RAN["moe_counters"].items()}
    assert reader.read({"ran": {"moe_counters": without},
                        "chips": 1}) is None


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to one window
    layer's block (``made_from`` in the file beside it says how), with
    what plain sums over names and scopes give for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".block1_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".block1_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": {**RAN, "moe_counters": expect["moe_counters"]},
           "chips": 1, "peaks": PEAKS}
    for name in ("moe_route_ms", "moe_dispatch_ms", "moe_experts_ms",
                 "mlp_ms", "attn_ms", "swa_flash_ms", "flash_fwd_ms",
                 "flash_bwd_ms"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    assert _reader("moe_balance_loss").read(run) == pytest.approx(
        expect["moe_balance_loss"])
    events = run["trace"]["ops"][0]
    # the router's events lie outside the block's mlp half, the balance
    # loss's inside the router's, and the backward ran as two kernels
    route = tr.under(events, "moe_route")
    assert route and not [e for e in route if e in tr.under(events, "mlp")]
    assert set(map(tuple, tr.under(events, "moe_balance"))) <= set(
        map(tuple, route))
    names = {e[0].split(".")[0] for e in tr.under(events, "attn_window")}
    assert {"tpu_custom_call:flash_fwd", "tpu_custom_call:flash_bwd_dkdv",
            "tpu_custom_call:flash_bwd_dq"} <= names


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "train_s16384_b1",
        "chips": 1, "why": cells[CELL]["why"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs[CONFIG]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert configs[CONFIG]["source"] == (
        "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
        "blob/main/config.json")
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    new = by_name["moe_balance_loss"]
    assert CELL in new["workloads"]
    assert (new["unit"], new["better"], new["source"], new["layer"],
            new["moves"]) == ("ratio", "lower", "program_counter", "Models",
                              "train_throughput")
    for name in JOINED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # flash_ms sums every Pallas call (the grouped matmul is one),
    # flash_roofline asserts head size n_embd // n_head and no window;
    # there is no output gate; the other readers are other families'
    for name in ("flash_ms", "flash_roofline", "attn_gate_ms",
                 "mla_flash_ms", "mla_flash_roofline", "mla_proj_ms",
                 "mtp_ms", "ssm_ms", "ssd_ms", "ssd_roofline",
                 "allreduce_ms", "sscan_ms", "diff_flash_ms", "gmu_ms"):
        assert CELL not in by_name[name]["workloads"], name
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == {
        "seq_len": 16384, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}
    assert cell["runner"] == "train" and len(cell["why"]) <= 200


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["first_held_expert"]) == (4, 16, 0)
    published = config["published"]
    assert (published["num_hidden_layers"],
            published["moe_num_primary_experts"],
            published["vocab_size"]) == (52, 64, 151936)
    assert published["sliding_window_layout"] == published["rope_layout"] \
        == [0, 1, 1, 1] * 13
    # the cut keeps published layers 0-3, the first whole period
    assert config["sliding_window_layout"] == config["rope_layout"] == \
        published["sliding_window_layout"][:4]
    # a quarter of the vocabulary, or ISSUE 43's fallback of an eighth
    assert config["vocab_size"] in (151936 // 4, 151936 // 8)
    for key, value in {
            "head_dim": 128, "hidden_size": 2560,
            "max_position_embeddings": 16384,
            "model_name": "smallthinker_21b_instruct",
            "moe_ffn_hidden_size": 768,
            "moe_num_active_primary_experts": 6,
            "moe_primary_router_apply_softmax": True,
            "norm_topk_prob": True, "num_attention_heads": 28,
            "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
            "rope_scaling": None, "rope_theta": 1500000,
            "sliding_window_size": 4096,
            "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    assert config["balance_loss_coef"] in (0.001, 0.01, 0.1)
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"router input", "balance loss", "rotary pairing",
            "attention biases and head norms", "window",
            "secondary experts"} <= set(config["assumed"])
    assert "four chips" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    """The published keys of the configuration file against what the
    named size built, and the two layouts against each other: a differing
    width is refused before anything is traced."""
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("smallthinker", ROOT)
    config = {**cell["config_values"], "sliding_window_size": 2048}
    with pytest.raises(ValueError, match="sliding_window_size=2048"):
        builder.build(config, cell["params"], seed=0)
    config = {**cell["config_values"], "rope_layout": [1, 1, 1, 1]}
    with pytest.raises(ValueError, match="rotates exactly the window"):
        builder.build(config, cell["params"], seed=0)


@pytest.mark.parametrize("stated", [1.0, 0.25])
def test_the_token_table_is_drawn_at_the_stated_scale(stated):
    """``embedding_init_std`` of the configuration file is the standard
    deviation of the table's channels in the seeded state: flax's draw
    (``hidden ** -0.5``) scaled; nothing else of the state moves with
    it."""
    import jax
    import numpy as np

    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("smallthinker", ROOT)
    params = {**cell["params"], **TINY}
    hidden = TINY["overrides"]["emb_dim"]
    flax = {**cell["config_values"], "embedding_init_std": hidden ** -0.5}
    base = builder.build(flax, params, seed=11).state[0]["params"]
    got = builder.build({**flax, "embedding_init_std": stated}, params,
                        seed=11).state[0]["params"]
    table = np.asarray(got["wte"]["embedding"])
    assert abs(table.std() / stated - 1) < 0.05
    np.testing.assert_allclose(
        table, np.asarray(base["wte"]["embedding"]) * stated * hidden ** 0.5,
        rtol=1e-6)
    rest = lambda p: {k: v for k, v in p.items() if k != "wte"}
    for a, b in zip(jax.tree.leaves(rest(got)), jax.tree.leaves(rest(base))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
