"""The cell ``nemotron3n_train_s16384`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 8 Mamba heads of 8 in 4
groups at a chunk of 16, 4 query heads over 2 key/value heads of 16, 16
ungated experts of width 32 of which 4 are held, 3 a token, a shared
expert of 64, 32 tokens, the three kinds of layer in the published order
``MEM*E``), its reference checks with the fp8 control, the family's
``fault_probes`` and the reference's departures, its model FLOPs and the
ungated experts' operations against a hand count, its new readers on a
hand-built trace and on a recording of the cell's own traced step, and
its entries in ``BENCHMARK.json`` pinned by name.  Nothing these runs
time is a measurement."""

import json
import os
import types

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "nemotron3n_train_s16384"
CONFIG = "nvidia-nemotron-3-nano-30b-a3b-bf16"
PATTERN = "MEM*E"
KINDS = ["mamba", "feed_forward", "mamba", "full_attention", "feed_forward"]
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 5, "layer_types": KINDS, "vocab_size": 256,
            "emb_dim": 64, "num_heads": 4, "num_kv_heads": 2,
            "head_size": 16, "ssm_heads": 8, "ssm_head_dim": 8,
            "ssm_state": 16, "ssm_groups": 4, "ssm_chunk": 16,
            "mlp_width": 48, "routed_experts": 16, "routed_held": 4,
            "routed_top_k": 3, "routed_width": 32, "shared_width": 64,
            "max_len": 128}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference, the fixture's seed): the sound
# program's gradient 2.7 % apart, a label's log-probability up to 0.034
# and the loss 4e-4; the thinnest control, fp8 weights, 38 %, 1.29 and
# 0.032; the state that forgets 41 %, experts_silent 48 %, a Mamba layer
# turned identity 81 %.  The gradient's limit is the geometric middle
# of the first two.  The limits the cell is held to are in its
# configuration file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.004, "logprob_abs": 0.2, "grad_rel": 0.1}
# The same program in float32 agrees with the reference to rounding, so
# the reference's departures are told from it whatever they weigh.
FLOAT32_TOLERANCE = {"loss_abs": 1e-4, "logprob_abs": 1e-3,
                     "grad_rel": 1e-3}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.2, "logprob_abs": 4.0, "grad_rel": 0.8}
DEPARTURES = ["experts_gated", "relu_not_squared", "norm_one_group",
              "norm_before_gate", "groups_one", "shared_expert_dropped",
              "shared_width_routed", "bias_in_weights", "scaling_dropped",
              "attention_rotated", "conv_bias_dropped", "skip_D_dropped",
              "second_half_added", "state_bfloat16"]
JOINED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "step_trace_s", "step_lower_s", "step_backend_s",
    "cache_load_s", "state_programs_s", "hvd_init_s", "setup_uncovered_s",
    "peak_hbm_gib", "optimizer_ms", "attn_ms", "mlp_ms", "head_ms",
    "flash_fwd_ms", "flash_bwd_ms", "flash_live_tile_share", "ssm_ms",
    "ssd_ms", "ssd_roofline", "gqa_flash_ms", "gqa_flash_roofline",
    "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms", "moe_rows_share",
    "moe_overflow_steps", "moe_logits_ms", "moe_topk_ms", "moe_sort_ms",
    "moe_unsort_ms", "moe_rows_in_ms", "moe_rows_out_ms", "moe_cast_ms",
    "moe_gate_ms", "moe_live_row_share", "moe_gmm_tile_fill",
    "remat_kept_share"]
NEW_READERS = {
    "ungated_experts_roofline": ("%", "higher", "device_trace", "Kernels"),
    "moe_shared_ms": ("ms", "lower", "device_trace", "Models"),
    "ssm_norm_ms": ("ms", "lower", "device_trace", "Models"),
    "ssd_kept_mib": ("MiB", "lower", "program_counter", "Kernels")}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE, dtype=None):
    root = make_root(tmp_path)
    params = json.loads(json.dumps(TINY))
    if dtype:
        params["overrides"]["dtype"] = dtype
    add_cell(root, "tiny_nemotron", CELL, params, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_train_runner_nemotron_h(tmp_path):
    import run as cli

    line = cli.execute("tiny_nemotron", seed=2**31 + 11, seconds=1.0,
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
    cell = registry.load_cell("tiny_nemotron", root)
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
    return _trained(tmp_path_factory.mktemp("nemotron"), TINY_TOLERANCE)


@pytest.fixture(scope="module")
def trained_float32(tmp_path_factory):
    return _trained(tmp_path_factory.mktemp("nemotron_float32"),
                    FLOAT32_TOLERANCE, "float32")


def _checks(trained, damage=None, sides=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        sides or trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, trained["tolerance"])


def test_untouched_program_passes_and_counts_its_rows(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    ran = trained["ran"]
    assert set(trained["variables"]) == {"params", "moe_state"}
    # the two expert layers; a mixer layer counts nothing
    assert set(ran["moe_counters"]) == {"block1", "block4"}
    for entry in ran["moe_counters"].values():
        assert entry["rows_dropped"] == 0
        assert 0 < entry["rows_held"] <= 2 * 32 * 3
    # under the names the readers that are there read their sizes by
    assert (ran["n_routed_experts"], ran["router_width"],
            ran["num_experts_per_tok"], ran["hidden_size"],
            ran["moe_intermediate_size"], ran["num_attention_heads"],
            ran["num_key_value_heads"], ran["head_dim"],
            ran["mamba_n_heads"], ran["mamba_d_head"],
            ran["mamba_n_groups"], ran["mamba_d_state"], ran["ssd_chunk"],
            ran["moe_shared_expert_intermediate_size"],
            ran["experts_gated"]) == (
                4, 16, 3, 64, 32, 4, 2, 16, 8, 8, 4, 16, 16, 64, False)
    assert ran["layer_types"] == KINDS
    assert ran["hybrid_override_pattern"] == PATTERN
    run = {"ran": ran, "chips": 1}
    # 64 tokens x 3 choices x 4 / 16 = 48 rows a layer is an even share
    assert _reader("moe_rows_share").read(run) == pytest.approx(sum(
        e["rows_held"] for e in ran["moe_counters"].values()) / (2 * 48))
    assert _reader("moe_overflow_steps").read(run) == 0
    # what the model counted while the step was traced: y in bfloat16 and
    # two chunk-start states of 8 x 8 x 16 float32 a sequence
    assert ran["ssd"] == {
        "groups": 4, "chunk": 16,
        "kept_mib": 2 * 8 * 8 * (32 * 2 + 2 * 16 * 4) / 2 ** 20}
    assert _reader("ssd_kept_mib").read(run) == ran["ssd"]["kept_mib"]


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


def test_silent_experts_are_not_correct(trained):
    damaged = trained["probes"]["experts_silent"](
        trained["variables"])["params"]
    # the last layer's alone: one expert layer of two has to show
    for i in (1, 4):
        silent = float(abs(damaged[f"block{i}"]["experts_fc2"]).max()) == 0.0
        assert silent == (i == 4)
        assert float(abs(damaged[f"block{i}"]["experts_fc1"]).max()) > 0.0
    checks = _checks(trained, trained["probes"]["experts_silent"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_a_state_that_forgets_is_not_correct(trained):
    sound = trained["variables"]["params"]
    damaged = trained["probes"]["state_forgets"](
        trained["variables"])["params"]
    for i, kind in enumerate(KINDS):
        if kind != "mamba":
            assert "A_log" not in damaged[f"block{i}"]
            continue
        assert float((damaged[f"block{i}"]["A_log"]
                      - sound[f"block{i}"]["A_log"]).min()) == 10.0
    checks = _checks(trained, trained["probes"]["state_forgets"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_a_mamba_layer_turned_identity_is_not_correct(trained):
    damaged = trained["probes"]["mamba_identity"](
        trained["variables"])["params"]
    # the last Mamba layer's alone
    for i in (0, 2):
        zero = float(abs(damaged[f"block{i}"]["out_proj"]["kernel"]).max())
        assert (zero == 0.0) == (i == 2)
    checks = _checks(trained, trained["probes"]["mamba_identity"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_ones_the_issue_names(trained):
    # ISSUE 61's thirteen, and the scan's state and decays held in
    # bfloat16 (the precision below the one stated)
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
    builder = registry.load_model_builder("nemotron_h", ROOT)
    config = cell["config_values"]
    ran = {"seq_len": 16384, "router_width": 128, "ssd_chunk": 128}
    flops = builder.train_flops_per_item(config, ran)
    d, inner = 2688, 4096
    # a token of one layer's scan at a chunk of 128: C B^T's causal half
    # a group, its product with x's a head, the state a chunk adds and the
    # read-out of the one it starts from
    scan = 8 * 128 * 128 / 2 + 64 * (128 * 64 / 2 + 2 * 128 * 64)
    mamba = 2 * d * (2 * inner + 2 * 8 * 128 + 64) + 2 * inner * d + 2 * scan
    triangle = 16384 * 16385 // 2
    attention = (2 * d * (4096 + 2 * 256) + 2 * 4096 * d
                 + 2 * 2 * 4096 * triangle / 16384)
    # six experts a token, a sixteenth of them held: three eighths of an
    # expert of 2 x 2688 x 1856 multiply-adds, the shared one of twice
    # the width whole; the router whole
    experts = (2 * d * 128 + 2 * 0.375 * 2 * d * 1856 + 2 * 2 * d * 3712)
    want = 3 * (2 * d * 16384 + 4 * mamba + attention + 4 * experts)
    assert flops == pytest.approx(want, rel=1e-12)
    assert flops == pytest.approx(2.346e9, rel=0.001)


def test_the_ungated_experts_operations_and_bytes_against_a_hand_count():
    from benchmark.harness import moe_flops, ungated_expert_flops

    rows = 4 * 6144  # four layers at an even share of 16384 x 6 x 8 / 128
    flops, nbytes = ungated_expert_flops.experts_train_flops_bytes(
        rows=rows, hidden=2688, width=1856, held=8, layers=4)
    assert flops == 3 * 2 * 2 * 2688 * 1856 * rows
    # a row, its output, their two gradients and the row's gradient in
    # bfloat16; two matrices an expert read twice and written once
    assert nbytes == 5 * 2688 * 2 * rows + 3 * 4 * 8 * 2 * 2688 * 1856 * 2
    gated = moe_flops.experts_train_flops_bytes(
        rows=rows, hidden=2688, width=1856, held=8, layers=4)
    assert gated[0] == 1.5 * flops      # what moe_experts_roofline counts


RAN = {"global_batch": 1, "seq_len": 16384, "num_attention_heads": 32,
       "num_key_value_heads": 2, "head_dim": 128, "hidden_size": 2688,
       "moe_intermediate_size": 1856, "n_routed_experts": 8,
       "router_width": 128, "num_experts_per_tok": 6,
       "experts_gated": False, "mamba_n_heads": 64, "mamba_d_head": 64,
       "mamba_n_groups": 8, "mamba_d_state": 128, "ssd_chunk": 128,
       "layer_types": ["mamba", "feed_forward", "mamba", "feed_forward",
                       "mamba", "full_attention", "feed_forward", "mamba",
                       "feed_forward"],
       "ssd": {"groups": 8.0, "chunk": 128.0, "kept_mib": 384.0},
       "moe_counters": {f"block{i}": {"rows_held": 6144, "rows_dropped": 0,
                                      "overflow_steps": 0}
                        for i in (1, 3, 6, 8)}}


def test_the_new_readers_on_a_hand_built_trace():
    """A Mamba layer's one scope with the scan and the gated norm inside
    it, an expert layer's with the grouped matmuls and the shared expert
    inside it, forward and backward."""
    from benchmark.harness import ungated_expert_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 3e6, step + "block0/ssm/in_proj/dot_general:"],
        ["tpu_custom_call:ssd_fwd.1", 3e6, 1e6,
         step + "block0/ssm/ssd_scan/jit(_forward)/pallas_call:"],
        ["fusion.2", 4e6, 2e6, step + "block0/ssm/ssm_norm/mul:"],
        ["fusion.3", 6e6, 2e6, step + "block0/ssm/out_proj/dot_general:"],
        ["fusion.4", 8e6, 1e6, step + "block1/mlp/moe_route/moe_topk/"
         "top_k:"],
        ["tpu_custom_call:gmm.1", 9e6, 4e6, step + "block1/mlp/"
         "moe_experts/pallas_call:"],
        ["fusion.5", 13e6, 0.5e6, step + "block1/mlp/moe_experts/moe_gate/"
         "square:"],
        ["fusion.6", 14e6, 5e6, step + "block1/mlp/moe_shared/shared_fc1/"
         "dot_general:"],
        ["fusion.7", 20e6, 7e6, back + "block1/mlp/moe_shared/shared_fc2/"
         "dot_general:"],
        ["tpu_custom_call:tgmm.1", 27e6, 7.5e6, back + "block1/mlp/"
         "moe_experts/pallas_call:"],
        ["fusion.8", 35e6, 3e6, back + "block0/ssm/ssm_norm/mul:"],
        ["tpu_custom_call:ssd_bwd.1", 38e6, 2e6,
         back + "block0/ssm/ssd_scan/jit(_backward)/pallas_call:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"ssm_ms": 13.0, "ssd_ms": 3.0, "ssm_norm_ms": 5.0,
            "mlp_ms": 25.0, "moe_shared_ms": 12.0, "moe_experts_ms": 12.0,
            "moe_gate_ms": 0.5, "moe_route_ms": 1.0, "ssd_kept_mib": 384.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    flops, nbytes = ungated_expert_flops.experts_train_flops_bytes(
        4 * 6144, 2688, 1856, 8, 4)
    assert _reader("ungated_experts_roofline").read(run) == pytest.approx(
        100 * max(flops / 197e12, nbytes / 819e9) / 12e-3)
    bound = run["notes"]["ungated_experts_roofline_bound"]
    assert (bound["side"], bound["rows"]) == ("compute", 4 * 6144)
    # a program without the scopes, the gauge or the counters (the
    # parent, another family): nothing to read, and no reader raises
    bare = {"trace": {"ops": {0: ops[:1] + ops[3:6]}, "steps": 1},
            "chips": 1, "peaks": PEAKS, "ran": {}}
    for name in ("moe_shared_ms", "ssm_norm_ms", "ssd_kept_mib",
                 "ungated_experts_roofline"):
        assert _reader(name).read(bare) is None, name
    # gated experts are moe_experts_roofline's, not this reader's
    for other in ({"experts_gated": True}, {"moe_counters": None}):
        assert _reader("ungated_experts_roofline").read(
            {**run, "ran": {**RAN, **other}}) is None, other
    assert _reader("ungated_experts_roofline").read(
        {**run, "ran": {k: v for k, v in RAN.items()
                        if k != "experts_gated"}}) is None
    no_trace = {"ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    for name in ("moe_shared_ms", "ssm_norm_ms", "ungated_experts_roofline"):
        assert _reader(name).read(no_trace) is None, name


def test_the_ungated_roofline_stays_under_100_on_rows_that_fill_every_tile():
    """By construction: the bound counts two matrices' multiply-adds for
    the rows routed and the least bytes; a grouped matmul that ran at the
    chip's peak on those rows alone, every tile full, would read 100 %,
    and any time over that reads under.  Rows that fill whole row tiles
    of 512 an expert, the time the MXU needs for exactly those products
    at peak plus the activation's pass over memory."""
    from benchmark.harness import ungated_expert_flops

    rows = 4 * 8 * 512 * 2          # four layers, two full tiles an expert
    flops, nbytes = ungated_expert_flops.experts_train_flops_bytes(
        rows, 2688, 1856, 8, 4)
    at_peak_s = max(flops / 197e12, nbytes / 819e9)
    gate_s = 3 * rows * 1856 * 2 * 2 / 819e9    # relu^2 read and written
    ops = [["tpu_custom_call:gmm.1", 0, (at_peak_s + gate_s) * 1e9,
            "jit(step)/jvp(GPT)/block1/mlp/moe_experts/pallas_call:"]]
    counters = {f"block{i}": {"rows_held": rows // 4} for i in (1, 3, 6, 8)}
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "chips": 1,
           "peaks": PEAKS, "ran": {**RAN, "moe_counters": counters}}
    share = _reader("ungated_experts_roofline").read(run)
    assert 90 < share < 100
    # the gated count on the same run would pass 100: why the cell is
    # not on moe_experts_roofline's list
    assert _reader("moe_experts_roofline").read(run) > 100


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to a Mamba block
    and the expert block after it (``made_from`` in the file beside it
    says how), with what plain sums over names and scopes give for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    recording = tr.load_recording(os.path.join(
        data, CELL + ".blocks2_3_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".blocks2_3_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    events = run["trace"]["ops"][0]
    assert len(events) == expect["events"]
    for name in ("ssm_ms", "ssd_ms", "ssm_norm_ms", "mlp_ms",
                 "moe_shared_ms", "moe_route_ms", "moe_dispatch_ms",
                 "moe_experts_ms", "moe_gate_ms"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    # the scan and the gated norm lie inside the Mamba block's one scope,
    # forward and backward, and apart; the shared expert and the grouped
    # matmuls inside the expert block's
    inside = set(map(tuple, tr.under(events, "ssm")))
    for inner in ("ssd_scan", "ssm_norm"):
        part = tr.under(events, inner)
        assert part and set(map(tuple, part)) <= inside, inner
        assert any("transpose(" in tr.scope_of(e) for e in part), inner
    assert not [e for e in tr.under(events, "ssm_norm")
                if e in tr.under(events, "ssd_scan")]
    mlp = set(map(tuple, tr.under(events, "mlp")))
    for inner in ("moe_shared", "moe_experts", "moe_route"):
        part = tr.under(events, inner)
        assert part and set(map(tuple, part)) <= mlp, inner
    # a layer of one half: nothing of block2 under mlp, of block3 under ssm
    assert not [e for e in tr.under(events, "mlp")
                if "/block2/" in tr.scope_of(e)]
    assert not [e for e in tr.under(events, "ssm")
                if "/block3/" in tr.scope_of(e)]
    names = {e[0].split(".")[0] for e in tr.under(events, "ssd_scan")}
    assert {"tpu_custom_call:ssd_fwd", "tpu_custom_call:ssd_bwd"} <= names


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
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    # by name, never by place or by count: a later cell, entry or reader
    # must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name, (unit, better, source, layer) in NEW_READERS.items():
        new = by_name[name]
        assert new["workloads"] == [CELL] or CELL in new["workloads"], name
        assert (new["unit"], new["better"], new["source"], new["layer"],
                new["moves"]) == (unit, better, source, layer,
                                  "train_throughput"), name
    for name in JOINED_READERS:
        assert CELL in by_name[name]["workloads"], name
    # moe_experts_roofline counts three matrices an expert; no loss
    # holds the load even; there is no window, latent, gate or
    # prediction module; the other readers are other families'
    for name in ("moe_experts_roofline", "moe_balance_loss", "mla_flash_ms",
                 "mla_proj_ms", "flash_ms", "flash_roofline", "attn_gate_ms",
                 "swa_flash_ms", "swa_flash_roofline", "mtp_ms",
                 "allreduce_ms", "sscan_ms", "diff_flash_ms", "gmu_ms",
                 "short_conv_ms", "kda_ms", "kda_scan_roofline", "hc_ms"):
        assert CELL not in by_name[name]["workloads"], name
    cell = registry.load_cell(CELL, ROOT)
    assert cell["params"] == {
        "seq_len": 16384, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}
    assert cell["runner"] == "train" and len(cell["why"]) <= 200
    assert cell["why"] == cells[CELL]["why"]


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert (config["num_hidden_layers"], config["hybrid_override_pattern"],
            config["n_routed_experts"], config["first_held_expert"],
            config["vocab_size"]) == (9, "MEMEM*EME", 8, 0, 131072 // 8)
    published = config["published"]
    assert (published["num_hidden_layers"], published["n_routed_experts"],
            published["vocab_size"]) == (52, 128, 131072)
    whole = published["hybrid_override_pattern"]
    assert (len(whole), whole.count("M"), whole.count("E"),
            whole.count("*")) == (52, 23, 23, 6)
    # the cut keeps the first nine published layers, nothing skipped
    assert whole.startswith(config["hybrid_override_pattern"])
    for key, value in {
            "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
            "expand": 2, "head_dim": 128, "hidden_size": 2688,
            "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
            "mamba_head_dim": 64, "mamba_hidden_act": "silu",
            "mamba_num_heads": 64, "mamba_proj_bias": False,
            "max_position_embeddings": 262144, "mlp_bias": False,
            "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
            "moe_intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
            "n_groups": 8, "n_shared_experts": 1, "norm_eps": 1e-05,
            "norm_topk_prob": True, "num_attention_heads": 32,
            "num_experts_per_tok": 6, "num_key_value_heads": 2,
            "num_logits_to_keep": 1, "partial_rotary_factor": 1,
            "rescale_prenorm_residual": True, "residual_in_fp32": False,
            "rope_theta": 10000, "routed_scaling_factor": 2.5,
            "sliding_window": None, "ssm_state_size": 128,
            "tie_word_embeddings": False, "time_step_floor": 0.0001,
            "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
            "use_bias": False, "use_conv_bias": True,
            "use_mamba_kernels": True}.items():
        assert config[key] == value, key
    assert config["bias_update_rate"] == 0.01
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"block", "mamba", "attention", "experts", "expand",
            "selection bias", "router", "chunk", "initialisation",
            "optimizer", "dropout"} <= set(config["assumed"])
    assert "sixteen chips" in config["deployment"]
    assert "666 962 944" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    """The published keys of the configuration file against what the
    named size built: a differing width is refused before anything is
    traced."""
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("nemotron_h", ROOT)
    for key, value in (("moe_intermediate_size", 1024), ("n_groups", 1),
                       ("moe_shared_expert_intermediate_size", 1856),
                       ("chunk_size", 256), ("num_key_value_heads", 8)):
        config = {**cell["config_values"], key: value}
        with pytest.raises(ValueError, match=f"{key}={value}"):
            builder.build(config, cell["params"], seed=0)
    config = {**cell["config_values"], "published": {"n_routed_experts": 64}}
    with pytest.raises(ValueError, match="router scores 128 experts"):
        builder.build(config, cell["params"], seed=0)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        builder.build({**cell["config_values"],
                       "hybrid_override_pattern": "MEMEM*EMX"},
                      cell["params"], seed=0)
