"""The cell ``kimilin_train_s16384`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 4 KDA heads of 16 at a
chunk of 16, 4 latent heads with keys of 16 + 8 over values of 16, a
dense width of 192, 16 experts of width 32 of which 4 are held, 4 a
token, 64 tokens, the cell's five layers), its reference checks with the
fp8 control, the family's ``fault_probes`` and the reference's
departures, its model FLOPs and the chunk rule's operations against a
hand count, its new readers on a hand-built trace and on a recording of
the cell's own traced step, and its entries in ``BENCHMARK.json`` pinned
by name.  Nothing these runs time is a measurement."""

import json
import os
import types

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "kimilin_train_s16384"
CONFIG = "kimi-linear-48b-a3b-instruct"
KINDS = ["kda", "kda", "kda", "mla", "kda"]
TINY = {"seq_len": 64, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 5, "layer_types": KINDS, "dense_layers_first": 1,
            "vocab_size": 256, "emb_dim": 64, "num_heads": 4,
            "num_kv_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "kda_heads": 4,
            "kda_head_dim": 16, "kda_chunk": 16, "mlp_ratio": 3,
            "routed_experts": 16, "routed_held": 4, "routed_top_k": 4,
            "routed_width": 32, "max_len": 128}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference, the fixture's seed): the sound program's
# gradient 15.8 % apart and a label's log-probability up to 0.57 (a
# choice of experts is discrete, and at hidden 64 with four of sixteen
# experts a token one expert is a large part of a token's output); the
# thinnest damage of the variables, experts_silent, 27.8 %, the state
# that forgets 68 %, fp8 weights 90 %.  The gradient's limit is the
# geometric middle of the first two.  The limits the cell is held to are
# in its configuration file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.08, "logprob_abs": 2.5, "grad_rel": 0.21}
# The same program in float32 agrees with the reference to rounding, so
# the reference's departures are told from it whatever they weigh.
FLOAT32_TOLERANCE = {"loss_abs": 1e-3, "logprob_abs": 0.01,
                     "grad_rel": 0.004}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.2, "logprob_abs": 4.0, "grad_rel": 0.8}
DEPARTURES = ["decay_dropped", "decay_per_head", "erase_dropped",
              "beta_one", "qk_l2norm_dropped", "conv_sees_next",
              "out_gate_dropped", "mla_rotated", "shared_expert_dropped",
              "bias_in_weights", "weights_unnormalised",
              "chunk_state_dropped", "state_bfloat16"]
JOINED_READERS = [
    "train_throughput", "step_ms_p90", "compile_s", "compile_trace_lower_s",
    "compile_cache_misses", "step_trace_s", "step_lower_s", "step_backend_s",
    "cache_load_s", "state_programs_s", "hvd_init_s", "setup_uncovered_s",
    "peak_hbm_gib", "optimizer_ms", "attn_ms", "mlp_ms", "head_ms",
    "flash_fwd_ms", "flash_bwd_ms", "flash_live_tile_share", "mla_proj_ms",
    "moe_route_ms", "moe_dispatch_ms", "moe_experts_ms",
    "moe_experts_roofline", "moe_rows_share", "moe_overflow_steps"]
NEW_READERS = {
    "kda_ms": ("ms", "lower", "Models"),
    "kda_prep_ms": ("ms", "lower", "Models"),
    "kda_scan_ms": ("ms", "lower", "Kernels"),
    "kda_scan_roofline": ("%", "higher", "Kernels"),
    "nope_mla_flash_ms": ("ms", "lower", "Kernels"),
    "nope_mla_flash_roofline": ("%", "higher", "Kernels")}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE, dtype=None):
    root = make_root(tmp_path)
    params = json.loads(json.dumps(TINY))
    if dtype:
        params["overrides"]["dtype"] = dtype
    add_cell(root, "tiny_kimi", CELL, params, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_train_runner_kimi_linear(tmp_path):
    import run as cli

    line = cli.execute("tiny_kimi", seed=2**31 + 11, seconds=1.0,
                       trace=False,
                       root=_tiny_root(tmp_path, LAX_TOLERANCE),
                       allow_cpu=True)
    json.dumps(line)
    assert line["correct"], line["checks"]
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
    cell = registry.load_cell("tiny_kimi", root)
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
    return _trained(tmp_path_factory.mktemp("kimi"), TINY_TOLERANCE)


@pytest.fixture(scope="module")
def trained_float32(tmp_path_factory):
    return _trained(tmp_path_factory.mktemp("kimi_float32"),
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
    # the four expert layers; layer 0 is dense and counts nothing
    assert set(ran["moe_counters"]) == {f"block{i}" for i in range(1, 5)}
    for entry in ran["moe_counters"].values():
        assert entry["rows_dropped"] == 0
        assert 0 < entry["rows_held"] <= 2 * 64 * 4
    # under the names the readers that are there read their sizes by
    assert (ran["n_routed_experts"], ran["router_width"],
            ran["num_experts_per_tok"], ran["hidden_size"],
            ran["moe_intermediate_size"], ran["num_attention_heads"],
            ran["qk_nope_head_dim"], ran["qk_rope_head_dim"],
            ran["v_head_dim"], ran["kv_lora_rank"], ran["q_lora_rank"],
            ran["intermediate_size"], ran["kda_num_heads"],
            ran["kda_head_dim"]) == (
                4, 16, 4, 64, 32, 4, 16, 8, 16, 32, None, 192, 4, 16)
    assert ran["layer_types"] == KINDS
    run = {"ran": ran, "chips": 1}
    # 128 tokens x 4 choices x 4 / 16 = 128 rows a layer is an even share
    assert _reader("moe_rows_share").read(run) == pytest.approx(sum(
        e["rows_held"] for e in ran["moe_counters"].values()) / (4 * 128))
    assert _reader("moe_overflow_steps").read(run) == 0
    # what the model counted while the step was traced: four layers at
    # the tiny chunk, each keeping a state every fourth chunk (one group
    # of the sequence's four) and o
    assert ran["kda"] == {
        "layers": 4, "chunk": 16,
        "kept_mib": (2 * 1 * 4 * 16 * 16 * 4 + 2 * 64 * 4 * 16 * 2) / 2 ** 20}
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


def test_a_state_that_forgets_is_not_correct(trained):
    sound = trained["variables"]["params"]
    damaged = trained["probes"]["state_forgets"](
        trained["variables"])["params"]
    # the last KDA layer's decays alone (block 4; block 3 is latent)
    for i, kind in enumerate(KINDS):
        if kind != "kda":
            assert "dt_bias" not in damaged[f"block{i}"]
            continue
        same = bool((damaged[f"block{i}"]["dt_bias"]
                     == sound[f"block{i}"]["dt_bias"]).all())
        assert same == (i != 4)
    assert float(damaged["block4"]["dt_bias"].min()) == 30.0
    assert float(damaged["block4"]["A_log"].min()) == 5.0
    checks = _checks(trained, trained["probes"]["state_forgets"])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_departures_are_the_ones_the_issue_names(trained):
    # ISSUE 51's eleven, the chunk's starting state dropped, which no
    # damage of the variables can make, and the recurrence's state and
    # decays held in bfloat16 (the precision below the one stated)
    assert list(trained["reference"].DEPARTURES) == DEPARTURES


def test_the_float32_program_is_the_reference_to_rounding(trained_float32):
    checks = _checks(trained_float32)
    assert all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("depart", DEPARTURES)
def test_a_departed_reference_is_not_correct(trained_float32, depart,
                                               monkeypatch):
    """The sound program against the plain reference with one fault
    seeded into the reference's mathematics."""
    from benchmark.harness import correct

    reference = trained_float32["reference"]
    # 64 tokens: the state dropped every 16th token, the tiny chunk
    monkeypatch.setattr(reference, "STATE_DROP", 16)
    departed = types.SimpleNamespace(
        loss=lambda c, v, b: reference.loss(c, v, b, depart=depart),
        logprob=lambda c, v, b: reference.logprob(c, v, b, depart=depart))
    sides = correct.reference_sides(trained_float32["program_loss"],
                                    departed, trained_float32["config"])
    checks = _checks(trained_float32, sides=sides)
    assert not all(c["ok"] for c in checks.values()), checks


def test_model_flops_against_a_hand_count():
    from benchmark.harness import kda_flops, registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("kimi_linear", ROOT)
    config = cell["config_values"]
    ran = {"seq_len": 16384, "router_width": 256,
           "kda": {"layers": 4.0, "chunk": 64.0}}
    flops = builder.train_flops_per_item(config, ran)
    d, inner = 2304, 4096
    # a head and chunk of 64: the Gram halves, the inverse by
    # substitution, W and U, the state's three products, A_qk U~
    chunk = (64 * 64 * 128 + 64 ** 3 / 6 + 64 * 64 * 128
             + 3 * 64 * 128 * 128 + 64 * 64 * 64)
    assert kda_flops.kda_forward_macs_per_token(32, 128, 128, 64) \
        == pytest.approx(32 * chunk / 64)
    kda = (2 * (d * 3 * inner + 2 * (d * 128 + 128 * inner) + d * 32
                + inner * d) + 2 * 32 * chunk / 64)
    triangle = 16384 * 16385 // 2
    latent = (2 * (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d)
              + 2 * 32 * (192 + 128) * triangle / 16384)
    dense = 2 * 3 * d * 9216
    # eight experts a token, a thirty-second of them held: a quarter of
    # an expert of 3 x 2304 x 1024 multiply-adds, the shared one whole;
    # the router whole
    routed = 2 * d * 256 + 1.25 * 2 * 3 * d * 1024
    want = 3 * (2 * d * 20480 + 4 * kda + latent + dense + 4 * routed)
    assert flops == pytest.approx(want, rel=1e-12)
    assert flops == pytest.approx(2.571e9, rel=0.001)


def test_the_rules_operations_and_bytes_against_a_hand_count():
    from benchmark.harness import kda_flops

    flops, nbytes = kda_flops.kda_train_flops_bytes(
        batch=1, seq_len=16384, heads=32, d_k=128, d_v=128, chunk=64,
        layers=4)
    per_token = kda_flops.kda_forward_macs_per_token(32, 128, 128, 64)
    assert flops == 3 * 2 * per_token * 16384 * 4
    # q, k, v, o in bfloat16, g and beta in float32, a head and token:
    # forward reads five and writes o, backward reads six and writes five
    inputs = 32 * (3 * 128 * 2 + 128 * 4 + 4)
    out = 32 * 128 * 2
    assert nbytes == (2 * (inputs + out) + inputs) * 16384 * 4
    assert flops / 197e12 < nbytes / 819e9          # memory bounds it
    # by squarings the inverse alone would be five times the rest
    assert 2 * 5 * 64 ** 3 > 64 ** 3 / 6
    # the latent call: four matmuls over 192 channels, three over 128
    flops, nbytes = kda_flops.unequal_flash_train_flops_bytes(
        batch=1, heads=32, seq_len=16384, qk_dim=192, v_dim=128, layers=1)
    assert flops == 2 * (16384 * 16385 // 2) * (4 * 192 + 3 * 128) * 32
    assert nbytes == 6 * 16384 * 320 * 2 * 32
    assert flops / 197e12 > nbytes / 819e9          # compute bounds it


RAN = {"global_batch": 1, "seq_len": 16384, "num_attention_heads": 32,
       "num_key_value_heads": 32, "qk_nope_head_dim": 128,
       "qk_rope_head_dim": 64, "v_head_dim": 128, "kv_lora_rank": 512,
       "hidden_size": 2304, "moe_intermediate_size": 1024,
       "n_routed_experts": 8, "router_width": 256, "num_experts_per_tok": 8,
       "layer_types": KINDS, "kda_num_heads": 32, "kda_head_dim": 128,
       "kda": {"layers": 4.0, "chunk": 64.0, "kept_mib": 256.0}}


def test_the_new_readers_on_a_hand_built_trace():
    """A KDA block's scope with the chain and the rule inside it, forward
    and backward, beside the latent block's kernels."""
    from benchmark.harness import kda_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 3e6, step + "block0/kda/qkv/dot_general:"],
        ["fusion.2", 3e6, 1e6, step + "block0/kda/kda_prep/mul:"],
        ["fusion.3", 4e6, 6e6, step + "block0/kda/kda_scan/jit(_forward)/"
         "closed_call/while/body/closed_call/dot_general:"],
        ["fusion.4", 10e6, 2e6, step + "block0/kda/o_proj/dot_general:"],
        ["tpu_custom_call:flash_fwd.1", 12e6, 10e6,
         step + "block3/attn/flash_fwd/pallas_call:"],
        ["fusion.5", 22e6, 1e6, step + "block3/attn/mla_proj/kv_a/"
         "dot_general:"],
        ["fusion.6", 23e6, 4e6, step + "block3/mlp/fc1/dot_general:"],
        ["fusion.7", 30e6, 1.5e6, back + "block0/kda/kda_prep/mul:"],
        ["fusion.8", 32e6, 14e6, back + "block0/kda/kda_scan/"
         "jit(_backward)/while/body/closed_call/transpose(jvp())/"
         "dot_general:"],
        ["tpu_custom_call:flash_bwd_dkdv.1", 50e6, 15e6,
         back + "block3/attn/flash_bwd_dkdv/pallas_call:"],
    ]
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": dict(RAN),
           "chips": 1, "peaks": PEAKS}
    want = {"kda_ms": 27.5, "kda_prep_ms": 2.5, "kda_scan_ms": 20.0,
            "nope_mla_flash_ms": 25.0, "flash_fwd_ms": 10.0,
            "flash_bwd_ms": 15.0, "attn_ms": 26.0, "mla_proj_ms": 1.0,
            "mlp_ms": 4.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    flops, nbytes = kda_flops.kda_train_flops_bytes(1, 16384, 32, 128, 128,
                                                    64, 4)
    assert _reader("kda_scan_roofline").read(run) == pytest.approx(
        100 * (nbytes / 819e9) / 20e-3)
    bound = run["notes"]["kda_scan_roofline_bound"]
    assert (bound["side"], bound["layers"], bound["chunk"]) == (
        "memory", 4, 64)
    flops, nbytes = kda_flops.unequal_flash_train_flops_bytes(
        1, 32, 16384, 192, 128, 1)
    assert _reader("nope_mla_flash_roofline").read(run) == pytest.approx(
        100 * (flops / 197e12) / 25e-3)
    assert run["notes"]["nope_mla_flash_roofline_bound"]["side"] == "compute"
    # (the times above are made up: the recording below holds the share
    # under 100)
    # a program without the scopes (the parent, another family): nothing
    # to read, and no reader raises
    bare = {"trace": {"ops": {0: ops[4:7]}, "steps": 1}, "chips": 1,
            "peaks": PEAKS, "ran": {}}
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None, name
    # another family's attention (equal widths as GLM's, a window, a
    # plain layer beside the latent one): not this reader's
    for other in ({"v_head_dim": 192},
                  {"layer_types": ["mla", "sliding_attention"]},
                  {"layer_types": ["full_attention"]}):
        run_other = {**run, "ran": {**RAN, **other}}
        assert _reader("nope_mla_flash_ms").read(run_other) is None, other
        assert _reader("nope_mla_flash_roofline").read(run_other) is None
    # the parent's program leaves no ran["kda"]: no roofline, no raise
    assert _reader("kda_scan_roofline").read(
        {**run, "ran": {k: v for k, v in RAN.items() if k != "kda"}}) is None
    no_trace = {"ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    for name in NEW_READERS:
        assert _reader(name).read(no_trace) is None, name


def test_the_readers_on_a_recording_of_the_cell():
    """One traced step of the cell on a TPU v5 lite, cut to the latent
    block and the KDA block after it, the rule's loops thinned to their
    first two groups (``made_from`` in the file beside it says how), with
    what plain sums over names and scopes give for it."""
    from benchmark.harness import trace as tr

    data = os.path.join(ROOT, "benchmark", "tests", "data")
    # not ``.json.gz``: the older tests take every such file in the
    # directory for a recording saved without scopes
    recording = tr.load_recording(os.path.join(
        data, CELL + ".blocks3_4_one_step.scoped.gz"))
    with open(os.path.join(
            data, CELL + ".blocks3_4_one_step.scoped.expect.json")) as f:
        expect = json.load(f)
    run = {"trace": {"ops": tr.device_ops(recording), "steps": 1},
           "ran": dict(RAN), "chips": 1, "peaks": PEAKS}
    events = run["trace"]["ops"][0]
    assert len(events) == expect["events"]
    for name in ("kda_ms", "kda_prep_ms", "kda_scan_ms", "nope_mla_flash_ms",
                 "flash_fwd_ms", "flash_bwd_ms", "attn_ms", "mla_proj_ms",
                 "mlp_ms", "moe_route_ms", "moe_dispatch_ms",
                 "moe_experts_ms"):
        assert _reader(name).read(run) == pytest.approx(
            expect[name], rel=1e-6), name
    # the chain and the rule lie inside the KDA block's scope, forward
    # and backward, and outside the latent block's; the latent layer's
    # projections inside attn; the backward ran as one kernel
    inside = set(map(tuple, tr.under(events, "kda")))
    for inner in ("kda_prep", "kda_scan"):
        part = tr.under(events, inner)
        assert part and set(map(tuple, part)) <= inside, inner
        assert any("transpose(" in tr.scope_of(e) for e in part), inner
        assert not [e for e in part if e in tr.under(events, "attn")]
    assert set(map(tuple, tr.under(events, "mla_proj"))) <= set(
        map(tuple, tr.under(events, "attn")))
    # the rule is XLA's loops in both directions: no kernel under it
    assert not [e for e in tr.under(events, "kda_scan")
                if e[0].startswith("tpu_custom_call")]
    names = {e[0].split(".")[0] for e in tr.under(events, "attn")}
    assert {"tpu_custom_call:flash_fwd",
            "tpu_custom_call:flash_bwd_dkdv"} <= names
    assert "tpu_custom_call:flash_bwd_dq" not in names
    assert 0 < _reader("nope_mla_flash_roofline").read(run) < 100
    assert 0 < _reader("kda_scan_roofline").read(run)


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
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert configs[CONFIG]["reduced"] == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
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
    # mla_flash_ms counts every layer as latent and its roofline one
    # head size; flash_ms sums every Pallas call; there is no window,
    # gate, balance loss or prediction module; the other readers are
    # other families'
    for name in ("mla_flash_ms", "mla_flash_roofline", "flash_ms",
                 "flash_roofline", "attn_gate_ms", "swa_flash_ms",
                 "swa_flash_roofline", "swa_live_tile_share",
                 "moe_balance_loss", "mtp_ms", "ssm_ms", "ssd_ms",
                 "ssd_roofline", "allreduce_ms", "sscan_ms",
                 "diff_flash_ms", "gmu_ms", "short_conv_ms",
                 "gqa_flash_ms", "gqa_flash_roofline"):
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
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["first_held_expert"], config["vocab_size"]) == (
                5, 8, 0, 163840 // 8)
    published = config["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (27, 256, 163840)
    whole = published["linear_attn_config"]
    assert whole["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert whole["kda_layers"] == [i for i in range(1, 27) if i % 4]
    # the cut keeps the first five published layers, nothing skipped, and
    # every width of the group
    linear = config["linear_attn_config"]
    assert linear == {**whole, "full_attn_layers": [4],
                      "kda_layers": [1, 2, 3, 5]}
    assert (linear["head_dim"], linear["num_heads"],
            linear["short_conv_kernel_size"]) == (128, 32, 4)
    for key, value in {
            "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
            "hidden_size": 2304, "intermediate_size": 9216,
            "kv_lora_rank": 512, "mla_use_nope": True,
            "model_max_length": 1048576, "model_type": "kimi_linear",
            "moe_intermediate_size": 1024, "moe_layer_freq": 1,
            "moe_renormalize": True,
            "moe_router_activation_func": "sigmoid",
            "num_attention_heads": 32, "num_expert_group": 1,
            "num_experts_per_token": 8, "num_key_value_heads": 32,
            "num_nextn_predict_layers": 0, "num_shared_experts": 1,
            "q_lora_rank": None, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
            "rope_scaling": None, "rope_theta": 10000,
            "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
            "topk_group": 1, "use_grouped_topk": True,
            "v_head_dim": 128}.items():
        assert config[key] == value, key
    assert config["bias_update_rate"] == 0.01
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"block", "kda", "decay", "latent attention", "selection bias",
            "router", "router input", "fused q, k and v",
            "fused gate and up", "initialisation", "optimizer",
            "dropout"} <= set(config["assumed"])
    assert "thirty-two chips" in config["deployment"]
    assert "602 433 408" in config["deployment"]


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    """The published keys of the configuration file against what the
    named size built: a differing width is refused before anything is
    traced."""
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("kimi_linear", ROOT)
    for key, value in (("intermediate_size", 12288), ("kv_lora_rank", 256),
                       ("moe_intermediate_size", 1536),
                       ("q_lora_rank", 768)):
        config = {**cell["config_values"], key: value}
        with pytest.raises(ValueError, match=f"{key}={value}"):
            builder.build(config, cell["params"], seed=0)
    linear = {**cell["config_values"]["linear_attn_config"], "head_dim": 64}
    with pytest.raises(ValueError, match="linear_attn_config="):
        builder.build({**cell["config_values"],
                       "linear_attn_config": linear}, cell["params"], seed=0)
    config = {**cell["config_values"], "published": {"num_experts": 128}}
    with pytest.raises(ValueError, match="router scores 256 experts"):
        builder.build(config, cell["params"], seed=0)
