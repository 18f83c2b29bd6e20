"""The cell ``xing4_train_s8192`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 4 heads with keys of
16 + 8 over values of 16, ranks 24 and 16, four streams, 16 experts of
width 32 of which 4 are held, 4 a token, a dense layer and two expert
layers), its reference checks with the fp8 control and the family's
three ``fault_probes``, its new readers on a small scoped recording, and
its entries in ``BENCHMARK.json`` pinned by name and membership.  Nothing
these runs time is a measurement."""

import json
import os

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "xing4_train_s8192"
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 3, "layer_types": ["mla"] * 3, "vocab_size": 256,
            "emb_dim": 64, "num_heads": 4, "num_kv_heads": 4,
            "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16, "mlp_width": 96,
            "routed_experts": 16, "routed_held": 4, "routed_width": 32,
            "max_len": 64,
            # the named size's scale is 192 ** -0.5 m^2; the reference
            # computes its own from the key's width: (16 + 8) ** -0.5 m^2
            "attention_scale": 24 ** -0.5 * 1.4158883083359672 ** 2}}
# What the tiny model on the CPU reads after 8 steps (bfloat16 compute
# against the float32 reference): the gradient 3 to 6 %; the controls
# from 20 %.  The limits the cell is held to are in its configuration
# file, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.02, "logprob_abs": 0.9, "grad_rel": 0.12}
# The runner's test trains for a second, however many steps that is on
# this machine: it holds the plumbing, not the numbers.
LAX_TOLERANCE = {"loss_abs": 0.1, "logprob_abs": 3.0, "grad_rel": 0.5}
NEW_READERS = ["hc_ms", "hc_coeff_ms", "hc_mix_ms", "hc_mix_roofline",
               "hc_stochastic_err"]
JOINED = ["train_throughput", "step_ms_p90", "attn_ms", "mlp_ms", "head_ms",
          "optimizer_ms", "flash_fwd_ms", "flash_bwd_ms",
          "flash_live_tile_share", "mla_proj_ms", "mla_flash_ms",
          "nope_mla_flash_ms", "nope_mla_flash_roofline", "moe_route_ms",
          "moe_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
          "moe_rows_share", "moe_overflow_steps", "moe_logits_ms",
          "moe_topk_ms", "moe_sort_ms", "moe_unsort_ms", "moe_rows_in_ms",
          "moe_rows_out_ms", "moe_cast_ms", "moe_gate_ms",
          "moe_live_row_share", "moe_gmm_tile_fill", "compile_s",
          "peak_hbm_gib", "compile_trace_lower_s", "compile_cache_misses",
          "step_trace_s", "step_lower_s", "step_backend_s", "cache_load_s",
          "state_programs_s", "hvd_init_s", "setup_uncovered_s"]


def _tiny_root(tmp_path, tolerance=TINY_TOLERANCE):
    root = make_root(tmp_path)
    add_cell(root, "tiny_xing", CELL, TINY, traffic="tiny",
             config_edits={"reference_tolerance": tolerance})
    return root


def test_train_runner_xing(tmp_path):
    import run as cli

    line = cli.execute("tiny_xing", seed=2**31 + 11, seconds=1.0,
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

    root = _tiny_root(tmp_path_factory.mktemp("xing"))
    cell = registry.load_cell("tiny_xing", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:built.carry_len]),
        built.state[built.carry_len:], steps=8)
    assert float(losses[-1]) < float(losses[0])
    reference = registry.load_reference(cell["config"], root)
    sides = correct.reference_sides(
        built.program_loss, reference, {**config, **built.ran})
    variables = built.variables(tuple(carry))
    return {"sides": sides, "variables": variables, "ran": built.ran,
            "sample": built.sample(params["reference_items"]),
            "reference": reference, "config": {**config, **built.ran},
            "probes": builder.fault_probes(config, built.ran)}


def _checks(trained, damage=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_and_publishes_its_counters(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    counted = trained["ran"]["hyper_connections"]
    assert counted["streams"] == 4 and counted["sinkhorn_iters"] == 20
    assert counted["sublayers"] == 6
    assert 0 <= counted["stochastic_err"] < 0.05
    assert _reader("hc_stochastic_err").read(
        {"ran": trained["ran"]}) == counted["stochastic_err"]
    assert set(trained["ran"]["moe_counters"]) == {"block1", "block2"}
    assert trained["ran"]["stream_itemsize"] == 2


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks


@pytest.mark.parametrize("probe", ["experts_silent", "rotary_key_zero",
                                   "mixing_uniform"])
def test_a_damaged_program_is_not_correct(trained, probe):
    damaged = trained["probes"][probe](trained["variables"])["params"]
    last = damaged["block2"]
    if probe == "experts_silent":
        assert float(abs(last["experts_fc2"]).max()) == 0.0
        assert float(abs(damaged["block1"]["experts_fc2"]).max()) > 0.0
    elif probe == "rotary_key_zero":
        for name in ("block0", "block2"):
            kernel = damaged[name]["kv_a"]["kernel"]
            assert float(abs(kernel[:, 16:]).max()) == 0.0
            assert float(abs(kernel[:, :16]).max()) > 0.0
    else:
        for block in (damaged["block0"], last):
            for half in ("hc_attn", "hc_mlp"):
                assert block[half + "_alpha"].tolist()[2] == 0.0
                assert block[half + "_alpha"].tolist()[:2] != [0.0, 0.0]
                assert float(abs(block[half + "_b"][8:]).max()) == 0.0
                assert float(abs(block[half + "_b"][:8]).max()) > 0.0
    checks = _checks(trained, trained["probes"][probe])
    assert not all(c["ok"] for c in checks.values()), checks


def test_the_family_states_three_probes(trained):
    assert set(trained["probes"]) == {"experts_silent", "rotary_key_zero",
                                      "mixing_uniform"}


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


STEP = "jit(local_step)/jvp(GPT)/"
BACK = "jit(local_step)/transpose(jvp(GPT))/"
# a small scoped recording: [name, start ns, duration ns, scope]
RECORDING = [
    ["fusion.1", 0, 2e6, STEP + "block1/hc_coeff/dot_general:"],
    ["fusion.2", 2e6, 1e6, STEP + "block1/hc_coeff/div:"],
    ["fusion.3", 3e6, 3e6, STEP + "block1/hc_read/mul:"],
    ["fusion.4", 6e6, 5e6, STEP + "block1/attn/proj/dot_general:"],
    ["fusion.5", 11e6, 4e6, STEP + "block1/hc_write/concatenate:"],
    ["fusion.6", 15e6, 6e6, STEP + "block1/mlp/fc1/dot_general:"],
    ["fusion.7", 21e6, 8e6, BACK + "block1/hc_write/mul:"],
    ["fusion.8", 29e6, 7e6, BACK + "block1/hc_read/mul:"],
    ["fusion.9", 36e6, 2e6, BACK + "block1/hc_coeff/div:"],
    ["fusion.10", 38e6, 9e6, BACK + "block1/attn/q_b/dot_general:"],
]


def test_the_new_readers_on_a_small_scoped_recording():
    from benchmark.harness import hyper_connection_bytes

    ran = {"global_batch": 1, "seq_len": 8192, "hidden_size": 3584,
           "hc_mult": 4, "num_hidden_layers": 5, "stream_itemsize": 2,
           "hyper_connections": {"stochastic_err": 0.0012, "streams": 4,
                                 "sinkhorn_iters": 20, "sublayers": 10}}
    run = {"trace": {"ops": {0: RECORDING}, "steps": 2}, "ran": ran,
           "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    want = {"hc_coeff_ms": 2.5, "hc_mix_ms": 11.0, "hc_ms": 13.5,
            # the halves hold none of it
            "attn_ms": 7.0, "mlp_ms": 3.0}
    for name, value in want.items():
        assert _reader(name).read(run) == pytest.approx(value), name
    assert _reader("hc_stochastic_err").read(run) == 0.0012
    # a sub-layer forward: the stream read twice and written once, u
    # written, y read: 14 arrays of 8192 x 3584 bfloat16; four times that
    # a training step (forward, recomputed forward, backward twice), ten
    # sub-layers: 32.9 GB
    assert hyper_connection_bytes.mix_forward_elements(
        8192, 3584, 4) == 14 * 8192 * 3584
    need = hyper_connection_bytes.mix_train_bytes(
        batch=1, seq_len=8192, channels=3584, streams=4, sublayers=10)
    assert need == 4 * 14 * 8192 * 3584 * 2 * 10 == 32_883_343_360
    share = _reader("hc_mix_roofline").read(run)
    assert share == pytest.approx(100 * (need / 819e9) / 11e-3)
    assert run["notes"]["hc_mix_roofline_bound"] == {
        "side": "memory", "seconds": need / 819e9, "bytes": need,
        "program_counted": ran["hyper_connections"]}
    # a program without the scopes, the counter or the streams (the
    # parent), an untraced run, the CPU: nothing to read, none raises
    bare = {"trace": {"ops": {0: [op[:3] + [""] for op in RECORDING]},
                      "steps": 2}, "chips": 1, "peaks": run["peaks"],
            "ran": {"global_batch": 1, "seq_len": 8192}}
    for name in NEW_READERS:
        assert _reader(name).read(bare) is None, name
        assert _reader(name).read({**bare, "trace": None}) is None, name
    for name in NEW_READERS[:4]:
        assert _reader(name).read({**run, "trace": None}) is None, name
    assert _reader("hc_mix_roofline").read(
        {k: v for k, v in run.items() if k != "peaks"}) is None
    assert _reader("hc_mix_roofline").read(
        {**run, "ran": {**ran, "hc_mult": 1}}) is None


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "xing4.0-29b-a4b",
        "traffic": "train_s8192_b1", "chips": 1, "why": cells[CELL]["why"]}
    assert len(cells[CELL]["why"]) <= 200
    # by name and membership, never by place, count or whole content: a
    # later cell, entry or reader must not fail this test
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in NEW_READERS:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_throughput"
    assert by_name["hc_mix_roofline"]["unit"] == "%"
    assert by_name["hc_stochastic_err"]["source"] == "program_counter"
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    # keys of 192 over values of 128: mla_flash_roofline takes one head
    # size; mtp_ms has no module to read; the others are other mixers'
    for name in ("mla_flash_roofline", "mtp_ms", "flash_ms",
                 "flash_roofline", "ssm_ms", "kda_ms", "allreduce_ms"):
        assert CELL not in by_name[name]["workloads"], name
    configs = {c["name"]: c for c in bench["configs"]}
    assert configs["xing4.0-29b-a4b"]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"]
    cell = registry.load_cell(CELL, ROOT)
    glm = registry.load_cell("glm47f_train_s8192", ROOT)
    assert cell["params"] == glm["params"] == {
        "seq_len": 8192, "per_chip_batch": 1, "attention": "flash",
        "remat": True, "optimizer": "adamw", "learning_rate": 0.0001,
        "warmup_steps": 3, "trace_steps": 4, "reference_items": 1}


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        config = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (catalog,) = [r for r in rows if r["name"] == "Xing4.0-29B-A4B"]
    assert config["source"] == catalog["source_url"]
    differing = {k for k, v in catalog["config"].items()
                 if config.get(k, "missing") != v}
    assert differing == set(config["reduced"])
    assert 8 * config["vocab_size"] == catalog["config"]["vocab_size"]
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
    assert {"xing4_0 keys", "hyper-connections",
            "hyper-connection initialisation", "selection bias",
            "rotary pairing", "initialisation", "optimizer",
            "fused gate and up"} <= set(config["assumed"])


def test_the_builder_refuses_a_file_that_differs_from_the_program():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("xing4_0", ROOT)
    for key, value in (("hc_sinkhorn_iters", 10), ("kv_lora_rank", 256)):
        config = {**cell["config_values"], key: value}
        with pytest.raises(ValueError, match=f"{key}={value}"):
            builder.build(config, cell["params"], seed=0)
    scaling = {**cell["config_values"]["rope_scaling"], "factor": 32}
    with pytest.raises(ValueError, match="rope_scaling="):
        builder.build({**cell["config_values"], "rope_scaling": scaling},
                      cell["params"], seed=0)


def test_model_flops_count_the_connections_projection():
    from benchmark.harness import registry

    cell = registry.load_cell(CELL, ROOT)
    builder = registry.load_model_builder("xing4_0", ROOT)
    ran = {"seq_len": 8192, "router_width": 64}
    flops = builder.train_flops_per_item(cell["config_values"], ran)
    # forward 1.16 GFLOP a token, 3.48 with the backward
    assert flops == pytest.approx(3.48e9, rel=0.01)
    without = builder.train_flops_per_item(
        {**cell["config_values"], "hc_mult": 0}, ran)
    assert flops - without == 3 * 5 * 2 * 2 * 14336 * 24


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b.reference.py")) as f:
        text = f.read()
    imports = [line for line in text.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports == ["import math", "import jax", "import jax.numpy as jnp"]
    assert 'jax.default_matmul_precision("highest")' in text
