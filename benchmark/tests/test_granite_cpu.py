"""The cell ``granite4hm_train_s8192`` on the CPU at a tiny size: through
``run.py``'s entry with ``overrides`` (hidden 64, 4 Mamba heads of 16,
state 16, chunk 8, a 4-layer pattern with one attention layer, 2 K/V
heads under 4 query heads), its reference checks with the fp8 control
and both of the family's ``fault_probes``, its three readers on a
hand-built trace, and its entries in ``BENCHMARK.json``.  Nothing these
runs time is a measurement."""

import json
import os

import pytest

from helpers import ROOT, add_cell, make_root

CELL = "granite4hm_train_s8192"
TINY = {"seq_len": 32, "per_chip_batch": 2, "trace_steps": 3,
        "reference_items": 2, "attention": "reference",
        "overrides": {
            "num_layers": 4,
            "layer_types": ["mamba", "attention", "mamba", "mamba"],
            "vocab_size": 256, "emb_dim": 64, "num_heads": 4,
            "num_kv_heads": 2, "ssm_heads": 4, "ssm_head_dim": 16,
            "ssm_state": 16, "ssm_chunk": 8, "attention_scale": 0.125}}
# What the tiny model on the CPU reads (bfloat16 compute against the
# float32 reference's token-by-token recurrence, six seeds, ``in_proj``
# times three as in the fixture below): log-probabilities apart by at
# most 0.0031, gradients by 2.4 % of the reference's norm; with every
# weight through fp8 e4m3 from 0.0115 and 8.5 %, a Mamba layer without
# its output from 0.035 and 26 %, a state that forgets from 0.063 and
# 43 %.  The limits the cell is held to are in its configuration file,
# from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.01, "logprob_abs": 0.006, "grad_rel": 0.045}


def _tiny_root(tmp_path):
    root = make_root(tmp_path)
    add_cell(root, "tiny_granite", CELL, TINY, traffic="tiny",
             config_edits={"reference_tolerance": TINY_TOLERANCE})
    return root


def test_train_runner_granite(tmp_path):
    import run as cli

    line = cli.execute("tiny_granite", seed=2**31 + 11, seconds=1.0,
                       trace=False, root=_tiny_root(tmp_path),
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

    root = _tiny_root(tmp_path_factory.mktemp("granite"))
    cell = registry.load_cell("tiny_granite", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:2]), built.state[2:], seconds=1.0)
    assert float(losses[-1]) < float(losses[0])
    sides = correct.reference_sides(
        built.program_loss, registry.load_reference(cell["config"], root),
        {**config, **built.ran})
    # At hidden 64 a normal-0.02 ``in_proj`` gives B and C a tenth of
    # the spread they have at the published width, and the state's share
    # of ``y`` vanishes beside ``D x``: three times the matrix, read by
    # program and reference alike, puts it back.
    variables = built.variables(tuple(carry))
    variables = {"params": {
        name: ({**blk, "in_proj": {"kernel": 3 * blk["in_proj"]["kernel"]}}
               if "in_proj" in blk else blk)
        for name, blk in variables["params"].items()}}
    return {"sides": sides, "variables": variables,
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


def _checks(trained, damage=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_with_room(trained):
    checks = _checks(trained)
    assert all(c["ok"] for c in checks.values()), checks
    assert checks["logprob_matches_reference"]["abs_diff_max"] \
        < TINY_TOLERANCE["logprob_abs"] / 1.5
    assert checks["gradient_matches_reference"][
        "diff_norm_over_reference_norm"] < TINY_TOLERANCE["grad_rel"] / 1.5


def test_weights_through_fp8_are_not_correct(trained):
    from benchmark.harness import correct

    checks = _checks(trained, correct.through_fp8)
    assert not all(c["ok"] for c in checks.values()), checks
    assert not checks["gradient_matches_reference"]["ok"], checks


def test_a_mamba_layer_without_its_output_is_not_correct(trained):
    damage = trained["probes"]["mamba_identity"]
    damaged = damage(trained["variables"])["params"]["block3"]
    assert float(abs(damaged["out_proj"]["kernel"]).max()) == 0.0
    assert float(abs(damaged["in_proj"]["kernel"]).max()) > 0.0
    checks = _checks(trained, damage)
    assert not checks["logprob_matches_reference"]["ok"], checks
    assert not checks["gradient_matches_reference"]["ok"], checks


def test_a_state_that_forgets_within_a_token_is_not_correct(trained):
    """The checks see the recurrence, not only ``D x``."""
    damage = trained["probes"]["state_forgets"]
    before = trained["variables"]["params"]
    after = damage(trained["variables"])["params"]
    assert float((after["block0"]["A_log"]
                  - before["block0"]["A_log"]).min()) == 10.0
    assert "A_log" not in after["block1"]            # the attention layer
    assert after["block0"]["D"] is before["block0"]["D"]
    checks = _checks(trained, damage)
    assert not all(c["ok"] for c in checks.values()), checks
    assert not checks["gradient_matches_reference"]["ok"], checks


def _reader(name):
    from benchmark.harness import registry

    return registry.load_module(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_the_three_readers_on_a_hand_built_trace():
    from benchmark.harness import ssd_flops

    step = "jit(step)/jvp(GPT)/"
    back = "jit(step)/transpose(jvp(GPT))/"
    ops = [
        ["fusion.1", 0, 4e6, step + "block0/ssm/in_proj/dot_general:"],
        ["fusion.2", 4e6, 6e6, step + "block0/ssm/ssd_scan/dot_general:"],
        ["fusion.3", 10e6, 9e6, back + "block0/ssm/ssd_scan/mul:"],
        ["fusion.4", 19e6, 5e6, back + "block0/ssm/out_proj/dot_general:"],
        ["fusion.5", 24e6, 7e6, step + "block1/attn/qkv/dot_general:"],
        ["fusion.6", 31e6, 3e6, step + "block1/mlp/fc1/dot_general:"],
    ]
    ran = {"global_batch": 1, "seq_len": 8192, "mamba_n_heads": 64,
           "mamba_d_head": 64, "mamba_n_groups": 1, "mamba_d_state": 128,
           "ssd_chunk": 256, "layer_types": ["mamba"] * 9 + ["attention"]}
    run = {"trace": {"ops": {0: ops}, "steps": 1}, "ran": ran, "chips": 1,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    assert _reader("ssm_ms").read(run) == pytest.approx(24.0)
    assert _reader("ssd_ms").read(run) == pytest.approx(15.0)
    assert _reader("attn_ms").read(run) == pytest.approx(7.0)
    # per token and layer: 256 * 128 / 2 for C B^T, 64 heads of
    # 256 * 64 / 2 + 2 * 128 * 64 for the three products with x and S
    macs = 256 * 128 / 2 + 64 * (256 * 64 / 2 + 2 * 128 * 64)
    assert ssd_flops.ssd_forward_macs_per_token(64, 64, 1, 128, 256) == macs
    need_flops, need_bytes = ssd_flops.ssd_train_flops_bytes(
        batch=1, seq_len=8192, heads=64, head_dim=64, groups=1, state=128,
        chunk=256, layers=9)
    assert need_flops == 3 * 2 * macs * 8192 * 9
    # x, y forward; x, dy, dx backward (8 KiB each a token), B and C
    # three times (512 B a pair), dt three times (256 B)
    assert need_bytes == (5 * 8192 + 3 * 512 + 3 * 256) * 8192 * 9
    share = _reader("ssd_roofline").read(run)
    # 3.57 ms of operations, 3.89 ms of bytes: the larger bounds
    assert need_flops / 197e12 < need_bytes / 819e9
    assert share == pytest.approx(100 * (need_bytes / 819e9) / 15e-3)
    assert 0 < share < 100
    assert run["notes"]["ssd_roofline_bound"]["side"] == "memory"
    # a program without the scopes (the parent), an untraced run, the CPU
    bare = {"trace": {"ops": {0: [op[:3] + [""] for op in ops]},
                      "steps": 1}, "ran": ran, "chips": 1,
            "peaks": run["peaks"]}
    for name in ("ssm_ms", "ssd_ms", "ssd_roofline"):
        assert _reader(name).read(bare) is None
        assert _reader(name).read({**run, "trace": None}) is None
    assert _reader("ssd_roofline").read(
        {k: v for k, v in run.items() if k != "peaks"}) is None


def test_the_cell_and_its_entries():
    from benchmark.harness import registry

    bench = registry.benchmark_json(ROOT)
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    per_layer = [m["name"] for m in bench["per_layer"]]
    assert per_layer[-3:] == ["ssm_ms", "ssd_ms", "ssd_roofline"]
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in per_layer[-3:]:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_throughput"
    for name in ("train_throughput", "step_ms_p90", "compile_s",
                 "peak_hbm_gib", "compile_trace_lower_s",
                 "compile_cache_misses", "attn_ms", "mlp_ms", "head_ms",
                 "optimizer_ms", "flash_fwd_ms", "flash_bwd_ms"):
        assert by_name[name]["workloads"][-1] == CELL
    # flash_ms sums every Pallas call, flash_roofline asserts full
    # multi-head attention in every layer: neither is this cell's
    for name in ("flash_ms", "flash_roofline"):
        assert CELL not in by_name[name]["workloads"]
    assert {"ssm", "ssd_scan"} <= set(registry.reader_scopes(ROOT))


def test_the_configuration_file_holds_the_published_values():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["vocab_size"] == 100352 == 8 * 12544
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 8192,
            "shared_intermediate_size": 8192, "num_attention_heads": 32,
            "num_key_value_heads": 8, "mamba_n_heads": 64,
            "mamba_d_head": 64, "mamba_d_state": 128, "mamba_n_groups": 1,
            "mamba_d_conv": 4, "mamba_expand": 2, "mamba_chunk_size": 256,
            "attention_multiplier": 0.015625, "embedding_multiplier": 12,
            "residual_multiplier": 0.22, "logits_scaling": 8,
            "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
            "position_embedding_type": "nope",
            "max_position_embeddings": 131072}.items():
        assert config[key] == value, key
    assert {"loss_abs", "logprob_abs", "grad_rel", "why"} <= set(
        config["reference_tolerance"])
