"""Each runner end to end on the CPU at a tiny size, through the Python
entry, with workload files of the tests' own: the control flow and the
last line's keys.  Nothing these runs time is a measurement."""

import json
import os
import subprocess
import sys

import pytest

from helpers import (ROOT, SERVE_METRICS, TINY_GPT, TINY_RESNET,
                     TINY_SERVE_CELL, add_cell, make_root)

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _check_line(line, metrics, but=()):
    assert LINE_KEYS <= set(line)
    json.dumps(line)  # one JSON object, nothing unserialisable
    assert all(c["ok"] for name, c in line["checks"].items()
               if name not in but), line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for name in metrics:
        value = line["metrics"][name]
        assert set(value) == {"value", "unit"} and value["value"] > 0


def test_train_runner_gpt(tmp_path):
    import run as cli

    root = make_root(tmp_path)
    add_cell(root, "tiny_gpt", "gpt2m_train_s1024", TINY_GPT,
             traffic="tiny", config_edits={"program": {"size": "nano"}})
    line = cli.execute("tiny_gpt", seed=2**31 + 11, seconds=1.0,
                       trace=False, root=root, allow_cpu=True)
    _check_line(line, ["train_throughput", "step_ms_p90", "setup_s"])
    checks = line["checks"]
    assert set(checks) == {"losses_finite", "loss_falls",
                           "nothing_built_in_window", "matches_reference",
                           "logprob_matches_reference",
                           "gradient_matches_reference"}
    assert checks["matches_reference"]["abs_diff"] < 1e-2
    assert checks["logprob_matches_reference"]["labels"] == 2 * 64
    notes = line["notes"]
    assert notes["checks_s"] > 0 and notes["drain_ms"] > 0
    # one loss more than stamps: the drained step keeps its loss
    assert line["attempted"] >= 3


# What the tiny model on the CPU reads (bfloat16 compute against the
# float32 reference, a dozen seeds): log-probabilities apart by at most
# 0.03, gradients by 1.5 % of the reference's norm; with every weight
# through fp8 e4m3 0.27 and 11 %, with the last block an identity 2.4
# and 90 %.  The limits the cells are held to are in their configuration
# files, from chip runs at the real size.
TINY_TOLERANCE = {"loss_abs": 0.01, "logprob_abs": 0.09, "grad_rel": 0.045}


@pytest.fixture(scope="module")
def trained_tiny_gpt(tmp_path_factory):
    """The tiny cell's program after a second of training, with what the
    reference checks compare it by."""
    from benchmark.harness import correct, registry
    from benchmark.runners import train

    root = make_root(tmp_path_factory.mktemp("probes"))
    add_cell(root, "tiny_gpt", "gpt2m_train_s1024", TINY_GPT,
             traffic="tiny", config_edits={"program": {"size": "nano"}})
    cell = registry.load_cell("tiny_gpt", root)
    config, params = cell["config_values"], cell["params"]
    builder = registry.load_model_builder(config["family"], root)
    built = builder.build(config, params, seed=2**31 + 77)
    carry, _, losses, _, _ = train._loop(
        built.step, list(built.state[:2]), built.state[2:], seconds=1.0)
    assert float(losses[-1]) < float(losses[0])
    sides = correct.reference_sides(
        built.program_loss, registry.load_reference(cell["config"], root),
        {**config, **built.ran})
    return {"sides": sides, "variables": built.variables(tuple(carry)),
            "sample": built.sample(params["reference_items"]),
            "probes": builder.fault_probes(config, built.ran)}


def _reference_checks(trained, damage=None):
    from benchmark.harness import correct

    variables = trained["variables"]
    numbers = correct.compare_sides(
        trained["sides"], variables, trained["sample"],
        program_variables=damage and damage(variables))
    return correct.reference_checks(numbers, TINY_TOLERANCE)


def test_untouched_program_passes_the_reference_checks(trained_tiny_gpt):
    checks = _reference_checks(trained_tiny_gpt)
    assert all(c["ok"] for c in checks.values()), checks
    # with room: a limit is set at a few times the largest sound reading
    assert checks["logprob_matches_reference"]["abs_diff_max"] < 0.045
    assert checks["gradient_matches_reference"][
        "diff_norm_over_reference_norm"] < 0.0225


def test_weights_through_fp8_fail_the_new_checks(trained_tiny_gpt):
    """The control: the nearest precision below the configuration's
    bfloat16.  The scalar loss alone does not see it."""
    from benchmark.harness import correct

    checks = _reference_checks(trained_tiny_gpt, correct.through_fp8)
    assert not checks["logprob_matches_reference"]["ok"], checks
    assert not checks["gradient_matches_reference"]["ok"], checks
    assert checks["matches_reference"]["ok"]      # the old check's blind spot


def test_the_fp8_control_rounds_as_the_cast_does():
    """``through_fp8`` is arithmetic (the TPU compiler removes a cast
    there and back); on the CPU the cast is kept, and the two agree on
    every value e4m3 holds: normal, subnormal, ties, the largest."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import correct

    key = jax.random.PRNGKey(5)
    x = jnp.concatenate([
        jax.random.normal(key, (50000,)) * 0.02,     # weights' own range
        jax.random.normal(key, (50000,)) * 3.0,
        jnp.linspace(-0.05, 0.05, 20001),            # across the subnormals
        jnp.array([0.0, 448.0, -448.0, 2.0 ** -6, 2.0 ** -6 * 0.999,
                   2.0 ** -9, 2.0 ** -10, 2.0 ** -10 * 1.01, 1e-8,
                   0.0146484375, 0.0166015625, 1.0625, 1.1875])])
    tree = {"w": x, "step": jnp.arange(3)}
    got = correct.through_fp8(tree)
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    assert bool((got["w"] == want).all())
    assert got["step"].dtype == tree["step"].dtype
    assert float(abs(got["w"] - x).max()) > 0.01     # it did round
    half = correct.through_fp8({"w": x.astype(jnp.bfloat16)})["w"]
    assert half.dtype == jnp.bfloat16


def test_an_identity_block_fails_the_new_checks(trained_tiny_gpt):
    damage = trained_tiny_gpt["probes"]["identity_block"]
    damaged = damage(trained_tiny_gpt["variables"])["params"]["block1"]
    assert float(abs(damaged["proj"]["kernel"]).max()) == 0.0
    assert float(abs(damaged["qkv"]["kernel"]).max()) > 0.0
    checks = _reference_checks(trained_tiny_gpt, damage)
    assert not checks["logprob_matches_reference"]["ok"], checks
    assert not checks["gradient_matches_reference"]["ok"], checks


class _FakeClock:
    """``time.perf_counter`` for ``_loop``: moves only when a step is
    waited for."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now


class _FakeLoss:
    def __init__(self, clock, takes):
        self.clock, self.takes = clock, takes

    def block_until_ready(self):
        self.clock.now += self.takes


def _fake_loop(monkeypatch, steps, slow=None):
    """``_loop`` over a step that takes 0.2 s on a clock of the test's
    own; the waits listed in ``slow`` (by order of dispatch) take longer."""
    from benchmark.runners import train

    clock = _FakeClock()
    monkeypatch.setattr(train, "time", clock)
    dispatched = []

    def step(carry):
        dispatched.append(None)
        takes = (slow or {}).get(len(dispatched), 0.2)
        return carry, _FakeLoss(clock, takes)

    _, stamps, losses, t_start, drain_s = train._loop(
        step, [0], [], steps=steps)
    assert len(dispatched) == steps == len(losses) == len(stamps) + 1
    run = {"stamps": stamps, "items_per_step": 8192, "chips": 1}
    return run, train.stalls(stamps, drain_s), drain_s


def test_a_late_drain_is_a_note_and_not_the_windows_last_step(monkeypatch):
    from benchmark.harness import registry

    def reader(name):
        return registry.load_module(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py"))

    steady, no_stalls, drain = _fake_loop(monkeypatch, steps=20)
    assert no_stalls == [] and drain == pytest.approx(0.2)
    # the last block_until_ready, after the loop's exit, sleeps 8.7 s
    late, stalled, drain = _fake_loop(monkeypatch, steps=20, slow={20: 8.7})
    assert drain == pytest.approx(8.7)
    for name in ("train_throughput", "step_ms_p90"):
        assert reader(name).read(late) == reader(name).read(steady)
    assert reader("train_throughput").read(steady) == pytest.approx(
        8192 / 0.2)
    assert [s["where"] for s in stalled] == ["drain"]
    assert stalled[0]["ms"] == pytest.approx(8700.0)
    assert stalled[0]["median_gap_ms"] == pytest.approx(200.0)
    assert len(stalled[0]["host_loadavg"]) == 3
    # a stall inside the loop still counts, in both metrics, and is named
    inside, stalled, _ = _fake_loop(monkeypatch, steps=20, slow={7: 2.0})
    assert reader("train_throughput").read(inside) < 0.95 * 8192 / 0.2
    assert [s["where"] for s in stalled] == ["gap 6 of 18"]


def test_train_runner_counts_a_build_inside_the_window():
    from benchmark.runners import train

    import jax
    import jax.numpy as jnp

    counter = train.BuildCounter()
    before = counter.count
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    assert counter.count > before


def test_train_runner_resnet(tmp_path):
    import run as cli

    root = make_root(tmp_path)
    # 8 filters, 32 pixels, 4 images: bfloat16 against float32 is far
    # noisier than at the cell's size, whose limits are the chip's
    add_cell(root, "tiny_resnet", "resnet50_train_b256", TINY_RESNET,
             traffic="tiny", config_edits={"reference_tolerance": {
                 "loss_abs": 0.2, "logprob_abs": 0.6, "grad_norm_rel": 0.2}})
    line = cli.execute("tiny_resnet", seed=5, seconds=1.0, trace=True,
                       root=root, allow_cpu=True)
    _check_line(line, ["compile_s"])
    assert line["checks"]["matches_reference"]["ok"]
    gradient = line["checks"]["gradient_matches_reference"]
    assert set(gradient["tolerance"]) == {"grad_norm_rel"}   # ResNet's choice
    # the scope is in the program, whether or not a CPU trace shows it
    assert "optimizer_ms" not in line["metrics"]


def test_serve_runner(tmp_path):
    import run as cli

    root = make_root(tmp_path)
    add_cell(root, "tiny_serve", TINY_SERVE_CELL, {}, traffic="tiny",
             config_edits={"program": {"size": "nano"}},
             metrics=SERVE_METRICS)
    line = cli.execute("tiny_serve", seed=2**31 + 3, seconds=3.0,
                       trace=False, root=root, allow_cpu=True)
    _check_line(line, ["ttft_p95_ms", "tpot_p95_ms", "setup_s"],
                but=("parent_off_backend",))
    requests = line["attempted"]
    assert 5 <= requests <= 40          # Poisson, 6 a second for 3 s
    line = cli.execute("tiny_serve", seed=2**31 + 3, seconds=3.0,
                       trace=True, root=root, allow_cpu=True)
    # This process has run JAX for the tests above, which the command's
    # own parent never does: that one check cannot hold here.
    _check_line(line, ["decode_compute_ms", "queue_wait_ms_p95",
                       "gen_late_ms_p95"], but=("parent_off_backend",))
    assert line["attempted"] == requests    # the same seed, the same offer
    # the device's time is not measured in a served cell yet
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert set(line["checks"]) == {
        "budgets_and_vocabulary", "same_prompt_same_tokens",
        "parent_off_backend", "rank_summary"}


def test_serve_runner_refuses_to_measure_without_a_tpu():
    from benchmark.harness.device import NoAccelerator
    from benchmark.runners import serve

    with pytest.raises(NoAccelerator):  # before it starts any process
        serve.run({"params": {}, "config_values": {}}, seed=1, seconds=1.0,
                  trace=False, t0=0.0)


def test_command_line_refuses_to_measure_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "gpt2m_train_s1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 3
    assert proc.stdout.strip() == ""
    assert "without the chip" in proc.stderr
