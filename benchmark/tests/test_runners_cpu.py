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
                           "nothing_built_in_window", "matches_reference"}
    assert checks["matches_reference"]["abs_diff"] < 1e-2


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
    add_cell(root, "tiny_resnet", "resnet50_train_b256", TINY_RESNET,
             traffic="tiny")
    line = cli.execute("tiny_resnet", seed=5, seconds=1.0, trace=True,
                       root=root, allow_cpu=True)
    _check_line(line, ["compile_s"])
    assert line["checks"]["matches_reference"]["ok"]


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
