"""The four readers that read what the program names itself (PR 24):
the flash kernels by their ``pallas_call`` names, on a recording made
after the kernels were named, and the program's compile log."""

import json
import os

import pytest

from benchmark.harness import registry
from benchmark.harness import trace as tr
from helpers import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")
NAMED = "gpt2m_train_s1024.named_kernels.two_steps"
UNNAMED = "gpt2m_train_s1024.two_steps"     # PR 23: kernels ``block<i>.<k>``


def _reader(name):
    return registry.load_module(os.path.join(ROOT, "benchmark", "metrics",
                                             name + ".py"))


def _traced_run(recording, steps=2):
    rec = tr.load_recording(os.path.join(DATA, recording + ".json.gz"))
    return {"trace": {"ops": tr.device_ops(rec), "steps": steps}}


def test_flash_forward_and_backward_by_kernel_name():
    with open(os.path.join(DATA, NAMED + ".expect.json")) as f:
        expect = json.load(f)["kernel_ns"]
    run = _traced_run(NAMED)
    forward = _reader("flash_fwd_ms").read(run)
    backward = _reader("flash_bwd_ms").read(run)
    assert forward == pytest.approx(expect["^tpu_custom_call:flash_fwd"]
                                    / 2 / 1e6)
    assert backward == pytest.approx(
        (expect["^tpu_custom_call:flash_bwd_dkdv"]
         + expect["^tpu_custom_call:flash_bwd_dq"]) / 2 / 1e6)
    # the two halves are the whole of what flash_ms reads
    assert forward + backward == pytest.approx(
        _reader("flash_ms").read(run), rel=1e-9)
    assert expect["^tpu_custom_call:block"] == 0


@pytest.mark.parametrize("name", ["flash_fwd_ms", "flash_bwd_ms"])
def test_flash_split_finds_nothing_in_an_unnamed_trace(name):
    """The parent commit's traced run simply lacks the metric."""
    assert _reader(name).read(_traced_run(UNNAMED)) is None
    assert _reader(name).read({"trace": None}) is None
    assert _reader("flash_ms").read(_traced_run(UNNAMED)) > 0


def test_a_second_pallas_kernel_is_not_counted_as_attention():
    run = {"trace": {"steps": 1, "ops": {0: [
        ["tpu_custom_call:flash_fwd.2", 0, 4e6],
        ["tpu_custom_call:flash_bwd_dkdv.2", 5e6, 3e6],
        ["tpu_custom_call:flash_bwd_dq.2", 9e6, 2e6],
        ["tpu_custom_call:fused_head.1", 12e6, 7e6],
        ["tpu_custom_call:flash_fwd_paged.1", 20e6, 7e6],
        ["fusion.flash_fwd", 30e6, 7e6]]}}}
    assert _reader("flash_fwd_ms").read(run) == pytest.approx(4.0)
    assert _reader("flash_bwd_ms").read(run) == pytest.approx(5.0)


LOG = [
    {"program": "make_state", "phase": "trace", "seconds": 1.0, "t_end": 5.0},
    {"program": "make_state", "phase": "lower", "seconds": 0.5, "t_end": 6.0},
    {"program": "make_state", "phase": "backend", "seconds": 9.0,
     "t_end": 16.0, "cache": "miss"},
    {"program": "local_step", "phase": "trace", "seconds": 4.0, "t_end": 21.0},
    {"program": "local_step", "phase": "lower", "seconds": 2.0, "t_end": 23.0},
    {"program": "local_step", "phase": "backend", "seconds": 0.7,
     "t_end": 24.0, "cache": "hit"},
    # after the window's first stamp: the reference check's program
    {"program": "program_loss", "phase": "trace", "seconds": 3.0,
     "t_end": 80.0},
    {"program": "program_loss", "phase": "backend", "seconds": 3.0,
     "t_end": 85.0, "cache": "miss"},
]


def test_compile_readers_read_the_log_up_to_the_window(monkeypatch):
    from horovod_tpu.obs import profile

    monkeypatch.setattr(profile, "compile_log", lambda: list(LOG),
                        raising=False)
    run = {"stamps": [30.0, 30.2, 30.4]}
    assert _reader("compile_trace_lower_s").read(run) == pytest.approx(7.5)
    assert _reader("compile_cache_misses").read(run) == 1
    warm = [dict(r, cache="hit") if "cache" in r else r for r in LOG]
    monkeypatch.setattr(profile, "compile_log", lambda: warm)
    assert _reader("compile_cache_misses").read(run) == 0   # healthy


@pytest.mark.parametrize("name", ["compile_trace_lower_s",
                                  "compile_cache_misses"])
def test_compile_readers_find_nothing_without_a_log(monkeypatch, name):
    from horovod_tpu.obs import profile

    run = {"stamps": [30.0, 30.2]}
    # a program without the log (the parent commit)
    monkeypatch.delattr(profile, "compile_log", raising=False)
    assert _reader(name).read(run) is None
    # a log nobody armed, and a runner without ready stamps (serve)
    monkeypatch.setattr(profile, "compile_log", lambda: [], raising=False)
    assert _reader(name).read(run) is None
    monkeypatch.setattr(profile, "compile_log", lambda: list(LOG))
    assert _reader(name).read({"requests": []}) is None


def test_the_four_entries_are_appended_and_name_their_cells():
    bench = registry.benchmark_json(ROOT)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == ["flash_fwd_ms", "flash_bwd_ms",
                          "compile_trace_lower_s", "compile_cache_misses"]
    gpt = {"gpt2m_train_s1024", "gpt2m_train_dp4"}
    cells = {w["name"] for w in bench["workloads"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(by_name["flash_fwd_ms"]["workloads"]) == gpt
    assert set(by_name["flash_bwd_ms"]["workloads"]) == gpt
    assert set(by_name["compile_trace_lower_s"]["workloads"]) == cells
    assert set(by_name["compile_cache_misses"]["workloads"]) == cells
