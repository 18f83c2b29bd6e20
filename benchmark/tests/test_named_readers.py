"""The readers that read what the program names itself: the flash
kernels by their ``pallas_call`` names, on a recording made after the
kernels were named, the program's compile log (PR 24), and the device
time under a scope, on a slice of the profiler's own file (PR 27)."""

import json
import os

import pytest

from benchmark.harness import registry
from benchmark.harness import trace as tr
from helpers import ROOT, xplane_slice

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


SCOPE_READERS = {"attn_ms": 74.0, "mlp_ms": 36.0, "head_ms": 24.0,
                 "optimizer_ms": 7.0}      # ms in the slice, 1000 x real


def _slice_run(tmp_path, steps=1):
    trace = tr.load_xplane(xplane_slice(tmp_path))
    # the slice's times are in microseconds; a thousand times longer and
    # they read as the milliseconds of a real step
    ops = {d: [[n, a * 1e3, b * 1e3, s] for n, a, b, s in events]
           for d, events in tr.device_ops(trace).items()}
    return {"trace": {"ops": ops, "steps": steps}}


@pytest.mark.parametrize("name", sorted(SCOPE_READERS))
def test_scope_readers_sum_forward_and_backward(tmp_path, name):
    run = _slice_run(tmp_path)
    assert _reader(name).read(run) == pytest.approx(SCOPE_READERS[name])
    two_steps = _slice_run(tmp_path, steps=2)
    assert _reader(name).read(two_steps) == pytest.approx(
        SCOPE_READERS[name] / 2)
    # a second device that ran nothing under the scope: the median of
    # the two, as the other per-device readers take it
    run["trace"]["ops"][1] = [["copy.1", 0.0, 5e6, ""]]
    assert _reader(name).read(run) == pytest.approx(SCOPE_READERS[name] / 2)
    # nothing to read: a recording without scopes, an untraced run
    assert _reader(name).read(_traced_run(NAMED)) is None
    assert _reader(name).read({"trace": None}) is None


def test_the_older_readers_read_the_same_events_from_the_new_loader(
        tmp_path):
    run = _slice_run(tmp_path)
    assert _reader("flash_fwd_ms").read(run) == pytest.approx(20.0)
    assert _reader("flash_bwd_ms").read(run) == pytest.approx(30.0)
    assert _reader("flash_ms").read(run) == pytest.approx(50.0)
    assert _reader("allreduce_ms").read(run) == pytest.approx(9.0)
    assert _reader("allreduce_exposed_ms").read(run) == pytest.approx(9.0)
    # the kernels are inside attn_ms, which is the point of the split
    assert _reader("attn_ms").read(run) - _reader("flash_ms").read(run) \
        == pytest.approx(24.0)


def test_collective_readers_on_the_four_chip_recording():
    """``allreduce_ms`` and ``allreduce_exposed_ms`` on a recording
    saved without scopes give the numbers its expect file always had."""
    name = "gpt2m_train_dp4.one_step_device0"
    with open(os.path.join(DATA, name + ".expect.json")) as f:
        expect = json.load(f)
    run = _traced_run(name, steps=1)
    assert _reader("allreduce_ms").read(run) == pytest.approx(
        expect["collective_ns"] / 1e6)
    assert _reader("allreduce_exposed_ms").read(run) == pytest.approx(
        expect["collective_exposed_ns"] / 1e6)
    assert _reader("flash_ms").read(run) == pytest.approx(
        expect["kernel_ns"]["^tpu_custom_call:"] / 1e6)


def test_every_reader_of_a_scope_names_it_for_the_breakdown():
    assert sorted(registry.reader_scopes(ROOT)) == [
        "attn", "head", "mlp", "optimizer_update"]


def test_the_scope_entries_are_appended_and_name_their_cells():
    bench = registry.benchmark_json(ROOT)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-4:] == ["attn_ms", "mlp_ms", "head_ms", "optimizer_ms"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    gpt = {"gpt2m_train_s1024", "gpt2m_train_dp4"}
    for name in ("attn_ms", "mlp_ms", "head_ms"):
        assert by_name[name]["layer"] == "Models"
    assert by_name["optimizer_ms"]["layer"] == "Step builder"
    for name in names[-4:]:
        # not ResNet's cell: XLA fuses its SGD update into the backward
        # convolutions' fusions, and 7 us a step are left under the scope
        assert set(by_name[name]["workloads"]) == gpt
        assert by_name[name]["moves"] == "train_throughput"
        assert by_name[name]["source"] == "device_trace"
        assert by_name[name]["unit"] == "ms"


def test_the_four_entries_are_appended_and_name_their_cells():
    bench = registry.benchmark_json(ROOT)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-8:-4] == ["flash_fwd_ms", "flash_bwd_ms",
                            "compile_trace_lower_s", "compile_cache_misses"]
    gpt = {"gpt2m_train_s1024", "gpt2m_train_dp4"}
    cells = {w["name"] for w in bench["workloads"]}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(by_name["flash_fwd_ms"]["workloads"]) == gpt
    assert set(by_name["flash_bwd_ms"]["workloads"]) == gpt
    assert set(by_name["compile_trace_lower_s"]["workloads"]) == cells
    assert set(by_name["compile_cache_misses"]["workloads"]) == cells
