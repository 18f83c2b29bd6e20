"""The reduction from a profiler trace to busy time, idle share, kernel
time by name and exposed collective time: first on intervals small
enough to check by eye, then on a recording cut from a real trace of
this benchmark on a TPU v5 lite (tests/data/)."""

import os
import re

import pytest

from benchmark.harness import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_union_and_subtract():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert tr.total(tr.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert tr.subtract([(0, 10)], [(1, 2), (4, 12)]) == [(0, 1), (2, 4)]
    assert tr.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert tr.subtract([(0, 3)], []) == [(0, 3)]


def test_busy_is_a_union_not_a_sum():
    ops = [["fusion.1", 0, 10], ["copy.2", 5, 10], ["fusion.3", 30, 10]]
    busy, window, merged = tr.busy(ops)
    assert (busy, window) == (25, 40)          # not 30: two ops overlap
    assert merged == [(0, 15), (30, 40)]
    assert tr.busy([]) == (0.0, 0.0, [])


def test_time_by_name_and_kernel_matching():
    ops = [["fusion.1", 0, 10], ["flash_fwd.2", 10, 7],
           ["flash_fwd.2", 20, 7], ["all-reduce.5", 30, 4]]
    assert tr.time_by_name(ops) == {"fusion.1": 10, "flash_fwd.2": 14,
                                    "all-reduce.5": 4}
    assert sum(e[2] for e in tr.matching(ops, re.compile("flash"))) == 14
    assert tr.top({"a": 2e9, "b": 5e9, "c": 1e9}, n=2) == [["b", 5.0],
                                                           ["a", 2.0]]


def test_exposed_collective_time():
    # all-reduce runs 8..18; other ops cover 0..10 and 15..20
    ops = [["fusion.1", 0, 10], ["all-reduce.1", 8, 10],
           ["fusion.2", 15, 5]]
    assert tr.exposed(ops, tr.COLLECTIVE) == (10, 5)
    # fully hidden, and fully exposed
    assert tr.exposed([["fusion.1", 0, 10], ["all-reduce.1", 2, 3]],
                      tr.COLLECTIVE) == (3, 0)
    assert tr.exposed([["fusion.1", 0, 10], ["all-gather.1", 12, 3]],
                      tr.COLLECTIVE) == (3, 3)


def test_idle_gaps_are_named_by_what_the_host_did():
    merged = [(0, 10), (14, 20), (30, 31)]
    spans = [["wait_loss", 12, 10]]
    assert tr.idle_gaps(merged, spans) == {"wait_loss": 4,
                                           "between_steps": 10}
    assert tr.idle_gaps([(0, 5)], spans) == {}


def test_device_time_averages_devices_and_finds_the_worst():
    ops = {0: [["fusion.1", 0, 8e9], ["fusion.2", 9e9, 1e9]],
           1: [["fusion.1", 0, 5e9], ["fusion.2", 9e9, 1e9]]}
    out = tr.device_time(ops, [["dispatch", 8e9, 1e9]])
    assert out["device"] == {"busy_s": 7.5, "window_s": 10.0}
    assert out["idle_share_worst"] == pytest.approx(0.4)
    assert out["breakdown"]["device_ops"] == [["fusion", 9.0]]
    assert out["breakdown"]["idle_gaps"] == [["dispatch", 1.0]]
    assert tr.device_time({}, []) is None


@pytest.mark.parametrize("name", sorted(
    f for f in os.listdir(DATA) if f.endswith(".json.gz"))
    if os.path.isdir(DATA) else [])
def test_recorded_trace(name):
    """A recording made by ``run.py --dump-trace`` on the chip and cut
    to two steps: the numbers below were read off it by hand (see
    data/README.txt)."""
    import json

    rec = tr.load_recording(os.path.join(DATA, name))
    with open(os.path.join(DATA, name.replace(".json.gz",
                                              ".expect.json"))) as f:
        expect = json.load(f)
    ops = tr.device_ops(rec)
    assert sorted(ops) == expect["devices"]
    first = ops[expect["devices"][0]]
    busy, window, merged = tr.busy(first)
    assert busy == pytest.approx(expect["busy_ns"])
    assert window == pytest.approx(expect["window_ns"])
    assert busy <= window
    for pattern, want in expect["kernel_ns"].items():
        got = sum(e[2] for e in tr.matching(first, re.compile(pattern)))
        assert got == pytest.approx(want), pattern
    total, alone = tr.exposed(first, tr.COLLECTIVE)
    assert total == pytest.approx(expect["collective_ns"])
    assert alone == pytest.approx(expect["collective_exposed_ns"])
    assert alone <= total
    gaps = tr.idle_gaps(merged, tr.host_spans(rec, expect["host_spans"]))
    assert sum(gaps.values()) == pytest.approx(window - busy)


@pytest.mark.parametrize("text, code, name", [
    ('%psum.220 = f32[1024,50257]{0,1:T(8,128)} all-reduce('
     '%bitcast_convert_fusion), channel_id=1, replica_groups={{0,1,2,3}}',
     "all-reduce", "all-reduce:psum.220"),
    ('%all-reduce.81 = (f32[12596224]{0:T(1024)}, f32[12596224]'
     '{0:T(1024)}) all-reduce(%a, %b), channel_id=1',
     "all-reduce", "all-reduce.81"),
    ('%fusion.21 = (f32[1024,50257]{0,1:T(8,128)}, f32[1024,50257]'
     '{0,1:T(8,128)}) fusion(f32[1024,50257]{0,1:T(8,128)} %p), '
     'kind=kOutput, calls=%fused_computation.28', "fusion", "fusion.21"),
    ('%block0.3 = (bf16[128,1024,64]{2,1,0:T(8,128)(2,1)}, f32[128,1024,'
     '128]{2,1,0:T(8,128)S(1)}) custom-call(bf16[128,1024,64]{2,1,0:'
     'T(8,128)(2,1)} %bitcast.2665), custom_call_target="tpu_custom_call"',
     "custom-call", "tpu_custom_call:block0.3"),
    ('%custom-call.58 = f32[12592128]{0:T(1024)S(1)} custom-call(f32[8]{0}'
     ' %slice-done), custom_call_target="ConcatBitcast"',
     "custom-call", "custom-call.58"),
    ("dispatch", "", "dispatch"),
])
def test_operation_names_as_the_tpu_profiler_writes_them(text, code, name):
    """The texts are events of a real trace (TPU v5 lite, PR 23)."""
    assert tr.op_code(text) == code
    assert tr.op_name(text) == name
    assert bool(tr.COLLECTIVE.match(name)) == (code == "all-reduce")
    assert bool(tr.PALLAS.match(name)) == name.startswith("tpu_custom")


def test_stems_group_operations_of_one_kind():
    assert tr.stem("fusion.21") == "fusion"
    assert tr.stem("tpu_custom_call:block23.5") == "tpu_custom_call:block"
    assert tr.stem("all-reduce:psum.220") == "all-reduce:psum"
    assert tr.stem("copy-done") == "copy-done"


# ---- the loader's own decoding of the profiler's file, with scopes ----

from helpers import BWD, FWD, xplane_slice  # noqa: E402


@pytest.fixture
def xplane_file(tmp_path):
    return xplane_slice(tmp_path)


def test_the_loader_keeps_name_time_and_scope(xplane_file):
    trace = tr.load_xplane(xplane_file, host_names=("dispatch", "wait_loss"))
    assert [p["name"] for p in trace["planes"]] == ["/device:TPU:0",
                                                    "/host:CPU"]
    ops = tr.device_ops(trace)[0]
    assert [e[0] for e in ops] == [
        "copy-start.17", "fusion", "convolution_add_fusion.3",
        "tpu_custom_call:flash_fwd.2", "convolution_add_fusion.1",
        "fusion.399", "multiply_reduce_fusion.9", "fusion.403",
        "tpu_custom_call:flash_bwd_dq.2", "fusion.225", "all-reduce.81",
        "fusion.297", "fusion.298", "fusion.6"]
    assert ops[0] == ["copy-start.17", 1000.0, 2000.0, ""]     # no scope
    assert ops[3][1:] == [16000.0, 20000.0,
                          FWD + "block0/attn/flash_fwd/pallas_call:"]
    assert ops[7][3] == BWD + "block0/mlp/fc2/dot_general:"   # by reference
    assert tr.host_spans(trace, ("dispatch", "wait_loss")) == [
        ["dispatch", 1900.0, 500.0, ""], ["wait_loss", 2900.0, 150000.0, ""]]
    busy, window, _ = tr.busy(ops)
    assert (busy, window) == (155500.0, 155500.0)


def test_scopes_read_forward_and_backward_together(xplane_file):
    ops = tr.device_ops(tr.load_xplane(xplane_file))[0]
    assert tr.scope_names(ops[2][3]) == ("block0", "attn", "qkv")
    assert tr.scope_names(ops[9][3]) == ("block0", "attn", "proj")
    assert tr.scope_names(ops[1][3]) == ("embed", "wte")
    assert tr.scope_names(ops[13][3]) == () == tr.scope_names("")
    assert [e[0] for e in tr.under(ops, "attn")] == [
        "convolution_add_fusion.3", "tpu_custom_call:flash_fwd.2",
        "tpu_custom_call:flash_bwd_dq.2", "fusion.225"]
    assert sum(e[2] for e in tr.under(ops, "allreduce")) == 9000.0
    assert tr.under(ops, "att") == []          # a name, not a prefix
    known = ["attn", "mlp", "head", "optimizer_update"]
    assert tr.time_by_scope(ops, known) == {
        "attn": 74000.0, "mlp": 36000.0, "head": 24000.0,
        "optimizer_update": 7000.0, "allreduce": 9000.0, "wte": 3000.0,
        "unscoped": 2500.0}
    # without a reader's scope an operation goes under its innermost name
    assert tr.time_by_scope(ops)["qkv"] == 10000.0
    out = tr.device_time({0: ops}, [], known)
    listed = out["breakdown"]["device_scopes"]
    assert [name for name, _ in listed] == [
        "attn", "mlp", "head", "allreduce", "optimizer_update", "wte",
        "unscoped"]
    assert [s for _, s in listed] == pytest.approx(
        [74e-6, 36e-6, 24e-6, 9e-6, 7e-6, 3e-6, 2.5e-6])
    assert out["breakdown"]["device_ops"][0] == ["fusion", pytest.approx(
        56.5e-6)]
    # unscoped closes the list however large, and is never cut from it
    many = {f"s{i}": float(i + 1) for i in range(12)} | {"unscoped": 99.0}
    listed = tr.top(many, last="unscoped", scale=1.0)
    assert len(listed) == 10 and listed[-1] == ["unscoped", 99.0]
    assert listed[0] == ["s11", 12.0]


def test_recordings_saved_without_scopes_still_load():
    for name in os.listdir(DATA):
        if not name.endswith(".json.gz"):
            continue
        ops = tr.device_ops(tr.load_recording(os.path.join(DATA, name)))
        first = next(iter(ops.values()))
        assert all(len(e) == 4 and e[3] == "" for e in first)
        assert tr.under(first, "attn") == []
        assert set(tr.time_by_scope(first)) == {"unscoped"}
