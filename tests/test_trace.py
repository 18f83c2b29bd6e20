"""Request-level tracing + MFU profiler (obs/trace.py, obs/profile.py,
obs/trace_merge.py) — ISSUE 11.

Covers: the span ring (capacity/overwrite/accounting), deterministic
sampling (pure function of the trace id — the HVD001 invariant applied
to sampling decisions), dump/flush over the shared pathspec rules, the
``trace_drop`` chaos fault, waterfall merge + latency-decomposition
report math (component tiling, epoch stitching, missing ranks), MFU
gauge math against hand-computed FLOPs for the bench gpt shape, the
sliding token-rate window, CLI mapping, and the 2-proc serve chaos
acceptance (leader kill mid-stream -> both incarnations on the merged
waterfall, ttft components sum to the histogram's sample, perf.mfu in
the per-rank record).
"""

from __future__ import annotations

import hashlib
import json
import os
import time

import pytest

from horovod_tpu.obs import trace as obs_trace
from horovod_tpu.obs import trace_merge
from horovod_tpu.obs.profile import (
    CPU_PEAK_ESTIMATE,
    MFUProfiler,
    flops_from_compiled,
    peak_flops,
)
from horovod_tpu.obs.registry import MetricsRegistry
from horovod_tpu.testing import faults
from horovod_tpu.utils import env as envmod


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv(envmod.TRACE, raising=False)
    monkeypatch.delenv(envmod.TRACE_SAMPLE_RATE, raising=False)
    monkeypatch.delenv(envmod.TRACE_CAPACITY, raising=False)
    monkeypatch.delenv("HVDTPU_ELASTIC_EPOCH", raising=False)
    monkeypatch.delenv(faults.SPEC_ENV, raising=False)
    faults.reset()
    obs_trace.reset_buffer()
    yield
    faults.reset()
    obs_trace.reset_buffer()


# ---------------------------------------------------------------------------
# span ring
# ---------------------------------------------------------------------------

def test_ring_overwrites_oldest_and_counts_drops():
    buf = obs_trace.TraceBuffer(capacity=64)
    for i in range(100):
        buf.add({"trace": "t", "name": f"s{i}", "t0": float(i), "dur": 0.0})
    assert buf.recorded == 100
    assert buf.dropped == 36
    snap = buf.snapshot()
    assert len(snap) == 64
    # chronological, oldest surviving span first
    assert snap[0]["name"] == "s36" and snap[-1]["name"] == "s99"


def test_ring_capacity_floor():
    assert obs_trace.TraceBuffer(capacity=1).capacity == \
        obs_trace.MIN_CAPACITY


def test_add_span_stamps_env_epoch_and_explicit_epoch(monkeypatch):
    monkeypatch.setenv("HVDTPU_ELASTIC_EPOCH", "3")
    obs_trace.add_span("r1", "prefill", 1.0, 1.5, slot=0)
    obs_trace.add_span("r1", "replay_prefill", 2.0, 2.1, epoch=4)
    spans = obs_trace.get_buffer().snapshot()
    assert spans[0]["epoch"] == 3 and spans[0]["args"] == {"slot": 0}
    assert spans[1]["epoch"] == 4
    assert spans[0]["dur"] == pytest.approx(0.5)


def test_span_context_manager_records_duration():
    with obs_trace.span("r2", "work", note="x"):
        time.sleep(0.01)
    (doc,) = obs_trace.get_buffer().snapshot()
    assert doc["name"] == "work" and doc["dur"] >= 0.009
    assert doc["args"]["note"] == "x"


# ---------------------------------------------------------------------------
# deterministic sampling
# ---------------------------------------------------------------------------

def test_sampling_is_pure_function_of_id():
    """The verdict must be derivable from the id alone (sha1-based, not
    ``hash()``): recomputing the documented formula here pins it against
    PYTHONHASHSEED, process boundaries, and rank — every rank holding
    the same id reaches the SAME verdict (the HVD001 invariant applied
    to sampling decisions)."""
    ids = [f"req-{i:04d}" for i in range(500)]
    for rid in ids:
        h = int(hashlib.sha1(rid.encode()).hexdigest()[:8], 16)
        expect = (h / float(0x100000000)) < 0.3
        assert obs_trace.sampled(rid, 0.3) == expect
        # repeated calls never flip
        assert obs_trace.sampled(rid, 0.3) == expect


def test_sampling_edges_and_monotonicity():
    ids = [f"r{i}" for i in range(300)]
    assert all(obs_trace.sampled(r, 1.0) for r in ids)
    assert not any(obs_trace.sampled(r, 0.0) for r in ids)
    low = {r for r in ids if obs_trace.sampled(r, 0.2)}
    high = {r for r in ids if obs_trace.sampled(r, 0.6)}
    assert low <= high  # raising the rate only adds traces
    assert 0.05 < len(low) / len(ids) < 0.45


def test_sample_rate_env(monkeypatch):
    monkeypatch.setenv(envmod.TRACE_SAMPLE_RATE, "0.25")
    assert obs_trace.sample_rate() == 0.25


# ---------------------------------------------------------------------------
# flush / pathspec / trace_drop chaos
# ---------------------------------------------------------------------------

def test_flush_unarmed_is_none():
    obs_trace.add_span("r", "s", 0.0, 1.0)
    assert obs_trace.flush() is None


def test_flush_writes_schema_dump_via_pathspec(tmp_path, monkeypatch):
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + "/")
    monkeypatch.setenv("HVDTPU_RANK", "1")
    obs_trace.add_span("r1", "prefill", 1.0, 1.25)
    path = obs_trace.flush()
    assert path is not None and path.endswith("spans.rank.1.json")
    doc = json.loads(open(path).read())
    assert doc["schema"] == obs_trace.SCHEMA
    assert doc["rank"] == "1"
    assert doc["recorded"] == 1 and doc["dropped"] == 0
    assert doc["spans"][0]["name"] == "prefill"


def test_trace_drop_fault_suppresses_one_flush(tmp_path, monkeypatch):
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + "/")
    monkeypatch.setenv(faults.SPEC_ENV, "trace_flush:action=trace_drop")
    faults.reset()
    obs_trace.add_span("r1", "prefill", 1.0, 1.25)
    assert obs_trace.flush() is None          # suppressed (chaos)
    assert obs_trace.flush() is not None      # next flush lands


def test_trace_drop_rejected_on_non_flush_points():
    with pytest.raises(ValueError, match="trace_drop"):
        faults.parse_spec("worker_exit:action=trace_drop")


# ---------------------------------------------------------------------------
# merge + report
# ---------------------------------------------------------------------------

def _dump(tmp_path, rank, spans, epoch=""):
    from horovod_tpu.obs import pathspec

    tag = (f"e{epoch}.rank.{rank}" if epoch != "" else f"rank.{rank}")
    path = tmp_path / f"spans.{tag}.json"
    pathspec.write_json_atomic(str(path), {
        "schema": obs_trace.SCHEMA, "rank": str(rank), "pid": 1,
        "wall_time": 0.0, "capacity": 64, "recorded": len(spans),
        "dropped": 0, "sample_rate": 1.0, "spans": spans,
    })
    return str(path)


def _req_spans(rid, base, epoch=0, ttft=True):
    """One request's leader-side span chain tiling [arrival, first
    token] exactly: queue_wait 10ms + schedule_broadcast 2ms +
    admit_wait 1ms + prefill 7ms -> ttft 20ms."""
    spans = [
        {"trace": rid, "name": "queue_wait", "t0": base, "dur": 0.010,
         "epoch": epoch},
        {"trace": rid, "name": "schedule_broadcast", "t0": base + 0.010,
         "dur": 0.002, "epoch": epoch},
        {"trace": rid, "name": "admit_wait", "t0": base + 0.012,
         "dur": 0.001, "epoch": epoch},
        {"trace": rid, "name": "prefill", "t0": base + 0.013,
         "dur": 0.007, "epoch": epoch,
         "args": {"ttft_ms": 20.0} if ttft else {}},
    ]
    return spans


def test_report_components_tile_ttft_and_stitch_epochs(tmp_path):
    base = 1000.0
    r0 = _req_spans("req-a", base) + [
        # epoch-1 replay incarnation of the same request
        {"trace": "req-a", "name": "replay_prefill", "t0": base + 0.5,
         "dur": 0.004, "epoch": 1, "args": {"resumed": 3}},
        # step lane: one whole step + named phases inside it
        {"trace": "serve.steps", "name": "step", "t0": base, "dur": 0.030,
         "epoch": 0, "args": {"step": 1}},
        {"trace": "serve.steps", "name": "decode_compute", "t0": base,
         "dur": 0.020, "epoch": 0, "args": {"step": 1}},
        {"trace": "serve.steps", "name": "stream_publish",
         "t0": base + 0.020, "dur": 0.004, "epoch": 0,
         "args": {"step": 1}},
        # step-lane prefill twin (service.py emits it unsampled): its
        # time must come OUT of the scheduler residual, not hide in it
        {"trace": "serve.steps", "name": "prefill", "t0": base + 0.024,
         "dur": 0.003, "epoch": 0, "args": {"step": 1}},
    ]
    # The peer derived the same schedule AND runs the same step loop:
    # every rank emits step-lane spans, and the scheduler residual must
    # stay per-rank (pooling ranks into one (epoch, step) bucket would
    # double it here).
    r1 = _req_spans("req-a", base) + [
        {"trace": "serve.steps", "name": "step", "t0": base, "dur": 0.030,
         "epoch": 0, "args": {"step": 1}},
        {"trace": "serve.steps", "name": "decode_compute", "t0": base,
         "dur": 0.020, "epoch": 0, "args": {"step": 1}},
        {"trace": "serve.steps", "name": "stream_publish",
         "t0": base + 0.020, "dur": 0.004, "epoch": 0,
         "args": {"step": 1}},
        {"trace": "serve.steps", "name": "prefill", "t0": base + 0.024,
         "dur": 0.003, "epoch": 0, "args": {"step": 1}},
    ]
    paths = [_dump(tmp_path, 0, r0), _dump(tmp_path, 1, r1)]

    rep = trace_merge.report(paths, expected_ranks=3)
    assert rep["schema"] == trace_merge.REPORT_SCHEMA
    assert rep["ranks_present"] == ["0", "1"]
    assert rep["missing_ranks"] == [2]
    entry = rep["requests"]["req-a"]
    # the component sum equals the recorded ttft (exact tiling)
    assert entry["ttft_ms"] == 20.0
    assert entry["component_sum_ms"] == pytest.approx(20.0, abs=0.01)
    assert entry["replayed"] is True
    assert entry["epochs"] == [0, 1]
    assert entry["ranks"] == ["0", "1"]
    # fleet percentiles exist for each recorded component
    assert rep["ttft_components"]["prefill"]["p50"] == pytest.approx(7.0)
    assert rep["ttft_ms"]["n"] == 1
    # tpot: decode_compute from spans, scheduler = step - named residual
    assert rep["tpot_components"]["decode_compute"]["p50"] == \
        pytest.approx(20.0)
    assert rep["tpot_components"]["scheduler"]["p50"] == \
        pytest.approx(3.0, abs=0.01)
    assert rep["tpot_components"]["stream_publish"]["p50"] == \
        pytest.approx(4.0)


def test_report_leader_is_lowest_rank_with_prefill(tmp_path):
    # rank 1 recorded the full chain; rank 0 only saw the replay --
    # the decomposition must come from a single clock (rank 1's)
    r0 = [{"trace": "req-b", "name": "replay_prefill", "t0": 5.0,
           "dur": 0.001, "epoch": 1}]
    r1 = _req_spans("req-b", 4.0)
    rep = trace_merge.report(
        [_dump(tmp_path, 0, r0), _dump(tmp_path, 1, r1)])
    entry = rep["requests"]["req-b"]
    # rank 0 has replay_prefill so it wins leader; its components are
    # empty -> no ttft claim ever gets made from a partial chain
    assert entry["ranks"] == ["0", "1"]
    assert entry["replayed"] is True


def test_merge_waterfall_lanes_and_epoch_tids(tmp_path):
    base = 50.0
    r0 = _req_spans("req-a", base) + [
        {"trace": "req-a", "name": "replay_prefill", "t0": base + 1.0,
         "dur": 0.004, "epoch": 1},
        {"trace": "serve.steps", "name": "step", "t0": base, "dur": 0.01,
         "epoch": 0, "args": {"step": 1}},
    ]
    launcher = [{"trace": "req-a", "name": "ingest", "t0": base - 0.01,
                 "dur": 0.01, "epoch": 0}]
    paths = [_dump(tmp_path, 0, r0),
             _dump(tmp_path, "launcher", launcher)]
    out = tmp_path / "wf.json"
    n = trace_merge.merge(paths, str(out))
    events = json.loads(out.read_text())
    xs = [e for e in events if e["ph"] == "X"]
    assert len(xs) == n == len(r0) + len(launcher)
    # step lane gets pid 1 (context first), request lane pid 2
    names = {m["args"]["name"]: m["pid"] for m in events
             if m["ph"] == "M" and m["name"] == "process_name"}
    assert names["serve.steps"] == 1 and names["req-a"] == 2
    # (rank, epoch) -> distinct tid: the replay incarnation is its own
    # sub-lane inside the request's pid
    req_tids = {(e["args"]["rank"], e["args"]["epoch"]): e["tid"]
                for e in xs if e["pid"] == names["req-a"]}
    assert req_tids[("0", 0)] != req_tids[("0", 1)]
    assert ("launcher", 0) in req_tids
    # wall-clock rebased to the job's earliest span
    assert min(e["ts"] for e in xs) == 0.0


def test_merge_glob_end_to_end_and_no_self_consumption(tmp_path,
                                                      monkeypatch):
    monkeypatch.setenv(envmod.TRACE, str(tmp_path) + "/")
    monkeypatch.setenv("HVDTPU_RANK", "0")
    obs_trace.add_span("req-x", "prefill", 1.0, 1.1,
                       ttft_ms=100.0)
    obs_trace.flush()
    out = trace_merge.merge_glob(str(tmp_path) + "/", expected_ranks=1)
    assert out is not None and out["events"] == 1
    assert out["doc"]["missing_ranks"] == []
    # a second merge must not re-ingest its own waterfall/report
    out2 = trace_merge.merge_glob(str(tmp_path) + "/", expected_ranks=1)
    assert out2["events"] == 1


def test_merge_tolerates_torn_file(tmp_path):
    good = _dump(tmp_path, 0, _req_spans("req-a", 1.0))
    bad = tmp_path / "spans.rank.1.json"
    bad.write_text('{"schema": "hvdtpu-trace-v1", "spans": [tr')
    rep = trace_merge.report([good, str(bad)], expected_ranks=2)
    assert rep["ranks_present"] == ["0"]
    assert rep["missing_ranks"] == [1]


def test_trace_merge_cli(tmp_path, capsys):
    _dump(tmp_path, 0, _req_spans("req-a", 1.0))
    rc = trace_merge.main([str(tmp_path / "out"),
                           str(tmp_path / "spans.rank.0.json")])
    assert rc == 0
    assert (tmp_path / "out.waterfall.json").exists()
    rep = json.loads((tmp_path / "out.report.json").read_text())
    assert "req-a" in rep["requests"]


# ---------------------------------------------------------------------------
# MFU profiler math
# ---------------------------------------------------------------------------

def test_peak_flops_table_and_estimate_flag():
    peak, est = peak_flops("TPU v4")
    assert peak == 275e12 and est is False
    peak32, _ = peak_flops("TPU v4", "fp32")
    assert peak32 == 275e12 / 4
    peak_cpu, est_cpu = peak_flops("cpu")
    assert peak_cpu == CPU_PEAK_ESTIMATE and est_cpu is True


def test_mfu_profiler_gauge_math():
    reg = MetricsRegistry()
    prof = MFUProfiler(2.75e12, "TPU v4", registry=reg)
    mfu = prof.observe(0.02)  # 2.75e12 / 0.02s = 137.5 TFLOP/s
    assert mfu == pytest.approx(137.5e12 / 275e12)
    assert reg.gauge("perf.mfu").value == pytest.approx(0.5)
    assert reg.gauge("perf.model_tflops").value == pytest.approx(137.5)
    assert reg.gauge("perf.step_ms").value == pytest.approx(20.0)
    assert reg.gauge("perf.mfu_estimate").value == 0.0
    s = prof.summary()
    assert s["mfu"] == 0.5 and s["estimate"] is False
    assert s["flops_source"] == "cost_analysis"


def test_mfu_profiler_estimate_flag_and_unknown_flops():
    reg = MetricsRegistry()
    prof = MFUProfiler(None, "cpu", registry=reg)
    assert prof.observe(0.01) is None       # step time lands anyway
    assert reg.gauge("perf.step_ms").value == pytest.approx(10.0)
    assert reg.gauge("perf.mfu_estimate").value == 1.0
    assert prof.summary()["mfu"] is None
    assert prof.summary()["estimate"] is True


def test_flops_from_compiled_real_artifact():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    compiled = f.lower(jnp.zeros((64, 64)), jnp.zeros((64, 64))).compile()
    flops = flops_from_compiled(compiled)
    # 2*M*N*K matmul FLOPs, as XLA counts them
    assert flops == pytest.approx(2 * 64 ** 3, rel=0.5)

    class _NoCost:
        def cost_analysis(self):
            raise NotImplementedError

    assert flops_from_compiled(_NoCost()) is None


# ---------------------------------------------------------------------------
# sliding token-rate window
# ---------------------------------------------------------------------------

def test_rate_window_sliding_and_early_epoch():
    from horovod_tpu.serve.service import RateWindow

    w = RateWindow(window_secs=5.0)
    assert w.rate(0.0) == 0.0   # nothing observed yet
    w.observe(0.0, 10)
    # before the window fills, divide by elapsed (early-epoch semantics)
    assert w.rate(2.0) == pytest.approx(10 / 2.0)
    w.observe(4.0, 10)
    assert w.rate(5.0) == pytest.approx(20 / 5.0)
    # the t=0 event slides out of [1.0, 6.0]
    assert w.rate(6.0) == pytest.approx(10 / 5.0)
    # all events expired -> zero, not a stale rate
    assert w.rate(100.0) == 0.0


# ---------------------------------------------------------------------------
# CLI mapping
# ---------------------------------------------------------------------------

def test_trace_cli_knobs_to_env():
    from horovod_tpu.run.config_parser import set_env_from_args
    from horovod_tpu.run.runner import parse_args

    args = parse_args(["-np", "2", "--trace", "/tmp/tr/",
                       "--trace-sample-rate", "0.5", "python", "x"])
    env = {}
    set_env_from_args(env, args)
    assert env[envmod.TRACE] == "/tmp/tr/"
    assert env[envmod.TRACE_SAMPLE_RATE] == "0.5"


def test_trace_cli_knobs_arm_the_launcher_process(monkeypatch):
    """--trace must arm the LAUNCHER's own os.environ too: the ingest
    pump and client result fetches are launcher-side span producers,
    and a flag-given sample rate must not diverge from the workers'."""
    from horovod_tpu.run.runner import _arm_launcher_trace_env

    monkeypatch.delenv(envmod.TRACE, raising=False)
    monkeypatch.delenv(envmod.TRACE_SAMPLE_RATE, raising=False)
    _arm_launcher_trace_env({envmod.TRACE: "/tmp/tr/",
                             envmod.TRACE_SAMPLE_RATE: "0.5"})
    assert os.environ[envmod.TRACE] == "/tmp/tr/"
    assert os.environ[envmod.TRACE_SAMPLE_RATE] == "0.5"
    # No flags -> no writes (an inherited shell export is untouched).
    monkeypatch.setenv(envmod.TRACE, "/from/shell/")
    _arm_launcher_trace_env({})
    assert os.environ[envmod.TRACE] == "/from/shell/"


def test_stale_merged_outputs_removed_for_plain_path_target(tmp_path,
                                                            monkeypatch):
    """A crashed re-run must not inherit the previous run's merged
    waterfall/report as its own — for EVERY target form, not just the
    directory one."""
    from horovod_tpu.run.runner import _clean_stale_obs_files

    target = str(tmp_path / "sp.json")
    wf, rep = trace_merge.merged_output_paths(target)
    for p in (wf, rep):
        with open(p, "w") as fh:
            fh.write("{}")
    keeper = tmp_path / "unrelated.json"
    keeper.write_text("{}")
    _clean_stale_obs_files({envmod.TRACE: target})
    assert not os.path.exists(wf) and not os.path.exists(rep)
    assert keeper.exists()


# ---------------------------------------------------------------------------
# 2-proc serve chaos acceptance (ISSUE 11)
# ---------------------------------------------------------------------------

@pytest.mark.multiprocess
def test_trace_acceptance_leader_kill_waterfall_and_mfu(tmp_path,
                                                        monkeypatch):
    """ISSUE 11 acceptance: 2-proc serving fleet with tracing armed,
    leader killed mid-stream.  The merged waterfall carries spans from
    both ranks and both incarnations of the replayed requests (stitched
    by epoch), every decomposed ttft's components sum to the recorded
    histogram sample within 5%, and the per-rank result embeds a
    cost_analysis()-derived perf.mfu, estimate-flagged on CPU."""
    jnp = pytest.importorskip("jax.numpy")
    import numpy as np

    from horovod_tpu.serve import ServeJob

    trace_dir = str(tmp_path) + "/"
    # launcher-side spans (ingest pump, result fetch) need the env in
    # THIS process; the worker fleet gets it through the env dict.
    monkeypatch.setenv(envmod.TRACE, trace_dir)
    obs_trace.reset_buffer()

    overrides = dict(num_layers=1, num_heads=2, emb_dim=32, max_len=64,
                     vocab_size=64, dtype=jnp.float32,
                     attention_impl="reference")
    spec = {"size": "nano", "overrides": overrides, "seed": 3,
            "num_slots": 2, "idle_secs": 0.005}
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 64, rs.randint(3, 9)).tolist()
               for _ in range(6)]
    steps = [3, 4, 5, 6, 4, 5]

    job = ServeJob(
        spec, np=2,
        env={"JAX_PLATFORMS": "cpu",
             "HVDTPU_TRACE": trace_dir,
             "HVDTPU_FAULT_SPEC": "worker_exit:step=6:rank=0"},
        max_retries=2, timeout=300,
    ).start()
    try:
        rids = []
        for p, s in zip(prompts, steps):
            rids.append(job.client.submit(p, max_new_tokens=s))
            time.sleep(0.05)
        docs = [job.client.result(r, timeout=240) for r in rids]
        results, ejob = job.stop()
    finally:
        job.shutdown()

    assert len(docs) == 6  # zero dropped through the kill
    assert [e[0] for e in ejob.trace].count("respawn") == 1

    # -- per-rank record embeds the cost_analysis MFU, estimate-flagged
    for rank, res in results.items():
        perf = res["perf"]
        assert perf["estimate"] is True          # CPU peak is a guess
        assert perf["flops_source"] == "cost_analysis"
        assert perf["flops_per_step"] and perf["flops_per_step"] > 0
        assert perf["mfu"] is not None and perf["mfu"] > 0

    # -- merged artifacts landed (ServeJob.shutdown ran the merge)
    wf_path = tmp_path / "trace_waterfall.json"
    rep_path = tmp_path / "trace_report.json"
    assert wf_path.exists() and rep_path.exists()

    events = json.loads(wf_path.read_text())
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs, "waterfall has no spans"
    span_ranks = {e["args"]["rank"] for e in xs}
    assert {"0", "1"} <= span_ranks, f"spans from {span_ranks} only"

    rep = json.loads(rep_path.read_text())
    assert rep["schema"] == trace_merge.REPORT_SCHEMA
    assert rep["missing_ranks"] == []
    assert set(rids) <= set(rep["requests"])

    # -- the kill produced at least one replayed request whose lane
    # carries BOTH incarnations, stitched by epoch
    replayed = [r for r in rep["requests"].values() if r["replayed"]]
    assert replayed, "leader kill mid-stream replayed no request"
    assert any(len(r["epochs"]) >= 2 for r in replayed)

    # -- every decomposed ttft: components sum to the histogram's
    # sample within 5% (sub-ms slack for float rounding)
    checked = 0
    for entry in rep["requests"].values():
        if entry["ttft_ms"] is None:
            continue
        checked += 1
        assert entry["component_sum_ms"] == pytest.approx(
            entry["ttft_ms"], rel=0.05, abs=0.5,
        ), f"decomposition does not tile ttft: {entry}"
    assert checked >= 4  # most requests decomposed on the leader clock

    # fleet-level percentiles exist for the core components
    assert rep["ttft_components"].get("prefill")
    assert rep["tpot_components"].get("decode_compute")

    # -- launcher-side spans (ingest pump) merged into the same view
    assert "launcher" in rep["ranks_present"]
