"""Campaign plane (ISSUE 19): resumable sweep campaigns
(horovod_tpu/bench/campaign.py), step-time anatomy (obs/anatomy.py) and
the perf-trend observatory (obs/trend.py).

The journal-atomicity chaos test runs the campaign CLI in a subprocess:
``action=abort`` delivers a real SIGABRT and must kill the campaign
driver, not the pytest process.  Everything else is in-process with an
injected runner (run_campaign's test seam).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys

import pytest

from horovod_tpu.bench import campaign
from horovod_tpu.obs import anatomy, trend
from horovod_tpu.testing import faults

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ spec/expand

def _grid_spec(**over):
    spec = {
        "name": "t",
        "base_args": ["--model", "resnet18"],
        "axes": {
            "overlap": ["off", "bucket"],
            "grad_bucket_mb": [2, 4],
            "hierarchical": [False, True],
        },
        "points": [],
        "retry_degraded": 1,
        "point_budget_secs": 60,
    }
    spec.update(over)
    return spec


def test_expand_grid_collapses_inert_bucket_axis():
    """overlap=off makes the bucket knob inert: the 2x2x2 grid yields
    (1 + 2) x 2 = 6 points, not 8."""
    points = campaign.expand_points(_grid_spec())
    assert len(points) == 6
    off = [p for p in points if p["knobs"].get("overlap") == "off"]
    assert len(off) == 2
    assert all("grad_bucket_mb" not in p["knobs"] for p in off)


def test_compile_key_classification():
    """Runtime env toggles (hierarchical/replay) share an executable;
    a bucket-size change does not."""
    spec = _grid_spec(axes={
        "overlap": ["bucket"],
        "grad_bucket_mb": [2, 4],
        "hierarchical": [False, True],
    })
    points = campaign.expand_points(spec)
    by_knobs = {tuple(sorted(p["knobs"].items())): p for p in points}
    k = by_knobs[(("grad_bucket_mb", "2"), ("hierarchical", "0"),
                  ("overlap", "bucket"))]["compile_key"]
    same_exe = by_knobs[(("grad_bucket_mb", "2"), ("hierarchical", "1"),
                         ("overlap", "bucket"))]["compile_key"]
    other_bucket = by_knobs[(("grad_bucket_mb", "4"), ("hierarchical", "0"),
                             ("overlap", "bucket"))]["compile_key"]
    assert k == same_exe
    assert k != other_bucket
    # hierarchical rides as an env knob, never a CLI flag
    assert all("--hierarchical" not in " ".join(p["argv"]) for p in points)
    assert any(p["env"].get("HVDTPU_HIERARCHICAL_ALLREDUCE") == "1"
               for p in points)


def test_axes_and_points_are_mutually_exclusive(tmp_path):
    path = tmp_path / "spec.json"
    path.write_text(json.dumps({
        "name": "bad", "axes": {"overlap": ["off"]},
        "points": [{"name": "p", "args": []}],
    }))
    with pytest.raises(campaign.CampaignError, match="both axes and points"):
        campaign.load_spec(str(path))


def test_explicit_points_keep_order_and_reject_duplicates():
    spec = _grid_spec(axes={}, points=[
        {"name": "b", "args": ["--iters", "2"], "budget_secs": 120},
        {"name": "a", "args": ["--iters", "3"],
         "env": {"HVDTPU_SCHEDULE_REPLAY": "1"}},
    ])
    points = campaign.expand_points(spec)
    assert [p["id"] for p in points] == ["b", "a"]  # plan order, not sorted
    assert points[0]["budget_secs"] == 120
    assert points[1]["env"] == {"HVDTPU_SCHEDULE_REPLAY": "1"}
    spec["points"].append({"name": "a", "args": []})
    with pytest.raises(campaign.CampaignError, match="duplicate"):
        campaign.expand_points(spec)


# --------------------------------------------------------- resume/retry

def _runner_factory(results, calls):
    """Injected runner: pops the scripted result per point id, logging
    which points actually ran."""
    def runner(point, spec):
        calls.append(point["id"])
        return results[point["id"]].pop(0)
    return runner


def _tiny_spec():
    return {
        "name": "resume", "base_args": [],
        "axes": {"hierarchical": [False, True]},
        "points": [], "retry_degraded": 1, "point_budget_secs": 60,
    }


OK = {"rc": 0, "parsed": {"metric": "m", "value": 1.0}, "tail": ""}
DEGRADED = {"rc": 0, "parsed": {"metric": "m", "degraded": True},
            "tail": ""}
FAILED = {"rc": 1, "parsed": None, "tail": "boom"}


def test_resume_skips_done_and_retries_degraded_exactly_once(tmp_path):
    spec = _tiny_spec()
    d = str(tmp_path)
    calls = []
    campaign.run_campaign(
        spec, d, runner=_runner_factory(
            {"hierarchical=0": [dict(OK)],
             "hierarchical=1": [dict(DEGRADED)]}, calls),
        log=lambda m: None)
    assert calls == ["hierarchical=0", "hierarchical=1"]
    journal = campaign.load_journal(d)
    assert journal["points"]["hierarchical=0"]["status"] == "done"
    assert journal["points"]["hierarchical=1"]["status"] == "degraded"

    # Second session: done point skipped, degraded point retried once.
    calls = []
    journal = campaign.run_campaign(
        spec, d, runner=_runner_factory(
            {"hierarchical=1": [dict(DEGRADED)]}, calls),
        log=lambda m: None)
    assert calls == ["hierarchical=1"]
    assert journal["points"]["hierarchical=1"]["attempts"] == 2
    # Retry ran against an executable a previous attempt already paid
    # to compile.
    assert journal["points"]["hierarchical=1"]["compile"] == "reused"

    # Third session: retry budget (1 + retry_degraded) spent — nothing
    # runs at all.
    calls = []
    journal = campaign.run_campaign(spec, d,
                                    runner=_runner_factory({}, calls),
                                    log=lambda m: None)
    assert calls == []
    assert journal["points"]["hierarchical=1"]["status"] == "degraded"


def test_failed_point_keeps_tail_and_sets_exit_semantics(tmp_path):
    spec = _tiny_spec()
    d = str(tmp_path)
    journal = campaign.run_campaign(
        spec, d, runner=_runner_factory(
            {"hierarchical=0": [dict(OK)],
             "hierarchical=1": [dict(FAILED)]}, []),
        log=lambda m: None)
    entry = journal["points"]["hierarchical=1"]
    assert entry["status"] == "failed"
    assert entry["tail"] == "boom"
    summary = campaign.summarize_journal(journal)
    assert summary["done"] == 1 and summary["failed"] == 1


def test_changed_spec_is_refused_unless_force_new(tmp_path):
    d = str(tmp_path)
    campaign.run_campaign(_tiny_spec(), d,
                          runner=lambda p, s: dict(OK),
                          log=lambda m: None)
    changed = _tiny_spec()
    changed["base_args"] = ["--model", "vgg16"]
    with pytest.raises(campaign.CampaignError, match="different"):
        campaign.run_campaign(changed, d, runner=lambda p, s: dict(OK),
                              log=lambda m: None)
    journal = campaign.run_campaign(changed, d,
                                    runner=lambda p, s: dict(OK),
                                    force_new=True, log=lambda m: None)
    assert journal["spec_sha"] == campaign.spec_sha(changed)


def test_corrupt_journal_is_refused(tmp_path):
    (tmp_path / campaign.JOURNAL_NAME).write_text("{ torn")
    with pytest.raises(campaign.CampaignError, match="corrupt"):
        campaign.load_journal(str(tmp_path))


def test_result_line_must_be_strict_json_object():
    assert campaign._parse_result_line("noise\n{\"a\": 1}") == {"a": 1}
    assert campaign._parse_result_line("Traceback ...\nValueError") is None
    assert campaign._parse_result_line("[1, 2]") is None  # not an object
    assert campaign._parse_result_line('{"v": NaN}') is None  # not strict
    assert campaign._parse_result_line("") is None


# ----------------------------------------------------------------- chaos

@pytest.fixture()
def fault_env(monkeypatch):
    faults.reset()
    yield monkeypatch
    monkeypatch.delenv(faults.SPEC_ENV, raising=False)
    faults.reset()


def test_injected_degrade_forces_point_without_running_it(
        tmp_path, fault_env):
    fault_env.setenv(faults.SPEC_ENV,
                     "campaign_point:step=1:action=degrade")
    calls = []
    journal = campaign.run_campaign(
        _tiny_spec(), str(tmp_path),
        runner=_runner_factory({"hierarchical=1": [dict(OK)]}, calls),
        log=lambda m: None)
    # Point 1 was journaled degraded WITHOUT its runner being invoked;
    # point 2 ran normally.
    assert calls == ["hierarchical=1"]
    entry = journal["points"]["hierarchical=0"]
    assert entry["status"] == "degraded"
    assert entry["forced_degraded"] is True
    assert entry["record"]["degraded"] is True


def _write_stub_bench(tmp_path):
    """A bench stand-in with no jax import: logs its argv to a count
    file and prints one strict-JSON record line."""
    stub = tmp_path / "stub_bench.py"
    stub.write_text(
        "import json, os, sys\n"
        "with open(os.environ['STUB_COUNT_FILE'], 'a') as f:\n"
        "    f.write(' '.join(sys.argv[1:]) + '\\n')\n"
        "print(json.dumps({'metric': 'stub_images_per_sec',\n"
        "                  'value': 123.0, 'device': 'cpu'}))\n"
    )
    return stub


def test_cli_abort_between_points_loses_only_inflight_point(tmp_path):
    """The acceptance chaos shape: a seeded SIGABRT between point 1's
    journal commit and point 2's launch kills the campaign; the journal
    on disk is complete and valid; the rerun (no fault) resumes and
    runs ONLY point 2."""
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps({
        "name": "ci", "base_args": [],
        "points": [{"name": "p1", "args": ["--iters", "1"]},
                   {"name": "p2", "args": ["--iters", "2"]}],
    }))
    stub = _write_stub_bench(tmp_path)
    count_file = tmp_path / "count.txt"
    d = tmp_path / "records"
    cmd = [sys.executable, "-m", "horovod_tpu.bench.campaign",
           "--spec", str(spec_path), "--record-dir", str(d),
           "--bench", f"{sys.executable} {stub}"]
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               STUB_COUNT_FILE=str(count_file),
               HVDTPU_FAULT_SPEC="campaign_point:step=2:action=abort")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          cwd=REPO_ROOT, timeout=300)
    assert proc.returncode in (-signal.SIGABRT, 128 + signal.SIGABRT), (
        proc.returncode, proc.stderr[-800:])
    journal = campaign.load_journal(str(d))  # parses = atomic commit held
    assert journal["points"]["p1"]["status"] == "done"
    assert journal["points"]["p2"]["status"] == "pending"
    assert count_file.read_text().count("\n") == 1

    env.pop("HVDTPU_FAULT_SPEC")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          cwd=REPO_ROOT, timeout=300)
    assert proc.returncode == 0, proc.stderr[-800:]
    journal = campaign.load_journal(str(d))
    assert journal["points"]["p1"]["status"] == "done"
    assert journal["points"]["p1"]["attempts"] == 1  # NOT re-run
    assert journal["points"]["p2"]["status"] == "done"
    assert count_file.read_text().count("\n") == 2
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["done"] == 2 and summary["failed"] == 0


# -------------------------------------------------------------- anatomy

def test_step_anatomy_components_tile_step_time():
    """Acceptance: compute + collective_wait + host_gap tile the mean
    step time within 5%, on a REAL compiled CPU artifact."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((32, 32), jnp.float32)
    compiled = fn.lower(x).compile()
    out = anatomy.step_anatomy(
        10.0, mfu=0.25, flops_per_step=2 * 32 ** 3,
        device_kind=jax.devices()[0].device_kind, dtype="fp32",
        compiled=compiled, steps_observed=4)
    comp = out["components_ms"]
    total = sum(comp.values())
    assert abs(total - out["step_ms"]) / out["step_ms"] <= 0.05
    assert 95.0 <= out["tile_pct"] <= 105.0
    assert comp["compute_ms"] == pytest.approx(2.5)  # mfu x step
    assert comp["host_gap_ms"] >= 0.0
    assert out["roofline"]["verdict"] in (
        "compute-bound", "memory-bound", "comms-bound")
    assert out["method"]["compute"] == "mfu x step"
    # A real compiled artifact yields an op table (dot/fusion at least).
    assert out.get("top_ops"), out
    assert anatomy.step_anatomy(0.0, mfu=0.5, device_kind="cpu") is None


def test_anatomy_amortizes_engine_collective_wait():
    """With the engine cycle histogram fed (the multi-proc shape), the
    collective-wait component is nonzero and the split still tiles."""
    from horovod_tpu.obs.registry import get_registry, reset_registry

    hist = get_registry().histogram("engine.cycle_time_ms")
    for _ in range(4):
        hist.observe(5.0)  # 20 ms of cycle time over 4 steps
    try:
        out = anatomy.step_anatomy(10.0, mfu=0.2, steps_observed=4,
                                   device_kind="cpu")
    finally:
        reset_registry()
    comp = out["components_ms"]
    assert comp["collective_wait_ms"] == pytest.approx(5.0)
    assert comp["compute_ms"] == pytest.approx(2.0)
    assert comp["host_gap_ms"] == pytest.approx(3.0)
    assert sum(comp.values()) == pytest.approx(out["step_ms"], rel=0.05)
    assert out["roofline"]["verdict"] == "comms-bound"  # 50% > 35%
    assert out["method"]["collective_wait"] \
        == "engine.cycle_time_ms histogram"


def test_roofline_verdict_thresholds():
    comms = anatomy.roofline_verdict(
        mfu=0.6, collective_frac=0.5, flops_per_step=None,
        bytes_per_step=None, device_kind="TPU v5 lite")
    assert comms["verdict"] == "comms-bound"  # comms outranks MFU
    compute = anatomy.roofline_verdict(
        mfu=0.5, collective_frac=0.0, flops_per_step=None,
        bytes_per_step=None, device_kind="TPU v5 lite")
    assert compute["verdict"] == "compute-bound"
    memory = anatomy.roofline_verdict(
        mfu=0.05, collective_frac=0.0, flops_per_step=1e9,
        bytes_per_step=1e9, device_kind="TPU v5 lite")
    assert memory["verdict"] == "memory-bound"
    assert memory["arithmetic_intensity"] == pytest.approx(1.0)


# ----------------------------------------------------------------- trend

@pytest.fixture()
def era_records(tmp_path):
    """One record per schema era the committed trajectory actually
    spans: r01 bare payload (no device), r02 device-stamped real, dark
    rounds (rc 124/86/1), degraded with and without a parsed payload,
    a degraded serve record, one corrupt file, one multichip round."""
    def w(name, doc):
        (tmp_path / name).write_text(doc if isinstance(doc, str)
                                     else json.dumps(doc))
    w("BENCH_r01.json", {"n": 1, "rc": 0,
                         "parsed": {"metric": "m", "value": 100.0}})
    w("BENCH_r02.json", {"n": 2, "rc": 0,
                         "parsed": {"metric": "ips", "value": 200.0,
                                    "device": "TPU v5 lite",
                                    "mfu": 0.30}})
    w("BENCH_r03.json", {"n": 3, "rc": 124})
    w("BENCH_r04.json", {"n": 4, "rc": 86, "parsed": None})
    w("BENCH_r05.json", {"n": 5, "rc": 1, "tail": "Traceback"})
    w("BENCH_r06.json", {"n": 6, "rc": 0, "degraded": True,
                         "parsed": {"metric": "ips", "value": 9.0,
                                    "device": "cpu", "degraded": True}})
    w("BENCH_r07.json", {"n": 7, "rc": 0, "degraded": True})
    w("BENCH_r08.json", {"n": 8, "rc": 0,
                         "parsed": {"metric": "serve_tokens_per_sec",
                                    "value": 10.0, "device": "cpu",
                                    "degraded": True}})
    w("BENCH_r09.json", "{ not json")
    w("MULTICHIP_r01.json", {"n": 1, "n_devices": 8, "ok": 3,
                             "skipped": 1})
    return tmp_path


def test_trend_loader_partitions_every_era(era_records):
    records = trend.load_bench_records(str(era_records))
    assert len(records) == 8  # corrupt r09 skipped, not fatal
    classes = [trend.classify(doc) for _, _, doc in records]
    assert classes == ["real", "real", "failed", "failed", "failed",
                       "degraded", "degraded", "degraded"]
    # r01-era payloads key as (metric, None), distinct from any device.
    assert trend.scenario_key(
        trend.parsed_payload(records[0][2])) == ("m", None)
    assert len(trend.load_multichip_records(str(era_records))) == 1


def test_degraded_streak_names_the_dark_run(era_records):
    streak = trend.degraded_streak(trend.load_bench_records(
        str(era_records)))
    assert streak["streak"] == 6
    assert streak["since"] == "BENCH_r03.json"
    assert streak["last_real_record"] == "BENCH_r02.json"
    assert "6 consecutive records without a real measurement" \
        in streak["verdict"]
    assert "BENCH_r02.json" in streak["verdict"]
    assert "on TPU v5 lite" in streak["verdict"]
    stamp = trend.trend_stamp(str(era_records))
    assert stamp["real"] == 2 and stamp["degraded"] == 3 \
        and stamp["failed"] == 3
    assert stamp["verdict"] == streak["verdict"]


def test_ewma_baseline_scenario_separation(era_records):
    records = trend.load_bench_records(str(era_records))
    # A CPU/degraded record must never baseline a TPU scenario, and a
    # deviceless r01 payload is its own scenario.
    assert trend.ewma_baseline(records, "ips", "TPU v5 lite")["value"] \
        == 200.0
    assert trend.ewma_baseline(records, "m", None)["value"] == 100.0
    assert trend.ewma_baseline(records, "ips", "cpu") is None  # degraded


def test_ewma_folds_oldest_to_newest(tmp_path):
    for n, value in ((1, 100.0), (2, 200.0), (3, 300.0)):
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "ips", "value": value,
                       "device": "TPU v5 lite"}}))
    base = trend.ewma_baseline(trend.load_bench_records(str(tmp_path)),
                               "ips", "TPU v5 lite")
    # alpha=0.5: ((100 -> 200) -> 300) = 0.5*300 + 0.5*(0.5*200+0.5*100)
    assert base["value"] == pytest.approx(225.0)
    assert base["records"] == ["BENCH_r01.json", "BENCH_r02.json",
                               "BENCH_r03.json"]
    assert base["newest"] == "BENCH_r03.json"


def _bench_mod():
    import bench

    return bench


@pytest.fixture()
def ewma_dir(tmp_path):
    """Three real records (1000, 1000, 1000) plus a degraded 9999 that
    must never become a bar."""
    for n, doc in enumerate((
        {"rc": 0, "parsed": {"metric": "ips", "value": 1000.0,
                             "device": "TPU v5 lite"}},
        {"rc": 0, "parsed": {"metric": "ips", "value": 1000.0,
                             "device": "TPU v5 lite"}},
        {"rc": 0, "parsed": {"metric": "ips", "value": 1000.0,
                             "device": "TPU v5 lite"}},
        {"rc": 0, "degraded": True,
         "parsed": {"metric": "ips", "value": 9999.0,
                    "device": "TPU v5 lite", "degraded": True}},
    ), start=1):
        doc["n"] = n
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))
    return tmp_path


def test_sentinel_flags_planted_regression(ewma_dir):
    out = {"metric": "ips", "value": 700.0, "device": "TPU v5 lite"}
    _bench_mod().attach_regression(out, record_dir=str(ewma_dir))
    assert out["regression"] is True
    assert out["deltas"]["value"]["pct"] == pytest.approx(-30.0)
    prov = out["baseline_record"]
    assert prov["baseline_records"] == [
        "BENCH_r01.json", "BENCH_r02.json", "BENCH_r03.json"]
    assert prov["degraded_records_skipped"] == 1
    # The streak verdict rides in the record itself.
    assert out["trend"]["last_real_record"] == "BENCH_r03.json"


def test_sentinel_quiet_on_noise(ewma_dir):
    out = {"metric": "ips", "value": 980.0, "device": "TPU v5 lite"}
    _bench_mod().attach_regression(out, record_dir=str(ewma_dir))
    assert out["regression"] is False
    out = {"metric": "other", "value": 1.0, "device": "TPU v5 lite"}
    _bench_mod().attach_regression(out, record_dir=str(ewma_dir))
    assert out["regression"] is None  # nothing comparable: no verdict


# ------------------------------------------------- digest/summary hookup

def test_trend_surfaces_in_summary_and_live_digest(monkeypatch,
                                                   era_records):
    from horovod_tpu.obs import live, summary

    monkeypatch.setenv(trend.RECORD_DIR_ENV, str(era_records))
    section = summary.trend_section({})
    assert "records 8" in section
    assert "6 consecutive records" in section
    agg = live.LiveAggregator()
    token = agg._trend_part()
    assert "6 records dark" in token
    assert "BENCH_r02.json" in token
    # Computed once per process: a changed dir must not change the token.
    monkeypatch.setenv(trend.RECORD_DIR_ENV, str(era_records / "nope"))
    assert agg._trend_part() == token
