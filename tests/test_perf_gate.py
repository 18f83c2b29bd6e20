"""Bench regression sentinel (scripts/perf_gate.py): a twelve-record
BENCH trajectory partitions with r01/r02 real, r03-r05 failed and
r06-r12 degraded and audits clean; a synthetic regressing candidate fails the gate; an
in-band candidate and a degraded candidate both pass; corrupt records
are skipped loudly."""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GATE = os.path.join(REPO_ROOT, "scripts", "perf_gate.py")

spec = importlib.util.spec_from_file_location("perf_gate", GATE)
perf_gate = importlib.util.module_from_spec(spec)
spec.loader.exec_module(perf_gate)


def _run(argv, capsys):
    rc = perf_gate.main(argv)
    out = capsys.readouterr()
    return rc, out.out, out.err


@pytest.fixture()
def real_baseline_dir(tmp_path):
    """A records dir with one real baseline (value 1000) and one
    degraded record that must never become a bar."""
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({
        "n": 1, "rc": 0,
        "parsed": {"metric": "resnet50_images_per_sec_per_chip",
                   "value": 1000.0, "device": "TPU v5 lite"},
    }))
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "n": 2, "rc": 0, "degraded": True, "failure_phase": "cpu",
        "parsed": {"metric": "resnet50_images_per_sec_per_chip",
                   "value": 9999.0, "device": "TPU v5 lite",
                   "degraded": True},
        "provenance": {"platform": "cpu", "device_kind": "cpu",
                       "jax_platforms": "cpu"},
    }))
    return tmp_path


def test_trajectory_partition_and_exit_zero(tmp_path, capsys):
    """Acceptance: over a twelve-record trajectory (two real rounds,
    three that died without a measurement, seven CPU dry runs) the
    audit labels r01-r02 real, r06-r12 degraded, and exits 0."""
    metric = "resnet50_bf16_images_per_sec_per_chip"

    def write(n, doc):
        doc["n"] = n
        (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps(doc))

    for n, value in ((1, 2000.0), (2, 2010.0)):
        write(n, {"rc": 0, "parsed": {
            "metric": metric, "value": value, "device": "TPU v5 lite"}})
    for n, rc in ((3, 1), (4, 124), (5, 1)):
        write(n, {"rc": rc, "parsed": None, "tail": "Traceback"})
    for n in range(6, 13):
        write(n, {"rc": 0, "degraded": True,
                  "failure_phase": "cpu-dry-run",
                  "parsed": {"metric": "resnet18_bf16_images_per_sec_per_chip",
                             "value": 10.0 + n, "device": "cpu",
                             "degraded": True},
                  "provenance": {"platform": "cpu", "device_kind": "cpu",
                                 "jax_platforms": "cpu"}})
    rc, out, _ = _run(["--records-dir", str(tmp_path)], capsys)
    assert rc == 0
    for n in ("r01", "r02"):
        assert any(line.strip().startswith("real")
                   and f"BENCH_{n}.json" in line
                   for line in out.splitlines()), n
    for n in range(6, 13):
        assert any(line.strip().startswith("degraded")
                   and f"BENCH_r{n:02d}.json" in line
                   for line in out.splitlines()), n
    # the dark rounds are their own bucket, not silently merged
    assert "failed" in out
    assert "# baselines" in out


def test_degraded_record_never_becomes_baseline(real_baseline_dir):
    base = perf_gate.baselines(
        perf_gate.load_records(str(real_baseline_dir)))
    key = ("resnet50_images_per_sec_per_chip", "TPU v5 lite")
    assert base[key][1]["value"] == 1000.0  # not the degraded 9999


def test_regressing_candidate_fails_the_gate(real_baseline_dir, tmp_path,
                                             capsys):
    cand = tmp_path / "fresh.json"
    cand.write_text(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 800.0,
        "device": "TPU v5 lite",
        "provenance": {"platform": "tpu", "device_kind": "TPU v5 lite",
                       "jax_platforms": ""},
    }))
    rc, out, _ = _run(["--records-dir", str(real_baseline_dir),
                       "--candidate", str(cand), "--json"], capsys)
    assert rc == 1
    assert "REGRESSION" in out
    verdict = json.loads(out[out.index("{"):])
    assert verdict["regression"] is True
    assert verdict["candidate"]["pct"] == pytest.approx(-20.0)


def test_in_band_candidate_passes(real_baseline_dir, tmp_path, capsys):
    cand = tmp_path / "fresh.json"
    cand.write_text(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 980.0,
        "device": "TPU v5 lite",
    }))
    rc, out, _ = _run(["--records-dir", str(real_baseline_dir),
                       "--candidate", str(cand)], capsys)
    assert rc == 0
    assert "OK" in out


def test_degraded_candidate_is_announced_not_judged(real_baseline_dir,
                                                    tmp_path, capsys):
    cand = tmp_path / "fresh.json"
    cand.write_text(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 1.0,
        "device": None, "degraded": True,
    }))
    rc, out, _ = _run(["--records-dir", str(real_baseline_dir),
                       "--candidate", str(cand)], capsys)
    assert rc == 0
    assert "DEGRADED" in out
    assert "REGRESSION" not in out


def test_candidate_without_baseline_scenario_passes(real_baseline_dir,
                                                    tmp_path, capsys):
    cand = tmp_path / "fresh.json"
    cand.write_text(json.dumps({
        "metric": "brand_new_metric", "value": 5.0, "device": "cpu",
    }))
    rc, out, _ = _run(["--records-dir", str(real_baseline_dir),
                       "--candidate", str(cand)], capsys)
    assert rc == 0
    assert "no real baseline" in out


def test_corrupt_record_skipped_loudly(real_baseline_dir, capsys):
    (real_baseline_dir / "BENCH_r03.json").write_text("{not json")
    rc, _, err = _run(["--records-dir", str(real_baseline_dir)], capsys)
    assert rc == 0
    assert "unreadable record BENCH_r03.json" in err


def test_empty_records_dir_is_bad_input(tmp_path, capsys):
    rc, _, err = _run(["--records-dir", str(tmp_path)], capsys)
    assert rc == 2
    assert "no BENCH_*.json" in err


def test_provenance_printed_beside_verdict(real_baseline_dir, tmp_path,
                                           capsys):
    cand = tmp_path / "fresh.json"
    cand.write_text(json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 980.0,
        "device": "TPU v5 lite",
        "provenance": {"platform": "cpu", "device_kind": "cpu",
                       "jax_platforms": "cpu"},
    }))
    _, out, _ = _run(["--records-dir", str(real_baseline_dir),
                      "--candidate", str(cand)], capsys)
    assert "platform=cpu" in out
    assert "JAX_PLATFORMS=cpu" in out
