#!/usr/bin/env python
"""Bench regression sentinel over the BENCH_r*.json trajectory.

A record trajectory can go dark without anyone reading it: a run of
degraded CPU dry runs or failed rounds leaves the last *real* perf
number many rounds old, and the trajectory "judges itself" against
placeholders.  This gate makes the trajectory machine-visible:

1. **Partition** every ``BENCH_r*.json`` into *real* (rc=0, a parsed
   measurement, not degraded), *degraded* (the explicit
   ``degraded: true`` stamp from bench.py — CPU dry runs and failed
   runs), and *failed* (a nonzero rc with no measurement at all), and
   print it.
2. **Baseline** per scenario ``(metric, device)``: the best value among
   real records only.  A degraded record is trajectory evidence, never
   a bar.  The audit also prints the degraded-streak verdict ("N
   consecutive records without a real measurement; last real number is
   rX") from the trend observatory (horovod_tpu/obs/trend.py), which
   owns record classification for this gate, bench.py's in-record
   sentinel and scripts/perf_report.py alike.
3. **Judge a candidate** (``--candidate fresh.json``) against its
   scenario's EWMA-over-the-last-K-real-records baseline
   (obs/trend.py's fold — one lucky round must not own the bar) with a
   configurable noise band
   (``--noise-pct``, default 5): a drop past the band exits nonzero so
   CI can gate on it.  Backend provenance (the ``provenance`` stamp
   bench.py embeds: platform / device kind / JAX_PLATFORMS) is printed
   beside the verdict so "failed on the chip" and "ran on CPU" stop
   looking alike.

Without a candidate the gate is an auditor: it prints the partition and
per-scenario baselines and exits 0 (the committed trajectory is what it
is; only a fresh run can regress).

Exit codes: 0 clean, 1 regression past the noise band, 2 bad input.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

# Record classification is single-sourced in the trend observatory
# (horovod_tpu/obs/trend.py): the gate, bench.py's in-record sentinel
# and scripts/perf_report.py must never disagree about what counts as
# a real measurement.
from horovod_tpu.obs import trend as _trend  # noqa: E402

parsed_payload = _trend.parsed_payload
classify = _trend.classify
scenario_key = _trend.scenario_key


def load_records(record_dir):
    """[(round n, filename, doc)] sorted by round; unreadable files are
    reported on stderr and skipped (one corrupt record must not blind
    the gate to the rest of the trajectory)."""
    records = []
    for path in sorted(glob.glob(os.path.join(record_dir, "BENCH_*.json"))):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as exc:
            print(f"# unreadable record {os.path.basename(path)}: {exc}",
                  file=sys.stderr)
            continue
        if not isinstance(doc, dict):
            continue
        n = doc.get("n")
        records.append((n if isinstance(n, int) else 0,
                        os.path.basename(path), doc))
    records.sort()
    return records


def provenance_of(doc):
    """The backend-provenance stamp (platform, device kind,
    JAX_PLATFORMS), wherever bench.py landed it."""
    for holder in (doc, parsed_payload(doc) or {}):
        prov = holder.get("provenance")
        if isinstance(prov, dict):
            return prov
    parsed = parsed_payload(doc)
    if isinstance(parsed, dict) and parsed.get("device"):
        return {"device_kind": parsed["device"]}
    return {}


def _prov_str(prov):
    if not prov:
        return "provenance unknown"
    bits = []
    if prov.get("platform"):
        bits.append(f"platform={prov['platform']}")
    if prov.get("device_kind"):
        bits.append(f"device={prov['device_kind']}")
    if prov.get("jax_platforms"):
        bits.append(f"JAX_PLATFORMS={prov['jax_platforms']}")
    return " ".join(bits) or "provenance unknown"


def partition(records):
    """{bucket: [(n, fname, doc)]} over the classified trajectory."""
    out = {"real": [], "degraded": [], "failed": []}
    for n, fname, doc in records:
        out[classify(doc)].append((n, fname, doc))
    return out


def baselines(records):
    """{(metric, device): (fname, parsed)} — best real value per
    scenario."""
    best = {}
    for _, fname, doc in records:
        if classify(doc) != "real":
            continue
        parsed = parsed_payload(doc)
        key = scenario_key(parsed)
        if key not in best or parsed["value"] > best[key][1]["value"]:
            best[key] = (fname, parsed)
    return best


def judge(candidate, base, noise_pct):
    """(verdict, pct_delta): 'regression' | 'ok' | 'improved'."""
    old, new = base["value"], candidate["value"]
    if not old:
        return "ok", 0.0
    pct = (new - old) / old * 100.0
    if pct < -abs(noise_pct):
        return "regression", pct
    return ("improved" if pct > abs(noise_pct) else "ok"), pct


def main(argv=None):
    p = argparse.ArgumentParser(
        description="Partition the BENCH trajectory and gate a fresh "
                    "measurement against the best non-degraded baseline.")
    p.add_argument("--records-dir", default=REPO_ROOT,
                   help="directory holding BENCH_r*.json "
                        "(default: repo root)")
    p.add_argument("--candidate", default=None,
                   help="fresh bench output JSON to judge (bench.py "
                        "stdout or a driver record); omitting it audits "
                        "the trajectory only")
    p.add_argument("--noise-pct", type=float, default=5.0,
                   help="regression band in percent (default 5): a "
                        "value drop past this fails the gate")
    p.add_argument("--json", action="store_true",
                   help="emit the machine verdict document on stdout "
                        "too")
    args = p.parse_args(argv)

    records = load_records(args.records_dir)
    if not records:
        print(f"no BENCH_*.json records under {args.records_dir}",
              file=sys.stderr)
        return 2
    buckets = partition(records)

    print(f"# BENCH trajectory: {len(records)} records "
          f"({len(buckets['real'])} real, "
          f"{len(buckets['degraded'])} degraded, "
          f"{len(buckets['failed'])} failed)")
    for bucket in ("real", "degraded", "failed"):
        for n, fname, doc in buckets[bucket]:
            parsed = parsed_payload(doc) or {}
            desc = parsed.get("metric") or doc.get(
                "failure_phase") or f"rc={doc.get('rc')}"
            val = parsed.get("value")
            val_s = f" value={val}" if isinstance(val, (int, float)) else ""
            print(f"  {bucket:9s} {fname}: {desc}{val_s} "
                  f"[{_prov_str(provenance_of(doc))}]")

    base = baselines(records)
    print(f"# baselines ({len(base)} scenario"
          f"{'s' if len(base) != 1 else ''}, real records only):")
    for (metric, device), (fname, parsed) in sorted(
            base.items(), key=lambda kv: str(kv[0])):
        print(f"  {metric} on {device or 'unknown device'}: "
              f"{parsed['value']} ({fname})")

    # The dark trajectory self-announces: how many rounds since the
    # last real number, and what that number was.  (Printed after the
    # per-record lines so the partition stays the first mention of
    # every record name — CI greps by first match.)
    streak = _trend.degraded_streak(records)
    print(f"# degraded-streak verdict: {streak['verdict']}")

    verdict = {
        "records": len(records),
        "real": [f for _, f, _ in buckets["real"]],
        "degraded": [f for _, f, _ in buckets["degraded"]],
        "failed": [f for _, f, _ in buckets["failed"]],
        "noise_pct": args.noise_pct,
        "degraded_streak": streak["streak"],
        "last_real_record": streak["last_real_record"],
        "regression": False,
    }

    rc = 0
    if args.candidate:
        try:
            with open(args.candidate) as f:
                cand_doc = json.load(f)
        except (OSError, ValueError) as exc:
            print(f"unreadable candidate {args.candidate}: {exc}",
                  file=sys.stderr)
            return 2
        cand = parsed_payload(cand_doc)
        if not isinstance(cand, dict) or not cand.get("metric") \
                or not isinstance(cand.get("value"), (int, float)):
            print(f"candidate {args.candidate} carries no measurement "
                  f"(metric/value)", file=sys.stderr)
            return 2
        prov = _prov_str(provenance_of(cand_doc))
        key = scenario_key(cand)
        if cand.get("degraded"):
            # A degraded candidate is a trajectory placeholder: it can
            # never regress a real baseline (it is not comparable), and
            # it must say so loudly rather than pass as healthy.
            print(f"# candidate is DEGRADED ({prov}): recorded for the "
                  f"trajectory, not judged against "
                  f"{key[0]} on {key[1] or 'unknown device'}")
            verdict["candidate"] = {"scenario": list(key),
                                    "degraded": True}
        elif key not in base:
            print(f"# candidate scenario {key[0]} on "
                  f"{key[1] or 'unknown device'} has no real baseline "
                  f"({prov}) — first real measurement, nothing to "
                  f"regress from")
            verdict["candidate"] = {"scenario": list(key),
                                    "baseline": None}
        else:
            # EWMA over the last K real records of the scenario, not
            # the single best one: one lucky round must not own the bar
            # (obs/trend.py owns the fold; same baseline bench.py's
            # in-record sentinel uses).
            ewma = _trend.ewma_baseline(records, *key)
            word, pct = judge(cand, ewma, args.noise_pct)
            print(f"# candidate {cand['value']} vs EWMA baseline "
                  f"{ewma['value']} over {len(ewma['records'])} real "
                  f"record{'s' if len(ewma['records']) != 1 else ''} "
                  f"({', '.join(ewma['records'])}): {pct:+.2f}% "
                  f"[band ±{args.noise_pct}%] -> {word.upper()} ({prov})")
            verdict["candidate"] = {
                "scenario": list(key),
                "value": cand["value"],
                "baseline": ewma["value"],
                "baseline_record": ewma["newest"],
                "baseline_records": ewma["records"],
                "pct": round(pct, 2),
                "verdict": word,
            }
            if word == "regression":
                verdict["regression"] = True
                rc = 1
    if args.json:
        print(json.dumps(verdict, indent=1))
    return rc


if __name__ == "__main__":
    sys.exit(main())
