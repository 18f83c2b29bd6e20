#!/usr/bin/env python
"""Perf-trend report: the whole BENCH/MULTICHIP trajectory, readable.

The observatory counterpart to scripts/perf_gate.py (which *gates*):
this script *reports*.  It loads every historical ``BENCH_r*.json`` /
``MULTICHIP_r*.json`` across all schema eras through the one shared
reader (horovod_tpu/obs/trend.py), separates real measurements from
degraded placeholders and failed rounds, prints the per-scenario EWMA
baselines and the degraded-streak verdict, and renders the campaign
verdict table for a ``campaign.json`` journal
(horovod_tpu/bench/campaign.py) when one exists.

``--write-docs`` re-renders the auto-generated trajectory section of
``docs/performance.md`` in place (between the ``perf-report`` markers),
so the committed docs can never drift from the committed records.

Campaign journals carry per-point status/provenance an ad-hoc sweep's
results file never had.

Exit codes: 0 report rendered, 2 bad input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from horovod_tpu.obs import trend  # noqa: E402

DOCS_BEGIN = "<!-- perf-report:begin -->"
DOCS_END = "<!-- perf-report:end -->"


def campaign_table(journal: dict) -> list:
    """Text lines for the per-point campaign verdict table."""
    lines = [f"campaign {journal.get('name')} "
             f"(spec {journal.get('spec_sha')}, "
             f"updated {journal.get('updated')}):"]
    for pid in journal.get("order", []):
        entry = journal.get("points", {}).get(pid, {})
        record = entry.get("record") or {}
        value = record.get("value")
        val_s = f" value={value}" if isinstance(value, (int, float)) else ""
        lines.append(
            f"  {entry.get('status', 'pending'):9s} {pid}: "
            f"attempts={entry.get('attempts', 0)} "
            f"compile={entry.get('compile', '—')}{val_s}"
        )
    return lines


def load_campaign(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "points" not in doc:
        raise ValueError(f"{path} is not a campaign journal")
    return doc


def write_docs(docs_path: str, records_dir: str) -> bool:
    """Replace the marker-fenced auto-generated section; returns True
    when the file changed.  Missing markers are an error — silently
    appending would duplicate the section on every run."""
    with open(docs_path) as f:
        text = f.read()
    if DOCS_BEGIN not in text or DOCS_END not in text:
        raise ValueError(
            f"{docs_path} has no {DOCS_BEGIN} / {DOCS_END} markers")
    head, rest = text.split(DOCS_BEGIN, 1)
    _, tail = rest.split(DOCS_END, 1)
    body = trend.render_markdown(records_dir)
    new = head + DOCS_BEGIN + "\n" + body + DOCS_END + tail
    if new == text:
        return False
    with open(docs_path, "w") as f:
        f.write(new)
    return True


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="Render the BENCH/MULTICHIP perf trajectory, EWMA "
                    "baselines, degraded-streak verdict and campaign "
                    "table.")
    p.add_argument("--records-dir", default=REPO_ROOT,
                   help="directory holding BENCH_*/MULTICHIP_* records "
                        "(default: repo root)")
    p.add_argument("--campaign", default=None,
                   help="campaign.json journal to render (default: "
                        "<records-dir>/campaign.json when present)")
    p.add_argument("--write-docs", nargs="?", const=os.path.join(
                       REPO_ROOT, "docs", "performance.md"),
                   default=None, metavar="PATH",
                   help="re-render the auto-generated trajectory "
                        "section of docs/performance.md (or PATH) in "
                        "place")
    p.add_argument("--json", action="store_true",
                   help="emit the machine-readable trend document too")
    args = p.parse_args(argv)

    records = trend.load_bench_records(args.records_dir)
    multichip = trend.load_multichip_records(args.records_dir)
    if not records and not multichip:
        print(f"no BENCH_*/MULTICHIP_*.json records under "
              f"{args.records_dir}", file=sys.stderr)
        return 2

    counts = {"real": 0, "degraded": 0, "failed": 0}
    print(f"# BENCH trajectory: {len(records)} records")
    for row in trend.trajectory(records):
        counts[row["class"]] += 1
        desc = row["metric"] or f"rc={row['rc']}"
        val_s = (f" value={row['value']}"
                 if isinstance(row["value"], (int, float)) else "")
        mfu_s = (f" mfu={row['mfu']}"
                 if isinstance(row["mfu"], (int, float)) else "")
        print(f"  {row['class']:9s} {row['file']}: {desc}{val_s}{mfu_s}"
              f" [{row['device'] or 'unknown device'}]")
    print(f"# partition: {counts['real']} real, {counts['degraded']} "
          f"degraded, {counts['failed']} failed")

    scenarios = sorted(
        {trend.scenario_key(trend.parsed_payload(doc))
         for _, _, doc in records if trend.classify(doc) == "real"},
        key=str)
    for metric, device in scenarios:
        base = trend.ewma_baseline(records, metric, device)
        if base:
            print(f"# EWMA baseline {metric} on "
                  f"{device or 'unknown device'}: {base['value']} "
                  f"over {', '.join(base['records'])}")

    streak = trend.degraded_streak(records)
    print(f"# degraded-streak verdict: {streak['verdict']}")

    if multichip:
        print(f"# MULTICHIP rounds: {len(multichip)}")
        for n, fname, doc in multichip:
            print(f"  {fname}: n_devices={doc.get('n_devices')} "
                  f"ok={doc.get('ok')} skipped={doc.get('skipped')}")

    journal_path = args.campaign or os.path.join(
        args.records_dir, "campaign.json")
    journal = None
    if os.path.exists(journal_path):
        try:
            journal = load_campaign(journal_path)
        except (OSError, ValueError) as exc:
            print(f"unreadable campaign journal {journal_path}: {exc}",
                  file=sys.stderr)
            return 2
        for line in campaign_table(journal):
            print(line)
    elif args.campaign:
        print(f"campaign journal {args.campaign} not found",
              file=sys.stderr)
        return 2

    if args.write_docs:
        try:
            changed = write_docs(args.write_docs, args.records_dir)
        except (OSError, ValueError) as exc:
            print(f"--write-docs failed: {exc}", file=sys.stderr)
            return 2
        print(f"# docs: {args.write_docs} "
              f"{'updated' if changed else 'already current'}")

    if args.json:
        doc = {
            "records": len(records),
            "partition": counts,
            "degraded_streak": streak,
            "trend": trend.trend_stamp(args.records_dir),
        }
        if journal is not None:
            from horovod_tpu.bench.campaign import (  # noqa: PLC0415
                summarize_journal,
            )

            doc["campaign"] = summarize_journal(journal)
        print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
