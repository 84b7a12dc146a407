#!/usr/bin/env python
"""Aggregate a flight-recorder JSONL trace into operator reports.

    PYTHONPATH=src python scripts/trace_report.py TRACE.jsonl
    PYTHONPATH=src python scripts/trace_report.py TRACE.jsonl --json

Produces, from the event stream alone (no live engine needed):

* **per-family dispatch histograms** — resolutions by bucket, deciding
  source, surface, and walk rank (how often dispatch fell past the top
  pick);
* **swap/demote timeline** — every provenance transition in tick order;
* **tick-latency percentiles** — p50/p90/p99 over ``TickSpan`` durations
  and p50/p90 of each phase (``plan``/``dispatch``/``sync``/``commit``
  and the caller's share between steps; tick indices are the
  timestamps, durations come from the engine's injectable clock);
* **staleness/drift report** — per family: demotions, hot-swaps,
  exhausted-ladder resets, and off-top-rank resolutions — the "is the
  offline ranking still right for this host/traffic?" signal;
* **reconstructed counters** — admissions/preemptions/sheds/cancels/
  poisons, fault firings by site, prefix-hit totals.  ``scripts/
  ci_obs.py`` asserts these equal the live stats dataclasses.

``aggregate(records)`` is importable; the CLI wraps it.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter
from typing import Any, Dict, Iterable, List, Mapping


def _percentile(xs: List[float], p: float) -> float:
    """Nearest-rank percentile (deterministic, no interpolation)."""
    if not xs:
        return 0.0
    xs = sorted(xs)
    k = max(0, min(len(xs) - 1, -(-int(p * len(xs)) // 100) - 1))
    return xs[k]


#: ``tick_span`` phase fields, in the order a step runs them
PHASES = ("plan", "dispatch", "sync", "commit", "caller")


def aggregate(records: Iterable[Mapping[str, Any]]) -> Dict[str, Any]:
    """Fold an event stream (dicts, as parsed from JSONL) into the report
    structure.  Pure and deterministic: same records, same output."""
    dispatch: Dict[str, Dict[str, Counter]] = {}
    timeline: List[Dict[str, Any]] = []
    durations: List[float] = []
    phases: Dict[str, List[float]] = {p: [] for p in PHASES}
    ticks = Counter()
    sched = Counter()
    faults = Counter()
    prefix = Counter()
    drift: Dict[str, Counter] = {}
    n = 0
    for rec in records:
        n += 1
        et = rec.get("etype")
        if et == "dispatch_decision":
            fam = dispatch.setdefault(rec["family"], {
                "by_bucket": Counter(), "by_source": Counter(),
                "by_surface": Counter(), "by_rank": Counter()})
            fam["by_bucket"][rec["bucket"] or "(warm)"] += 1
            fam["by_source"][rec["source"]] += 1
            fam["by_surface"][rec["surface"]] += 1
            fam["by_rank"][str(rec["rank"])] += 1
            if rec["rank"] > 0:
                drift.setdefault(rec["family"], Counter())["off_top"] += 1
        elif et in ("swap", "degrade"):
            d = drift.setdefault(rec["family"], Counter())
            d["swaps" if et == "swap" else "demotions"] += 1
            if rec.get("exhausted"):
                d["exhausted_resets"] += 1
            timeline.append({
                "tick": rec["tick"], "seq": rec["seq"], "kind": et,
                "family": rec["family"],
                "old": rec["old"][1], "new": rec["new"][1],
                "detail": (f"{rec['windows']} windows" if et == "swap"
                           else rec["source"])})
        elif et == "tick_span":
            durations.append(float(rec["duration_us"]))
            for p in PHASES:
                phases[p].append(float(rec[f"{p}_us"]))
            for k in ("admitted", "prefill_tokens", "decode_rows",
                      "preempted", "cancelled", "finished"):
                ticks[k] += rec[k]
            ticks["spans"] += 1
        elif et == "admission_decision":
            sched[rec["action"]] += 1
        elif et == "fault_fired":
            faults[f"{rec['site']}:{rec['kind']}"] += 1
            faults["total"] += 1
        elif et == "prefix_hit":
            prefix["hits"] += 1
            prefix["blocks"] += rec["blocks"]
            prefix["tokens_saved"] += rec["tokens"]
    timeline.sort(key=lambda e: (e["tick"], e["seq"]))
    return {
        "events": n,
        "dispatch": {f: {k: dict(c) for k, c in hists.items()}
                     for f, hists in sorted(dispatch.items())},
        "timeline": timeline,
        "ticks": {
            **{k: int(v) for k, v in sorted(ticks.items())},
            "p50_us": _percentile(durations, 50),
            "p90_us": _percentile(durations, 90),
            "p99_us": _percentile(durations, 99),
            "phases": {p: {"p50_us": _percentile(v, 50),
                           "p90_us": _percentile(v, 90)}
                       for p, v in phases.items()},
        },
        "sched": {k: int(v) for k, v in sorted(sched.items())},
        "faults": {k: int(v) for k, v in sorted(faults.items())},
        "prefix": {k: int(v) for k, v in sorted(prefix.items())},
        "drift": {f: {k: int(v) for k, v in sorted(c.items())}
                  for f, c in sorted(drift.items())},
    }


def load_records(path: str) -> List[Dict[str, Any]]:
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def _render(rep: Dict[str, Any]) -> str:
    out = [f"trace: {rep['events']} events"]
    t = rep["ticks"]
    if t.get("spans"):
        out.append(
            f"ticks: {t['spans']} spans, latency p50={t['p50_us']:.1f}us "
            f"p90={t['p90_us']:.1f}us p99={t['p99_us']:.1f}us; "
            f"admitted={t['admitted']} prefill_tokens={t['prefill_tokens']} "
            f"decode_rows={t['decode_rows']} preempted={t['preempted']} "
            f"cancelled={t['cancelled']} finished={t['finished']}")
        out.append("phases: " + " ".join(
            f"{p} p50={v['p50_us']:.1f}us p90={v['p90_us']:.1f}us"
            for p, v in t["phases"].items()))
    if rep["sched"]:
        out.append("sched: " + " ".join(f"{k}={v}" for k, v in
                                        rep["sched"].items()))
    if rep["prefix"]:
        p = rep["prefix"]
        out.append(f"prefix: hits={p.get('hits', 0)} "
                   f"blocks={p.get('blocks', 0)} "
                   f"tokens_saved={p.get('tokens_saved', 0)}")
    if rep["faults"]:
        out.append("faults: " + " ".join(
            f"{k}={v}" for k, v in rep["faults"].items() if k != "total"))
    for fam, hists in rep["dispatch"].items():
        srcs = " ".join(f"{k}={v}" for k, v in
                        sorted(hists["by_source"].items()))
        ranks = " ".join(f"r{k}={v}" for k, v in
                         sorted(hists["by_rank"].items()))
        out.append(f"dispatch {fam}: {srcs} | {ranks}")
        for bucket, cnt in sorted(hists["by_bucket"].items()):
            out.append(f"  {bucket}: {cnt}")
    if rep["drift"]:
        out.append("drift:")
        for fam, c in rep["drift"].items():
            out.append("  " + fam + ": " + " ".join(
                f"{k}={v}" for k, v in c.items()))
    if rep["timeline"]:
        out.append("timeline:")
        for ev in rep["timeline"]:
            out.append(f"  tick {ev['tick']}: {ev['kind']} {ev['family']} "
                       f"{ev['old']} -> {ev['new']} ({ev['detail']})")
    return "\n".join(out)


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="flight-recorder JSONL file")
    ap.add_argument("--json", action="store_true",
                    help="emit the aggregate as JSON instead of text")
    args = ap.parse_args(argv)
    rep = aggregate(load_records(args.trace))
    if args.json:
        print(json.dumps(rep, indent=2, sort_keys=True))
    else:
        print(_render(rep))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
