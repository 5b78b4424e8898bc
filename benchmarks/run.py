"""Benchmark entry point: one module per paper table/figure + the roofline
report (assignment §Roofline, from the dry-run artifacts if present),
plus an aggregation pass that folds every recorded ``BENCH_*.json``
(scheduling / scenarios / carbon / autoscale) into one summary
(``BENCH_summary.json``), and a cross-run regression gate.

Usage:
    PYTHONPATH=src python -m benchmarks.run            # run benchmarks
    PYTHONPATH=src python -m benchmarks.run --check    # regression gate

``--check`` diffs each recorded BENCH_*.json against its committed
baseline under ``benchmarks/baselines/`` (see
``repro.telemetry.baseline``) and exits nonzero on any regression.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# The recorded sweep files the aggregation pass knows how to headline.
BENCH_FILES = ("BENCH_scheduling.json", "BENCH_scenarios.json",
               "BENCH_carbon.json", "BENCH_autoscale.json",
               "BENCH_pareto.json")

BASELINE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "baselines")


def _headline(name: str, data: dict) -> dict:
    """Compress one recorded sweep into its headline numbers."""
    results = data.get("results", [])
    out: dict = {"bench": data.get("bench", name), "cells": len(results)}
    if name == "BENCH_scheduling.json":
        # best batched-vs-per-pod us/pod speedup at any fleet size
        perpod = {r["n_nodes"]: r["us_per_pod"] for r in results
                  if r.get("mode") == "per-pod" and r.get("backend") == "numpy"}
        speedups = [perpod[r["n_nodes"]] / r["us_per_pod"] for r in results
                    if r.get("mode") == "batched" and r.get("us_per_pod")
                    and r["n_nodes"] in perpod]
        if speedups:
            out["max_batched_speedup"] = round(max(speedups), 2)
    elif name == "BENCH_scenarios.json":
        rates = [r["unschedulable_rate"] for r in results
                 if "unschedulable_rate" in r]
        if rates:
            out["max_unschedulable_rate"] = max(rates)
    elif name == "BENCH_carbon.json":
        red = [s["carbon_reduction_pct"]
               for s in data.get("carbon_reduction_summary", [])]
        if red:
            out["carbon_reduction_pct_range"] = [min(red), max(red)]
    elif name == "BENCH_autoscale.json":
        red = [s["idle_reduction_pct"]
               for s in data.get("idle_reduction_summary", [])
               if s["policy"] == "idle_timeout"]
        if red:
            out["idle_reduction_pct_range"] = [min(red), max(red)]
    elif name == "BENCH_pareto.json":
        # headline: best fused-vs-serial speedup at S >= 512 on jax (the
        # acceptance number); falls back to any-S when the sweep was small
        ups = [r["speedup_fused_vs_serial"] for r in results
               if r.get("backend") == "jax"
               and r.get("speedup_fused_vs_serial")
               and r.get("n_schemes", 0) >= 512]
        if not ups:
            ups = [r["speedup_fused_vs_serial"] for r in results
                   if r.get("backend") == "jax"
                   and r.get("speedup_fused_vs_serial")]
        if ups:
            out["max_grid_speedup_jax"] = round(max(ups), 2)
    return out


def _provenance_warnings(summary: dict) -> list[str]:
    """Mismatched environment fingerprints across the aggregated sweeps:
    different git SHAs or pallas interpret-mode flags mean the summary
    mixes runs that are not comparable as one sweep."""
    provs = {name: head["provenance"] for name, head in summary.items()
             if isinstance(head, dict)
             and isinstance(head.get("provenance"), dict)}
    warnings: list[str] = []
    for field, what in (("git_sha", "git SHAs"),
                        ("pallas_interpret", "pallas interpret-mode "
                                             "flags")):
        values = {name: p[field] for name, p in provs.items()
                  if field in p and p[field] is not None}
        if len(set(values.values())) > 1:
            detail = ", ".join(f"{name}={v}"
                               for name, v in sorted(values.items()))
            warnings.append(
                f"aggregated sweeps carry mismatched {what} ({detail}) "
                f"— the summary mixes runs from different "
                f"{'commits' if field == 'git_sha' else 'pallas modes'}")
    return warnings


def aggregate(out: str | None = "BENCH_summary.json") -> dict:
    """Fold every recorded BENCH_*.json into one summary dict (and file).
    Missing or unreadable sweeps are skipped with a warning — run their
    benchmarks to (re-)record them."""
    summary: dict = {}
    for name in BENCH_FILES:
        if not os.path.exists(name):
            print(f"warning: {name} not recorded yet — run its sweep "
                  f"benchmark to record it (skipping)")
            continue
        try:
            with open(name) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: could not read {name} ({e}) — re-run its "
                  f"sweep benchmark (skipping)")
            continue
        if not isinstance(data, dict) or not isinstance(
                data.get("results", []), list):
            print(f"warning: {name} is not a sweep report (expected an "
                  f"object with a 'results' list) — re-run its sweep "
                  f"benchmark (skipping)")
            continue
        try:
            head = _headline(name, data)
        except (AttributeError, KeyError, TypeError, ZeroDivisionError) as e:
            print(f"warning: {name} has an unexpected shape ({e!r}) — "
                  f"re-run its sweep benchmark (skipping)")
            continue
        # carry each sweep's recorded environment fingerprint forward so
        # the summary's numbers stay attributable without the sweep files
        if isinstance(data.get("provenance"), dict):
            head["provenance"] = data["provenance"]
        summary[name] = head
    if not summary:
        print("no BENCH_*.json recorded yet; run the sweep benchmarks first")
        return summary
    # a summary stitched from sweeps recorded at different commits or
    # pallas modes is not one coherent run — say so, loudly
    warnings = _provenance_warnings(summary)
    for w in warnings:
        print(f"warning: {w}")
    if warnings:
        summary["provenance_warnings"] = warnings
    print(f"{'sweep':28s} headline")
    for name, head in summary.items():
        extras = {k: v for k, v in head.items()
                  if k not in ("bench", "cells", "provenance")}
        print(f"{head['bench']:28s} {head['cells']} cells  "
              + "  ".join(f"{k}={v}" for k, v in extras.items()))
    from benchmarks.common import provenance
    summary["provenance"] = provenance()
    if out:
        with open(out, "w") as f:
            json.dump(summary, f, indent=1)
        print(f"wrote {out}")
    return summary


def check(files=BENCH_FILES, baseline_dir: str = BASELINE_DIR,
          verbose: bool = False) -> int:
    """Regression gate: diff each fresh BENCH_*.json against its
    committed baseline; returns the exit code (1 iff any gated metric
    regressed). Missing current files or baselines are warnings, not
    failures — a sweep that was never run can't regress."""
    from repro.telemetry.baseline import (append_history, compare_reports,
                                          format_verdict)
    from benchmarks.common import HISTORY_DIR, provenance

    exit_code = 0
    checked = 0
    for name in files:
        base_path = os.path.join(baseline_dir, name)
        if not os.path.exists(name):
            print(f"warning: {name} not recorded — run its sweep before "
                  f"checking (skipping)")
            continue
        if not os.path.exists(base_path):
            print(f"warning: no committed baseline at {base_path} "
                  f"(skipping {name})")
            continue
        try:
            with open(name) as f:
                current = json.load(f)
            with open(base_path) as f:
                baseline = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"warning: could not read {name} or its baseline "
                  f"({e}) — skipping")
            continue
        verdict = compare_reports(current, baseline)
        print(format_verdict(verdict, verbose=verbose))
        checked += 1
        bench = verdict["bench"] or name
        append_history(
            {"kind": "check", "bench": bench,
             "status": verdict["status"],
             "regressions": verdict["regressions"],
             "provenance": current.get("provenance") or provenance()},
            os.path.join(HISTORY_DIR, f"{bench}.jsonl"))
        if verdict["status"] == "regression":
            exit_code = 1
    if not checked:
        print("nothing checked: no (recorded sweep, committed baseline) "
              "pair found")
    return exit_code


def main() -> None:
    from repro.device import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    print("=" * 72)
    print("Table VI — energy by profile x competition (paper headline)")
    print("=" * 72)
    from benchmarks import table6_energy
    table6_energy.run()

    print()
    print("=" * 72)
    print("Fig 2 analogue — node allocation patterns (paper §V.D)")
    print("=" * 72)
    from benchmarks import node_allocation
    node_allocation.run()

    print()
    print("=" * 72)
    print("Scheduling time (paper §IV.C) — decision latency vs fleet size")
    print("=" * 72)
    from benchmarks import scheduling_time
    scheduling_time.run()

    print()
    print("=" * 72)
    print("Table VII — real-world impact extrapolation (paper §V.E-F)")
    print("=" * 72)
    from benchmarks import table7_impact
    table7_impact.run()

    if os.path.isdir("experiments/dryrun"):
        print()
        print("=" * 72)
        print("Roofline (assignment) — from dry-run artifacts")
        print("=" * 72)
        from benchmarks import roofline_report
        recs = roofline_report.load("experiments/dryrun", "single")
        if recs:
            print(roofline_report.fmt(recs))

    print()
    print("=" * 72)
    print("Recorded sweep summary — BENCH_*.json aggregation")
    print("=" * 72)
    aggregate()

    print(f"\n# benchmarks completed in {time.time() - t0:.1f}s")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="diff recorded BENCH_*.json against the "
                         "committed baselines and exit nonzero on "
                         "regression (runs no benchmarks)")
    ap.add_argument("--baseline-dir", default=BASELINE_DIR,
                    help="baseline directory for --check")
    ap.add_argument("--verbose", action="store_true",
                    help="with --check, print ok rows too")
    args = ap.parse_args()
    if args.check:
        sys.exit(check(baseline_dir=args.baseline_dir,
                       verbose=args.verbose))
    main()
