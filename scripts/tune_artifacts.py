#!/usr/bin/env python
"""Tune dispatch tables against measured hardware: measure -> calibrate ->
compact -> rewrite.

Loads each (family, machine) dispatch table (compiling it first when absent),
times the top-k pre-ranked candidates per data-shape bucket as Pallas,
compiled on a TPU and interpreted on the CPU backend (deterministic seeds,
trimmed-mean over repeats), fits the
KLARAPTOR-style per-family calibration, computes the "few fit most" variant
subset, and rewrites the table in place with the optional FORMAT_VERSION-2
sections (``calibration``, ``measured_ranks``, ``compaction``).  The runtime
``DispatchCache`` then prefers the measured order; untuned tables keep
resolving symbolically.  See docs/tuning.md for the full workflow.

    PYTHONPATH=src python scripts/tune_artifacts.py \
        --family matmul --machine tpu_v5e --out artifacts
    PYTHONPATH=src python scripts/tune_artifacts.py --dry-run   # CI smoke
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.artifacts import ArtifactStore, compile_family      # noqa: E402
from repro.core.params import MACHINES                          # noqa: E402
from repro.tuning import MeasureConfig, calibrate_table, \
    compact_table, measure_table                                # noqa: E402
from repro.tuning.compact import compaction_summary             # noqa: E402
from repro.tuning.measure import interpret_pallas, measure_shape, \
    parse_bucket_key                                            # noqa: E402


def _load_or_compile(store, family, machine, quick):
    table = store.load_dispatch(family.name, machine.name)
    if table is None:
        print(f"[compile] no dispatch table for {family.name}/{machine.name}"
              f" under {store.root}; compiling", flush=True)
        compile_family(family, store, machines=[machine], quick=quick)
        table = store.load_dispatch(family.name, machine.name)
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--family", action="append", default=None,
                    help="kernel family to tune (repeatable; default all)")
    ap.add_argument("--machine", action="append", default=None,
                    choices=sorted(MACHINES),
                    help="target machine (repeatable; default all)")
    ap.add_argument("--out", default=None,
                    help="artifact root (default: $REPRO_ARTIFACT_DIR "
                         "or ./artifacts)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timed repeats per candidate")
    ap.add_argument("--warmup", type=int, default=1,
                    help="untimed warm-up runs per candidate")
    ap.add_argument("--trim", type=int, default=1,
                    help="repeats trimmed from each end before the mean")
    ap.add_argument("--top-k", type=int, default=4,
                    help="candidates measured per bucket (prefix of the "
                         "table's symbolic ranking)")
    ap.add_argument("--max-dim", type=int, default=256,
                    help="clamp measured data dims (interpreted Pallas pays "
                         "per grid step on CPU; raise on a real TPU)")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="few-fit-most relative tolerance vs per-bucket best")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for deterministic operand tensors")
    ap.add_argument("--quick", action="store_true",
                    help="when compiling a missing table, build one bucket")
    ap.add_argument("--dry-run", action="store_true",
                    help="resolve tables and list the measurement plan "
                         "without running any kernel (CI smoke)")
    args = ap.parse_args(argv)

    from repro.artifacts.compile import registered_families
    registry = registered_families()
    names = args.family if args.family else sorted(registry)
    unknown = [n for n in names if n not in registry]
    if unknown:
        ap.error(f"unknown kernel family {unknown}; have {sorted(registry)}")
    machines = [MACHINES[m] for m in (args.machine or sorted(MACHINES))]
    store = ArtifactStore(args.out)
    cfg = MeasureConfig(iters=args.iters, warmup=args.warmup, trim=args.trim,
                        max_dim=args.max_dim, top_k=args.top_k,
                        seed=args.seed)
    meta = {"iters": cfg.iters, "warmup": cfg.warmup, "trim": cfg.trim,
            "max_dim": cfg.max_dim, "top_k": cfg.top_k, "seed": cfg.seed,
            "interpret": interpret_pallas()}

    failures = 0
    for name in names:
        family = registry[name]
        for machine in machines:
            t0 = time.perf_counter()
            table = _load_or_compile(store, family, machine, args.quick)
            if table is None:
                print(f"[FAIL] {name}/{machine.name}: could not load or "
                      f"compile a dispatch table", file=sys.stderr)
                failures += 1
                continue
            buckets = table.get("buckets", {})
            plan_rows = sum(min(len(v), cfg.top_k) for v in buckets.values())
            if args.dry_run:
                print(f"[dry-run] {name}/{machine.name}: "
                      f"{len(buckets)} buckets, {plan_rows} candidate "
                      f"timings planned (top-{cfg.top_k}, "
                      f"max_dim={cfg.max_dim})")
                for b in sorted(buckets):
                    head = buckets[b][:cfg.top_k]
                    try:
                        shape = measure_shape(
                            name, parse_bucket_key(b),
                            [e["assignment"] for e in head], cfg.max_dim)
                    except (KeyError, TypeError, ValueError):
                        # same tolerance as measure_table: a mangled bucket
                        # is skipped, not a crash
                        print(f"           {b} -> skipped (unparseable)")
                        continue
                    print(f"           {b} -> measure at {shape} "
                          f"({len(head)} candidates)")
                continue
            samples = measure_table(
                family, table, cfg,
                progress=lambda s: print(f"  [measure] {s}", flush=True))
            ok = [s for s in samples if s.us is not None]
            tuned = calibrate_table(family, table, samples, meta=meta)
            tuned = compact_table(tuned, samples, tolerance=args.tolerance)
            path = store.save_dispatch(tuned)
            cal = tuned.get("calibration")
            fit_line = ("no fit (too few samples)" if cal is None else
                        f"fit n={cal['n_samples']} "
                        f"rms_log_resid={cal['rms_log_residual']:.3f} "
                        f"top1_agreement={cal['top1_agreement']}")
            print(f"[OK] {name}/{machine.name}: {len(ok)}/{len(samples)} "
                  f"candidates measured across {len(buckets)} buckets "
                  f"({time.perf_counter() - t0:.1f}s)\n"
                  f"     {fit_line}\n"
                  f"     compaction: {compaction_summary(tuned)}\n"
                  f"     -> {path}", flush=True)
            if not ok:
                print(f"[FAIL] {name}/{machine.name}: every measurement "
                      f"failed", file=sys.stderr)
                failures += 1
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
