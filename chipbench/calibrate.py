#!/usr/bin/env python3
"""Readings behind a cell's limit: the program's widest logit gap and the
control's, over many seeds in one process.

    python chipbench/calibrate.py --workload yi-6b.chat --seconds 40 \\
        --seeds 101,102,103

Each seed is a whole run as ``run.py`` makes it (weights, engine, warm-up,
window, comparison) with the control on, the first ``--trace-seeds`` of
them traced; one JSON line per seed, with ``correct`` as the program's
numbers give it and ``control_correct`` as the harness gives it with the
control in the program's place.  The limit goes above the largest program
reading and below the smallest control reading.  The benchmark's runs never
call it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R                                                  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace-seeds", type=int, default=0)
    args = ap.parse_args()
    import check
    cell = {t: R.cells.load(args.workload, t) for t in (False, True)}
    devices = R.require_chips(cell[False].chips)
    compiles = R.start_jax()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        traced = i < args.trace_seeds
        out, info = R.run_cell(cell[traced], seed, args.seconds, traced, True,
                               devices, compiles, time.perf_counter())
        gap = dict(out["checks"]["max_logit_gap"],
                   value=info["program_max_logit_gap"])
        print(json.dumps({"seed": seed, "traced": traced,
                          "correct": check.verdict(dict(out["checks"],
                                                        max_logit_gap=gap)),
                          "control_correct": out["correct"],
                          "metrics": {k: v["value"]
                                      for k, v in out["metrics"].items()},
                          "device": out["device"], **info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
