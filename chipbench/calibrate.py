#!/usr/bin/env python3
"""Readings behind a cell's limits: the program's numbers compared and the
control's, over many seeds in one process.

    python chipbench/calibrate.py --workload yi-6b.chat --seconds 40 \\
        --seeds 101,102,103

Each seed is a whole run as ``run.py`` makes it (set-up, window,
comparison) with the control on, the first ``--trace-seeds`` of them
traced; one JSON line per seed, with ``program_checks`` and ``correct`` as
the program's numbers give them, and ``control_checks`` and
``control_correct`` as the harness gives them with the control in the
program's place.  A limit goes above the largest program reading and below
the smallest control reading.  A training cell needs no window for its
readings (``--seconds 0``), and ``--faults`` adds, on the first
``--fault-seeds`` seeds, the readings of faults planted in the reference
put in the program's place (:data:`FAULTS`).  The benchmark's runs never
call it.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run as R                                                  # noqa: E402

#: a training fault, planted in the reference in the program's place: the
#: rows of each step's batch that the faulty step would average over
FAULTS = {
    "half": lambda b: b // 2,        # half of the batch, the mean over it
    "exchange": lambda b: b // 4,    # one chip's rows of four, no exchange
}
#: the leaf whose gradient, or update, the "answer" fault doubles
ANSWER = "ln_f/scale"


def fault_readings(cell, seen: dict, kinds, devices) -> dict:
    """For each fault, the reference over the rows the fault keeps, read
    against the run's own sound reference (``seen``: its seed, batches and
    result).  The final norm's gradient doubled where it is produced reads
    ``g / max(g, median)`` on the gradient's worst leaf, and its update
    doubled reads the same of its change, from the sound reference alone
    (``answer``)."""
    import train
    lim, job = cell.config["check"], train.job_settings(cell)
    out = {}
    for kind in kinds:
        keep = FAULTS[kind](len(seen["batches"][0]["tokens"]))
        cut = [{k: v[:keep] for k, v in b.items()} for b in seen["batches"]]
        got = seen["real"](cell.config, seen["seed"], cut, job, False,
                           devices[:math.gcd(keep, len(devices))])
        out[kind] = train.compare(got, seen["want"], lim,
                                  cell.config["optimizer"]["clip_norm"]
                                  )[1]["readings"]
    out["answer"] = {}
    for part in ("grad", "change"):
        v = seen["want"][f"{part}_norms"]
        if any(v.values()):
            med = statistics.median(v.values())
            out["answer"][f"{part}_norm_gap"] = v[ANSWER] / max(v[ANSWER],
                                                               med)
    return out


def recording(ref, seen: dict) -> None:
    """Keep what the run hands its sound reference, and what it returns."""
    real = ref.train_check

    def train_check(model, seed, batches, job, control=False, devices=None):
        out = real(model, seed, batches, job, control, devices)
        if not control:
            seen.update(seed=seed, batches=batches, want=out, real=real)
        return out

    ref.train_check = train_check


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--trace-seeds", type=int, default=0)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="run the control on the first N seeds only "
                         "(default: every seed)")
    ap.add_argument("--faults", default="",
                    help="training faults to read, of " + ",".join(FAULTS))
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--trace-out", default=None,
                    help="write the first traced seed's trace, as plain "
                         "data, to this gzipped JSON file")
    args = ap.parse_args()
    import check
    cell = {t: R.cells.load(args.workload, t) for t in (False, True)}
    devices = R.require_chips(cell[False].chips)
    compiles = R.start_jax()
    faults, seen = [k for k in args.faults.split(",") if k], {}
    if faults:
        recording(R.cells.modules(cell[False])[1], seen)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        traced = i < args.trace_seeds
        control = args.control_seeds is None or i < args.control_seeds
        out, info = R.run_cell(cell[traced], seed, args.seconds, traced,
                               control, devices, compiles,
                               time.perf_counter(),
                               args.trace_out if i == 0 else None)
        program = {k: dict(c, value=info["program_checks"][k])
                   for k, c in out["checks"].items()}
        line = {"seed": seed, "traced": traced,
                "correct": check.verdict(program)}
        if control:
            line.update(control_correct=out["correct"],
                        control_checks={k: c["value"]
                                        for k, c in out["checks"].items()})
        if faults and i < args.fault_seeds:
            line["fault_readings"] = fault_readings(cell[traced], seen,
                                                    faults, devices)
        print(json.dumps(dict(line, metrics={k: v["value"] for k, v
                                             in out["metrics"].items()},
                              device=out["device"], **info)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
