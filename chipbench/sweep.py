#!/usr/bin/env python3
"""Find the knee of a serving cell: one set-up, then a window at each rate.

    python chipbench/sweep.py --workload yi-6b.chat --seed 1 --seconds 30 \\
        --rates 2,3,4,5,6

For each rate of the cell's mix (the rest of the mix as the file has it), it
prints one JSON line: the median and 90th percentile of time to first
token, the 95th
of the gap between tokens, the requests still waiting for a first token at
the close, those completed, and the output tokens per second; then it
drains the engine before the next rate.  The knee is the highest rate at
which the 90th percentile of the wait for a slot stays under a second and
no request is left queued at the close.  The benchmark's runs never call
it.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import readers
    import serve

    cell = R.cells.load(args.workload, False)
    R.require_chips(cell.chips)
    compiles = R.start_jax()
    conf = cell.config
    _, ref = R.cells.modules(cell)
    cfg = serve.model_config(conf)
    eng = serve.build_engine(
        cfg, serve.program_params(cfg, ref, conf, args.seed), conf["engine"])
    serve.warm_up(eng)
    vocab = ref.dims(conf)["vocab"]
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.traffic,
                   arrivals=dict(cell.traffic["arrivals"], rate=rate))
        arrivals = cell.generator.generate(mix, args.seed, args.seconds,
                                           vocab)
        win = serve.run_window(eng, arrivals, args.seconds, None, compiles)
        queued = len(eng.sched.queue)
        run = dict(win, trace=None)
        done = sum(1 for s in win["served"] if s.req.done)
        print(json.dumps({
            "rate": rate, "due": len(win["due"]),
            "ttft_p50_ms": 1e3 * readers.percentile(readers.ttft_s(run), 50),
            "ttft_p90_ms": 1e3 * readers.percentile(readers.ttft_s(run), 90),
            "itl_p95_ms": 1e3 * readers.percentile(readers.itl_s(run), 95),
            "queue_wait_p90_ms": 1e3 * readers.percentile(
                readers.queue_wait_s(run), 90),
            "waiting_first_token_at_close": sum(
                1 for s in win["due"] if not s.times),
            "queued_at_close": queued, "completed": done,
            "output_tok_s": sum(len(s.times) for s in win["served"])
            / win["window_s"],
            "compiles_in_window": win["compiles_in_window"],
            "memory_peak_bytes": max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in R.require_chips(cell.chips))}), flush=True)
        t0 = time.perf_counter()
        eng.run_until_drained(max_ticks=10 ** 7)
        print(f"drained in {time.perf_counter() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
