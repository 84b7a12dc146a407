#!/usr/bin/env python3
"""The on-chip benchmark: one cell, one run, one result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process.  It refuses to run without a TPU (or
with fewer chips than the cell asks for), keeps JAX's compile cache inside
the checkout, builds the cell from the files ``BENCHMARK.json`` names
(``cell.py``), hands it to the runner its traffic mix names (``serve.py``
or ``train.py``: set-up, the window, then the comparison with the
configuration's reference), and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit.  The same numbers end
standard error.

``--control 1`` runs the correctness control (the reference in float8) in
the program's place: its first choices are compared as the served tokens,
or its training steps as the program's, so ``correct`` has to come out
false.  The program's own numbers are then printed beside it on standard
error.  The benchmark's own runs never ask for it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                                  # noqa: E402
import json                                                      # noqa: E402
import os                                                        # noqa: E402
import shutil                                                    # noqa: E402
import sys                                                       # noqa: E402
import tempfile                                                  # noqa: E402
from pathlib import Path                                         # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell as cells                                             # noqa: E402


class NoChip(RuntimeError):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="also write the trace, as plain data, to this "
                         "gzipped JSON file")
    return ap.parse_args(argv)


def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; no CPU fallback."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"this cell needs {n} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:n]


def start_jax() -> list:
    """Compile cache in the checkout (or ``$JAX_COMPILATION_CACHE_DIR``),
    every program cached, and a counter of compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = [0]

    def count(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(count)
    return compiles


def run_cell(cell, seed: int, seconds: float, trace: bool, control: bool,
             devices: list, compiles: list, t_start: float,
             trace_out: str = None):
    """One run of ``cell``: the result line, and what else the run saw."""
    import jax
    import check
    import devtrace

    runner, ref = cells.modules(cell)
    peak = json.loads((HERE / "peaks.json").read_text())
    if devices[0].device_kind not in peak:
        raise KeyError(f"no peaks for device kind {devices[0].device_kind!r} "
                       f"in peaks.json")
    peak = peak[devices[0].device_kind]
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    record, state = runner.measure(cell, ref, seed, seconds, trace_dir,
                                   compiles, t_start, devices)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    red = None
    if trace_dir is not None:
        tr = devtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_out:
            import gzip
            with gzip.open(trace_out, "wt") as f:
                json.dump(tr, f)
        red = devtrace.reduce(tr)
        if red is None:
            raise RuntimeError("the trace holds no device operation inside "
                               "the traced window")

    record.update(trace=red, dims=ref.dims(cell.config), peak=peak, ref=ref)
    metrics = {}
    for name, entry in cell.metrics.items():
        v = cell.readers[name].read(record)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": entry["unit"]}

    # correctness, after the window and with the program's state freed
    checks, attempted, failed, info = runner.check(cell, ref, state, seed,
                                                   control, devices)
    out = {"correct": check.verdict(checks), "attempted": attempted,
           "failed": failed, "metrics": metrics,
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": int(mem)}}
    if red is not None:
        out["device"].update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = red["breakdown"]
    out["checks"] = checks              # last: the numbers and their limits
    return out, info


def main(argv=None, require=require_chips) -> int:
    args = parse(argv)
    cell = cells.load(args.workload, bool(args.trace))
    try:
        devices = require(cell.chips)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    compiles = start_jax()
    out, info = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         bool(args.control), devices, compiles, T_START,
                         args.trace_out)
    import check
    print(f"chipbench: {json.dumps(info)}", file=sys.stderr)
    for line in check.lines(out["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip the runtime's teardown logs, so the check lines end stderr
    os._exit(code)
