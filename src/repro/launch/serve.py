"""Serving launcher: paged continuous-batching engine over a reduced config.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 12
    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
        --block-size 16 --prefill-chunk 32 --num-blocks 64   # KV-pool knobs
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.obs import FlightRecorder, install
from repro.plans import PlanStore
from repro.runtime import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="full config at published widths, weights "
                         "stored in bf16 (TPU-scale; default is smoke)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV cache block size in token positions "
                         "(joins the kernel-dispatch bucket keys)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool size in blocks incl. the reserved garbage "
                         "block (default: every slot can hold max-len; "
                         "smaller exercises admission waits + preemption)")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="max tokens prefilled per engine tick (chunked "
                         "prefill; tails quantize to powers of two)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="map page-aligned prompt blocks already resident "
                         "in the pool (refcounted, copy-on-write) instead "
                         "of re-prefilling them; auto-disabled for "
                         "SSM-bearing configs")
    ap.add_argument("--async-depth", type=int, default=1,
                    help="engine pipeline depth: 1 = synchronous, 2 = plan "
                         "tick t+1 on the host while the device executes "
                         "tick t (commit barrier before the next dispatch)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warm-kernels", action="store_true",
                    help="pre-resolve kernel-variant dispatch at engine "
                         "start (uses a shipped serve-plan artifact when "
                         "one matches, else compiled artifacts/online "
                         "warm-up)")
    ap.add_argument("--plan-dir", default=None,
                    help="artifact root holding serve-plan artifacts "
                         "(scripts/plan_artifacts.py output; default: "
                         "$REPRO_ARTIFACT_DIR or ./artifacts)")
    ap.add_argument("--strict-plans", action="store_true",
                    help="refuse to start from a serve plan whose recorded "
                         "dispatch-table digests no longer match this "
                         "host's tables (default: warn and fall back to "
                         "online warm-up)")
    ap.add_argument("--monitor", action="store_true",
                    help="adaptive loop: probe frozen kernel picks with "
                         "cheap wall-clock timings during traffic and "
                         "hot-swap any pick measurement persistently "
                         "contradicts (requires --warm-kernels)")
    ap.add_argument("--monitor-window", type=int, default=8,
                    help="probes per decision window")
    ap.add_argument("--monitor-every", type=int, default=4,
                    help="engine ticks between probes")
    ap.add_argument("--swap-threshold", type=float, default=1.25,
                    help="challenger must beat the incumbent median by this "
                         "ratio for a window to disagree")
    ap.add_argument("--swap-patience", type=int, default=2,
                    help="consecutive disagreeing windows before a hot-swap")
    ap.add_argument("--degrade", action="store_true",
                    help="graceful degradation: a failed kernel call demotes "
                         "the frozen pick down the candidate ranking and "
                         "retries once; a second failure preempts the "
                         "affected sequences (recompute) instead of killing "
                         "the engine")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: submissions beyond this "
                         "many waiting requests are shed with a structured "
                         "queue_full error + retry hint (default: unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline: queued or running requests "
                         "older than this are cancelled with a structured "
                         "deadline error (default: none)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="flight recorder: write the run's provenance "
                         "trace (scheduling decisions, dispatch "
                         "resolutions, swaps/demotions, fault firings) as "
                         "JSONL to PATH; feed it to scripts/trace_report.py")
    ap.add_argument("--trace-sample", type=int, default=0, metavar="N",
                    help="with --trace: sample 1-in-N hits of the frozen "
                         "warm_callable lane as dispatch_decision records "
                         "(default 0 = the warm lane stays uncounted)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="flight-recorder ring size in events; the oldest "
                         "age out first and are counted as dropped")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (dataclasses.replace(get_config(args.arch), param_dtype="bfloat16")
           if args.full else get_smoke_config(args.arch))
    if cfg.encoder is not None:
        raise SystemExit("enc-dec serving demo not wired for CLI; "
                         "see tests/test_serving.py")
    recorder = None
    if args.trace:
        recorder = FlightRecorder(capacity=args.trace_capacity,
                                  sample_frozen_every=args.trace_sample)
        install(recorder)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    plan_store = PlanStore(args.plan_dir) if args.plan_dir else None
    eng = ServeEngine(cfg, params, max_batch=args.max_batch,
                      max_len=args.max_len, page_size=args.block_size,
                      num_blocks=args.num_blocks,
                      prefill_chunk=args.prefill_chunk,
                      prefix_sharing=args.prefix_sharing,
                      async_depth=args.async_depth,
                      warm_kernels=args.warm_kernels,
                      plan_store=plan_store,
                      strict_plans=args.strict_plans,
                      monitor=args.monitor,
                      monitor_window=args.monitor_window,
                      monitor_every=args.monitor_every,
                      swap_threshold=args.swap_threshold,
                      swap_patience=args.swap_patience,
                      degrade=args.degrade,
                      max_queue=args.max_queue,
                      deadline_ms=args.deadline_ms)
    if eng.kernel_plan:
        print(f"warm-up: {len(eng.kernel_plan)} kernel picks resolved "
              f"(final provenance reported after the run)")

    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        eng.submit(rng.integers(0, cfg.vocab, plen), max_new=args.max_new)
    done = eng.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.out) for r in done)
    for r in done[:4]:
        if r.error is not None:
            print(f"req {r.rid}: [{r.error.code}] {r.error}")
        else:
            print(f"req {r.rid}: {r.out}")
    print(f"{len(done)} requests, {toks} tokens in {dt:.1f}s "
          f"({toks/dt:.1f} tok/s)")
    # the unified registry replaces the old scattered stats prints; the
    # kernel report reads the *current* frozen plan, so picks changed by
    # a monitor hot-swap or a degradation demote carry their live
    # provenance, not the warm-up snapshot
    reg = eng.registry()
    print(reg.summary_line())
    for line in reg.kernel_report():
        print(line)
    if eng.monitor is not None:
        for ev in eng.monitor.events:
            print(f"swap {ev.describe()}")
    for ev in eng.degrade_events:
        print(f"degrade {ev.describe()}")
    if recorder is not None:
        with open(args.trace, "w") as fh:
            fh.write(recorder.export_jsonl())
        print(f"trace: {recorder.emitted} events "
              f"({recorder.dropped} dropped) -> {args.trace}")


if __name__ == "__main__":
    main()
