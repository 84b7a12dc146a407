"""Benchmark harness — one function per paper table/figure.

Wall-clock numbers are CPU-XLA (the container's only runtime) and are used
for *relative* variant comparisons; the TPU-side ranking column comes from
the comprehensive tree's offline performance model, which is the mechanism
the paper evaluates.  CSV columns: name,us_per_call,derived.

    PYTHONPATH=src python -m benchmarks.run [--quick]
    PYTHONPATH=src python -m benchmarks.run --only dispatch,compile \
        --json BENCH_dispatch.json        # machine-readable, CI gate input

``--json`` writes every measured row as ``{"rows": [{name, us, derived}]}``
(plus meta); ``scripts/check_bench.py`` compares that against the committed
``benchmarks/baseline.json`` and fails CI on a >2x regression of ANY gated
row (cold/warm dispatch, fast-lane warm ops, serve decode, plan-backed
start, compile and tuning sweeps) — and, under ``--strict``, on any
measured row missing from the baseline.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import TPU_V5E, best_variant, comprehensive_tree, \
    enumerate_candidates
from repro.kernels import ops, ref
from repro.launch.compile_cache import enable_compile_cache
from repro.kernels.jacobi1d import FAMILY as JACOBI
from repro.kernels.matadd import FAMILY as MATADD
from repro.kernels.matmul import FAMILY as MATMUL
from repro.kernels.transpose import FAMILY as TRANSPOSE


def _time(fn, *args, iters=5, warmup=2) -> float:
    """Median wall-time in microseconds (jit path, CPU)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def bench_table1_matmul(quick=False):
    """Paper Table 1: best thread-block format shifts with input size.

    Derived column: the offline-model ranking of (bn,s,bm) per size —
    the framework-level reproduction of the size-dependent optimum."""
    rows = []
    sizes = [1 << 9] if quick else [1 << 10, 1 << 11]
    mm = jax.jit(ref.matmul)
    for n in sizes:
        a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
        us = _time(mm, a, b, iters=3 if n > 1024 else 5)
        cands = enumerate_candidates(MATMUL, TPU_V5E,
                                     {"M": n, "N": n, "K": n})
        cands.sort(key=lambda c: c.score, reverse=True)
        top = cands[0]
        derived = (f"best=(bm={top.assignment['bm']} "
                   f"bn={top.assignment['bn']} s={top.assignment['s']} "
                   f"bk={top.assignment['bk']}) score={top.score:.3f} "
                   f"nleaves={len(set(c.leaf_index for c in cands))}")
        rows.append((f"table1_matmul_n{n}", us, derived))
    return rows


def bench_table2_jacobi(quick=False):
    """Paper Table 2: 1D Jacobi, thread-block x granularity sweep."""
    n = (1 << 12) + 2 if quick else (1 << 15) + 2
    steps = 4
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))
    jac = jax.jit(lambda v: ref.jacobi1d(v, steps))
    us = _time(jac, x)
    cand = best_variant(JACOBI, TPU_V5E, {"N": n, "T": steps})
    return [(f"table2_jacobi_n{n}", us, f"best={cand.describe()}")]


def bench_table3_transpose(quick=False):
    """Paper Table 3: matrix transposition block sweep."""
    n = 1 << 10 if quick else 1 << 13
    a = jax.random.normal(jax.random.PRNGKey(3), (n, n))
    tr = jax.jit(ref.transpose)
    us = _time(tr, a)
    cand = best_variant(TRANSPOSE, TPU_V5E, {"M": n, "N": n})
    return [(f"table3_transpose_n{n}", us, f"best={cand.describe()}")]


def bench_fig2_matadd(quick=False):
    """Paper Fig. 2: the matrix-addition comprehensive kernel (case count)."""
    n = 1 << 10 if quick else 1 << 12
    a = jax.random.normal(jax.random.PRNGKey(4), (n, n))
    add = jax.jit(ref.matadd)
    us = _time(add, a, a)
    leaves = comprehensive_tree(MATADD)
    cand = best_variant(MATADD, TPU_V5E, {"M": n, "N": n})
    return [(f"fig2_matadd_n{n}", us,
             f"cases={len(leaves)} best={cand.describe()}")]


def bench_dispatch_cache(quick=False):
    """Amortized dispatch: cold tree-search vs warm DispatchCache lookup.

    Derived column reports the speedup — the number that justifies shipping
    precompiled artifacts for serving-style traffic where the same
    (family, machine, shape) triple recurs millions of times.  The cold row
    is the compiled symbolic core's headline number (vectorized candidate
    enumeration; was ~6.4s with per-candidate exact Fraction arithmetic)."""
    from repro.artifacts.dispatch import DispatchCache
    from repro.core.select import STATS
    cache = DispatchCache()
    data = {"M": 1024, "N": 1024, "K": 1024}
    STATS.reset()
    t0 = time.perf_counter()
    cold = cache.best_variant(MATMUL, TPU_V5E, data)
    cold_us = (time.perf_counter() - t0) * 1e6
    iters = 200 if quick else 2000
    t0 = time.perf_counter()
    for _ in range(iters):
        warm = cache.best_variant(MATMUL, TPU_V5E, data)
    warm_us = (time.perf_counter() - t0) * 1e6 / iters
    assert warm == cold and STATS.enumerate_calls == 1
    return [
        ("dispatch_cold_matmul", cold_us,
         f"best={cold.describe()} rows={STATS.rows_screened}"),
        ("dispatch_warm_matmul", warm_us,
         f"speedup={cold_us / max(warm_us, 1e-9):.0f}x "
         f"enumerate_calls={STATS.enumerate_calls}"),
    ]


def bench_dispatch_reference(quick=False):
    """The pre-compiled-core exact enumeration, for the speedup column."""
    from repro.core.select import enumerate_candidates
    n = 512 if quick else 1024
    data = {"M": n, "N": n, "K": n}
    t0 = time.perf_counter()
    cands = enumerate_candidates(MATMUL, TPU_V5E, data, use_compiled=False)
    ref_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    enumerate_candidates(MATMUL, TPU_V5E, data, use_compiled=True)
    fast_us = (time.perf_counter() - t0) * 1e6
    return [("dispatch_reference_matmul", ref_us,
             f"cands={len(cands)} compiled={fast_us:.0f}us "
             f"speedup={ref_us / max(fast_us, 1e-9):.0f}x")]


def bench_compile_sweep(quick=False):
    """Offline ``compile_family`` sweep (what scripts/compile_artifacts.py
    pays per family x machine x bucket) — the compiled core's other
    beneficiary."""
    from repro.artifacts import ArtifactStore, compile_family
    with tempfile.TemporaryDirectory() as tmp:
        t0 = time.perf_counter()
        report = compile_family(MATMUL, ArtifactStore(tmp),
                                machines=[TPU_V5E], quick=quick)
        us = (time.perf_counter() - t0) * 1e6
    return [("compile_sweep_matmul", us,
             f"buckets={report['dispatch'][TPU_V5E.name]['buckets']} "
             f"enumerate_calls={report['enumerate_calls']} "
             f"rows={report['rows_screened']}")]


#: One serving-representative shape per family for the warm-path benches.
WARM_SHAPES = {
    "matmul": {"M": 1024, "N": 1024, "K": 1024},
    "matadd": {"M": 1024, "N": 1024},
    "jacobi1d": {"N": 4096},
    "transpose": {"M": 1024, "N": 1024},
    "flash_attention": {"SQ": 512, "HD": 64},
    "ssd_scan": {"SQ": 512, "HD": 64, "STATE": 64},
}


def bench_warm_dispatch(quick=False):
    """Steady-state select+instantiate per family — the path serving traffic
    multiplies by tokens x ops x requests.

    The measured row is the ops-layer fast lane exactly as the op wrappers
    run it: lock-free cache read + ``DispatchCache.warm_callable``
    returning the pre-built kernel callable (``DispatchCache.freeze`` +
    the instantiation cache).  The derived column reports the
    pre-fast-lane warm path for the speedup, again at the ops layer:
    resolution through the LRU tier — sorted ``DispatchKey`` rebuild under
    the cache lock — plus a fresh ``instantiate`` partial rebuild per
    call, exactly the per-call costs the fast lane removes (ISSUE 4; the
    old path additionally took a per-call default-cache lock, which
    ``get_default_cache`` no longer does, so the comparison is if anything
    conservative)."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.kernels.ops import FAMILIES
    prior = get_default_cache()
    fast_cache = DispatchCache()
    fast_cache.freeze([(FAMILIES[f], TPU_V5E, d)
                       for f, d in WARM_SHAPES.items()])
    legacy_cache = DispatchCache()    # unfrozen: pre-fast-lane resolution
    iters = 2000 if quick else 20000
    rows = []
    try:
        for fname, data in WARM_SHAPES.items():
            fam = FAMILIES[fname]
            # both loops exclude the per-call data-structure build (items
            # tuple here, data dict on the legacy path — ops wrappers build
            # either as a literal from shapes, at near-identical cost):
            # what's timed is resolution, not operand packaging
            items = tuple(data.items())
            set_default_cache(fast_cache)
            fast_us = float("inf")
            for _ in range(3):                 # best-of-3: both loops are
                t0 = time.perf_counter()       # pure host work, min is right
                for _ in range(iters):
                    fn = get_default_cache().warm_callable(fam, TPU_V5E,
                                                           items, False)
                fast_us = min(fast_us,
                              (time.perf_counter() - t0) * 1e6 / iters)
            set_default_cache(legacy_cache)
            legacy_cache.best_variant(fam, TPU_V5E, data)    # warm the LRU
            legacy_iters = max(1, iters // 10)
            legacy_us = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(legacy_iters):
                    # pre-fast-lane select(): locked LRU resolve with
                    # per-call sorted-key rebuild
                    cand = get_default_cache().best_variant(fam, TPU_V5E,
                                                            data)
                    legacy_fn = fam.instantiate_fresh(cand.plan,
                                                      cand.assignment, False)
                legacy_us = min(legacy_us, (time.perf_counter() - t0)
                                * 1e6 / legacy_iters)
            assert fn is not None and legacy_fn is not None
            rows.append((f"warm_dispatch_{fname}", fast_us,
                         f"legacy={legacy_us:.2f}us "
                         f"speedup={legacy_us / max(fast_us, 1e-9):.1f}x "
                         f"ns_per_op={fast_us * 1e3:.0f}"))
    finally:
        set_default_cache(prior)
    return rows


def bench_serve_decode(quick=False):
    """Tokens/s through ``ServeEngine.run_until_drained`` on the dry-run
    (smoke) model with warm+frozen kernel dispatch — the end-to-end number
    the warm-path fast lane exists to protect.  Row value is host-side
    microseconds per generated token (CPU-XLA; relative signal)."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.runtime import ServeEngine
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    # warm_kernels freezes into the process-default cache: run against a
    # private one so later bench groups see an unmutated default
    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          warm_kernels=True)
        rng = np.random.default_rng(0)
        # warmup tick set: compile prefill/decode outside the timed region.
        # A 31-token prompt prefills in chunks 16+8+4+2+1 — every quantized
        # chunk shape the timed prompts (4..23 tokens) can hit.
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)
        eng.run_until_drained()
        nreq, max_new = (3, 8) if quick else (8, 16)
        for _ in range(nreq):
            plen = int(rng.integers(4, 24))
            eng.submit(rng.integers(0, cfg.vocab, plen), max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run_until_drained()
        dt = time.perf_counter() - t0
    finally:
        set_default_cache(prior)
    toks = sum(len(r.out) for r in done)
    assert len(done) == nreq and toks > 0
    return [("serve_decode_smoke", dt * 1e6 / toks,
             f"tok/s={toks / dt:.0f} requests={nreq} "
             f"frozen={len(eng.kernel_plan)}picks")]


def _serve_load_scenario(arch, row, *, quick, nreq, arrival_scale=2.0,
                         plen_fn=None, max_new_hi=None, shared_len=0,
                         prefix_sharing=True, async_depth=2):
    """One load-bench traffic scenario: Poisson arrivals (inter-arrival
    gaps ~ Exp(``arrival_scale``) ticks; 0 = burst, everything at tick 0)
    of mixed-length requests against the ``arch`` smoke config, reported
    as three ``{row}_{tok,p50,p99}_us`` rows.  ``plen_fn(rng)`` draws one
    prompt length (default: the 70% short / 30% long production mix);
    ``shared_len > 0`` prepends a common system-prompt prefix of that many
    tokens to every request — the prefix-sharing fast path (auto-disabled
    engine-side for SSM-bearing archs).  Per-token latency charges each
    generated token its engine tick's wall time — the inter-token gap a
    client of that request observes.  Pool invariants (incl. block-table /
    free-list disjointness) are asserted every tick."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.runtime import ServeEngine
    cfg = get_smoke_config(arch)
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          page_size=16, prefill_chunk=16,
                          prefix_sharing=prefix_sharing,
                          async_depth=async_depth, warm_kernels=True)
        rng = np.random.default_rng(0)
        # warmup: a 31-token prompt prefills in chunks 16+8+4+2+1 —
        # every quantized chunk shape the timed run can hit — plus decode
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)
        eng.run_until_drained()
        shared = rng.integers(0, cfg.vocab, shared_len)
        if plen_fn is None:
            def plen_fn(r):                  # 70% short / 30% long mix
                return (int(r.integers(4, 13)) if r.random() < 0.7
                        else int(r.integers(24, 57)))
        gaps = (rng.exponential(scale=arrival_scale, size=nreq)
                if arrival_scale > 0 else np.zeros(nreq))
        arrive = np.floor(np.cumsum(gaps)).astype(int)
        plens = [plen_fn(rng) for _ in range(nreq)]
        news = [int(rng.integers(4, max_new_hi or (9 if quick else 17)))
                for _ in range(nreq)]
        per_token, done, submitted, tick = [], [], 0, 0
        t_start = time.perf_counter()
        while len(done) < nreq and tick < 10_000:
            while submitted < nreq and arrive[submitted] <= tick:
                tail = rng.integers(0, cfg.vocab, plens[submitted])
                eng.submit(np.concatenate([shared, tail]),
                           max_new=news[submitted])
                submitted += 1
            before = sum(len(s.req.out) for s in eng.sched.running())
            t0 = time.perf_counter()
            finished = eng.step()
            dt = (time.perf_counter() - t0) * 1e6
            after = sum(len(s.req.out) for s in eng.sched.running()) \
                + sum(len(r.out) for r in finished)
            per_token.extend([dt] * max(0, after - before))
            done.extend(finished)
            eng.pool.check_invariants(
                [s.blocks for s in eng.sched.running()])
            tick += 1
        total_s = time.perf_counter() - t_start
    finally:
        set_default_cache(prior)
    toks = sum(len(r.out) for r in done)
    assert len(done) == nreq and toks > 0 and per_token
    st, pst = eng.sched.stats, eng.pool.stats
    lat = np.asarray(per_token)
    meta = (f"tok/s={toks / total_s:.0f} requests={nreq} ticks={tick} "
            f"chunks={st.prefill_chunks} preempt={st.preemptions} "
            f"waits={st.admission_waits} "
            f"prefix_saved={pst.prefix_tokens_saved} "
            f"cow={pst.cow_copies}")
    return [
        (f"{row}_tok_us", total_s * 1e6 / toks, meta),
        (f"{row}_p50_us", float(np.percentile(lat, 50)), f"tokens={toks}"),
        (f"{row}_p99_us", float(np.percentile(lat, 99)), f"tokens={toks}"),
    ]


def bench_serve_load(quick=False):
    """Poisson-arrival load over the paged engine across the config zoo:
    requests arrive mid-flight with mixed prompt/output lengths, exercising
    chunked prefill interleaved with decode, refcounted block-pool churn,
    prefix sharing, async tick overlap, and admission head-room — the
    production-traffic shapes the scheduler exists for.

    Scenarios (each contributes ``*_tok_us``/``*_p50_us``/``*_p99_us``
    rows, all gated in ``benchmarks/baseline.json``):

    - ``serve_load`` — the llama3 70/30 short/long mix (the PR 6 rows),
      now with prefix sharing + ``async_depth=2`` enabled and a 16-token
      shared system prefix on every prompt; the acceptance gate that the
      new machinery does not regress the existing mix.
    - ``serve_load_mamba`` — the same mix on ``mamba2_130m``: prefix
      sharing auto-disables (recurrent state cannot skip prompt tokens),
      so this gates the async-overlap path on the SSM decode step.
    - ``serve_load_moe`` — the mix on the ``llama4_scout_17b_a16e`` smoke
      scale: routed-expert prefill/decode under paged serving.
    - ``serve_load_burst`` — every request arrives at tick 0 (admission
      pressure, head-room waits, same-tick admissions that cannot share).
    - ``serve_load_flood`` — long-context flood: every prompt is 48–89
      tokens against ``max_len=128``, maximal chunked-prefill pressure and
      pool churn.
    """
    quick_n, full_n = (3, 5), (5, 12)
    n_small = quick_n[0] if quick else full_n[0]
    n_mix = quick_n[1] if quick else full_n[1]
    rows = []
    rows += _serve_load_scenario("llama3_8b", "serve_load", quick=quick,
                                 nreq=n_mix, shared_len=16)
    rows += _serve_load_scenario("mamba2_130m", "serve_load_mamba",
                                 quick=quick, nreq=n_small, shared_len=16)
    rows += _serve_load_scenario("llama4_scout_17b_a16e", "serve_load_moe",
                                 quick=quick, nreq=n_small, shared_len=16)
    rows += _serve_load_scenario("llama3_8b", "serve_load_burst",
                                 quick=quick, nreq=n_mix, arrival_scale=0,
                                 shared_len=16)
    rows += _serve_load_scenario(
        "llama3_8b", "serve_load_flood", quick=quick, nreq=n_small,
        arrival_scale=1.0, max_new_hi=9,
        plen_fn=lambda r: int(r.integers(48, 90)))
    return rows


def bench_serve_prefix_hit(quick=False):
    """Prefix-sharing payoff: N requests sharing an 80% prompt prefix vs
    the same N with disjoint prompts, on the llama3 smoke config with
    ``prefix_sharing=True`` and ``async_depth=2``.

    A leader request carrying the shared prefix drains first (its blocks
    stay resident in the pool's prefix index after retirement), then the N
    followers are submitted together.  Gated rows (``--strict`` in CI):

    - ``serve_prefix_prefill_tok`` — prompt tokens actually computed for
      the N shared-prefix followers (the number prefix sharing shrinks;
      the run **asserts ≥ 2x reduction** vs the disjoint control).
    - ``serve_prefix_p50_us`` / ``serve_prefix_p99_us`` — per-token
      latency of the shared-prefix run (each token charged its tick's
      wall time), so CoW copies and index upkeep cannot silently eat the
      tokens they save.
    """
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.runtime import ServeEngine
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    nreq = 4 if quick else 8
    plen, shared_frac = 40, 0.8
    shared_n = int(plen * shared_frac)

    def drive(shared):
        rng = np.random.default_rng(0)
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          page_size=16, prefill_chunk=16,
                          prefix_sharing=True, async_depth=2,
                          warm_kernels=True)
        # warmup compiles every chunk shape; drop whatever it cached so
        # both runs start from an identical (empty) prefix index
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)
        eng.run_until_drained()
        eng.pool.release_prefix_cache()
        prefix = rng.integers(0, cfg.vocab, shared_n)
        eng.submit(np.concatenate([prefix,
                                   rng.integers(0, cfg.vocab,
                                                plen - shared_n)]),
                   max_new=4)
        eng.run_until_drained()              # leader: populates the index
        st0 = eng.sched.stats.prefill_tokens
        for _ in range(nreq):
            head = (prefix if shared
                    else rng.integers(0, cfg.vocab, shared_n))
            eng.submit(np.concatenate(
                [head, rng.integers(0, cfg.vocab, plen - shared_n)]),
                max_new=8)
        per_token, done, tick = [], [], 0
        while len(done) < nreq and tick < 10_000:
            before = sum(len(s.req.out) for s in eng.sched.running())
            t0 = time.perf_counter()
            finished = eng.step()
            dt = (time.perf_counter() - t0) * 1e6
            after = sum(len(s.req.out) for s in eng.sched.running()) \
                + sum(len(r.out) for r in finished)
            per_token.extend([dt] * max(0, after - before))
            done.extend(finished)
            eng.pool.check_invariants(
                [s.blocks for s in eng.sched.running()])
            tick += 1
        assert len(done) == nreq
        return (eng.sched.stats.prefill_tokens - st0,
                np.asarray(per_token), eng.pool.stats)

    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        disjoint_toks, _, _ = drive(shared=False)
        shared_toks, lat, pst = drive(shared=True)
    finally:
        set_default_cache(prior)
    reduction = disjoint_toks / max(shared_toks, 1)
    assert reduction >= 2.0, (
        f"prefix sharing saved too little prefill: {shared_toks} tokens "
        f"computed vs {disjoint_toks} disjoint ({reduction:.2f}x < 2x)")
    meta = (f"disjoint={disjoint_toks}tok reduction={reduction:.1f}x "
            f"hits={pst.prefix_hits} saved={pst.prefix_tokens_saved} "
            f"cow={pst.cow_copies}")
    return [
        ("serve_prefix_prefill_tok", float(shared_toks), meta),
        ("serve_prefix_p50_us", float(np.percentile(lat, 50)),
         f"requests={nreq}"),
        ("serve_prefix_p99_us", float(np.percentile(lat, 99)),
         f"requests={nreq}"),
    ]


def bench_plan_load(quick=False):
    """Plan-backed serving start (load a shipped serve-plan artifact +
    ``DispatchCache.freeze_resolved``) vs the online traced warm-up it
    replaces — the number that justifies building plans offline and
    shipping them to every host of a serving mesh.  The measured row is the
    plan path; the derived column reports the online path and asserts the
    plan-backed start performed ZERO cold resolutions with picks identical
    to the online freeze (the acceptance properties of ISSUE 5)."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.plans import PlanStore, build_serve_plan, warm_from_plan
    from repro.runtime.serving import warm_kernel_dispatch
    cfg = get_smoke_config("llama3_8b")
    prior = get_default_cache()
    try:
        with tempfile.TemporaryDirectory() as tmp:
            store = PlanStore(tmp)
            plan, _ = build_serve_plan(cfg, max_len=128,
                                       cache=DispatchCache())
            store.save_plan(plan)
            # online traced warm-up on a fresh cache (trees stay memoized
            # process-wide, so this is the in-process re-warm cost, not the
            # fresh-process cold number gated by dispatch_cold_matmul)
            online_cache = DispatchCache()
            set_default_cache(online_cache)
            t0 = time.perf_counter()
            online_picks = warm_kernel_dispatch(cfg, max_len=128,
                                                plan_store=False)
            online_us = (time.perf_counter() - t0) * 1e6
            # plan-backed start on another fresh cache
            plan_cache = DispatchCache()
            t0 = time.perf_counter()
            picks = warm_from_plan(cfg, max_len=128, store=store,
                                   cache=plan_cache)
            plan_us = (time.perf_counter() - t0) * 1e6
    finally:
        set_default_cache(prior)
    assert picks is not None and plan_cache.stats.cold_builds == 0
    assert {k: v["candidate"] for k, v in picks.items()} == \
           {k: v["candidate"] for k, v in online_picks.items()}
    return [("plan_load_smoke", plan_us,
             f"online={online_us:.0f}us "
             f"speedup={online_us / max(plan_us, 1e-9):.0f}x "
             f"entries={len(picks)} cold=0")]


def bench_tuning_sweep(quick=False):
    """The measure -> calibrate -> compact loop (scripts/tune_artifacts.py)
    end to end for one matmul bucket on interpreted Pallas — the cost of
    closing the offline-ranking loop against the machine, and the CI gate
    that keeps the tuning pipeline runnable."""
    from repro.artifacts import ArtifactStore, compile_family
    from repro.tuning import MeasureConfig, calibrate_table, compact_table, \
        measure_table
    n = 128 if quick else 256
    shape = {"M": n, "N": n, "K": n}
    cfg = MeasureConfig(iters=2, warmup=1, trim=0, max_dim=n,
                        top_k=2 if quick else 4)
    with tempfile.TemporaryDirectory() as tmp:
        store = ArtifactStore(tmp)
        compile_family(MATMUL, store, machines=[TPU_V5E], shapes=[shape])
        table = store.load_dispatch(MATMUL.name, TPU_V5E.name)
        t0 = time.perf_counter()
        samples = measure_table(MATMUL, table, cfg)
        tuned = compact_table(calibrate_table(MATMUL, table, samples),
                              samples)
        store.save_dispatch(tuned)
        us = (time.perf_counter() - t0) * 1e6
    ok = sum(s.us is not None for s in samples)
    comp = tuned["compaction"]
    return [("tuning_sweep_matmul", us,
             f"measured={ok}/{len(samples)} "
             f"variants={comp['total_variants_measured']}->"
             f"{len(comp['variants'])} "
             f"covered={comp['buckets_covered']}/{comp['buckets_total']}")]


def bench_tree_build():
    """Offline cost of comprehensive optimization itself (paper §6 claims
    the computer-algebra part is not a bottleneck)."""
    from repro.core import comprehensive_optimization
    rows = []
    for fam in (MATMUL, MATADD, JACOBI, TRANSPOSE):
        t0 = time.perf_counter()
        leaves = comprehensive_optimization(fam)
        us = (time.perf_counter() - t0) * 1e6
        rows.append((f"treebuild_{fam.name}", us, f"leaves={len(leaves)}"))
    return rows


def bench_lm_step(quick=False):
    """End-to-end smoke-scale LM train step wall time."""
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.optim import adamw, constant
    from repro.runtime import build_train_step
    rows = []
    for arch in (["llama3_8b"] if quick else
                 ["llama3_8b", "mamba2_130m", "kimi_k2_1t_a32b"]):
        cfg = get_smoke_config(arch)
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        opt = adamw(constant(1e-3))
        state = opt.init(params)
        step = jax.jit(build_train_step(cfg, opt, microbatches=2))
        B, S = 4, 64
        batch = {"tokens": jnp.zeros((B, S), jnp.int32),
                 "labels": jnp.zeros((B, S), jnp.int32)}
        zero = jnp.zeros((), jnp.int32)
        us = _time(lambda p, s, b: step(p, s, b, zero),
                   params, state, batch, iters=3)
        toks = B * S / (us / 1e6)
        rows.append((f"train_step_{arch}", us, f"tok/s={toks:.0f}"))
    return rows


def bench_adaptive_swap(quick=False):
    """Adaptive-serving loop (ISSUE 8): how fast the monitor detects and
    corrects a wrong frozen pick, and what serving costs after the swap.

    ``adaptive_detect_ticks`` is the detection latency in engine ticks for
    a fabricated drift scenario driven by a deterministic skewed timer
    (window x patience probes at probe_every=1 — the architectural bound,
    so a regression means the decision loop itself got lazier, not noise).
    ``adaptive_post_swap_tok_us`` is host µs per generated token through a
    monitored ``ServeEngine`` whose swap fires during warmup traffic —
    the monitored steady state, directly comparable to
    ``serve_decode_smoke``."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.core.select import rank_candidates
    from repro.kernels.ops import FAMILIES
    from repro.models import init_model
    from repro.plans.trace import trace_warm_set
    from repro.runtime import KernelMonitor, ServeEngine
    from repro.runtime.monitor import cand_key

    def skewed_timer(skews, default=4e-3):
        def timer(family, plan, assignment, data, cfg):
            key = tuple(sorted((k, int(v)) for k, v in assignment.items()))
            for (_, asg), secs in skews.items():
                if asg == key:
                    return [secs]
            return [default]
        return timer

    rows = []
    fam = FAMILIES["matmul"]
    data = {"M": 256, "N": 256, "K": 256}

    # -- detection latency: ticks from drift onset to hot-swap ---------------
    cache = DispatchCache()
    ranked = rank_candidates(fam, TPU_V5E, data)
    wrong, best = ranked[1], ranked[0]
    cache.freeze_resolved([(fam, TPU_V5E, data, wrong, "symbolic")])
    mon = KernelMonitor(cache, machine=TPU_V5E, window=4, patience=2,
                        probe_every=1, top_k=2, seed=0,
                        timer=skewed_timer({cand_key(wrong): 8e-3,
                                            cand_key(best): 1e-3}))
    mon.track(fam, data)
    detect = None
    for t in range(16 * mon.window * mon.patience):
        mon.on_tick(t)
        if mon.stats.swaps:
            detect = t + 1
            break
    assert detect is not None and mon.stats.swaps == 1
    rows.append(("adaptive_detect_ticks", float(detect),
                 f"window={mon.window} patience={mon.patience} "
                 f"probes={mon.stats.probes}"))

    # -- post-swap serving cost ----------------------------------------------
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          page_size=16, warm_kernels=True, plan_store=False)
        live = get_default_cache()
        # narrow the monitor to one matmul triple whose frozen pick the
        # timer calls slow: the swap fires on the first warmup tick
        op = next(o for o in trace_warm_set(cfg, max_len=128, page_size=16)
                  if o.family == "matmul")
        ent = live.frozen_entry("matmul", TPU_V5E.name, op.data_dict())
        eng.monitor = KernelMonitor(
            live, machine=TPU_V5E, window=1, patience=1, probe_every=1,
            top_k=2, seed=0,
            timer=skewed_timer({cand_key(ent.candidate): 8e-3}))
        eng.monitor.track(FAMILIES["matmul"], op.data_dict())
        rng = np.random.default_rng(0)
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)   # warmup
        eng.run_until_drained()
        assert eng.monitor.stats.swaps >= 1        # swap landed pre-timing
        nreq, max_new = (3, 8) if quick else (8, 16)
        for _ in range(nreq):
            plen = int(rng.integers(4, 24))
            eng.submit(rng.integers(0, cfg.vocab, plen), max_new=max_new)
        t0 = time.perf_counter()
        done = eng.run_until_drained()
        dt = time.perf_counter() - t0
    finally:
        set_default_cache(prior)
    toks = sum(len(r.out) for r in done)
    assert len(done) == nreq and toks > 0
    rows.append(("adaptive_post_swap_tok_us", dt * 1e6 / toks,
                 f"tok/s={toks / dt:.0f} swaps={eng.monitor.stats.swaps} "
                 f"{eng.monitor.stats_line()}"))
    return rows


def bench_chaos(quick=False):
    """Serving cost under sustained recoverable faults (ISSUE 9): one
    injected ``serve.decode`` kernel failure per ~8-tick window against a
    warm+frozen engine with graceful degradation on.  Every fault demotes
    the pick down the candidate ranking (or retries a non-frozen call), so
    the row prices the demote-and-retry machinery itself — directly
    comparable to ``serve_decode_smoke``, whose fault-free path it
    shadows.  All requests must still finish, with >= 1 DegradeEvent
    recorded."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.runtime import ServeEngine, faults
    from repro.runtime.faults import FaultSpec
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          warm_kernels=True, degrade=True)
        rng = np.random.default_rng(0)
        # warmup tick set (compile outside the timed region), fault-free
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)
        eng.run_until_drained()
        nreq, max_new = (3, 8) if quick else (8, 16)
        for _ in range(nreq):
            plen = int(rng.integers(4, 24))
            eng.submit(rng.integers(0, cfg.vocab, plen), max_new=max_new)
        # the engine's tick cursor (sched.ticks) kept counting through
        # warmup: schedule one decode failure in every 8-tick window the
        # timed run can possibly reach
        start = eng.sched.ticks
        sched = [FaultSpec("serve.decode", t, "error")
                 for t in range(start + 8, start + 400, 8)]
        t0 = time.perf_counter()
        with faults.inject(sched) as inj:
            done = eng.run_until_drained()
        dt = time.perf_counter() - t0
    finally:
        set_default_cache(prior)
    toks = sum(len(r.out) for r in done)
    assert len(done) == nreq and toks > 0
    assert len(inj.fired) >= 1                 # the drill really fired
    assert len(eng.degrade_events) >= 1        # and demoted down the ranking
    return [("serve_degraded_tok_us", dt * 1e6 / toks,
             f"tok/s={toks / dt:.0f} faults={len(inj.fired)} "
             f"demotions={eng._cache.stats.demotions} "
             f"{eng.robustness_line()}")]


def bench_obs_overhead(quick=False):
    """Cost of the flight recorder (ISSUE 10) on the serve fast path: the
    ``serve_decode_smoke`` workload run with tracing off vs tracing ON
    (full event stream + 1-in-8 warm-lane sampling) against one warm
    engine, alternating batches of identical prompts, best-of-N each.
    Row value is the percent regression of us/token with tracing on —
    the baseline pins 5.0 so the standard 2x CI gate enforces the
    tentpole's < 10% overhead contract.  Tracing *off* must stay the
    PR 4 contract: the warm lane costs one module-global load + None
    test, nothing counted."""
    from repro.artifacts.dispatch import (DispatchCache, get_default_cache,
                                          set_default_cache)
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.obs import tracing
    from repro.runtime import ServeEngine
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    prior = get_default_cache()
    set_default_cache(DispatchCache())
    try:
        eng = ServeEngine(cfg, params, max_batch=4, max_len=128,
                          warm_kernels=True)
        rng = np.random.default_rng(0)
        # warmup tick set: compile every quantized chunk shape outside the
        # timed region (see bench_serve_decode)
        eng.submit(rng.integers(0, cfg.vocab, 31), max_new=2)
        eng.run_until_drained()
        nreq, max_new = (3, 8) if quick else (8, 16)
        prompts = [rng.integers(0, cfg.vocab,
                                int(rng.integers(4, 24))) for _ in range(nreq)]

        def run_batch():
            for p in prompts:
                eng.submit(p, max_new=max_new)
            t0 = time.perf_counter()
            done = eng.run_until_drained()
            dt = time.perf_counter() - t0
            toks = sum(len(r.out) for r in done)
            assert len(done) == nreq and toks > 0
            return dt * 1e6 / toks

        reps, events = 2 if quick else 3, 0
        off_us, on_us = [], []
        for _ in range(reps):                # interleave to cancel drift
            off_us.append(run_batch())
            with tracing(capacity=1 << 16, sample_frozen_every=8) as rec:
                on_us.append(run_batch())
            events += rec.emitted
    finally:
        set_default_cache(prior)
    off, on = min(off_us), min(on_us)
    pct = max(0.1, (on - off) / off * 100.0)
    return [("obs_overhead_pct", pct,
             f"off={off:.1f}us/tok on={on:.1f}us/tok "
             f"events={events} reps={reps}")]


# Named groups for --only filtering (comma-separated exact names).
BENCH_GROUPS = (
    ("table1", bench_table1_matmul),
    ("jacobi", bench_table2_jacobi),
    ("transpose", bench_table3_transpose),
    ("matadd", bench_fig2_matadd),
    ("dispatch", bench_dispatch_cache),
    ("dispatch_reference", bench_dispatch_reference),
    ("warm", bench_warm_dispatch),
    ("serve", bench_serve_decode),
    ("load", bench_serve_load),
    ("prefix", bench_serve_prefix_hit),
    ("plan", bench_plan_load),
    ("compile", bench_compile_sweep),
    ("tuning", bench_tuning_sweep),
    ("treebuild", lambda quick: bench_tree_build()),
    ("lm", bench_lm_step),
    ("adaptive", bench_adaptive_swap),
    ("chaos", bench_chaos),
    ("obs", bench_obs_overhead),
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated group names to run "
                         f"(one of: {', '.join(n for n, _ in BENCH_GROUPS)})")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON "
                         "(scripts/check_bench.py gates CI on it)")
    args = ap.parse_args()
    enable_compile_cache()

    selected = None
    if args.only:
        wanted = [w.strip() for w in args.only.split(",") if w.strip()]
        known = {n for n, _ in BENCH_GROUPS}
        unknown = [w for w in wanted if w not in known]
        if unknown:
            ap.error(f"unknown --only group(s) {unknown}; "
                     f"have {sorted(known)}")
        selected = [(n, f) for n, f in BENCH_GROUPS if n in wanted]
    groups = selected if selected is not None else list(BENCH_GROUPS)

    rows = []
    print("name,us_per_call,derived")
    for _, fn in groups:
        for name, us, derived in fn(args.quick):
            rows.append({"name": name, "us": us, "derived": derived})
            print(f"{name},{us:.1f},{derived}", flush=True)

    if args.json:
        payload = {"meta": {"quick": bool(args.quick),
                            "only": args.only or ""},
                   "rows": rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {len(rows)} rows to {args.json}", flush=True)


if __name__ == "__main__":
    main()
