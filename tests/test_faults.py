"""Chaos-injection drills for fault-tolerant serving (ISSUE 9).

The acceptance property is **chaos parity**: for every recoverable seeded
fault schedule, ``run_until_drained`` completes with token streams
identical to the fault-free run for every non-shed request, with zero
KV-pool invariant violations (checked with block tables every tick), and
with a recorded ``DegradeEvent``/shed wherever the schedule implies one.
Unrecoverable (fatal) faults must fail loudly — and leave the engine
drainable afterwards.

Layers drilled:

* the injector itself — byte-exact schedule replay, FIFO per-site firing,
  tick gating;
* the stores — truncating/garbling a dispatch table or serve plan at
  *every byte offset* reads as a silent cache miss (the PR 1 forgiving-
  read policy), never an exception; injected I/O errors likewise;
* ``DispatchCache.demote`` — next-ranked fallback, frozen republish,
  exhaustion wrap-around, promotion-clears-demotion;
* the engine — parity sweep over seeded schedules (prefix-sharing staged
  workload, so CoW/prefill/decode/alloc sites all really run), poison-by-
  recompute, deadline/TTL cancellation, bounded-queue shedding, submit
  validation, the tick watchdog, and monitor probe failures.

Determinism: every schedule is seeded; no test depends on wall-clock time
(deadline tests inject ``FakeClock``, the watchdog test a
``CountingClock``)."""
import json

import numpy as np
import pytest

from repro.artifacts import DispatchCache
from repro.artifacts.dispatch import cand_key, set_default_cache
from repro.artifacts.store import (ArtifactStore, atomic_write_text,
                                   read_json_dict)
from repro.core import TPU_V5E
from repro.core.select import Candidate, rank_candidates
from repro.kernels.ops import FAMILIES
from repro.runtime import faults
from repro.runtime.faults import (ANY_TICK, FatalFault, FaultInjector,
                                  FaultSchedule, FaultSpec, InjectedIOFault,
                                  TickWatchdog)
from repro.runtime.kv_pool import PagedKVPool
from repro.runtime.scheduler import Request, RequestError, Scheduler

MATMUL = FAMILIES["matmul"]
DATA = {"M": 128, "N": 128, "K": 128}


@pytest.fixture(autouse=True)
def _isolate_default_cache():
    set_default_cache(DispatchCache())
    yield
    set_default_cache(None)
    faults.install(None)


# ---------------------------------------------------------------------------
# the injector: deterministic schedules, FIFO firing, tick gating
# ---------------------------------------------------------------------------

def test_random_schedules_replay_byte_exactly():
    for seed in range(20):
        a, b = FaultSchedule.random(seed), FaultSchedule.random(seed)
        assert a == b and list(a) == list(b)
    assert FaultSchedule.random(1) != FaultSchedule.random(2)


def test_specs_fire_at_their_tick_fifo_per_site():
    inj = FaultInjector([FaultSpec("pool.alloc", 3, "exhaust", arg=1),
                         FaultSpec("pool.alloc", 3, "exhaust", arg=2),
                         FaultSpec("pool.alloc", 9, "exhaust", arg=3)])
    assert inj.fire("pool.alloc") is None          # tick 0: no match
    inj.tick = 3
    assert inj.fire("pool.alloc").arg == 1         # FIFO within the tick
    assert inj.fire("pool.alloc").arg == 2
    assert inj.fire("pool.alloc") is None          # both consumed
    inj.tick = 9
    assert inj.fire("pool.alloc").arg == 3
    assert [s.arg for s in inj.fired] == [1, 2, 3]
    assert inj.pending() == []


def test_any_tick_fires_on_next_call_and_fired_log_replays():
    sched = FaultSchedule([FaultSpec("artifact.read", ANY_TICK, "io"),
                           FaultSpec("serve.decode", ANY_TICK, "error")])

    def drive():
        with faults.inject(sched) as inj:
            with pytest.raises(InjectedIOFault):
                faults.maybe_fault("artifact.read")
            with pytest.raises(faults.InjectedFault):
                faults.maybe_fault("serve.decode")
            assert faults.maybe_fault("serve.decode") is None  # consumed
            return list(inj.fired)

    assert drive() == drive()                      # identical fired logs
    assert faults.get_injector() is None           # inject() disarms


def test_inject_disarms_even_when_the_drill_raises():
    with pytest.raises(RuntimeError, match="drill"):
        with faults.inject([FaultSpec("x", ANY_TICK)]):
            raise RuntimeError("drill")
    assert faults.get_injector() is None


# ---------------------------------------------------------------------------
# stores: torn/garbled bytes at EVERY offset are a silent cache miss
# ---------------------------------------------------------------------------

def _torn_sweep(read_fn, path, site):
    """Run ``read_fn`` under a torn and a garble fault at every byte offset
    of ``path``; it must never raise, and every corrupted read must be a
    miss (``None``) — or, for a truncation that only drops trailing
    whitespace, the intact payload."""
    intact = read_fn()
    assert intact is not None
    n = len(path.read_text())
    assert n > 0
    for kind in ("torn", "garble"):
        for off in range(n):
            with faults.inject([FaultSpec(site, ANY_TICK, kind, off)]):
                got = read_fn()
            if kind == "garble":                   # NUL never parses
                assert got is None, (kind, off)
            else:
                assert got is None or got == intact, (kind, off)


def test_torn_dispatch_table_reads_as_cache_miss(tmp_path):
    store = ArtifactStore(tmp_path)
    path = store.dispatch_path("matmul", TPU_V5E.name)
    atomic_write_text(path, json.dumps(
        {"format": 2, "kind": "dispatch", "family": "matmul",
         "machine": TPU_V5E.name, "buckets": {"M128|N128": []}}))
    _torn_sweep(lambda: read_json_dict(path), path, "artifact.read")


def test_torn_serve_plan_reads_as_cache_miss(tmp_path):
    from repro.plans import serde as plan_serde
    from repro.plans.store import PlanStore
    store = PlanStore(tmp_path)
    # a structurally-valid plan written through the real serializer, read
    # through the real (forgiving) loader
    plan = plan_serde.ServePlan(
        config="torn-drill", machine=TPU_V5E.name,
        machine_bindings=dict(TPU_V5E.bindings()), max_len=64,
        page_size=8, include_train=False, entries=(), table_digests=())
    path = store.save_plan(plan)
    _torn_sweep(lambda: store.load_plan("torn-drill", TPU_V5E.name),
                path, "plan.read")


def test_injected_io_error_is_cache_miss_never_exception(tmp_path):
    store = ArtifactStore(tmp_path)
    path = store.dispatch_path("matmul", TPU_V5E.name)
    atomic_write_text(path, json.dumps({"format": 2, "kind": "dispatch"}))
    with faults.inject([FaultSpec("artifact.read", ANY_TICK, "io")]):
        assert read_json_dict(path) is None        # miss, not OSError
    assert read_json_dict(path) is not None        # spec consumed; recovers


def test_fatal_read_fault_propagates_loudly(tmp_path):
    path = tmp_path / "x.json"
    path.write_text("{}")
    with faults.inject([FaultSpec("artifact.read", ANY_TICK, "fatal")]):
        with pytest.raises(FatalFault):
            read_json_dict(path)


# ---------------------------------------------------------------------------
# DispatchCache.demote: falling down the proven ranking
# ---------------------------------------------------------------------------

def test_demote_falls_to_next_ranked_candidate():
    cache = DispatchCache()
    ranked = rank_candidates(MATMUL, TPU_V5E, DATA)
    assert cand_key(cache.best_variant(MATMUL, TPU_V5E, DATA)) == \
        cand_key(ranked[0])
    err = RuntimeError("kernel exploded")
    nxt = cache.demote(MATMUL, TPU_V5E, DATA, error=err, tick=7)
    assert cand_key(nxt) == cand_key(ranked[1])
    # sticky: subsequent resolutions keep the degraded pick
    assert cand_key(cache.best_variant(MATMUL, TPU_V5E, DATA)) == \
        cand_key(ranked[1])
    assert cache.stats.demotions == 1
    (ev,) = cache.degrade_events
    assert ev.tick == 7 and ev.family == "matmul" and not ev.exhausted
    assert ev.old == cand_key(ranked[0]) and ev.new == cand_key(ranked[1])
    assert "kernel exploded" in ev.error and "demoted" in ev.describe()


def test_demote_republishes_frozen_entry():
    cache = DispatchCache()
    ranked = rank_candidates(MATMUL, TPU_V5E, DATA)
    cache.freeze([(MATMUL, TPU_V5E, DATA)])
    before = cache.frozen_entry(MATMUL.name, TPU_V5E.name, DATA)
    assert cand_key(before.candidate) == cand_key(ranked[0])
    nxt = cache.demote(MATMUL, TPU_V5E, DATA, error=RuntimeError("x"))
    after = cache.frozen_entry(MATMUL.name, TPU_V5E.name, DATA)
    assert cand_key(after.candidate) == cand_key(nxt)
    assert cand_key(after.candidate) != cand_key(before.candidate)
    # the republished entry carries ready callables, like any frozen entry
    assert len(after.fns) == 2 and all(callable(f) for f in after.fns)


def test_demotion_exhaustion_wraps_to_top_and_resets(monkeypatch):
    """When every ranked candidate has been demoted the ladder resets to
    the top pick with ``exhausted=True`` — dispatch always answers."""
    cands = [Candidate(leaf_index=i, plan=None,
                       assignment={"bm": 2 ** (3 + i)}, score=-float(i))
             for i in range(3)]
    import repro.artifacts.dispatch as dispatch_mod
    monkeypatch.setattr(dispatch_mod, "rank_candidates",
                        lambda *a, **k: list(cands))
    cache = DispatchCache()
    assert cand_key(cache.best_variant(MATMUL, TPU_V5E, DATA)) == \
        cand_key(cands[0])
    assert cand_key(cache.demote(MATMUL, TPU_V5E, DATA,
                                 error=RuntimeError("a"))) == \
        cand_key(cands[1])
    assert cand_key(cache.demote(MATMUL, TPU_V5E, DATA,
                                 error=RuntimeError("b"))) == \
        cand_key(cands[2])
    wrapped = cache.demote(MATMUL, TPU_V5E, DATA, error=RuntimeError("c"))
    assert cand_key(wrapped) == cand_key(cands[0])
    assert cache.degrade_events[-1].exhausted
    assert not any(e.exhausted for e in cache.degrade_events[:-1])
    # the reset cleared the marks: the ladder restarts from rank 1
    assert cache.demoted_keys(MATMUL.name, TPU_V5E.name, DATA) == frozenset()
    assert cand_key(cache.demote(MATMUL, TPU_V5E, DATA,
                                 error=RuntimeError("d"))) == \
        cand_key(cands[1])
    assert cache.stats.demotions == 4


def test_promotion_clears_demotion_mark():
    """The monitor's measured re-promote (freeze_resolved publish) is the
    recovery signal: publishing a demoted candidate back into the fast
    lane drops its runtime-broken mark, so the tiers agree with the frozen
    lane."""
    cache = DispatchCache()
    ranked = rank_candidates(MATMUL, TPU_V5E, DATA)
    cache.freeze([(MATMUL, TPU_V5E, DATA)])
    cache.demote(MATMUL, TPU_V5E, DATA, error=RuntimeError("flaky"))
    assert cand_key(ranked[0]) in cache.demoted_keys(
        MATMUL.name, TPU_V5E.name, DATA)
    # measurement says the old pick recovered: promote it back
    cache.freeze_resolved([(MATMUL, TPU_V5E, DATA, ranked[0], "measured")])
    assert cache.demoted_keys(MATMUL.name, TPU_V5E.name, DATA) == frozenset()
    ent = cache.frozen_entry(MATMUL.name, TPU_V5E.name, DATA)
    assert cand_key(ent.candidate) == cand_key(ranked[0])


# ---------------------------------------------------------------------------
# scheduler-level robustness (pure host-side: no engine, no jax arrays)
# ---------------------------------------------------------------------------

def _sched(**kw):
    pool = PagedKVPool(kw.pop("num_blocks", 17), kw.pop("page_size", 8))
    return Scheduler(pool, max_batch=kw.pop("max_batch", 2),
                     max_len=kw.pop("max_len", 64), **kw)


def test_submit_validation_raises_structured_request_errors():
    s = _sched()
    for req, code in [
            (Request(1, np.array([], np.int32)), "empty_prompt"),
            (Request(2, np.arange(4, dtype=np.int32), 0), "bad_max_new"),
            (Request(3, np.arange(60, dtype=np.int32), 30), "too_long")]:
        with pytest.raises(RequestError) as ei:
            s.submit(req)
        assert ei.value.code == code and ei.value.rid == req.rid
        assert isinstance(ei.value, ValueError)    # back-compat contract
        assert ei.value.retry_after_ticks is None  # retrying cannot help
    assert s.stats.shed == 0 and not s.queue       # nothing was enqueued


def test_queue_full_sheds_with_retry_hint_never_raises():
    s = _sched(max_queue=2)
    reqs = [Request(i, np.arange(8, dtype=np.int32), 4) for i in range(5)]
    errs = [s.submit(r) for r in reqs]
    assert errs[:2] == [None, None]
    for r, e in zip(reqs[2:], errs[2:]):
        assert e is not None and e.code == "queue_full"
        assert e.retry_after_ticks >= 1
        assert r.done and r.error is e             # structured, not raised
    assert s.stats.shed == 3 and len(s.queue) == 2


def test_deadline_expires_queued_and_running(fake_clock):
    s = _sched(clock=fake_clock)
    live = Request(1, np.arange(8, dtype=np.int32), 4)
    doomed = Request(2, np.arange(8, dtype=np.int32), 4, deadline=5.0)
    s.submit(live)
    s.submit(doomed)
    plan = s.tick()                                # both admitted, in time
    assert len(plan.admitted) == 2 and not plan.cancelled
    fake_clock.advance(10.0)                       # past doomed's deadline
    plan = s.tick()
    assert [r.rid for r in plan.cancelled] == [2]
    assert doomed.done and doomed.error.code == "deadline"
    assert doomed.error.retry_after_ticks == 1
    assert s.stats.cancelled == 1
    assert not live.done                           # untouched
    # the cancelled sequence released its slot and blocks
    assert all(sq is None or sq.req.rid == 1 for sq in s.slots)
    s.pool.check_invariants(
        block_tables=[sq.blocks for sq in s.running()])


def test_deadline_expires_while_still_queued(fake_clock):
    s = _sched(max_batch=1, clock=fake_clock)
    s.submit(Request(1, np.arange(8, dtype=np.int32), 4))
    stuck = Request(2, np.arange(8, dtype=np.int32), 4, deadline=5.0)
    s.submit(stuck)                                # waits behind rid 1
    fake_clock.advance(10.0)
    plan = s.tick()
    assert stuck in plan.cancelled and stuck.error.code == "deadline"
    assert not s.queue                             # removed, not admitted


def test_poison_preempts_by_recompute():
    s = _sched()
    req = Request(1, np.arange(8, dtype=np.int32), 4)
    s.submit(req)
    s.tick()
    (seq,) = s.running()
    assert s.poison(seq)
    assert seq.dead and s.slots[seq.slot] is None
    assert s.queue[0] is req                       # requeued at the front
    assert s.stats.poisoned == 1 and s.stats.preemptions == 0
    assert not s.poison(seq)                       # already gone: moot
    s.pool.check_invariants(block_tables=[])


# ---------------------------------------------------------------------------
# the tick watchdog
# ---------------------------------------------------------------------------

def test_watchdog_flags_only_outliers_after_min_samples():
    wd = TickWatchdog(factor=4.0, window=16, min_samples=4)
    for _ in range(4):
        assert not wd.observe(1.0)                 # building the baseline
    assert not wd.observe(3.9)                     # under 4x the median
    assert wd.observe(5.0)                         # over: flagged
    assert wd.stats.slow_ticks == 1 and wd.stats.worst_ratio >= 5.0
    # one hung tick cannot hide itself: it is judged against the history
    # *before* it joins the window, and the median is robust afterwards
    assert wd.observe(50.0, tick=99)
    assert wd.stats.slow_ticks == 2
    assert wd.stats.last_slow_tick == 99
    assert "slow=2" in wd.stats_line()


def test_watchdog_rejects_bad_factor():
    with pytest.raises(ValueError):
        TickWatchdog(factor=1.0)


# ---------------------------------------------------------------------------
# engine-level chaos (the acceptance sweep)
# ---------------------------------------------------------------------------

ENGINE_SITES = ("pool.alloc", "serve.cow", "serve.prefill", "serve.decode",
                "serve.tick")


@pytest.fixture(scope="module")
def smoke_model():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import init_model
    cfg = get_smoke_config("yi_6b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _build_engine(cfg, params, **kw):
    from repro.runtime import ServeEngine
    kw.setdefault("max_batch", 3)
    kw.setdefault("max_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(cfg, params, **kw)


def _drain_checked(eng, max_ticks=300):
    """run_until_drained with the pool invariants re-proved every tick."""
    done = []
    for _ in range(max_ticks):
        done.extend(eng.step())
        eng.pool.check_invariants(
            block_tables=[s.blocks for s in eng.sched.running()])
        if not eng.sched.has_work():
            break
    while eng._inflight:
        done.extend(eng._commit(eng._inflight.popleft()))
    return done


def _chaos_prompts(cfg):
    """A leader plus followers sharing its first 22 tokens: 22 % 4 != 0
    diverges mid-block, so followers map a partial tail block and the
    scheduler must plan real CoW copies (the ``serve.cow`` site runs)."""
    rng = np.random.default_rng(1234)
    lead = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    follows = [np.concatenate([lead[:22], rng.integers(0, cfg.vocab, 6)]
                              ).astype(np.int32) for _ in range(2)]
    return [lead] + follows


def _staged_run(eng, prompts, *, max_new=5):
    """Drain the leader first (populating the prefix index), then the
    followers — mid-block divergence then forces CoW.  Pool invariants are
    proved every tick; returns {rid: tokens}."""
    outs = {}
    eng.submit(prompts[0], max_new=max_new)
    for r in _drain_checked(eng):
        outs[r.rid] = list(r.out)
    for p in prompts[1:]:
        eng.submit(p, max_new=max_new)
    for r in _drain_checked(eng):
        outs[r.rid] = list(r.out)
    return outs


@pytest.mark.slow
def test_chaos_parity_sweep(smoke_model):
    """The acceptance property: >= 12 seeded recoverable schedules across
    the engine's injection sites; every drained run is token-exact vs the
    fault-free reference, with clean pool invariants every tick."""
    cfg, params = smoke_model
    prompts = _chaos_prompts(cfg)
    ref_eng = _build_engine(cfg, params, prefix_sharing=True)
    ref = _staged_run(ref_eng, prompts)
    assert len(ref) == len(prompts)
    assert all(len(o) == 5 for o in ref.values())
    assert ref_eng.pool.stats.cow_copies >= 2      # the cow site really runs

    total_fired = 0
    for seed in range(12):
        schedule = FaultSchedule.random(seed, sites=ENGINE_SITES,
                                        max_tick=24, n=4)
        eng = _build_engine(cfg, params, prefix_sharing=True, degrade=True)
        with faults.inject(schedule) as inj:
            got = _staged_run(eng, prompts)
        assert got == ref, (seed, list(schedule), inj.fired)
        total_fired += len(inj.fired)
    assert total_fired > 0                         # the sweep injected faults


@pytest.mark.slow
def test_degrade_event_recorded_with_frozen_kernels(smoke_model):
    """A kernel-call failure under ``degrade`` with a frozen warm plan
    demotes a pick (DegradeEvent recorded) and stays token-exact."""
    cfg, params = smoke_model
    prompts = _chaos_prompts(cfg)[:2]
    ref_eng = _build_engine(cfg, params, warm_kernels=True)
    ref = {}
    for p in prompts:
        ref_eng.submit(p, max_new=5)
    for r in _drain_checked(ref_eng):
        ref[r.rid] = list(r.out)

    set_default_cache(DispatchCache())             # fresh cache per engine
    eng = _build_engine(cfg, params, warm_kernels=True, degrade=True)
    for p in prompts:
        eng.submit(p, max_new=5)
    sched = [FaultSpec("serve.prefill", 1, "error"),
             FaultSpec("serve.decode", 6, "error")]
    with faults.inject(sched) as inj:
        done = _drain_checked(eng)
    assert {r.rid: list(r.out) for r in done} == ref
    assert len(inj.fired) == 2
    assert len(eng.degrade_events) >= 1            # the schedule implies one
    assert eng._cache.stats.demotions >= 1
    assert "demotions=" in eng.robustness_line()


@pytest.mark.slow
def test_double_fault_poisons_and_recomputes(smoke_model):
    """Two faults on the same site+tick beat the one-retry budget: the
    affected sequences are poisoned (preempt-by-recompute) and every
    request still finishes with the fault-free tokens."""
    cfg, params = smoke_model
    prompts = _chaos_prompts(cfg)
    ref_eng = _build_engine(cfg, params)
    ref = {}
    for p in prompts:
        ref_eng.submit(p, max_new=5)
    for r in _drain_checked(ref_eng):
        ref[r.rid] = list(r.out)

    eng = _build_engine(cfg, params, degrade=True)
    for p in prompts:
        eng.submit(p, max_new=5)
    sched = [FaultSpec("serve.decode", 6, "error"),
             FaultSpec("serve.decode", 6, "error")]
    with faults.inject(sched) as inj:
        done = _drain_checked(eng)
    assert len(inj.fired) == 2
    assert eng.sched.stats.poisoned >= 1
    assert {r.rid: list(r.out) for r in done} == ref


@pytest.mark.slow
def test_fatal_fault_fails_loudly_engine_stays_drainable(smoke_model):
    cfg, params = smoke_model
    eng = _build_engine(cfg, params, degrade=True)
    for p in _chaos_prompts(cfg):
        eng.submit(p, max_new=4)
    with faults.inject([FaultSpec("serve.decode", ANY_TICK, "fatal")]):
        with pytest.raises(FatalFault):
            for _ in range(100):
                eng.step()
                if not eng.sched.has_work():
                    break
    # loud — but not wedged: the engine drains to completion afterwards
    done = _drain_checked(eng)
    assert len(done) == 3 and all(len(r.out) == 4 for r in done)


@pytest.mark.slow
def test_pool_exhaust_fault_forces_recovery(smoke_model):
    """Injected allocation refusals exercise the preemption/head-room
    machinery mid-flight; outputs stay token-exact."""
    cfg, params = smoke_model
    prompts = _chaos_prompts(cfg)
    ref_eng = _build_engine(cfg, params)
    ref = {}
    for p in prompts:
        ref_eng.submit(p, max_new=5)
    for r in _drain_checked(ref_eng):
        ref[r.rid] = list(r.out)

    eng = _build_engine(cfg, params)               # no degrade needed
    for p in prompts:
        eng.submit(p, max_new=5)
    sched = [FaultSpec("pool.alloc", t, "exhaust") for t in (1, 3, 5, 8)]
    with faults.inject(sched) as inj:
        done = _drain_checked(eng)
    assert len(inj.fired) >= 1
    assert eng.pool.stats.alloc_failures >= 1
    assert {r.rid: list(r.out) for r in done} == ref


@pytest.mark.slow
def test_engine_deadline_and_shed_surface_as_done(smoke_model, fake_clock):
    cfg, params = smoke_model
    prompts = _chaos_prompts(cfg) + [_chaos_prompts(cfg)[0]]
    eng = _build_engine(cfg, params, max_queue=2, deadline_ms=1000.0,
                        clock=fake_clock)
    rids = [eng.submit(p, max_new=4) for p in prompts]
    assert rids == [1, 2, 3, 4]
    done = list(eng.step())                        # surfaces the shed pair
    fake_clock.advance(10.0)                       # everything times out
    done += _drain_checked(eng)
    by_code = {}
    for r in done:
        by_code.setdefault(r.error.code if r.error else "ok", []).append(r)
    assert len(by_code.get("queue_full", [])) == 2  # max_queue=2, 4 submits
    assert len(by_code.get("deadline", [])) == 2
    assert eng.sched.stats.shed == 2 and eng.sched.stats.cancelled == 2
    assert "shed=2" in eng.robustness_line()
    eng.pool.check_invariants(block_tables=[])


@pytest.mark.slow
def test_watchdog_flags_injected_slow_tick(smoke_model, counting_clock):
    cfg, params = smoke_model
    # tick durations count clock reads, so a real host stall (a loaded CI
    # machine) cannot flag some other tick
    eng = _build_engine(cfg, params, clock=counting_clock)
    eng.submit(np.arange(2, 10), max_new=24)
    # a 10-second hang injected at tick 16, after the median settles
    with faults.inject([FaultSpec("serve.tick", 16, "slow",
                                  arg=10_000_000)]) as inj:
        _drain_checked(eng)
    assert len(inj.fired) == 1
    assert eng.watchdog.stats.slow_ticks >= 1
    assert eng.watchdog.stats.last_slow_tick == 16
    assert "watchdog" in eng.robustness_line()


@pytest.mark.slow
def test_monitor_probe_fault_is_data(smoke_model, skewed_timer):
    cfg, params = smoke_model
    eng = _build_engine(cfg, params, warm_kernels=True, monitor=True,
                        monitor_every=1, monitor_timer=skewed_timer)
    eng.submit(np.arange(2, 10), max_new=6)
    with faults.inject([FaultSpec("monitor.probe", t, "error")
                        for t in (1, 2)]) as inj:
        _drain_checked(eng)
    assert len(inj.fired) >= 1
    assert eng.monitor.stats.probe_failures >= 1   # failure is data
