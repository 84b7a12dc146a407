"""Observability layer (ISSUE 10): flight recorder, event schema,
decision provenance, and the unified metrics registry.

Acceptance properties:

- the ring is bounded with monotonic seq ids and counted (never silent)
  drops; the frozen warm lane stays uncounted unless 1-in-N sampling is
  opted into;
- JSONL export is byte-deterministic (sorted keys, minimal separators,
  tick-index timestamps) and every record validates against
  ``EVENT_SCHEMA``;
- ``SwapEvent.describe`` and ``DegradeEvent.describe`` render through
  ONE pinned transition convention (satellite: the two logs cannot
  drift);
- over a seeded 500-cycle alloc/retire + preempt workload, the live
  ``PoolStats``/``SchedStats`` counters exactly equal an independently
  hand-tracked reference, and the trace reconstructs them;
- ``DispatchCache`` emits a provenance record per non-frozen resolution
  (tier source, candidate rank, demotion marks) and ``demote`` lands in
  the trace;
- ``ObsRegistry`` snapshots every surface and renders the summary line;
- each ``TickSpan`` splits its step into plan / dispatch / sync / commit
  phases that add up to its duration, plus the caller's time between
  steps; each request carries submit / admit / first-token stamps; while
  a recorder is installed the step is mirrored into nested ``serve.*``
  profiler spans, and with none no span object is built; the jitted
  steps keep the module names trace reductions key on.
"""
import dataclasses
import json
from collections import Counter

import numpy as np
import pytest

from repro.artifacts import DispatchCache
from repro.artifacts.dispatch import DegradeEvent
from repro.core import TPU_V5E
from repro.kernels.matmul import FAMILY as MATMUL
from repro.obs import (FlightRecorder, ObsRegistry, describe_transition,
                       get_recorder, install, tracing, validate_record)
from repro.obs.events import AdmissionDecision, DispatchDecision, TickSpan
from repro.runtime.kv_pool import PREFIX_ROOT, PagedKVPool
from repro.runtime.monitor import SwapEvent
from repro.runtime.scheduler import Request, Scheduler

MM_DATA = {"M": 64, "N": 64, "K": 64}


def _adm(i):
    return AdmissionDecision(tick=i, action="admit", rid=i, slot=0,
                             queue_depth=0)


def _span(tick):
    return TickSpan(tick=tick, admitted=1, prefill_tokens=8, decode_rows=2,
                    preempted=0, cancelled=0, finished=1, duration_us=12.5,
                    plan_us=2.0, dispatch_us=3.0, sync_us=6.0,
                    commit_us=1.5, caller_us=4.0)


# ---------------------------------------------------------------------------
# Flight recorder: ring bounds, sampling, determinism
# ---------------------------------------------------------------------------

def test_ring_bounds_counted_drops_and_monotonic_seq():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.emit(_adm(i))
    assert rec.emitted == 20
    assert len(rec) == 8
    assert rec.dropped == 12                 # aged out, counted not silent
    seqs = [r["seq"] for r in rec.records()]
    assert seqs == list(range(12, 20))       # ids climb across drops
    for r in rec.records():
        validate_record(r)


def test_recorder_rejects_bad_knobs():
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)
    with pytest.raises(ValueError):
        FlightRecorder(sample_frozen_every=-1)


def test_warm_lane_sampling_is_one_in_n():
    rec = FlightRecorder(sample_frozen_every=3)
    for _ in range(10):
        rec.sample_warm("matmul", "tpu_v5e", {"M": 8})
    recs = rec.records()
    assert len(recs) == 3                    # calls 3, 6, 9
    for r in recs:
        validate_record(r)
        assert r["surface"] == "warm_sampled"
        assert r["source"] == "frozen"
        assert r["family"] == "matmul"


def test_export_jsonl_is_byte_deterministic():
    def build():
        rec = FlightRecorder()
        rec.tick = 3
        rec.emit(DispatchDecision(
            tick=3, family="matmul", machine="tpu_v5e", data=(("M", 8),),
            bucket="b0", leaf=2, assignment=(("TX", 4),), source="measured",
            surface="resolve", rank=1, demoted=0))
        rec.emit(_span(3))
        return rec.export_jsonl()

    a, b = build(), build()
    assert a == b and a.endswith("\n")
    for line in a.splitlines():
        rec = json.loads(line)
        validate_record(rec)
        assert list(rec) == sorted(rec)      # sorted keys on the wire
        assert ": " not in line and ", " not in line   # minimal separators


def test_tracing_context_restores_previous_recorder():
    outer = FlightRecorder()
    install(outer)
    try:
        with tracing() as inner:
            assert get_recorder() is inner
        assert get_recorder() is outer
    finally:
        install(None)


# ---------------------------------------------------------------------------
# Schema validation
# ---------------------------------------------------------------------------

def test_validate_record_rejects_malformed_records():
    good = {"seq": 0, "etype": "fault_fired", "tick": 1, "site": "s",
            "kind": "error", "arg": 0}
    validate_record(good)                    # sanity: the fixture is valid
    bads = [
        {**good, "etype": "nope"},                       # unknown etype
        {k: v for k, v in good.items() if k != "site"},  # missing field
        {**good, "arg": "zero"},                         # wrong type
        {**good, "extra": 1},                            # unknown field
        {**good, "seq": -1},                             # bad seq
        {"seq": 0, "etype": "admission_decision", "tick": 0,
         "action": "explode", "rid": 1, "slot": -1,
         "queue_depth": 0},                              # unknown action
    ]
    for bad in bads:
        with pytest.raises(ValueError):
            validate_record(bad)


PHASE_FIELDS = ("plan_us", "dispatch_us", "sync_us", "commit_us",
                "caller_us")


@pytest.mark.parametrize("field", PHASE_FIELDS)
def test_tick_span_phase_fields_are_required(field):
    rec = FlightRecorder()
    rec.emit(_span(0))
    good = rec.records()[0]
    validate_record(good)                    # the new fields are accepted
    with pytest.raises(ValueError, match=field):
        validate_record({k: v for k, v in good.items() if k != field})
    with pytest.raises(ValueError):
        validate_record({**good, field: "fast"})


# ---------------------------------------------------------------------------
# Satellite: one pinned rendering convention for swap + degrade logs
# ---------------------------------------------------------------------------

def test_swap_and_degrade_describe_share_pinned_format():
    old = (2, (("TX", 8),))
    new = (5, (("TX", 16),))
    swap = SwapEvent(tick=7, family="matmul", data=(("M", 512), ("N", 512)),
                     old=old, new=new, incumbent_us=12.0, challenger_us=3.5,
                     windows=2)
    assert swap.describe() == (
        "tick 7: swapped matmul@M=512,N=512 "
        "(('TX', 8),) (12.0us) -> (('TX', 16),) (3.5us) after 2 windows")
    ev = DegradeEvent(tick=9, family="matmul", machine="tpu_v5e",
                      data=(("M", 512),), old=old, new=new,
                      error="InjectedFault('serve.decode')",
                      source="measured")
    assert ev.describe() == (
        "tick 9: demoted matmul@M=512 "
        "(('TX', 8),) -> (('TX', 16),) (measured) "
        "after InjectedFault('serve.decode')")
    ex = dataclasses.replace(ev, exhausted=True)
    assert ex.describe() == ev.describe() + " [ladder exhausted; reset]"
    # both renderings come out of the one shared helper
    assert describe_transition(
        tick=1, verb="v", family="f", data=(("a", 2),), old="O", new="N",
        note="n", cause="c", tail="!") == "tick 1: v f@a=2 O -> N (n) after c!"


# ---------------------------------------------------------------------------
# Satellite: counters vs a hand-tracked reference (seeded 500 cycles)
# ---------------------------------------------------------------------------

def test_pool_counters_match_hand_tracked_reference(rng):
    """500 seeded alloc/register/retire cycles: ``peak_live`` and
    ``cache_evictions`` must equal a reference tracked from the pool's
    *structural* observables (free list + refcount table sizes), not its
    stats."""
    pool = PagedKVPool(17, 4)                # 16 allocatable blocks
    live, tok = [], 0
    expected_peak = expected_evictions = 0
    for _ in range(500):
        if rng.random() < 0.55 or not live:
            n = int(rng.integers(1, 4))
            free_before = pool.num_free
            reclaim_before = pool.num_reclaimable
            got = pool.alloc(n)
            if got is None:                  # refusal: genuinely short
                assert n > free_before + reclaim_before
                continue
            # alloc reclaims exactly the shortfall from the prefix cache
            expected_evictions += max(0, n - free_before)
            h = PREFIX_ROOT                  # pin each block in the index
            for b in got:
                h = pool.register_prefix(h, tuple(range(tok, tok + 4)), b)
                tok += 4
            live.append(got)
            expected_peak = max(expected_peak, pool.num_live)
        else:
            pool.free(live.pop(int(rng.integers(len(live)))))
    assert pool.stats.peak_live == expected_peak
    assert pool.stats.cache_evictions == expected_evictions
    assert expected_evictions > 0            # the mix really hit pressure
    pool.check_invariants(block_tables=live)


def test_sched_counters_match_hand_tracked_reference_and_trace(rng):
    """500 seeded scheduler ticks under pool pressure + a queue bound
    (the ``test_kv_pool._drive`` engine stand-in): ``admissions``/
    ``preemptions``/``shed`` must equal per-tick hand counts, and the
    emitted ``admission_decision`` stream must reconstruct all of them
    (the action <-> counter mapping is 1:1)."""
    pool = PagedKVPool(7, 4)                 # 6 blocks: decode growth preempts
    sched = Scheduler(pool, max_batch=2, max_len=24, prefill_chunk=8,
                      watermark_blocks=0, max_queue=3)
    admitted_ref = preempt_ref = shed_ref = 0
    rid = 0
    with tracing(capacity=1 << 15) as rec:
        for _ in range(500):
            if rng.random() < 0.5:
                req = Request(rid, np.zeros(int(rng.integers(4, 9)),
                                            np.int32),
                              max_new=int(rng.integers(4, 15)))
                rid += 1
                if sched.submit(req) is not None:
                    shed_ref += 1
            plan = sched.tick()
            admitted_ref += len(plan.admitted)
            preempt_ref += len(plan.preempted)
            if plan.prefill is not None:
                seq, _, chunk = plan.prefill
                sched.note_prefill(seq, chunk)
                if not seq.prefilling:
                    seq.req.out.append(0)    # last-chunk logits seed decode
            for seq in plan.decode:
                seq.req.out.append(0)
                sched.note_decode(seq)
            for seq in list(sched.running()):
                if not seq.prefilling and len(seq.req.out) >= seq.req.max_new:
                    seq.req.done = True
                    sched.retire(seq)
            pool.check_invariants(
                block_tables=[s.blocks for s in sched.running()])
    assert sched.stats.admissions == admitted_ref
    assert sched.stats.preemptions == preempt_ref
    assert sched.stats.shed == shed_ref
    assert preempt_ref > 0 and shed_ref > 0  # the workload exercised both
    assert rec.dropped == 0
    actions = Counter(r["action"] for r in rec.records()
                      if r["etype"] == "admission_decision")
    assert actions["admit"] == admitted_ref
    assert actions["preempt"] == preempt_ref
    assert actions["shed"] == shed_ref
    assert actions["wait"] == sched.stats.admission_waits
    assert actions["cancel"] == actions["poison"] == 0


# ---------------------------------------------------------------------------
# Dispatch provenance: tier source + candidate rank + demotion marks
# ---------------------------------------------------------------------------

def test_dispatch_decisions_carry_rank_and_source():
    cache = DispatchCache()
    with tracing() as rec:
        cand, src = cache.best_variant_with_source(MATMUL, TPU_V5E, MM_DATA)
        cache.best_variant(MATMUL, TPU_V5E, MM_DATA)   # memory-LRU hit
    recs = [r for r in rec.records() if r["etype"] == "dispatch_decision"]
    assert len(recs) == 2                    # one record per resolution
    cold, mem = recs
    for r in recs:
        validate_record(r)
        assert r["surface"] == "resolve"
        assert r["source"] == src
        assert r["leaf"] == cand.leaf_index
        assert r["demoted"] == 0
    assert mem["rank"] == cold["rank"]       # the LRU replays the walk rank
    assert cold["rank"] >= 0


def test_demote_lands_in_trace_with_provenance():
    cache = DispatchCache()
    cache.best_variant(MATMUL, TPU_V5E, MM_DATA)       # resolve untraced
    with tracing() as rec:
        new = cache.demote(MATMUL, TPU_V5E, MM_DATA,
                           error=RuntimeError("boom"), tick=5)
        cand2 = cache.best_variant(MATMUL, TPU_V5E, MM_DATA)
    assert cand2 == new                      # the demotion took effect
    degr = [r for r in rec.records() if r["etype"] == "degrade"]
    assert len(degr) == 1
    validate_record(degr[0])
    assert degr[0]["tick"] == 5
    assert "boom" in degr[0]["error"]
    post = [r for r in rec.records() if r["etype"] == "dispatch_decision"]
    assert post and post[-1]["demoted"] >= 1  # marks visible to dispatch


# ---------------------------------------------------------------------------
# Registry: snapshot / summary_line
# ---------------------------------------------------------------------------

def test_registry_snapshot_render_and_summary():
    pool = PagedKVPool(9, 4)
    sched = Scheduler(pool, max_batch=2, max_len=16)
    rec = FlightRecorder(capacity=16)
    rec.emit(_adm(0))
    reg = ObsRegistry(pool=pool, sched=sched, recorder=rec)
    snap = reg.snapshot()
    assert snap["pool"]["capacity"] == 8
    assert snap["pool"]["peak_live"] == 0
    assert snap["sched"]["ticks"] == 0
    assert snap["recorder"] == {"emitted": 1, "buffered": 1, "dropped": 0,
                                "capacity": 16, "sample_frozen_every": 0}
    assert snap["monitor"] == {} and snap["watchdog"] == {}
    line = reg.summary_line()
    assert line.startswith("obs ")
    assert "ticks=0" in line and "trace n=1" in line


# ---------------------------------------------------------------------------
# The engine tick on the program's clock: phases, request stamps, spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_model():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import init_model
    cfg = get_smoke_config("yi_6b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(smoke_model, **kw):
    from repro.runtime import ServeEngine
    cfg, params = smoke_model
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 4)
    kw.setdefault("prefill_chunk", 8)
    return ServeEngine(cfg, params, **kw)


def _prompts(smoke_model, n, length, seed=5):
    cfg, _ = smoke_model
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, length).astype(np.int32)
            for _ in range(n)]


def test_tick_phases_add_up_and_caller_is_time_between_steps(
        smoke_model, counting_clock):
    clock = counting_clock
    eng = _engine(smoke_model, clock=clock)
    prompts = _prompts(smoke_model, 3, 9)
    with tracing(capacity=1 << 12) as rec:
        returned = None
        expected_caller = []
        for i in range(40):
            if i < len(prompts):
                eng.submit(prompts[i], max_new=6)   # the caller's own work
            clock.advance(0.25 * (i % 3))
            # the step's first read lands one clock step after this
            expected_caller.append(
                0.0 if returned is None else clock.now + clock.step - returned)
            eng.step()
            returned = clock.now
            if not eng.sched.has_work():
                break
    spans = [r for r in rec.records() if r["etype"] == "tick_span"]
    assert len(spans) == len(expected_caller) > 10
    for span, caller in zip(spans, expected_caller):
        validate_record(span)
        phases = (span["plan_us"] + span["dispatch_us"] + span["sync_us"]
                  + span["commit_us"])
        assert min(span[f] for f in PHASE_FIELDS) >= 0.0
        assert span["plan_us"] > 0 and span["dispatch_us"] > 0
        # the phases cover the step, up to float rounding
        assert 0.0 <= span["duration_us"] - phases + 1e-6 <= 1e-3
        assert span["caller_us"] == pytest.approx(caller * 1e6, abs=1e-3)
    # a tick that committed tokens waited on them
    assert all(s["sync_us"] > 0 for s in spans if s["decode_rows"])


def test_request_stamps_are_ordered_and_admit_survives_preemption(
        smoke_model, fake_clock):
    # the tight pool of test_chunked_prefill's preemption test: the two
    # rows' joint decode growth overflows it
    eng = _engine(smoke_model, num_blocks=9, watermark_blocks=0,
                  clock=fake_clock)
    for p in _prompts(smoke_model, 3, 9, seed=3):
        eng.submit(p, max_new=10)
    reqs = {r.rid: r for r in eng.sched.queue}
    first_admit, clock_at = {}, {}
    finished = []
    with tracing(capacity=1 << 12) as rec:
        for _ in range(200):
            fake_clock.advance(0.01)
            clock_at[eng.sched.ticks] = fake_clock.now
            finished.extend(eng.step())
            for rid, r in reqs.items():
                if r.t_admit is not None:
                    first_admit.setdefault(rid, r.t_admit)
            if not eng.sched.has_work():
                break
    assert sorted(r.rid for r in finished) == sorted(reqs)
    for rid, r in reqs.items():
        assert r.t_submit <= r.t_admit <= r.t_first, rid
        assert r.t_admit == first_admit[rid]
    admits = {}
    for e in rec.records():
        if e["etype"] == "admission_decision" and e["action"] == "admit":
            admits.setdefault(e["rid"], []).append(clock_at[e["tick"]])
    readmitted = [rid for rid, at in admits.items() if len(at) > 1]
    assert eng.sched.stats.preemptions > 0 and readmitted
    for rid in readmitted:
        # re-admitted in a later step, stamped at the first admission
        assert reqs[rid].t_admit == admits[rid][0] < admits[rid][-1]


def test_tick_span_counts_the_kv_pages_decode_rows_own(smoke_model):
    """``kv_pages_read`` is the pages the decoding rows own, the sum of
    ``ceil((pos + 1) / page_size)`` over the rows the decode step masks
    in; ``kv_pages_table`` is the whole ``max_batch x blocks_per_seq``
    table the gather path reads.  The CPU engine gathers."""
    eng = _engine(smoke_model, max_batch=3)
    decode, calls = eng._decode, []

    def spy(params, last_tok, cache, index, tables, mask):
        calls.append((np.asarray(index), np.asarray(mask)))
        return decode(params, last_tok, cache, index, tables, mask)

    eng._decode = spy
    for i, p in enumerate(_prompts(smoke_model, 4, 7)):
        eng.submit(p[:5 + 4 * i], max_new=6 + i)
    with tracing(capacity=1 << 12) as rec:
        for _ in range(100):
            eng.step()
            if not eng.sched.has_work():
                break
    spans = [r for r in rec.records() if r["etype"] == "tick_span"]
    decoded = [s for s in spans if s["decode_rows"]]
    assert len(decoded) == len(calls) > 5
    table = eng.max_batch * eng.blocks_per_seq
    for span, (index, mask) in zip(decoded, calls):
        want = sum(-(-(int(i) + 1) // eng.page_size) for i in index[mask])
        assert span["kv_pages_read"] == want > 0
        assert span["kv_pages_table"] == table
        assert span["decode_kernel"] is False
    assert max(s["kv_pages_read"] for s in decoded) > eng.max_batch
    assert all(s["kv_pages_read"] == s["kv_pages_table"] == 0
               for s in spans if not s["decode_rows"])


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each span
    built, and its parent when entered."""

    built = []
    entered = []
    _open = []

    def __init__(self, name):
        self.name = name
        _Annotation.built.append(name)

    def __enter__(self):
        parent = _Annotation._open[-1] if _Annotation._open else None
        _Annotation.entered.append((self.name, parent))
        _Annotation._open.append(self.name)
        return self

    def __exit__(self, *exc):
        _Annotation._open.pop()
        return False


def test_serve_spans_only_with_a_recorder_and_nested(smoke_model,
                                                     monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    monkeypatch.setattr(_Annotation, "built", [])
    monkeypatch.setattr(_Annotation, "entered", [])
    cfg, _ = smoke_model
    # a leader, then a follower diverging mid-block: the follower maps the
    # shared prefix and its first write copies a block (``serve.cow``)
    rng = np.random.default_rng(1234)
    lead = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    follow = np.concatenate([lead[:22], rng.integers(0, cfg.vocab, 6)]
                            ).astype(np.int32)
    eng = _engine(smoke_model, max_len=64, prefix_sharing=True)
    assert get_recorder() is None
    eng.submit(lead, max_new=4)
    eng.run_until_drained()
    assert _Annotation.built == []          # tracing off: nothing built
    with tracing() as rec:
        eng.submit(follow, max_new=4)
        eng.run_until_drained()
    assert rec.records() and _Annotation._open == []
    parents = {}
    for name, parent in _Annotation.entered:
        parents.setdefault(name, set()).add(parent)
    assert parents == {
        "serve.step": {None},
        "serve.plan": {"serve.step"}, "serve.dispatch": {"serve.step"},
        "serve.sync": {"serve.step"}, "serve.commit": {"serve.step"},
        "serve.cow": {"serve.dispatch"}, "serve.prefill": {"serve.dispatch"},
        "serve.decode": {"serve.dispatch"}}
    steps = sum(1 for r in rec.records() if r["etype"] == "tick_span")
    assert Counter(n for n, _ in _Annotation.entered)["serve.step"] == steps


def test_jitted_steps_keep_the_names_trace_reductions_key_on(smoke_model):
    import jax.numpy as jnp
    eng = _engine(smoke_model)
    nblk, B = eng.blocks_per_seq, eng.max_batch
    prefill = eng._prefill.lower(
        eng.params, jnp.zeros((1, 8), jnp.int32), eng.cache, jnp.int32(0),
        jnp.zeros((1, nblk), jnp.int32), jnp.int32(0))
    decode = eng._decode.lower(
        eng.params, eng.last_tok, eng.cache, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, nblk), jnp.int32), jnp.zeros((B,), bool))
    assert "module @jit_prefill" in prefill.as_text()
    assert "module @jit_decode" in decode.as_text()
