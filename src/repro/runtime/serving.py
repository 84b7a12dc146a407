"""Paged serving engine: refcounted block KV pool + chunked prefill +
prefix sharing + async plan/execute/commit tick overlap.

A minimal-but-real engine in the vLLM mold, sized for the dry-run shapes:

* **block/paged KV cache** — attention K/V live in a shared pool of
  fixed-size blocks (:class:`repro.runtime.kv_pool.PagedKVPool` owns the
  accounting, :func:`repro.models.init_paged_cache` the device layout).
  A request owns ``ceil(tokens / page_size)`` blocks listed in its block
  table; retirement drops refcounts copy-free.  KV memory scales with
  *live tokens*, not ``max_batch × max_len``.
* **prefix sharing (copy-on-write)** — with ``prefix_sharing=True`` the
  pool indexes full ``page_size``-aligned prompt blocks by chain hash; a
  request whose prompt shares a prefix with a live or recently-retired
  sequence *maps* the resident blocks (refcount up, prefill skipped) and
  only computes the tail.  A write into a shared block first duplicates
  it device-side (:func:`repro.models.paged_copy_block`) — the scheduler
  plans the copy, :meth:`ServeEngine._dispatch` executes it before the
  tick's prefill/decode.  Recurrent SSM state cannot skip prompt tokens,
  so sharing is forced off for SSM-bearing configs (``ssm``/``hybrid``).
* **chunked prefill** — prompts enter the cache one scheduler-visible
  chunk per tick, interleaved with decode, so a long prompt never stalls
  in-flight decodes for its whole length.  Chunk lengths are quantized
  (``prefill_chunk``-sized chunks + a power-of-two tail) so the compiled
  prefill-shape set is O(log ``prefill_chunk``), with no padding — the
  recurrent SSM state threads exactly and chunked prefill is token-for-
  token equal to whole-prompt prefill.
* **async tick overlap** — each engine step is **plan → dispatch →
  commit**.  Dispatch enqueues the tick's jit'd closures and keeps the
  sampled tokens *on device* (``last_tok`` chains device-resident into
  the next dispatch), so with ``async_depth=2`` the host plans and
  dispatches tick *t+1* while the device still executes tick *t*; the
  only host synchronization is the commit barrier, which materializes a
  finished tick's sampled tokens, appends them to request outputs, and
  reconciles EOS/``max_new`` truncation *before the next dispatch*.
  ``async_depth=1`` commits each tick immediately after dispatch — the
  fully synchronous engine.  Speculation is bounded host-side: the
  scheduler's dispatch guard never sends a sequence past its ``max_new``
  budget, preempted sequences are marked dead so their uncommitted
  in-flight tokens are dropped (greedy recompute regenerates them
  deterministically), and tokens past an EOS are truncated at commit.

The compiled steps are shape-stable — decode is (B, 1) tokens + (B, nblk)
block tables every tick; prefill compiles one variant per quantized chunk
length; the CoW block copy is one scalar-indexed kernel — so serving
never recompiles after warmup.

With ``monitor=True`` (and warmed kernels) the engine additionally runs
the **adaptive loop** (:mod:`repro.runtime.monitor`): cheap wall-clock
probes over the frozen kernel picks during live traffic, and an atomic
hot-swap of any pick that measurement persistently contradicts —
KLARAPTOR's runtime selection grafted onto the offline plan.  Plan-backed
starts also digest-check their serve plan against the host's dispatch
tables (``strict_plans`` escalates the staleness warning to a refusal).
"""
from __future__ import annotations

import collections
import contextlib
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.params import MachineDescription, default_machine
from ..models import (init_paged_cache, paged_copy_block, paged_decode_step,
                      paged_prefill_chunk)
from ..models.layers import paged_kernel_serves
from ..models.config import ModelConfig
from ..obs import ObsRegistry
from ..obs import recorder as obs
from ..obs.events import TickSpan
from . import faults
from .faults import TickWatchdog
from .kv_pool import GARBAGE_BLOCK, PagedKVPool
from .monitor import KernelMonitor
from .scheduler import Request, Scheduler, SeqState, TickPlan
from .steps import greedy_sample

PyTree = Any

_NO_SPAN = contextlib.nullcontext()


def _span(traced: bool, name: str):
    """A ``jax.profiler.TraceAnnotation`` on the host plane while a
    recorder is installed; otherwise one shared no-op context, so a step
    with tracing off builds no span object."""
    return jax.profiler.TraceAnnotation(name) if traced else _NO_SPAN


def warm_kernel_dispatch(cfg: ModelConfig, *,
                         machine: Optional[MachineDescription] = None,
                         max_len: int = 512,
                         page_size: int = 0,
                         freeze: bool = True,
                         plan_store: Any = None,
                         strict_plans: bool = False) -> Dict[str, Any]:
    """Pre-resolve the kernel variants this model's serve path will ask for.

    Thin wrapper over :mod:`repro.plans`: the warm set is no longer a hand
    list but the config's *traced* dispatch set
    (:func:`repro.plans.trace.trace_warm_set` — so Mamba/hybrid configs warm
    ``ssd_scan``, MoE configs warm their router/expert projections, whisper
    warms the encoder shapes).  ``page_size > 0`` traces the *paged* serve
    path: attention sequence extents round up to the block grid, so the
    dispatch bucket keys carry the block size and a paged engine start hits
    the same frozen entries it will dispatch through (``page_size=0`` keeps
    the dense trace).  Two paths:

    - **plan-backed** (preferred): with ``freeze=True``, a serve-plan
      artifact built offline by ``scripts/plan_artifacts.py`` — looked up in
      ``plan_store`` (a :class:`repro.plans.PlanStore`), or the
      ``REPRO_ARTIFACT_DIR``-resolved store when ``plan_store`` is ``None``
      — is fed straight to :meth:`DispatchCache.freeze_resolved`.  Zero
      online tree enumeration; ``stats.cold_builds`` stays 0.  Pass
      ``plan_store=False`` to skip the artifact probe.  A plan whose
      recorded dispatch-table digests no longer match this host's tables
      is *stale*: by default it warns (``StalePlanWarning``) and falls
      through to online warm-up; ``strict_plans=True`` raises
      :class:`repro.plans.StalePlanError` instead (the ``--strict-plans``
      refusal).
    - **online fallback**: trace, resolve every triple through the tiers
      (triples infeasible at this config's shapes are dropped), and — with
      ``freeze=True`` (default) — snapshot them into the process cache's
      frozen dispatch plan (:meth:`DispatchCache.freeze`): the steady-state
      read path then takes no lock, re-sorts no keys, and returns the
      pre-instantiated kernel callable.

    Returns ``{label: {"candidate": Candidate, "rank_source": str}}`` where
    ``label`` is the traced op label (``family@<sorted dims>``) and
    ``rank_source`` reports whether the pick was decided by a *measured*
    (tuned — see ``scripts/tune_artifacts.py``) ranking, the *symbolic*
    precompiled ranking, or a *cold* rebuild: the calibrated-vs-symbolic
    observability hook for serving start-up logs.  Attribution comes from
    the resolution itself (:meth:`DispatchCache.best_variant_with_source`),
    or — plan-backed — from the resolution recorded at plan-build time.
    """
    from ..artifacts.dispatch import get_default_cache
    from ..kernels.ops import FAMILIES
    from ..plans.loader import warm_from_plan
    from ..plans.trace import trace_warm_set
    cache = get_default_cache()
    machine = machine or default_machine()

    if freeze and plan_store is not False:
        picks = warm_from_plan(cfg, machine=machine, max_len=max_len,
                               page_size=page_size,
                               store=plan_store or None, cache=cache,
                               strict=strict_plans)
        if picks is not None:
            return picks

    wanted: List[Any] = []
    picks: Dict[str, Any] = {}
    for op in trace_warm_set(cfg, max_len=max_len, page_size=page_size):
        fam, data = FAMILIES[op.family], op.data_dict()
        try:
            # feasibility probe (and the full resolution when not freezing;
            # under freeze the snapshot below re-resolves via the warm LRU)
            cand, source = cache.best_variant_with_source(fam, machine, data)
        except ValueError:
            continue                        # no feasible leaf at this shape
        wanted.append((op.label, op.family, fam, data))
        if not freeze:
            picks[op.label] = {"candidate": cand, "rank_source": source}
    if freeze:
        # freeze resolves through the locked tiers (never the old frozen
        # plan), so a re-warm-up after compiling/tuning artifacts reports
        # and pins FRESH resolutions; picks come from the published plan
        plan = cache.freeze([(fam, machine, data)
                             for _, _, fam, data in wanted])
        for label, fname, _, data in wanted:
            ent = plan.get(fname, machine.name, data)
            picks[label] = {"candidate": ent.candidate,
                            "rank_source": ent.source}
    return picks


def engine_steps(cfg: ModelConfig) -> Tuple[Any, Any]:
    """The engine's (prefill-chunk, decode) step functions, before jit.

    ``prefill(params, tokens (1, C), cache, start, block_table (1, nblk),
    slot) -> (seed token (1, 1), cache)`` and ``decode(params, last_tok
    (B, 1), cache, index (B,), block_tables (B, nblk), mask (B,)) ->
    (tokens (B, 1), last_tok, cache)``.  The engine jits both with the
    cache donated; compile tests lower the same functions."""

    def prefill(params, tokens, cache, start, block_table, slot):
        logits, cache = paged_prefill_chunk(params, cfg, tokens, cache,
                                            start, block_table, slot)
        # sample in-jit: the seed token stays device-resident until the
        # commit barrier materializes it
        return greedy_sample(logits), cache

    def decode(params, last_tok, cache, index, block_tables, mask):
        logits, cache = paged_decode_step(params, cfg, last_tok, cache,
                                          index, block_tables, ssm_mask=mask)
        nxt = greedy_sample(logits)
        # chain last_tok device-side: decoding rows advance to their
        # sampled token, everything else (dead rows, mid-prefill rows)
        # keeps its value — no host sync between ticks
        return nxt, jnp.where(mask[:, None], nxt, last_tok), cache

    return prefill, decode


@dataclass
class _InFlight:
    """One dispatched-but-uncommitted tick: the device handles of its
    sampled tokens plus the sequences they belong to.  Committing it is
    the pipeline's only host sync."""

    prefill_seed: Optional[Tuple[SeqState, jax.Array]] = None  # (seq, (1,1))
    decode_toks: Optional[jax.Array] = None                    # (B, 1)
    decode_seqs: List[SeqState] = field(default_factory=list)
    sync_s: float = 0.0          # host seconds blocked materializing them


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: PyTree, *,
                 max_batch: int = 8, max_len: int = 512,
                 page_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: int = 32,
                 watermark_blocks: Optional[int] = None,
                 prefix_sharing: bool = False,
                 async_depth: int = 1,
                 warm_kernels: bool = False,
                 plan_store: Any = None,
                 strict_plans: bool = False,
                 monitor: bool = False,
                 monitor_window: int = 8,
                 monitor_every: int = 4,
                 swap_threshold: float = 1.25,
                 swap_patience: int = 2,
                 monitor_timer: Any = None,
                 degrade: bool = False,
                 max_queue: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 watchdog: bool = True,
                 clock: faults.Clock = faults.default_clock,
                 machine: Optional[MachineDescription] = None):
        if cfg.encoder is not None:
            raise ValueError("ServeEngine does not serve encoder-decoder "
                             "configs")
        if async_depth < 1:
            raise ValueError(f"async_depth must be >= 1: {async_depth}")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.page_size = page_size
        self.async_depth = async_depth
        # the case discussion binds the resources of the chip this process
        # runs on (TPU_V5E, the modelled target, on the CPU backend)
        self.machine = machine = machine or default_machine()
        # graceful degradation (repro.runtime.faults + DispatchCache.demote):
        # a recoverable failure inside a guarded tick stage demotes a frozen
        # kernel pick and retries once; a second failure poisons the affected
        # sequences (preempt-by-recompute) instead of killing the engine.
        # Off by default: masking a genuine bug behind a silent retry is the
        # wrong default for development; serving deployments opt in.
        self.degrade = degrade
        self.deadline_ms = deadline_ms
        self.clock = clock
        self.watchdog: Optional[TickWatchdog] = (TickWatchdog() if watchdog
                                                 else None)
        # prompt-skipping needs every skipped position recoverable from the
        # KV pool alone; SSM recurrent state must thread through *every*
        # prompt token, so SSM-bearing configs always prefill in full
        self.prefix_sharing = prefix_sharing and cfg.block not in (
            "ssm", "hybrid")
        self.blocks_per_seq = -(-max_len // page_size)
        if num_blocks is None:
            # default pool: every slot can hold a full-length sequence
            # (+ the reserved garbage block), so admission is slot-bound
            # exactly like the dense engine was.  Size it smaller to
            # exercise head-room waits and preemption.
            num_blocks = max_batch * self.blocks_per_seq + 1
        # resolve kernel-variant dispatch up front: a shipped serve-plan
        # artifact when one matches (zero cold resolutions), else the traced
        # online warm-up (artifact/LRU resolution + freeze).  The paged
        # block size is part of the traced bucket keys.
        self.kernel_plan = (warm_kernel_dispatch(cfg, machine=machine,
                                                 max_len=max_len,
                                                 page_size=page_size,
                                                 plan_store=plan_store,
                                                 strict_plans=strict_plans)
                            if warm_kernels else None)
        # adaptive loop (repro.runtime.monitor): live counters over the
        # frozen picks + hot-swap when measurement disagrees.  Off by
        # default — probing runs real kernels; enable it with an injected
        # timer (tests/benchmarks) or on hosts where probe cost is cheap.
        self.monitor: Optional[KernelMonitor] = None
        if monitor and self.kernel_plan is not None:
            self.monitor = KernelMonitor(
                machine=machine, window=monitor_window,
                probe_every=monitor_every, threshold=swap_threshold,
                patience=swap_patience, timer=monitor_timer)
            self.monitor.track_frozen()
        self.pool = PagedKVPool(num_blocks, page_size)
        self.sched = Scheduler(self.pool, max_batch=max_batch,
                               max_len=max_len, prefill_chunk=prefill_chunk,
                               watermark_blocks=watermark_blocks,
                               prefix_sharing=self.prefix_sharing,
                               max_queue=max_queue, clock=clock)
        # the cache this engine demotes through — captured at construction
        # so benches/tests that install a private default cache get their
        # degrade events in that cache, not a later global
        from ..artifacts.dispatch import get_default_cache
        self._cache = get_default_cache()
        self._degrade_rr = 0                 # round-robin over frozen triples
        self._rejected: List[Request] = []   # shed at submit, surfaced by step

        # one compile per quantized chunk length; decode + CoW copy are
        # shape-stable
        prefill, decode = engine_steps(cfg)
        self._prefill = jax.jit(prefill, donate_argnums=(2,))
        self._decode = jax.jit(decode, donate_argnums=(2,))
        self._copy = jax.jit(paged_copy_block, donate_argnums=(0,))
        self.cache = init_paged_cache(cfg, num_blocks, page_size, max_batch)
        # the decode program's attention read, as its layers route it
        self.decode_kernel = "k" in self.cache and paged_kernel_serves(
            cfg, decode=True, pool_dtype=self.cache["k"].dtype)
        self.last_tok = jnp.zeros((max_batch, 1), jnp.int32)
        self._inflight: Deque[_InFlight] = collections.deque()
        self._rid = 0
        self._returned: Optional[float] = None  # clock when step() returned

    # -- client API -----------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new: int = 16,
               eos: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        """Queue a request; returns its rid.

        Malformed input (empty prompt, ``max_new < 1``, prompt + budget
        over ``max_len``/pool capacity) raises a structured
        :class:`~repro.runtime.scheduler.RequestError` — a ``ValueError``
        subclass, so pre-existing callers keep working.  A well-formed
        request shed by the queue bound (``max_queue``) does NOT raise: it
        comes back *done* from a later :meth:`step` with ``req.error.code
        == "queue_full"`` and a retry-after hint.  ``deadline_ms``
        overrides the engine-level TTL for this request (absolute deadline
        = now + TTL on the engine's clock)."""
        self._rid += 1
        ms = deadline_ms if deadline_ms is not None else self.deadline_ms
        deadline = (self.clock() + ms / 1000.0) if ms is not None else None
        req = Request(self._rid, np.asarray(prompt, np.int32), max_new, eos,
                      deadline=deadline)
        if self.sched.submit(req) is not None:
            self._rejected.append(req)       # shed: surfaced as done
        return self._rid

    # -- tick execution -------------------------------------------------------
    def _block_table(self, seq: SeqState) -> np.ndarray:
        """Fixed-width (nblk,) table: owned blocks in logical order, tail
        padded with the garbage block (never addressed: positions beyond
        the sequence are causally masked)."""
        bt = np.full(self.blocks_per_seq, GARBAGE_BLOCK, np.int32)
        bt[:len(seq.blocks)] = seq.blocks
        return bt

    def _reset_slot(self, slot: int) -> None:
        # KV needs no wipe — stale blocks are position-masked until their
        # next owner overwrites them — but the recurrent SSM state is
        # per-slot and must start from zero for a new occupant.
        self.last_tok = self.last_tok.at[slot].set(0)
        if "ssm" in self.cache:
            self.cache["ssm"] = self.cache["ssm"].at[:, slot].set(0.0)

    def step(self) -> List[Request]:
        """One engine tick: plan + dispatch the next tick, then commit the
        oldest in-flight tick(s) down to the pipeline depth.  At
        ``async_depth=1`` the dispatched tick commits immediately
        (synchronous engine); at depth ``d`` the newest ``d − 1`` ticks
        stay in flight across the return, overlapping host planning with
        device execution.

        Where the watchdog or a recorder needs the tick's duration, the
        phase boundaries are read on the same injectable clock; with a
        recorder installed they become a ``TickSpan`` and the step is
        mirrored into ``serve.*`` profiler spans on the host plane."""
        faults.set_tick(self.sched.ticks)    # arm the drill's tick cursor
        obs.set_tick(self.sched.ticks)       # ...and the trace's, in lockstep
        orec = obs.get_recorder()
        traced = orec is not None
        timed = self.watchdog is not None or traced
        t0 = self.clock() if timed else 0.0
        with _span(traced, "serve.step"):
            done: List[Request] = []
            if self._rejected:               # shed submits surface as done
                done.extend(self._rejected)
                self._rejected.clear()
            tick = self.sched.ticks
            with _span(traced, "serve.plan"):
                if self.monitor is not None:
                    # adaptive loop: cheap counter sampling + (rarely) a
                    # hot-swap through the cache's atomic publish; one
                    # modulo check on non-probe ticks
                    self.monitor.on_tick(self.sched.ticks)
                plan = self.sched.tick()
            t1 = self.clock() if timed else 0.0
            done.extend(plan.cancelled)      # deadline-expired: partial out
            with _span(traced, "serve.dispatch"):
                pages = self._dispatch(plan, traced)
            t2 = self.clock() if timed else 0.0
            sync = 0.0
            while len(self._inflight) > self.async_depth - 1:
                rec = self._inflight.popleft()
                done.extend(self._commit(rec, timed, traced))
                sync += rec.sync_s
        if timed:
            t3 = self.clock()
            caller = t0 - self._returned if self._returned is not None \
                else 0.0
            self._returned = t3
            dt = t3 - t0
            spec = faults.maybe_fault("serve.tick")
            if spec is not None and spec.kind == "slow":
                dt += spec.arg / 1e6         # injected hang, in microseconds
            if self.watchdog is not None:
                self.watchdog.observe(dt, tick)
            if orec is not None:
                # one span per tick: what the plan scheduled, what
                # committed, and the host-side duration and its phases on
                # the engine's injectable clock (tick indices are the only
                # timestamps, so a counting clock makes the whole trace
                # deterministic)
                orec.emit(TickSpan(
                    tick=tick, admitted=len(plan.admitted),
                    prefill_tokens=(plan.prefill[2]
                                    if plan.prefill is not None else 0),
                    decode_rows=len(plan.decode),
                    preempted=len(plan.preempted),
                    cancelled=len(plan.cancelled), finished=len(done),
                    duration_us=dt * 1e6, plan_us=(t1 - t0) * 1e6,
                    dispatch_us=(t2 - t1) * 1e6, sync_us=sync * 1e6,
                    commit_us=(t3 - t2 - sync) * 1e6,
                    caller_us=caller * 1e6,
                    decode_kernel=self.decode_kernel, kv_pages_read=pages,
                    kv_pages_table=(self.max_batch * self.blocks_per_seq
                                    if pages else 0)))
        else:
            self._returned = None
        return done

    def _guard(self, site: str, seqs: Tuple[SeqState, ...], fn, *args):
        """Run one guarded tick stage: consult the fault injector, then the
        stage itself.  A recoverable failure with ``degrade`` on demotes
        the next frozen kernel pick (round-robin over the frozen triples —
        the engine cannot attribute a batched-step failure to one kernel,
        so successive failures walk the whole warm set down their
        rankings) and retries the stage once; a second failure **poisons**
        ``seqs`` — preempt-by-recompute, reconciled at the commit barrier
        — and returns ``None`` (the stage's work is skipped this tick).
        With ``degrade`` off, or on a :class:`~repro.runtime.faults.
        FatalFault`, the exception propagates — the caller's partial-tick
        bookkeeping keeps the engine drainable."""
        try:
            faults.maybe_fault(site)
            return fn(*args)
        except faults.FatalFault:
            raise
        except Exception as e:               # noqa: BLE001 — degrade surface
            if not self.degrade:
                raise
            self._demote_next(e)
            try:
                faults.maybe_fault(site)
                return fn(*args)
            except faults.FatalFault:
                raise
            except Exception:                # noqa: BLE001 — second strike
                for seq in seqs:
                    self.sched.poison(seq)
                return None

    def _demote_next(self, error: Exception) -> None:
        """Fall one frozen pick down its ranking (no-op without a frozen
        plan: there is no pinned pick to blame, and the locked tiers
        already re-resolve per call)."""
        plan = self._cache.frozen_plan
        triples = [t for t in (plan.triples if plan is not None else ())
                   if t[1].name == self.machine.name]
        if not triples:
            return
        fam, mach, data = triples[self._degrade_rr % len(triples)]
        self._degrade_rr += 1
        self._cache.demote(fam, mach, data, error=error,
                           tick=self.sched.ticks)

    def _dispatch(self, plan: TickPlan, traced: bool = False) -> int:
        """Execute one tick plan: enqueue the CoW copies, at most one
        prefill chunk, and the batched decode; record the device handles
        of the sampled tokens as an in-flight tick.  No host sync here —
        position accounting advances speculatively (note_prefill /
        note_decode), outputs land at commit.  Returns the KV pages the
        dispatched decode's rows own (0 without a decode).

        Every device stage runs under :meth:`_guard`; a stage that fails
        twice poisons its sequences and is skipped (a poisoned sequence is
        dead — later stages this tick must not touch it, hence the
        ``dead`` re-checks).  The in-flight record is appended even when a
        fatal fault aborts the tick midway: whatever was dispatched before
        the abort must still reach the commit barrier, or the pipeline's
        position accounting wedges and the engine can never drain.
        ``traced`` wraps each enqueue in a ``serve.cow`` /
        ``serve.prefill`` / ``serve.decode`` profiler span."""
        for seq in plan.admitted:
            self._reset_slot(seq.slot)
        rec = _InFlight()
        pages = 0
        try:
            for (src, dst), owner in zip(plan.cow, plan.cow_owners):
                # duplicate shared blocks BEFORE this tick writes into
                # them; other owners keep reading the original
                with _span(traced, "serve.cow"):
                    out = self._guard("serve.cow", (owner,), self._copy,
                                      self.cache, jnp.int32(src),
                                      jnp.int32(dst))
                if out is not None:
                    self.cache = out
            if plan.prefill is not None and not plan.prefill[0].dead:
                seq, start, chunk = plan.prefill
                toks = jnp.asarray(seq.target[None, start:start + chunk])
                with _span(traced, "serve.prefill"):
                    out = self._guard(
                        "serve.prefill", (seq,), self._prefill, self.params,
                        toks, self.cache, jnp.int32(start),
                        jnp.asarray(self._block_table(seq)[None]),
                        jnp.int32(seq.slot))
                if out is not None:
                    seed, self.cache = out
                    self.sched.note_prefill(seq, chunk)
                    if not seq.prefilling:
                        # final chunk: its last-token logits seed decode,
                        # exactly as whole-prompt prefill would
                        self.last_tok = self.last_tok.at[seq.slot].set(seed[0])
                        rec.prefill_seed = (seq, seed)
            decoding = [s for s in plan.decode if not s.dead]
            if decoding:
                bts = np.full((self.max_batch, self.blocks_per_seq),
                              GARBAGE_BLOCK, np.int32)
                idx = np.zeros(self.max_batch, np.int32)
                mask = np.zeros(self.max_batch, bool)
                for seq in decoding:
                    bts[seq.slot, :len(seq.blocks)] = seq.blocks
                    idx[seq.slot] = seq.pos
                    mask[seq.slot] = True
                # one decode for the whole pool with per-row block tables
                # (continuous batching); non-decoding rows write the garbage
                # block and keep their SSM state via the mask.
                with _span(traced, "serve.decode"):
                    out = self._guard("serve.decode", tuple(decoding),
                                      self._decode, self.params,
                                      self.last_tok, self.cache,
                                      jnp.asarray(idx), jnp.asarray(bts),
                                      jnp.asarray(mask))
                if out is not None:
                    toks, self.last_tok, self.cache = out
                    pages = sum(-(-(seq.pos + 1) // self.page_size)
                                for seq in decoding)
                    for seq in decoding:
                        self.sched.note_decode(seq)
                    rec.decode_toks = toks
                    rec.decode_seqs = list(decoding)
        except BaseException:
            # partial tick (degrade off or fatal): keep what was dispatched
            # committable, then fail loudly — run_until_drained still works
            self._inflight.append(rec)
            raise
        self._inflight.append(rec)
        return pages

    def _commit(self, rec: _InFlight, timed: bool = False,
                traced: bool = False) -> List[Request]:
        """Commit barrier: materialize one finished tick's sampled tokens
        (the pipeline's only host sync), append them to request outputs —
        skipping sequences preempted (dead: greedy recompute regenerates
        their tokens) or already finished (EOS found by an earlier commit:
        later speculative tokens are discarded) — then reconcile EOS /
        ``max_new`` and retire.  A request's first committed token stamps
        its ``t_first``.  ``timed`` records the host's wait for the device
        in ``rec.sync_s``; ``traced`` wraps the wait in ``serve.sync`` and
        the rest in ``serve.commit``."""
        t = self.clock() if timed else 0.0
        with _span(traced, "serve.sync"):
            seed = (None if rec.prefill_seed is None
                    else int(np.asarray(rec.prefill_seed[1])[0, 0]))
            nxt = np.asarray(rec.decode_toks) if rec.decode_seqs else None
        if timed:
            rec.sync_s = self.clock() - t
        with _span(traced, "serve.commit"):
            now: Optional[float] = None
            appends = ([(rec.prefill_seed[0], seed)] if seed is not None
                       else [])
            appends += [(seq, int(nxt[seq.slot, 0]))
                        for seq in rec.decode_seqs]
            for seq, tok in appends:
                req = seq.req
                if seq.dead or req.done:
                    continue
                if req.t_first is None:
                    now = self.clock() if now is None else now
                    req.t_first = now
                req.out.append(tok)
            return self._retire()

    def _retire(self) -> List[Request]:
        done = []
        for seq in list(self.sched.running()):
            if seq.prefilling:
                continue
            req = seq.req
            if req.eos is not None and req.eos in req.out:
                # stop at the first EOS; later speculative tokens are
                # truncated away
                req.out = req.out[:req.out.index(req.eos) + 1]
                req.done = True
            elif len(req.out) >= req.max_new:
                req.out = req.out[:req.max_new]
                req.done = True
            if req.done:
                done.append(req)
                self.sched.retire(seq)       # copy-free: refcounts drop
        return done

    # -- observability --------------------------------------------------------
    def registry(self) -> ObsRegistry:
        """This engine's unified metrics registry: pool, scheduler,
        dispatch cache, monitor, and watchdog behind one ``snapshot()`` /
        ``summary_line()`` surface.  Parts are
        resolved per snapshot, so a monitor attached later is reported."""
        return ObsRegistry.from_engine(self)

    @property
    def degrade_events(self):
        """The dispatch cache's recorded :class:`~repro.artifacts.dispatch.
        DegradeEvent`s (this engine demotes through its captured cache)."""
        return self._cache.degrade_events

    def robustness_line(self) -> str:
        s = self.sched.stats
        line = (f"robustness shed={s.shed} cancelled={s.cancelled} "
                f"poisoned={s.poisoned} "
                f"demotions={self._cache.stats.demotions}")
        if self.watchdog is not None:
            line += " | " + self.watchdog.stats_line()
        return line

    def run_until_drained(self, max_ticks: int = 1000) -> List[Request]:
        finished: List[Request] = []
        for _ in range(max_ticks):
            finished.extend(self.step())
            if not self.sched.has_work():
                break
        # drain the pipeline: ticks still in flight when the queue empties
        # (async_depth > 1) carry the final tokens of the last requests
        while self._inflight:
            finished.extend(self._commit(self._inflight.popleft()))
        return finished
