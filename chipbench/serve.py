"""The serving runner: drive the program's ``ServeEngine`` through one
cell's window (a traffic mix without ``"runner"``, or ``"runner": "serve"``).

Set-up builds the weights on the device in one jitted call from the seed,
builds the engine with the configuration's settings, and runs every program
the window will use once: each quantized prefill chunk length, the decode
step, and one tiny request in every slot for the engine's small eager
updates.  The window is an open loop on the host clock: a request is
submitted between steps once it is due, and each token is stamped when the
step that commits it returns.  Where the engine keeps ticks in flight
(``async_depth`` > 1), the window's close commits them before it reads the
clock, so all the work sent counts, over all the time it took.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import check as C
from reference import weights as W

#: longest the comparison waits, after the window, for requests to finish
FINISH_CAP_S = 150.0
#: share of the window after which a traced run starts its trace
TRACE_FROM = 0.5
TRACE_SECONDS = 3.0


def model_config(config: dict):
    """The program's ``ModelConfig``: its registry entry plus overrides (a
    nested group of settings overridden key by key)."""
    from repro.configs import get_config
    prog = config["program"]
    base = get_config(prog["model"])
    over = {k: (dataclasses.replace(getattr(base, k), **v)
                if isinstance(v, dict) else v)
            for k, v in prog.get("overrides", {}).items()}
    return dataclasses.replace(base, **over)


def program_params(cfg, ref, model: dict, seed: int, shardings=None):
    """The program's parameter tree, filled from the benchmark's weights
    (the recipe of the reference module ``ref``) in one jitted call, each
    leaf in the type the program stores it in, and laid out by
    ``shardings`` where given."""
    from repro.models import init_model
    dm = ref.dims(model)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    layer_dtypes = jax.tree.map(lambda s: s.dtype, shapes["layers"])

    def build(key):
        def one(i):
            t = ref.program_layer(dm, ref.layer(dm, key, i))
            return jax.tree.map(lambda v, dt: v.astype(dt), t, layer_dtypes)

        tree = dict(W.program_outside(ref.outside(dm, key)),
                    layers=jax.lax.map(one, jnp.arange(dm["layers"])))
        return jax.tree.map(lambda v, s: v.astype(s.dtype), tree, shapes)

    return jax.block_until_ready(
        jax.jit(build, out_shardings=shardings)(W.seed_key(seed)))


def chunk_lengths(prefill_chunk: int) -> List[int]:
    """Every prefill length the scheduler can ask for: full chunks, then
    powers of two below them."""
    out = {prefill_chunk}
    c = 1
    while c < prefill_chunk:
        out.add(c)
        c *= 2
    return sorted(out)


def build_engine(cfg, params, engine: dict):
    from repro.runtime import ServeEngine
    return ServeEngine(cfg, params, **engine)


def warm_up(eng) -> None:
    """Run each program of the window once (compiled or loaded from the
    persistent cache).  Prefill and decode write only the garbage block."""
    nblk, B = eng.blocks_per_seq, eng.max_batch
    table = jnp.zeros((1, nblk), jnp.int32)
    for c in chunk_lengths(eng.sched.prefill_chunk):
        _, eng.cache = eng._prefill(eng.params, jnp.zeros((1, c), jnp.int32),
                                    eng.cache, jnp.int32(0), table,
                                    jnp.int32(0))
    _, eng.last_tok, eng.cache = eng._decode(
        eng.params, eng.last_tok, eng.cache, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, nblk), jnp.int32), jnp.zeros((B,), bool))
    for _ in range(B):
        eng.submit(np.ones(2, np.int32), max_new=2)
    eng.run_until_drained()
    jax.block_until_ready(eng.cache)


@dataclasses.dataclass
class Served:
    due: float
    prompt: np.ndarray
    max_new: int
    req: Any = None                      # the engine's Request
    times: List[float] = dataclasses.field(default_factory=list)
    admitted: Optional[float] = None


def _tick_work(before: Dict[int, tuple], eng) -> dict:
    """What one step computed, read off the scheduler's sequences: decode
    rows (their positions) and the prefill chunk (start, length)."""
    seqs = {id(s): s for s, *_ in before.values()}
    for s in eng.sched.running():
        seqs.setdefault(id(s), s)
    decode, prefill = [], None
    for k, s in seqs.items():
        pos0, filled0, was_prefilling = before.get(k, (s, 0, 0, True))[1:]
        if s.filled > filled0:
            prefill = (filled0, s.filled - filled0)
        elif not was_prefilling and s.pos > pos0:
            decode.append(pos0)
    return {"decode": decode, "prefill": prefill}


def run_window(eng, arrivals, seconds: float, trace_dir: Optional[str],
               compiles: List[int]) -> dict:
    """The measured window.  Returns the requests, the ticks and the clock
    marks of the window and of the traced part: ``traced`` is [trace
    running from, trace stopped at, start of trace called at] in seconds
    after the window opened."""
    served = [Served(a.due, a.prompt, a.max_new) for a in arrivals]
    by_rid: Dict[int, Served] = {}
    active: List[Served] = []
    ticks: List[dict] = []
    tick_time: Dict[int, float] = {}
    nxt = 0
    traced = None
    compiles_at_start = compiles[0]
    t0 = time.perf_counter()
    trace_at = t0 + TRACE_FROM * seconds
    end = t0 + seconds
    window_span = None
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if trace_dir is not None and traced is None and now >= trace_at:
            called = now - t0
            jax.profiler.start_trace(trace_dir)
            window_span = jax.profiler.TraceAnnotation("chipbench.window")
            window_span.__enter__()
            traced = [time.perf_counter() - t0, None, called]
            trace_stop = time.perf_counter() + TRACE_SECONDS
        if traced is not None and traced[1] is None and now >= trace_stop:
            window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            traced[1] = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            while nxt < len(served) and t0 + served[nxt].due <= now:
                s = served[nxt]
                rid = eng.submit(s.prompt, max_new=s.max_new)
                s.req = next(r for r in reversed(eng.sched.queue)
                             if r.rid == rid)
                by_rid[rid] = s
                active.append(s)
                nxt += 1
        if not eng.sched.has_work():
            wake = min(end, t0 + served[nxt].due) if nxt < len(served) \
                else end
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                time.sleep(max(0.0, wake - time.perf_counter()))
            continue
        tick = eng.sched.ticks
        before = {id(s): (s, s.pos, s.filled, s.prefilling)
                  for s in eng.sched.running()}
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.step"):
            eng.step()
        te = time.perf_counter()
        tick_time[tick] = ts - t0
        rec = _tick_work(before, eng)
        rec.update(t0=ts - t0, t1=te - t0)
        ticks.append(rec)
        for seq in eng.sched.running():
            s = by_rid[seq.req.rid]
            if s.admitted is None:
                s.admitted = tick_time.get(seq.admitted_at, ts - t0)
        still = []
        for s in active:
            s.times.extend([te - t0] * (len(s.req.out) - len(s.times)))
            if not s.req.done:
                still.append(s)
        active = still
    # time is up: send nothing more, commit the ticks still in flight
    # (``async_depth`` > 1), count their tokens, and read the clock after
    drain(eng)
    te = time.perf_counter()
    for s in active:
        s.times.extend([te - t0] * (len(s.req.out) - len(s.times)))
    window_s = time.perf_counter() - t0
    if traced is not None and traced[1] is None:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced[1] = window_s
    return {"served": served[:nxt], "due": [s for s in served
                                            if s.due < seconds],
            "ticks": ticks, "window_s": window_s, "traced": traced,
            "compiles_in_window": compiles[0] - compiles_at_start}


def drain(eng) -> None:
    """Commit every tick still in flight and dispatch nothing more: an
    engine with ``async_depth`` > 1 keeps its newest ticks on the device
    across ``step``; at ``async_depth`` 1 there are none."""
    eng.run_until_drained(max_ticks=0)


def finish(eng, served: List[Served], k: int, cap_s: float) -> float:
    """After the window: step on, submitting nothing, until ``k`` of the
    requests served have finished (the comparison samples finished ones),
    the engine runs dry, or ``cap_s`` passes.  Returns the seconds taken;
    nothing here is measured."""
    t0 = time.perf_counter()
    while (sum(1 for s in served if s.req.done) < k
           and eng.sched.has_work()
           and time.perf_counter() - t0 < cap_s):
        eng.step()
    drain(eng)
    return time.perf_counter() - t0


def measure(cell, ref, seed: int, seconds: float, trace_dir, compiles,
            t_start: float, devices):
    """Set-up and the window.  Returns the run's record for the readers and
    what :func:`check` needs."""
    conf = cell.config
    dm = ref.dims(conf)
    cfg = model_config(conf)
    eng = build_engine(cfg, program_params(cfg, ref, conf, seed),
                       conf["engine"])
    warm_up(eng)
    arrivals = cell.generator.generate(cell.traffic, seed, seconds,
                                       dm["vocab"])
    setup_s = time.perf_counter() - t_start
    win = run_window(eng, arrivals, seconds, trace_dir, compiles)
    record = {"setup_s": setup_s, "window_s": win["window_s"],
              "served": win["served"], "due": win["due"],
              "ticks": win["ticks"], "traced": win["traced"]}
    return record, {"eng": eng, "win": win}


def check(cell, ref, state: dict, seed: int, control: bool, devices):
    """After the window: a sample of the finished requests through the
    reference.  Returns the numbers compared with their limits, the
    requests attempted and failed, and what else the comparison saw."""
    import readers
    conf, win, eng = cell.config, state.pop("win"), state.pop("eng")
    lim = conf["check"]
    t_fin = finish(eng, win["served"], lim["sequences"], FINISH_CAP_S)
    chosen = C.sample(C.finished(win["served"]), seed, lim["sequences"])
    del eng
    gc.collect()
    t_ref = time.perf_counter()
    widest, low, n_tok = C.logit_gaps(ref, conf, seed, chosen,
                                      lim["sequences"],
                                      conf["engine"]["max_len"], control)
    t_ref = time.perf_counter() - t_ref
    failed = C.failed(win["served"])
    # under the control, its first choices stand in the served tokens' place
    checks = {"max_logit_gap": {"value": low if control else widest,
                                "limit": lim["max_logit_gap"]},
              "failed_requests": {"value": failed, "limit": 0}}
    info = {"tokens_compared": n_tok, "requests_compared": len(chosen),
            "compiles_in_window": win["compiles_in_window"],
            "still_waiting_at_close": sum(1 for s in win["due"]
                                          if not s.times),
            "program_max_logit_gap": widest,
            "program_checks": {"max_logit_gap": widest,
                               "failed_requests": failed},
            "control_max_logit_gap": low, "reference_s": t_ref,
            "finish_s": t_fin, "ticks": len(win["ticks"]),
            "tokens": sum(len(s.times) for s in win["served"]),
            "ttft_p90_ms": 1e3 * readers.percentile(readers.ttft_s(
                {"window_s": win["window_s"], "due": win["due"]}), 90),
            "trace_start_s": (win["traced"][0] - win["traced"][2]
                              if win["traced"] else None)}
    return checks, len(win["due"]), failed, info
