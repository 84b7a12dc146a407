"""Drive the program's ``ServeEngine`` through one cell's window.

Set-up builds the weights on the device in one jitted call from the seed,
builds the engine with the configuration's settings, and runs every program
the window will use once: each quantized prefill chunk length, the decode
step, and one tiny request in every slot for the engine's small eager
updates.  The window is an open loop on the host clock: a request is
submitted between steps once it is due, and each token is stamped when the
step that commits it returns.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from reference import weights as W

#: share of the window after which a traced run starts its trace
TRACE_FROM = 0.5
TRACE_SECONDS = 3.0


def model_config(config: dict):
    """The program's ``ModelConfig``: its registry entry plus overrides."""
    from repro.configs import get_config
    prog = config["program"]
    return dataclasses.replace(get_config(prog["model"]),
                               **prog.get("overrides", {}))


def program_params(cfg, model: dict, seed: int):
    """The program's parameter tree, filled from the benchmark's weights
    (:mod:`reference.weights`) in one jitted call, each leaf in the type
    the program stores it in."""
    from repro.models import init_model
    dm = W.dims(model)
    shapes = jax.eval_shape(lambda k: init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    layer_dtypes = jax.tree.map(lambda s: s.dtype, shapes["layers"])

    def build(key):
        def one(i):
            p = W.layer(dm, key, i)
            attn = {k: p[k] for k in ("wq", "wk", "wv", "wo")}
            if dm["bias"]:
                attn.update(bq=p["bq"], bk=p["bk"], bv=p["bv"])
            t = {"ln1": {"scale": p["ln1"]}, "attn": attn,
                 "ln2": {"scale": p["ln2"]},
                 "mlp": {"wi": p["wu"], "wg": p["wg"], "wo": p["wd"]}}
            return jax.tree.map(lambda v, dt: v.astype(dt), t, layer_dtypes)

        o = W.outside(dm, key)
        tree = {"embed": {"tok": o["embed"], "out": o["unembed"]},
                "layers": jax.lax.map(one, jnp.arange(dm["layers"])),
                "ln_f": {"scale": o["ln_f"]}}
        return jax.tree.map(lambda v, s: v.astype(s.dtype), tree, shapes)

    return jax.block_until_ready(jax.jit(build)(W.seed_key(seed)))


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
    window_s = time.perf_counter() - t0
    if traced is not None and traced[1] is None:
        window_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        traced[1] = window_s
    return {"served": served[:nxt], "due": [s for s in served
                                            if s.due < seconds],
            "ticks": ticks, "window_s": window_s, "traced": traced,
            "compiles_in_window": compiles[0] - compiles_at_start}


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
    return time.perf_counter() - t0
