"""Reductions shared by the metric readers in ``chipbench/metrics/``.

A reader gets the run's record (see ``run.py`` and the runners,
``serve.py`` and ``train.py``) and returns one number, or ``None`` where the
run holds nothing to read; the harness then leaves the metric out of its
line.  The work count is the configuration's reference module's
(``run["ref"]``).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

import devtrace
import work


def percentile(values: List[float], q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def ttft_s(run: dict) -> List[float]:
    """Due time to first token of every request due in the window; one
    still waiting at the close counts with its wait so far."""
    w = run["window_s"]
    return [(s.times[0] if s.times else w) - s.due for s in run["due"]]


def itl_s(run: dict) -> List[float]:
    return [b - a for s in run["served"] for a, b in zip(s.times, s.times[1:])]


def queue_wait_s(run: dict) -> List[float]:
    """Due time to admission.  In a traced run, only requests due before
    the trace was started: starting the profiler stalls the loop, and
    requests due then queue behind the stall."""
    w = run["window_s"]
    before = run["traced"][2] if run["traced"] else w
    return [(s.admitted if s.admitted is not None else w) - s.due
            for s in run["due"] if s.due < before]


def traced_ticks(run: dict) -> List[dict]:
    if run["traced"] is None:
        return []
    a, b = run["traced"][:2]
    return [t for t in run["ticks"] if t["t0"] >= a and t["t1"] <= b]


def tick_work(run: dict, tick: dict, part: str):
    """(FLOPs, bytes) of the decode (``part="decode"``) or prefill step of a
    tick, or None where the tick ran no such step."""
    dm = run["dims"]
    if part == "decode":
        if not tick["decode"]:
            return None
        return run["ref"].step(dm, [(p, 1) for p in tick["decode"]],
                               len(tick["decode"]))
    if tick["prefill"] is None:
        return None
    return run["ref"].step(dm, [tick["prefill"]], 1)


def device_seconds(run: dict, part: str) -> List[float]:
    red = run["trace"]
    return devtrace.module_seconds(red, part) if red else []


def idle_share(run: dict) -> Optional[float]:
    red = run["trace"]
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def host_gap_ms_per_tick(run: dict) -> Optional[float]:
    red = run["trace"]
    if not red or not red["step_idle_s"]:
        return None
    return 1e3 * float(np.mean(red["step_idle_s"]))


def decode_roofline(run: dict) -> Optional[float]:
    peak = run["peak"]
    bounds = [work.least_seconds(*w, peak) for t in traced_ticks(run)
              if (w := tick_work(run, t, "decode")) is not None]
    dev = device_seconds(run, "decode")
    if not bounds or not dev:
        return None
    return 100.0 * float(np.mean(bounds)) / float(np.mean(dev))


def mfu(run: dict) -> Optional[float]:
    red, ticks = run["trace"], traced_ticks(run)
    if not red or not ticks:
        return None
    flops = sum(w[0] for t in ticks for part in ("decode", "prefill")
                if (w := tick_work(run, t, part)) is not None)
    return 100.0 * flops / (red["window_s"] * run["peak"]["flops"]
                            * red["devices"])


def whole_steps(run: dict) -> List[dict]:
    """Training steps of the window that ended inside it."""
    return [s for s in run["steps"] if s["t1"] <= run["window_s"]]


def train_tok_s(run: dict) -> Optional[float]:
    """Tokens of the window's whole steps over the time from the first
    one's start to the last one's end."""
    done = whole_steps(run)
    if not done:
        return None
    return sum(s["tokens"] for s in done) / (done[-1]["t1"] - done[0]["t0"])


def traced_steps(run: dict) -> List[dict]:
    if run["traced"] is None:
        return []
    a, b = run["traced"][:2]
    return [s for s in run["steps"] if s["t0"] >= a and s["t1"] <= b]


def train_mfu(run: dict) -> Optional[float]:
    """Work-count FLOPs of the traced training steps over the traced time
    times the chips' peak FLOP/s."""
    red, steps = run["trace"], traced_steps(run)
    if not red or not steps:
        return None
    flops = sum(run["ref"].train_step(run["dims"], s["batch"], s["seq_len"])
                for s in steps)
    return 100.0 * flops / (red["window_s"] * run["peak"]["flops"]
                            * red["devices"])


def collective_exposed_share(run: dict) -> Optional[float]:
    red = run["trace"]
    if not red or red["window_s"] <= 0 or red["collective_s"] is None:
        return None
    return 100.0 * red["collective_exposed_s"] / red["window_s"]
