"""Device-idle time inside the harness's engine steps, put down to the
program's own spans.

While a recorder is installed (``program_trace.py``) the engine mirrors
each step into profiler spans on the host plane: ``serve.step`` holding
``serve.plan``, ``serve.dispatch``, ``serve.sync`` and ``serve.commit``.
They are written by the same profiler as the device's operations, so they
share the device trace's clock.  Works on the plain data of
:func:`devtrace.load`.
"""
from __future__ import annotations

from typing import Dict, Optional

import devtrace

STEP = "chipbench.step"
PROGRAM_STEP = "serve.step"
PHASES = ("serve.plan", "serve.dispatch", "serve.sync", "serve.commit")
HARNESS = "harness"


def step_idle_by_span(tr: dict) -> Optional[Dict[str, float]]:
    """Device-idle seconds inside each ``chipbench.step`` of the traced
    window (on the first device, as ``devtrace.reduce`` counts
    ``step_idle_s``), summed by the innermost program span that covers
    each gap's midpoint: a phase, ``serve.step`` outside its phases, or
    ``harness`` where no program span covers it.  ``None`` where the
    trace holds no window or no device operation."""
    win = devtrace.host_events(tr, (devtrace.WINDOW,))
    ops = [line["events"] for p in devtrace.device_planes(tr)
           for line in p["lines"] if line["name"] == devtrace.OPS]
    if not win or not ops:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    busy = devtrace.union([(max(s, w0), min(s + d, w1))
                           for _, s, d in ops[0] if s < w1 and s + d > w0])
    program = devtrace.host_events(tr, PHASES + (PROGRAM_STEP,))
    out = dict.fromkeys(PHASES + (PROGRAM_STEP, HARNESS), 0.0)
    for _, s, d in devtrace.host_events(tr, (STEP,)):
        e = s + d
        if s < w0 or e > w1:
            continue
        edges = [s] + [x for b0, b1 in busy if b0 < e and b1 > s
                       for x in (max(b0, s), min(b1, e))] + [e]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cover = [p for p in program if p[1] <= mid < p[1] + p[2]]
            label = min(cover, key=lambda p: p[2])[0] if cover else HARNESS
            out[label] += (g1 - g0) / 1e9
    return out
