"""The program's own counters of the engine tick and of each request.

Importing this module installs a ``repro.obs.FlightRecorder`` for the
rest of the process.  Only the per-layer readers import it, and
``cell.load`` loads those only for ``--trace 1`` runs: the program traces
exactly in the traced runs, and the ``--trace 0`` runs, which carry every
end-to-end metric, run with tracing off.

With a recorder installed the engine emits one ``tick_span`` record a
step, with its host phases in microseconds on the engine's clock
(``plan_us``, ``dispatch_us``, ``sync_us``, ``commit_us``, and the
caller's ``caller_us`` between steps), and mirrors each step into
``serve.*`` profiler spans (``spans.py`` reads those).  Each ``Request``
carries ``t_submit``, ``t_admit`` and ``t_first`` whether or not a
recorder is installed.  A program that has none of these gives the
readers nothing to read: they return ``None``, and the metric is left out
of the line.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from repro.obs import FlightRecorder, get_recorder, install

#: ticks a 50 s window makes, and the warm-up's, are a few thousand
CAPACITY = 1 << 17

install(FlightRecorder(capacity=CAPACITY))


def window_spans(run: dict) -> Optional[List[dict]]:
    """The ``tick_span`` records of the window's engine steps, in order:
    the last ``len(run["ticks"])`` ones (readers run before the
    post-window finish steps).  ``None`` where the ring dropped any of
    them, or where their decode rows disagree with the harness's count."""
    rec = get_recorder()
    n = len(run["ticks"])
    if rec is None or not n:
        return None
    spans = [r for r in rec.records() if r["etype"] == "tick_span"][-n:]
    if len(spans) < n:
        return None
    if any(s["decode_rows"] != len(t["decode"])
           for s, t in zip(spans, run["ticks"])):
        return None
    return spans


def phase_ms(run: dict, phase: str) -> Optional[float]:
    """Mean host milliseconds of one phase (``plan``, ``dispatch``,
    ``sync``, ``commit``, ``caller``) over the window's engine steps."""
    spans = window_spans(run)
    field = f"{phase}_us"
    if not spans or field not in spans[0]:
        return None
    return 1e-3 * float(np.mean([s[field] for s in spans]))


def stamp_gaps_s(run: dict, start: str, end: str) -> List[float]:
    """``end - start`` of the requests' stamps (``t_submit``, ``t_admit``,
    ``t_first``), over the requests ``readers.queue_wait_s`` takes (due in
    the window; in a traced run, before the trace started) that have both."""
    before = run["traced"][2] if run["traced"] else run["window_s"]
    out = []
    for s in run["due"]:
        a = getattr(s.req, start, None)
        b = getattr(s.req, end, None)
        if s.due < before and a is not None and b is not None:
            out.append(b - a)
    return out
