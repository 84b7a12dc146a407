"""From the profiler's ``.xplane.pb`` to device busy and idle time.

:func:`load` turns a trace into plain data (planes, their lines, and events
as ``[name, start_ns, duration_ns]``); :func:`reduce` works on that data
alone, so the recorded trace in ``tests/`` checks it without a chip.

Device planes are those named ``/device:TPU:<n>``.  On each, the line
``XLA Ops`` holds the operations that ran and ``XLA Modules`` the jitted
programs (``jit_prefill``, ``jit_decode``, ...).  Host spans are the
harness's own ``TraceAnnotation`` events on the host plane: ``chipbench.window``
around the traced part of the window, ``chipbench.step`` around each engine
step, ``chipbench.wait`` while the harness waits for the next arrival.
"""
from __future__ import annotations

import bisect
import glob
import math
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
HOST_SPANS = ("chipbench.step", "chipbench.wait", "chipbench.submit")
OPS, MODULES = "XLA Ops", "XLA Modules"
TOP = 10
#: operations that hold others (the layer loop): their time is their
#: children's, listed on their own
CONTAINERS = ("%while", "%conditional", "%call")
#: operations that exchange data between chips (sync or async halves)
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
               "collective-permute")


def load(log_dir: str) -> dict:
    """The newest ``.xplane.pb`` under ``log_dir``, as plain data."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    # of a device plane, only the lines the reductions read
    keep = lambda plane, line: (not plane.name.startswith("/device:")
                                or line.name in (OPS, MODULES))
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[ev.name, float(ev.start_ns),
                                float(ev.duration_ns)]
                               for ev in line.events]}
                   for line in plane.lines if keep(plane, line)]}
        for plane in pd.planes]}


def op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return text.split(" = ", 1)[0]


def _line(plane: dict, name: str) -> List[list]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def device_planes(tr: dict) -> List[dict]:
    return [p for p in tr["planes"] if p["name"].startswith("/device:TPU:")]


def host_events(tr: dict, names) -> List[list]:
    out = []
    for p in tr["planes"]:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                out.extend(e for e in line["events"] if e[0] in names)
    return sorted(out, key=lambda e: e[1])


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(busy: List[Tuple[float, float]], s: float, e: float) -> float:
    """Length of [s, e] that the merged intervals ``busy`` cover (a sorted
    list, so only those that reach into [s, e] are visited)."""
    total = 0.0
    first = max(0, bisect.bisect_right(busy, (s, math.inf)) - 1)
    for b0, b1 in busy[first:]:
        if b0 >= e:
            break
        total += max(0.0, min(e, b1) - max(s, b0))
    return total


def overlap(a: List[Tuple[float, float]],
            b: List[Tuple[float, float]]) -> float:
    """Length that two lists of merged intervals have in common, in one
    pass over both."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def is_collective(name: str) -> bool:
    return any(c in name for c in COLLECTIVES)


def exposed(plane: dict, w0: float, w1: float) -> Tuple[float, float]:
    """(seconds in collective operations, seconds of them that no other
    operation of the plane overlaps), inside [w0, w1]."""
    coll, other = [], []
    for name, s, d in _line(plane, OPS):
        if s >= w1 or s + d <= w0:
            continue
        name = op_name(name)
        iv = (max(s, w0), min(s + d, w1))
        if is_collective(name):
            coll.append(iv)
        elif not name.startswith(CONTAINERS):
            other.append(iv)
    coll = union(coll)
    total = sum(e - s for s, e in coll)
    return total / 1e9, (total - overlap(coll, union(other))) / 1e9


def reduce(tr: dict) -> Optional[dict]:
    """Busy and idle time of the devices inside the traced window (each
    averaged over the devices), time in collectives and the part of it no
    other operation hides (averaged likewise; ``None`` where no device ran
    one), device time per jitted program, and where the idle time fell on
    the host.  ``None`` where the trace holds no window or no device
    operation."""
    win = host_events(tr, (WINDOW,))
    devs = [p for p in device_planes(tr) if _line(p, OPS)]
    if not win or not devs:
        return None
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    spans = [e for e in host_events(tr, HOST_SPANS)
             if e[1] >= w0 and e[1] + e[2] <= w1]
    busy_s, gaps = [], defaultdict(lambda: [0.0, 0])
    modules: Dict[str, List[float]] = defaultdict(list)
    ops: Dict[str, float] = defaultdict(float)
    step_idle: List[float] = []
    coll = [exposed(plane, w0, w1) for plane in devs]
    for i, plane in enumerate(devs):
        busy = union([(max(s, w0), min(s + d, w1))
                      for _, s, d in _line(plane, OPS)
                      if s < w1 and s + d > w0])
        busy_s.append(sum(e - s for s, e in busy) / 1e9)
        if i:
            continue
        # the first device speaks for the host's view of idle time
        for name, s, d in _line(plane, MODULES):
            if w0 <= s < w1:
                modules[name].append(d / 1e9)
        for name, s, d in _line(plane, OPS):
            name = op_name(name)
            if w0 <= s < w1 and not name.startswith(CONTAINERS):
                ops[name] += d / 1e9
        for name, s, d in spans:
            if name == "chipbench.step":
                step_idle.append((d - covered(busy, s, s + d)) / 1e9)
        edges = [w0] + [x for b in busy for x in b] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = next((n for n, s, d in spans if s <= mid < s + d),
                         "host (no span)")
            gaps[label][0] += (g1 - g0) / 1e9
            gaps[label][1] += 1
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s),
        "devices": len(devs),
        "collective_s": (sum(c[0] for c in coll) / len(coll)
                         if any(c[0] for c in coll) else None),
        "collective_exposed_s": sum(c[1] for c in coll) / len(coll),
        "modules": dict(modules),
        "step_idle_s": step_idle,
        "breakdown": {
            "device_ops": sorted(([n, t] for n, t in ops.items()),
                                 key=lambda x: -x[1])[:TOP],
            "idle_gaps": sorted(([f"{n} ({c} gaps)", t]
                                 for n, (t, c) in gaps.items()),
                                key=lambda x: -x[1])[:TOP],
        },
    }


def module_seconds(red: dict, part: str) -> List[float]:
    """Device seconds of each call of the jitted programs whose name holds
    ``part`` (``jit_decode`` holds ``decode``)."""
    return [d for name, ds in red["modules"].items() if part in name
            for d in ds]
