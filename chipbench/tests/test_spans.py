"""Device idle inside the engine steps, put down to the program's spans:
on hand-made events, and on a slice of a trace recorded on a TPU v5e with
the program's ``serve.*`` spans (``tests/data/trace_yi_chat_spans.json.gz``)."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace                                                   # noqa: E402
import spans                                                      # noqa: E402

MS = 1e6   # nanoseconds


def _trace():
    host = [["chipbench.window", 0, 100 * MS],
            ["chipbench.step", 10 * MS, 40 * MS],
            ["serve.step", 12 * MS, 36 * MS],
            ["serve.plan", 12 * MS, 4 * MS],
            ["serve.dispatch", 16 * MS, 6 * MS],
            ["serve.decode", 18 * MS, 3 * MS],
            ["serve.sync", 23 * MS, 20 * MS],
            ["serve.commit", 43 * MS, 4 * MS],
            ["chipbench.wait", 50 * MS, 50 * MS]]
    ops = [["fusion.1", 20 * MS, 21 * MS], ["fusion.2", 60 * MS, 5 * MS]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": []}]}]}


def test_idle_by_span_by_hand():
    idle = spans.step_idle_by_span(_trace())
    # step 10-50, busy 20-41: gaps 10-20 (mid 15: plan) and 41-50 (mid
    # 45.5: commit); the wait outside any step is not counted
    assert idle == pytest.approx({
        "serve.plan": 0.010, "serve.dispatch": 0.0, "serve.sync": 0.0,
        "serve.commit": 0.009, "serve.step": 0.0, "harness": 0.0})
    assert sum(idle.values()) == pytest.approx(
        sum(devtrace.reduce(_trace())["step_idle_s"]))


def test_gaps_outside_program_spans_go_to_the_harness():
    tr = _trace()
    host = tr["planes"][0]["lines"][0]["events"]
    host[:] = [e for e in host if e[0] != "serve.plan"]
    tr["planes"][1]["lines"][0]["events"] = [["fusion.1", 14 * MS, 3 * MS]]
    idle = spans.step_idle_by_span(tr)
    # gaps 10-14 (mid 12: serve.step, outside its phases) and 17-50 (mid
    # 33.5: sync)
    assert idle["serve.step"] == pytest.approx(0.004)
    assert idle["serve.sync"] == pytest.approx(0.033)
    host[:] = [e for e in host if not e[0].startswith("serve.")]
    idle = spans.step_idle_by_span(tr)
    assert idle["harness"] == pytest.approx(0.037)
    assert sum(idle.values()) == pytest.approx(0.037)


def test_no_window_reads_nothing():
    tr = _trace()
    tr["planes"][0]["lines"][0]["events"].pop(0)
    assert spans.step_idle_by_span(tr) is None


RECORDED = HERE / "tests" / "data" / "trace_yi_chat_spans.json.gz"


def test_recorded_trace_shares_the_clock_and_attributes_all_idle():
    with gzip.open(RECORDED, "rt") as f:
        tr = json.load(f)
    harness = devtrace.host_events(tr, ("chipbench.step",))
    program = devtrace.host_events(tr, ("serve.step",))
    assert program and len(program) == len(harness)
    for _, s, d in program:
        # the program's step lies inside the harness's: one clock
        assert any(hs <= s and s + d <= hs + hd for _, hs, hd in harness)
    for name in spans.PHASES:
        assert devtrace.host_events(tr, (name,)), name
    idle = spans.step_idle_by_span(tr)
    red = devtrace.reduce(tr)
    assert sum(idle.values()) == pytest.approx(sum(red["step_idle_s"]),
                                               rel=1e-9, abs=1e-12)
    assert sum(red["step_idle_s"]) > 0
