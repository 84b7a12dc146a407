"""The readers of the program's own counters (``program_trace.py`` and the
five metrics that use it): on hand-made run records with a recorder
holding hand-made ``tick_span`` records, and on a tiny engine driven
through the harness's window on the CPU."""
from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell as cells                                              # noqa: E402
import program_trace                                              # noqa: E402
from repro.obs import FlightRecorder, get_recorder, install       # noqa: E402
from repro.obs.events import TickSpan                             # noqa: E402

METRICS = ("plan_ms_per_tick.chat", "dispatch_ms_per_tick.chat",
           "commit_ms_per_tick.chat", "admit_wait_p90_ms.chat",
           "first_token_after_admit_p50_ms.chat")


def _reader(name):
    return cells.load_module(HERE / "metrics" / f"{name}.py")


@pytest.fixture
def recorder():
    """A fresh recorder for the test; the one before it comes back after."""
    prev = get_recorder()
    rec = FlightRecorder(capacity=64)
    install(rec)
    yield rec
    install(prev)


def _span(tick, rows, plan, dispatch=2000.0, commit=500.0):
    return TickSpan(tick=tick, admitted=0, prefill_tokens=0,
                    decode_rows=rows, preempted=0, cancelled=0, finished=0,
                    duration_us=plan + dispatch + 30000.0 + commit,
                    plan_us=plan, dispatch_us=dispatch, sync_us=30000.0,
                    commit_us=commit, caller_us=100.0)


def _req(t_submit, t_admit, t_first):
    return SimpleNamespace(t_submit=t_submit, t_admit=t_admit,
                           t_first=t_first)


def _run(rows, traced=None, due=()):
    return {"ticks": [{"decode": [0] * r, "prefill": None} for r in rows],
            "window_s": 10.0, "traced": traced,
            "due": [SimpleNamespace(due=d, req=r) for d, r in due]}


def test_window_spans_are_the_last_ticks_and_readers_average_them(recorder):
    # two warm-up steps, then the window's three
    for i, (rows, plan) in enumerate([(4, 9e6), (4, 9e6), (1, 1000.0),
                                      (2, 2000.0), (3, 6000.0)]):
        recorder.emit(_span(i, rows, plan))
    run = _run([1, 2, 3])
    spans = program_trace.window_spans(run)
    assert [s["tick"] for s in spans] == [2, 3, 4]
    assert _reader("plan_ms_per_tick.chat").read(run) == pytest.approx(3.0)
    assert _reader("dispatch_ms_per_tick.chat").read(run) == \
        pytest.approx(2.0)
    assert _reader("commit_ms_per_tick.chat").read(run) == pytest.approx(0.5)


def test_a_dropped_ring_reads_nothing(recorder):
    for i in range(70):                      # capacity 64: 6 aged out
        recorder.emit(_span(i, 1, 1000.0))
    assert recorder.dropped == 6
    assert program_trace.window_spans(_run([1] * 64)) is not None
    run = _run([1] * 65)
    assert program_trace.window_spans(run) is None
    for m in METRICS[:3]:
        assert _reader(m).read(run) is None


def test_rows_that_disagree_with_the_harness_read_nothing(recorder):
    for i, rows in enumerate([2, 3, 4]):
        recorder.emit(_span(i, rows, 1000.0))
    assert program_trace.window_spans(_run([2, 3, 4])) is not None
    run = _run([2, 5, 4])
    assert program_trace.window_spans(run) is None
    assert _reader("plan_ms_per_tick.chat").read(run) is None


def test_spans_without_phases_or_no_recorder_read_nothing(recorder):
    # a program whose tick spans carry no phases (before they were added)
    recorder._ring.append({"seq": 0, "etype": "tick_span", "tick": 0,
                           "decode_rows": 1, "duration_us": 5.0})
    run = _run([1])
    assert program_trace.window_spans(run) is not None
    assert _reader("plan_ms_per_tick.chat").read(run) is None
    install(None)
    assert program_trace.window_spans(run) is None


def test_request_stamp_readers():
    due = [(0.5, _req(1.00, 1.02, 1.30)),        # waits 20 ms, first +280
           (1.0, _req(1.50, 1.60, 1.70)),        # 100 ms, +100
           (2.0, _req(2.00, 2.04, 2.24)),        # 40 ms, +200
           (2.5, _req(2.60, None, None)),        # never admitted: left out
           (3.0, None),                          # never submitted: left out
           (6.0, _req(6.00, 9.00, 9.50))]        # due after the trace start
    run = _run([], traced=[5.5, 8.5, 5.0], due=due)
    assert sorted(program_trace.stamp_gaps_s(run, "t_submit", "t_admit")) \
        == pytest.approx([0.02, 0.04, 0.10])
    assert _reader("admit_wait_p90_ms.chat").read(run) == \
        pytest.approx(88.0)                      # numpy's linear p90
    assert _reader("first_token_after_admit_p50_ms.chat").read(run) == \
        pytest.approx(200.0)
    # a program whose requests carry no stamps
    bare = _run([], due=[(0.5, SimpleNamespace(rid=1))])
    for m in METRICS[3:]:
        assert _reader(m).read(bare) is None


def test_the_readers_read_a_tiny_engine_through_the_window(recorder):
    """The harness's window over a real (tiny) engine: every reader finds
    its counters, and they agree with what the harness saw."""
    import numpy as np
    import serve
    from conftest import TINY_ENGINE, TINY_MIX, TINY_MODEL, TINY_PROGRAM
    from reference import attn_mlp
    install(FlightRecorder(capacity=1 << 14))
    conf = dict(TINY_MODEL, program=TINY_PROGRAM, engine=TINY_ENGINE)
    cfg = serve.model_config(conf)
    eng = serve.build_engine(cfg, serve.program_params(cfg, attn_mlp, conf, 7),
                             conf["engine"])
    serve.warm_up(eng)
    gen = cells.load_module(HERE / "traffic" / "requests.py")
    arrivals = gen.generate(TINY_MIX, 7, 2.0, attn_mlp.dims(conf)["vocab"])
    win = serve.run_window(eng, arrivals, 2.0, None, [0])
    run = {"window_s": win["window_s"], "served": win["served"],
           "due": win["due"], "ticks": win["ticks"], "traced": None}
    spans = program_trace.window_spans(run)
    assert spans is not None and len(spans) == len(win["ticks"])
    values = {m: _reader(m).read(run) for m in METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    # the phases lie inside the harness's step spans
    step_ms = 1e3 * np.mean([t["t1"] - t["t0"] for t in win["ticks"]])
    assert sum(values[m] for m in METRICS[:3]) < step_ms
    for s in win["served"]:
        r = s.req
        if r.t_first is not None:
            assert r.t_submit <= r.t_admit <= r.t_first
