"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (``tests/data/trace_*.json.gz``)."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import devtrace                                                   # noqa: E402

MS = 1e6   # nanoseconds


def _trace():
    host = [["chipbench.window", 0, 100 * MS],
            ["chipbench.step", 10 * MS, 30 * MS],
            ["chipbench.wait", 40 * MS, 20 * MS],
            ["chipbench.step", 60 * MS, 30 * MS]]
    ops = [["fusion.1", 12 * MS, 10 * MS], ["fusion.2", 20 * MS, 15 * MS],
           ["fusion.1", 62 * MS, 20 * MS]]
    mods = [["jit_decode(1)", 12 * MS, 23 * MS],
            ["jit_prefill(2)", 62 * MS, 20 * MS]]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": mods}]}]}


def test_busy_idle_and_attribution_by_hand():
    red = devtrace.reduce(_trace())
    assert red["window_s"] == pytest.approx(0.100)
    # ops cover 12-35 and 62-82 ms: 43 ms busy
    assert red["busy_s"] == pytest.approx(0.043)
    # step 1 (10-40) is busy 23 ms, step 2 (60-90) 20 ms
    assert red["step_idle_s"] == pytest.approx([0.007, 0.010])
    assert devtrace.module_seconds(red, "decode") == pytest.approx([0.023])
    assert devtrace.module_seconds(red, "prefill") == pytest.approx([0.020])
    gaps = dict(red["breakdown"]["idle_gaps"])
    # 0-12: no span; 35-62: mid 48.5 in the wait; 82-100: mid 91, no span
    assert gaps["chipbench.wait (1 gaps)"] == pytest.approx(0.027)
    assert gaps["host (no span) (2 gaps)"] == pytest.approx(0.030)
    assert dict(red["breakdown"]["device_ops"])["fusion.1"] == \
        pytest.approx(0.030)


def test_no_window_or_no_device_reads_nothing():
    tr = _trace()
    tr["planes"][1]["name"] = "/device:CPU:0"
    assert devtrace.reduce(tr) is None


def _two_chips():
    """Two devices: on the first an all-gather half hidden behind a fusion
    and an exposed reduce-scatter; on the second one exposed all-reduce."""
    tr = _trace()
    ops0 = [["fusion.1", 12 * MS, 10 * MS],
            ["%all-gather-start.3 = bf16[] all-gather-start()", 16 * MS,
             12 * MS],
            ["%reduce-scatter.2 = f32[] reduce-scatter()", 40 * MS, 5 * MS],
            ["%while.1 = () while()", 0, 100 * MS]]
    ops1 = [["%all-reduce.7 = f32[] all-reduce()", 50 * MS, 4 * MS],
            ["fusion.9", 60 * MS, 10 * MS]]
    tr["planes"][1:] = [
        {"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Ops",
                                                "events": ops}]}
        for i, ops in enumerate((ops0, ops1))]
    return tr


def test_collectives_and_their_exposed_part_by_hand():
    red = devtrace.reduce(_two_chips())
    # device 0: collectives 16-28 and 40-45 (17 ms), the fusion hides
    # 16-22 and the loop that holds every op hides nothing: 11 ms exposed;
    # device 1: 4 ms, all exposed
    assert red["collective_s"] == pytest.approx((0.017 + 0.004) / 2)
    assert red["collective_exposed_s"] == pytest.approx((0.011 + 0.004) / 2)
    assert devtrace.reduce(_trace())["collective_s"] is None


RECORDED = sorted((HERE / "tests" / "data").glob("trace_*.json.gz"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace(path):
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    want = json.loads(path.with_suffix("").with_suffix(".expected.json")
                      .read_text())
    red = devtrace.reduce(tr)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]
    for part, n in want["calls"].items():
        assert len(devtrace.module_seconds(red, part)) == n
    steps = [e for e in devtrace.host_events(tr, ("chipbench.step",))]
    assert len(red["step_idle_s"]) == want["steps"] <= len(steps)
    # busy time again, counted on a grid of microseconds
    w = devtrace.host_events(tr, ("chipbench.window",))[0]
    grid = np.zeros(int(w[2] // 1e3) + 1, bool)
    for plane in devtrace.device_planes(tr):
        for line in plane["lines"]:
            if line["name"] == "XLA Ops":
                for _, s, d in line["events"]:
                    a, b = max(s, w[1]), min(s + d, w[1] + w[2])
                    if b > a:
                        grid[int((a - w[1]) // 1e3):int((b - w[1]) // 1e3)] = True
    assert red["busy_s"] == pytest.approx(grid.sum() * 1e-6, abs=2e-4)
