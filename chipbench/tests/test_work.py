"""The work count against hand counts, and the traffic generator."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import cell as cells                                              # noqa: E402
import work                                                       # noqa: E402
from reference import weights as W                                # noqa: E402

TINY = {"d": 4, "nh": 2, "nk": 1, "hd": 2, "f": 8, "vocab": 10,
        "layers": 1, "bias": False}


def test_decode_row_by_hand():
    # matmul weights 16 + 16 + 16 + 96 = 144; weight bytes
    # 2 * (144 + 2 * 4 norms + 4 final norm + 4 * 10 head) = 392
    assert work.layer_matmul_params(TINY) == 144
    assert work.weight_bytes(TINY) == 392
    assert work.kv_bytes_per_position(TINY) == 8
    # one token at position 3: head 80, layers 2 * 144, attention over 4
    # keys 4 * nh * hd * 4 = 64; bytes: weights, 4 positions of KV, the
    # embedding row
    assert work.step(TINY, [(3, 1)], 1) == (432.0, 432.0)


def test_prefill_chunk_by_hand():
    # 4 tokens from 0: causal pairs 1 + 2 + 3 + 4 = 10
    assert work.step(TINY, [(0, 4)], 1) == (80 + 2 * 144 * 4 + 16 * 10,
                                            392 + 8 * 4 + 2 * 4 * 4)


@pytest.mark.parametrize("name,kv,weights_gb", [
    ("qwen1.5-4b", 409_600, 7.12), ("yi-6b", 65_536, 11.6)])
def test_published_sizes(name, kv, weights_gb):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    dm = W.dims(conf)
    assert work.kv_bytes_per_position(dm) == kv
    assert work.weight_bytes(dm) / 1e9 == pytest.approx(weights_gb, rel=0.01)


def test_roofline_takes_the_larger_bound():
    peak = {"flops": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds(50.0, 20.0, peak) == 2.0
    assert work.least_seconds(500.0, 20.0, peak) == 5.0


def _mix(name):
    c = cells.load(name, False)
    return c.traffic, c.generator


@pytest.mark.parametrize("name", cells.all_cells())
def test_every_seed_gets_the_same_work_in_another_order(name):
    mix, gen = _mix(name)
    a = gen.generate(mix, 2 ** 31 + 3, 40, 1000)
    b = gen.generate(mix, 17, 40, 1000)
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new for x in a) == sorted(x.max_new for x in b)
    # the same gaps, less the one after the last arrival
    gaps = lambda r: set(np.round(np.diff([x.due for x in r]), 9).tolist())
    assert len(gaps(a) ^ gaps(b)) <= 2
    # an open loop's order follows the seed; a backlog's never does
    backlog = mix["arrivals"]["process"] == "backlog"
    assert ([len(x.prompt) for x in a] == [len(x.prompt) for x in b]) \
        == backlog
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])
    again = gen.generate(mix, 17, 40, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(b, again))
    p, o = mix["prompt"], mix["output"]
    assert all(p["min"] <= len(x.prompt) <= p["max"] for x in a)
    assert all(o["min"] <= x.max_new <= o["max"] for x in a)


def test_open_loop_rate_is_exact_and_rounds_are_balanced():
    mix, gen = _mix("yi-6b.chat")
    r = gen.generate(mix, 5, 40, 1000)
    rate = mix["arrivals"]["rate"]
    assert len(r) == round(rate * 40)
    assert max(x.due for x in r) < 40
    # each round of `strata` requests holds one length of every stratum
    s = mix["strata"]
    first, second = r[:s], r[s:2 * s]
    assert abs(np.mean([len(x.prompt) for x in first])
               - np.mean([len(x.prompt) for x in second])) \
        < 0.25 * np.mean([len(x.prompt) for x in r])


def test_backlog_is_all_due_at_the_open():
    mix, gen = _mix("qwen1.5-4b.longctx")
    r = gen.generate(mix, 9, 40, 1000)
    assert len(r) == mix["arrivals"]["count"]
    assert all(x.due == 0.0 for x in r)


def test_readers_by_hand():
    import readers
    from types import SimpleNamespace as S
    reqs = [S(due=0.0, admitted=0.5, times=[1.0, 1.5, 2.5]),
            S(due=1.0, admitted=None, times=[]),
            S(due=4.0, admitted=9.0, times=[9.5])]
    run = {"window_s": 10.0, "due": reqs, "served": reqs, "traced": None}
    assert readers.ttft_s(run) == [1.0, 9.0, 5.5]
    assert readers.itl_s(run) == [0.5, 1.0]
    assert readers.queue_wait_s(run) == [0.5, 9.0, 5.0]
    # a traced run leaves out requests due after the trace was started
    run["traced"] = [3.5, 6.5, 3.0]
    assert readers.queue_wait_s(run) == [0.5, 9.0]
    assert readers.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert readers.percentile([], 90) is None
