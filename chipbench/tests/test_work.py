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
from reference import attn_mlp, hybrid                            # noqa: E402

TINY = {"d": 4, "nh": 2, "nk": 1, "hd": 2, "f": 8, "vocab": 10,
        "layers": 1, "bias": False}


def test_decode_row_by_hand():
    # matmul weights 16 + 16 + 16 + 96 = 144; weight bytes
    # 2 * (144 + 2 * 4 norms + 4 final norm + 4 * 10 head) = 392
    assert attn_mlp.layer_matmul_params(TINY) == 144
    assert attn_mlp.weight_bytes(TINY) == 392
    assert attn_mlp.kv_bytes_per_position(TINY) == 8
    # one token at position 3: head 80, layers 2 * 144, attention over 4
    # keys 4 * nh * hd * 4 = 64; bytes: weights, 4 positions of KV, the
    # embedding row
    assert attn_mlp.step(TINY, [(3, 1)], 1) == (432.0, 432.0)


def test_prefill_chunk_by_hand():
    # 4 tokens from 0: causal pairs 1 + 2 + 3 + 4 = 10
    assert attn_mlp.step(TINY, [(0, 4)], 1) == (80 + 2 * 144 * 4 + 16 * 10,
                                            392 + 8 * 4 + 2 * 4 * 4)


@pytest.mark.parametrize("name,kv,weights_gb", [
    ("qwen1.5-4b", 409_600, 7.12), ("yi-6b", 65_536, 11.6)])
def test_published_sizes(name, kv, weights_gb):
    conf = json.loads((HERE / "configs" / f"{name}.json").read_text())
    dm = attn_mlp.dims(conf)
    assert attn_mlp.kv_bytes_per_position(dm) == kv
    assert attn_mlp.weight_bytes(dm) / 1e9 == pytest.approx(weights_gb,
                                                             rel=0.01)


HYBRID = dict(TINY, window=3, ssm_heads=2, ssm_head_dim=2, ssm_state=2)


def test_hybrid_step_by_hand():
    # SSD matmuls: wx 4*4 + wb, wc 2 * 4*2 + wa 4*2 + wo 4*4 = 56 beside
    # attn_mlp's 144; recurrence 4 * 2 * 2 * 2 = 32 a token
    assert hybrid.layer_matmul_params(HYBRID) == 200
    # one token at position 5 sees its window of 3; bytes: weights
    # 2 * (200 + 3 * 4 norms + 2 biases + 4 + 40), 2 earlier positions
    # and itself of KV (8 each), the state (2 * 2 * 2 * 4 B) read and
    # written, the embedding row
    assert hybrid.step(HYBRID, [(5, 1)], 1) == (
        80 + 2 * 200 + 16 * 3 + 32, 2 * 258 + 8 * 3 + 2 * 32 + 8)


def test_training_steps_by_hand():
    # a sequence of 5 under a window of 3: pairs 1 + 2 + 3 + 3 + 3 = 12
    assert hybrid.window_pairs(0, 5, 3) == 12
    assert hybrid.train_step(HYBRID, 2, 5) == 3 * (
        2 * (200 + 40) * 10 + 16 * 2 * 12 + 32 * 10)
    # attention over the whole causal prefix: 15 pairs
    assert attn_mlp.train_step(TINY, 2, 5) == 3 * (
        2 * (144 + 40) * 10 + 16 * 2 * 15)


def test_published_hymba_counts():
    """At the published 32 layers, 1.31 B parameters as
    ModelConfig.param_count gives them, the embedding's 51 M gathered and
    not multiplied; 5.31e14 FLOPs a step of 16 x 4096.  The cell's stage of
    8 layers: 0.405 B parameters."""
    import json as _json
    conf = _json.loads((HERE / "configs" / "hymba-1.5b.json").read_text())

    def params(dm):
        norms = dm["layers"] * (3 * dm["d"] + dm["ssm_heads"]) + dm["d"]
        return (dm["layers"] * hybrid.layer_matmul_params(dm)
                + 2 * dm["d"] * dm["vocab"] + norms)

    dm = hybrid.dims(dict(conf, num_hidden_layers=32))
    assert params(dm) == pytest.approx(1.311e9, rel=1e-3)
    assert hybrid.train_step(dm, 16, 4096) == pytest.approx(5.31e14,
                                                            rel=0.01)
    assert params(hybrid.dims(conf)) == pytest.approx(0.4046e9, rel=1e-3)


def test_roofline_takes_the_larger_bound():
    peak = {"flops": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds(50.0, 20.0, peak) == 2.0
    assert work.least_seconds(500.0, 20.0, peak) == 5.0


def _mix(name):
    c = cells.load(name, False)
    return c.traffic, c.generator


SERVING = [n for n in cells.all_cells()
           if cells.load(n, False).runner == "serve"]
#: the training mixes (no cell runs one yet)
TRAINING = sorted(p.stem for p in (HERE / "traffic").glob("*.json")
                  if json.loads(p.read_text()).get("runner") == "train")


@pytest.mark.parametrize("name", SERVING)
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


@pytest.mark.parametrize("name", TRAINING)
def test_every_training_seed_gets_batches_of_the_same_shape(name):
    """The job's batches: the same shape for every seed, tokens drawn from
    the seed, rows that all differ, and the same batch again for a step."""
    mix = json.loads((HERE / "traffic" / f"{name}.json").read_text())
    gen = cells.load_module(HERE / "traffic" / f"{mix['generator']}.py")
    data = lambda seed: gen.dataset(mix, seed, 32001)
    a = data(2 ** 31 + 3).batch_at(1)
    b = data(17).batch_at(1)
    shape = (mix["global_batch"], mix["seq_len"])
    assert a["tokens"].shape == b["tokens"].shape == shape
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert not np.array_equal(a["tokens"], b["tokens"])
    assert len({r.tobytes() for r in a["tokens"]}) == shape[0]
    np.testing.assert_array_equal(data(17).batch_at(1)["tokens"],
                                  b["tokens"])
    assert a["tokens"].max() < 32001


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 3])
def test_the_training_generator_is_the_programs_stream(seed):
    """The benchmark's copy draws what the program's ``SyntheticLM`` does
    for the same settings."""
    from repro.data import DataConfig, SyntheticLM
    gen = cells.load_module(HERE / "traffic" / "synthetic.py")
    mix = {"seq_len": 96, "global_batch": 3,
           "data": {"zipf_alpha": 1.1, "bigram_fraction": 0.7}}
    ours = gen.dataset(mix, seed, 1000)
    theirs = SyntheticLM(DataConfig(vocab=1000, seq_len=96, global_batch=3,
                                    seed=seed, **mix["data"]))
    for step in (0, 5):
        a, b = ours.batch_at(step), theirs.batch_at(step)
        for k in ("tokens", "labels"):
            assert a[k].dtype == b[k].dtype
            np.testing.assert_array_equal(a[k], b[k])


def test_training_readers_by_hand():
    import readers
    steps = [{"t0": 0.0, "t1": 2.0, "tokens": 100, "batch": 2, "seq_len": 5},
             {"t0": 2.1, "t1": 4.0, "tokens": 100, "batch": 2, "seq_len": 5},
             {"t0": 4.1, "t1": 6.5, "tokens": 100, "batch": 2, "seq_len": 5}]
    run = {"window_s": 5.0, "steps": steps, "traced": [2.05, 4.05, 2.0],
           "trace": {"window_s": 2.0, "devices": 4, "busy_s": 1.5,
                     "collective_s": 0.5, "collective_exposed_s": 0.2},
           "ref": hybrid, "dims": HYBRID, "peak": {"flops": 1e4}}
    # the third step ends after the window: two whole steps over 4 s
    assert readers.train_tok_s(run) == 50.0
    assert readers.traced_steps(run) == steps[1:2]
    assert readers.train_mfu(run) == pytest.approx(
        100 * hybrid.train_step(HYBRID, 2, 5) / (2.0 * 1e4 * 4))
    assert readers.collective_exposed_share(run) == pytest.approx(10.0)
    assert readers.idle_share(run) == pytest.approx(25.0)
    run["trace"]["collective_s"] = None       # no collective ran
    assert readers.collective_exposed_share(run) is None


def test_fault_readings_cut_the_rows_and_read_against_the_sound_run():
    """``calibrate.py --faults``: each fault is the reference over the
    rows it keeps, read against the run's own sound reference; the final
    norm's gradient doubled reads its share of the median leaf."""
    import types
    import calibrate
    import train
    calls = []

    def real(model, seed, batches, job, control, devices):
        calls.append((len(batches[0]["tokens"]), len(devices)))
        k = len(batches[0]["tokens"])
        return {"loss": [1.0 + 0.1 / k], "change_norms": {"a": 0.0},
                "grad_norms": {"a": 2.0 / k, "b": 1.0, "c": 2.0,
                               "ln_f/scale": 0.5}}

    rows = np.arange(16 * 3).reshape(16, 3)
    seen = {"seed": 5, "batches": [{"tokens": rows, "labels": rows}],
            "want": real(None, 5, [{"tokens": rows}], None, False, [0] * 4),
            "real": real}
    calls.clear()
    cell = types.SimpleNamespace(
        config={"check": {"steps": 1}, "optimizer": {"clip_norm": 1.0},
                "objective": {}},
        traffic={"schedule": {}})
    out = calibrate.fault_readings(cell, seen, ["half", "exchange"],
                                   [0, 1, 2, 3])
    assert calls == [(8, 4), (4, 4)]
    want = seen["want"]
    for kind, keep in (("half", 8), ("exchange", 4)):
        got = real(None, 5, [{"tokens": rows[:keep]}], None, False, [0])
        assert out[kind] == train.compare(got, want, {}, 1.0)[1]["readings"]
    assert out["answer"] == {"grad_norm_gap": 0.5 / 0.75}
