"""Every cell is found by name; a new cell is new files only; the harness's
whole run on the CPU, sound and with the timed path broken underneath."""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell as cells                                              # noqa: E402
from conftest import TINY_LIMIT, run_tiny                         # noqa: E402

BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", cells.all_cells())
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_is_found_by_name(name, trace):
    c = cells.load(name, trace)
    assert c.config["engine"]["max_len"] > 0
    assert c.traffic["generator"] == "requests"
    assert c.metrics, "every cell reports metrics in both kinds of run"
    for m, reader in c.readers.items():
        assert callable(reader.read), m
    if not trace:
        assert "setup_s" in c.metrics and len(c.metrics) >= 2


def test_per_layer_metrics_move_an_end_to_end_metric_of_their_cells():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert "workloads" not in moved or w in moved["workloads"], \
                (m["name"], w)


def test_benchmark_file_keeps_the_contract_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        conf = json.loads((HERE.parent / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_config_files_agree_with_the_program_registry():
    """The published keys the reference reads and the program's config
    (registry entry plus overrides) describe the same model."""
    import serve
    for c in BENCH["configs"]:
        conf = json.loads((HERE.parent / c["file"]).read_text())
        cfg = serve.model_config(conf)
        assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.d_ff, cfg.vocab,
                cfg.layers) == (
            conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["intermediate_size"],
            conf["vocab_size"], conf["num_hidden_layers"])
        assert cfg.rope_theta == conf["rope_theta"]
        assert cfg.norm_eps == conf["rms_norm_eps"]
        assert cfg.qkv_bias == conf.get("qkv_bias", False)
        assert cfg.param_dtype == conf["torch_dtype"]


ARGS = ["--workload", "tiny.mix", "--seed", str(2 ** 31 + 4242),
        "--seconds", "3", "--trace", "0"]


def test_a_cell_added_as_files_runs_and_is_correct(tiny_root):
    res, err = run_tiny(tiny_root, ARGS)
    assert res["correct"] is True, err
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert res["attempted"] == 24 and res["failed"] == 0
    assert err.rstrip().splitlines()[-2].startswith("check max_logit_gap")


def test_the_control_fails_the_limit(tiny_root):
    """The reference in float8, put in the program's place, reads a widest
    gap above the cell's limit and the harness says not correct; the
    float32 program reads about 0."""
    res, err = run_tiny(tiny_root, ARGS[:-1] + ["0", "--control", "1"])
    info = json.loads(err.split("chipbench: ", 1)[1].splitlines()[0])
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT
    assert res["checks"]["max_logit_gap"]["value"] == \
        info["control_max_logit_gap"]
    assert info["program_max_logit_gap"] < TINY_LIMIT / 10


def _broken(kind):
    from repro.runtime import serving
    real = serving.engine_steps

    def steps(cfg):
        prefill, decode = real(cfg)
        if kind == "state":
            def dec(params, last_tok, cache, *rest):
                nxt, lt, _ = decode(params, last_tok, cache, *rest)
                return nxt, lt, cache          # KV never written
            return prefill, dec
        if kind == "token":
            def dec(params, last_tok, cache, index, tables, mask):
                nxt, _, cache = decode(params, last_tok, cache, index,
                                       tables, mask)
                nxt = (nxt + 1) % cfg.vocab    # altered where produced
                return nxt, jnp.where(mask[:, None], nxt, last_tok), cache
            return prefill, dec
        if kind == "half":
            def dec(params, last_tok, cache, index, tables, mask):
                nxt, _, cache = decode(params, last_tok, cache, index,
                                       tables, mask)
                rows = jnp.arange(nxt.shape[0])[:, None]
                nxt = jnp.where(rows < nxt.shape[0] // 2, nxt, 0)  # left out
                return nxt, jnp.where(mask[:, None], nxt, last_tok), cache
            return prefill, dec
        raise ValueError(kind)
    return steps


@pytest.mark.parametrize("kind", ["state", "token", "half"])
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, kind):
    from repro.runtime import serving
    monkeypatch.setattr(serving, "engine_steps", _broken(kind))
    res, _ = run_tiny(tiny_root, ARGS)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > TINY_LIMIT
