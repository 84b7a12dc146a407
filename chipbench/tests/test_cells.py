"""Every cell is found by name; a new cell is new files only; the harness's
whole run on the CPU, sound and with the timed path broken underneath."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell as cells                                              # noqa: E402
from conftest import (TINY_ENGINE, TINY_HYBRID,                   # noqa: E402
                      TINY_HYBRID_PROGRAM, TINY_LIMIT, TINY_MIX, TINY_MODEL,
                      TINY_PROGRAM, add_cell, make_root, run_tiny)

BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", cells.all_cells())
@pytest.mark.parametrize("trace", [False, True])
def test_every_cell_is_found_by_name(name, trace):
    c = cells.load(name, trace)
    runner, ref = cells.modules(c)
    assert runner.__name__ == c.runner and c.runner in ("serve", "train")
    assert ref.__name__ == f"reference.{c.config['reference']}"
    if c.runner == "serve":
        assert c.config["engine"]["max_len"] > 0
        assert c.traffic["generator"] == "requests"
    else:
        assert callable(c.generator.dataset)
        assert c.traffic["seq_len"] > 0 and c.traffic["global_batch"] > 0
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
    """Every configuration file's published keys, as the reference reads
    them, and the program's config (registry entry plus overrides)
    describe the same model."""
    import serve
    for path in sorted((HERE / "configs").glob("*.json")):
        conf = json.loads(path.read_text())
        ref = __import__(f"reference.{conf['reference']}",
                         fromlist=["dims"])
        cfg, dm = serve.model_config(conf), ref.dims(conf)
        assert (cfg.d_model, cfg.heads, cfg.kv_heads, cfg.hd, cfg.d_ff,
                cfg.vocab, cfg.layers) == (
            conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], dm["hd"],
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"])
        assert cfg.rope_theta == conf["rope_theta"]
        assert cfg.norm_eps == conf["rms_norm_eps"]
        assert cfg.qkv_bias == conf.get("qkv_bias", False)
        assert cfg.dtype == conf["torch_dtype"]
        if "engine" in conf:                      # served as published
            assert cfg.param_dtype == conf["torch_dtype"]
        if "window" in dm:
            assert (cfg.window, cfg.ssm.heads, cfg.ssm.head_dim,
                    cfg.ssm.state) == (dm["window"], dm["ssm_heads"],
                                       dm["ssm_head_dim"], dm["ssm_state"])


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


def test_a_window_over_ticks_in_flight_counts_them_all():
    """With ``async_depth`` 2 the engine keeps its newest tick on the
    device across ``step``.  Once the window's time is up the harness sends
    nothing more, commits what is in flight and reads the clock after: every
    token committed is counted, and stamped inside the window."""
    import serve
    from reference import attn_mlp
    conf = dict(TINY_MODEL, program=TINY_PROGRAM,
                engine=dict(TINY_ENGINE, async_depth=2))
    cfg = serve.model_config(conf)
    eng = serve.build_engine(cfg, serve.program_params(cfg, attn_mlp, conf,
                                                       7), conf["engine"])
    serve.warm_up(eng)
    gen = cells.load_module(HERE / "traffic" / "requests.py")
    arrivals = gen.generate(TINY_MIX, 7, 1.0, attn_mlp.dims(conf)["vocab"])
    steps = []
    step = eng.step

    def counted():
        out = step()
        steps.append(len(eng._inflight))
        return out

    eng.step = counted
    win = serve.run_window(eng, arrivals, 1.0, None, [0])
    assert steps and max(steps) == 1        # a tick was in flight
    assert not eng._inflight                # and was committed at the close
    for s in win["served"]:
        assert len(s.times) == len(s.req.out)
        assert all(t <= win["window_s"] for t in s.times)
    assert sum(len(s.times) for s in win["served"]) > 0


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


@pytest.fixture
def hybrid_root(tmp_path):
    """A second architecture, added as files: the hybrid block served."""
    root = make_root(tmp_path)
    add_cell(root, "tinyhy.mix",
             dict(TINY_HYBRID, program=TINY_HYBRID_PROGRAM,
                  engine=TINY_ENGINE,
                  check={"sequences": 8, "max_logit_gap": TINY_LIMIT}),
             TINY_MIX, 1, ["ttft_p50_ms", "itl_p95_ms"])
    return root


@pytest.mark.parametrize("control", [0, 1])
def test_a_hybrid_cell_added_as_files_runs_and_is_correct(hybrid_root,
                                                          control):
    """Correct, and under the float8 control not correct."""
    res, err = run_tiny(hybrid_root, ["--workload", "tinyhy.mix", "--seed",
                                      str(2 ** 31 + 4243), "--seconds", "3",
                                      "--trace", "0", "--control",
                                      str(control)])
    gap = res["checks"]["max_logit_gap"]["value"]
    info = json.loads(err.split("chipbench: ", 1)[1].splitlines()[0])
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p95_ms"}
    assert info["program_max_logit_gap"] < TINY_LIMIT / 10
    assert res["correct"] is (not control)
    assert (gap > TINY_LIMIT) is bool(control)


#: drives a tiny training cell, added as files, through run.py's main on
#: four CPU devices, once sound, once under the control and once with each
#: fault planted in the program's train step; one JSON line each
TRAIN_DRIVER = r"""
import dataclasses, json, sys
from pathlib import Path
sys.path.insert(0, sys.argv[1])
import jax
from jax.sharding import PartitionSpec as P
from conftest import (TINY_HYBRID, TINY_HYBRID_PROGRAM, TINY_JOB, TINY_TRAIN,
                      TINY_TRAIN_LIMITS, TRAIN_TOK_S, add_cell, make_root,
                      run_tiny)
import repro.launch.train as T
from repro.distributed import sharding as dist

root = make_root(Path(sys.argv[2]))
add_cell(root, "tinyhy.job",
         dict(TINY_HYBRID, program=TINY_HYBRID_PROGRAM, **TINY_TRAIN,
              check=TINY_TRAIN_LIMITS),
         TINY_JOB, 4, [], [TRAIN_TOK_S])
real = T.build_train_step


def planted(kind):
    def build(cfg, opt, **kw):
        fn = real(cfg, opt, **kw)
        if kind == "state":             # the step returns its state unchanged
            def f(p, o, b, i):
                return p, o, fn(p, o, b, i)[2]
        elif kind == "half":            # half the batch, the mean over it
            def f(p, o, b, i):
                return fn(p, o, {k: v[:v.shape[0] // 2]
                                 for k, v in b.items()}, i)
        elif kind == "exchange":        # each chip's own rows, no reduction
            def local(p, o, b, i):
                with dist.use_mesh_rules(None, None):
                    return fn(p, o, b, i)
            f = jax.shard_map(local, mesh=dist.current_mesh(),
                              in_specs=(P(), P(), P("data"), P()),
                              out_specs=(P(), P(), P()), check_vma=False)
        elif kind == "answer":          # a leaf's gradient altered (doubled)
            def update(g, *rest):       # where the step hands it over
                g = dict(g, ln_f={"scale": 2 * g["ln_f"]["scale"]})
                return opt.update(g, *rest)
            f = real(cfg, dataclasses.replace(opt, update=update), **kw)
        return f
    return build


for kind in sys.argv[3].split(","):
    T.build_train_step = planted(kind) if kind not in ("sound", "control")         else real
    res, err = run_tiny(root, ["--workload", "tinyhy.job", "--seed",
                               str(2 ** 31 + 4244), "--seconds", "2",
                               "--trace", "0", "--control",
                               "1" if kind == "control" else "0"])
    print(json.dumps({"kind": kind, "result": res}), flush=True)
"""
KINDS = ["sound", "control", "state", "half", "exchange", "answer"]


@pytest.fixture(scope="module")
def training_runs(tmp_path_factory):
    """One process with four CPU devices runs every kind in turn."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(HERE.parent / "src"))
    out = subprocess.run(
        [sys.executable, "-c", TRAIN_DRIVER, str(HERE / "tests"),
         str(tmp_path_factory.mktemp("train")), ",".join(KINDS)],
        env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-4000:]
    runs = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith('{"kind"')]
    return {r["kind"]: r["result"] for r in runs}


@pytest.mark.parametrize("kind", KINDS)
def test_a_training_cell_added_as_files_is_correct_only_when_sound(
        training_runs, kind):
    """A second runner, added as files: a tiny training cell on a mesh of
    four devices (FSDP), correct; the float8 control and every fault
    planted in the timed step (state unchanged, half the batch, the
    exchange between devices left out, a leaf's gradient altered) not."""
    res = training_runs[kind]
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"setup_s", "train_tok_s"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["correct"] is (kind == "sound"), res["checks"]
    if kind != "sound":
        assert any(c["value"] > c["limit"] for c in res["checks"].values())
