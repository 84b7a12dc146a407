"""Tiny cells built from files alone, in a copy of the benchmark.

``tiny_root`` copies ``BENCHMARK.json`` and ``chipbench/`` into a temporary
checkout, links the program's ``src/``, and adds a configuration, a traffic
mix and a cell as new files and new entries only: what a later PR adding a
cell does.  ``add_cell`` adds more the same way: a hybrid serving cell and
a training cell, each another architecture or runner named by its files.
``run_tiny`` drives a cell through ``run.py``'s ``main`` on the CPU, with
the look for a chip replaced.
"""
from __future__ import annotations

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 5000000.0, "qkv_bias": True,
}
TINY_PROGRAM = {"model": "qwen1.5-4b", "overrides": {
    "layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2, "d_ff": 128,
    "vocab": 512, "rope_theta": 5000000.0, "norm_eps": 1e-06,
    "dtype": "float32", "param_dtype": "float32"}}
TINY_ENGINE = {"max_batch": 4, "max_len": 512, "page_size": 16,
               "prefill_chunk": 32, "num_blocks": 80, "async_depth": 1}
TINY_MIX = {"generator": "requests", "why": "tiny backlog",
            "arrivals": {"process": "backlog", "count": 24},
            "prompt": {"median": 40, "sigma": 0.5, "min": 8, "max": 120},
            "output": {"median": 12, "sigma": 0.4, "min": 4, "max": 24},
            "strata": 4}
#: widest logit gap of a float32 program at the tiny size (0 up to
#: rounding) against the float8 control's (several tenths)
TINY_LIMIT = 0.05

#: the program's hybrid block (hymba-1.5b's family) at a tiny size
TINY_HYBRID = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0, "sliding_window": 32,
    "mamba_d_state": 8, "ssd_heads": 4, "ssd_head_dim": 16,
    "reference": "hybrid"}
TINY_HYBRID_PROGRAM = {"model": "hymba-1.5b", "overrides": {
    "layers": 2, "d_model": 64, "heads": 4, "kv_heads": 2, "d_ff": 128,
    "vocab": 512, "head_dim": 16, "window": 32,
    "ssm": {"state": 8, "heads": 4, "head_dim": 16, "chunk": 16},
    "dtype": "float32", "param_dtype": "float32"}}
#: a training job at the tiny size: 8 sequences of 64, 2 microbatches
TINY_JOB = {"runner": "train", "generator": "synthetic",
            "why": "tiny training job", "seq_len": 64,
            "global_batch": 8, "data": {"zipf_alpha": 1.2,
                                        "bigram_fraction": 0.5},
            "schedule": {"lr": 0.003, "warmup_steps": 10,
                         "total_steps": 1000, "lr_floor": 0.1}}
TINY_TRAIN = {"train": {"microbatches": 2},
              "optimizer": {"name": "adamw", "b1": 0.9, "b2": 0.95,
                            "eps": 1e-08, "weight_decay": 0.1,
                            "clip_norm": 1.0},
              "objective": {"z_loss": 0.0001}}
#: the float32 program's worst leaf of the first gradient reads under 1e-6
#: at the tiny size, the float8 control's several hundredths
TINY_TRAIN_LIMITS = {"steps": 3, "grad_norm_gap": 3e-3}


def make_root(dst: Path, *, model=None, program=None, engine=None,
              limit=TINY_LIMIT) -> Path:
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    os.symlink(REPO / "src", dst / "src")
    cb = dst / "chipbench"
    conf = dict(model or TINY_MODEL, source="tests", reduced=[],
                reference="attn_mlp", program=program or TINY_PROGRAM,
                engine=engine or TINY_ENGINE,
                check={"sequences": 8, "max_logit_gap": limit})
    (cb / "configs" / "tiny.json").write_text(json.dumps(conf))
    (cb / "traffic" / "tinymix.json").write_text(json.dumps(TINY_MIX))
    peaks = json.loads((cb / "peaks.json").read_text())
    # the one edit, made only in this copy: a row for the CPU, so the
    # readers of roofline shares have peaks to read
    peaks["cpu"] = {"flops": 1e12, "bytes_per_s": 1e11, "source": "tests"}
    (cb / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "tests",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "tiny.mix", "config": "tiny",
                               "traffic": "tinymix", "chips": 1,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("ttft_p50_ms", "itl_p95_ms",
                         "queue_wait_p90_ms.chat", "decode_ms.chat",
                         "idle_share.chat"):
            m["workloads"].append("tiny.mix")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    return dst


def add_cell(root: Path, cell: str, config: dict, mix: dict, chips: int,
             metrics, new=()) -> None:
    """A configuration (``cell``'s first part), a mix (its second) and the
    cell, as new files and new entries; each of the benchmark's
    ``metrics`` is extended to the cell, and each of ``new`` (kind, entry)
    is added for the cell alone."""
    conf_name, mix_name = cell.split(".", 1)
    cb = root / "chipbench"
    (cb / "configs" / f"{conf_name}.json").write_text(json.dumps(
        dict(config, source="tests", reduced=[])))
    (cb / "traffic" / f"{mix_name}.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": conf_name, "source": "tests",
                             "file": f"chipbench/configs/{conf_name}.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": cell, "config": conf_name,
                               "traffic": mix_name, "chips": chips,
                               "why": "tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in metrics and "workloads" in m:
            m["workloads"].append(cell)
    for kind, entry in new:
        bench[kind].append(dict(entry, workloads=[cell]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


#: the training cells' end-to-end metric, which no cell of the benchmark
#: reports yet (its reader is ``metrics/train_tok_s.py``)
TRAIN_TOK_S = ("end_to_end", {"name": "train_tok_s", "unit": "tokens/s",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock"})


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def run_tiny(root: Path, argv, monkeypatch=None):
    """``main(argv)`` of the copy's run.py on the CPU; returns (result line
    as a dict, standard error)."""
    import jax
    saved = list(sys.path)
    # the copy's modules shadow the checkout's for the length of the run
    own = {p.stem for p in (REPO / "chipbench").glob("*.py")} | {"reference"}
    shadowed = {k: sys.modules.pop(k) for k in list(sys.modules)
                if k.split(".")[0] in own}
    mods = {k: v for k, v in sys.modules.items()}
    sys.path.insert(0, str(root / "chipbench"))
    try:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chipbench_run_under_test", root / "chipbench" / "run.py")
        run = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = run
        spec.loader.exec_module(run)
        jax.config.update("jax_enable_compilation_cache", False)
        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            code = run.main(argv, require=lambda n: jax.devices()[:n])
        assert code == 0, err.getvalue()
        return json.loads(out.getvalue().strip().splitlines()[-1]), \
            err.getvalue()
    finally:
        sys.path[:] = saved
        # drop the copy's own modules; what they imported from elsewhere
        # (the program, JAX) stays loaded, as it may be imported only once
        for k in list(sys.modules):
            if k not in mods and (k.split(".")[0] in own
                                  or k.startswith("chipbench_")):
                del sys.modules[k]
        sys.modules.update(shadowed)
