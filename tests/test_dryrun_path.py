"""The dry-run lowering path, in-process on a 1x1 mesh (smoke configs).

The real 512-device dry-run runs as subprocesses (scripts/dryrun_sweep.py);
this exercises the same code — abstract state, shardings, lower, compile,
collective parse — fast enough for CI."""
import dataclasses

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed import sharding as dist
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh
from repro.launch.specs import (abstract_state, cache_specs, probe_config,
                                skip_reason, state_shardings,
                                train_batch_specs)
from repro.models.config import SHAPES_BY_NAME, ShapeConfig
from repro.optim import adamw, constant
from repro.runtime.steps import build_serve_steps, build_train_step


def _small_shape(kind):
    return ShapeConfig("t", 64, 4, kind)


@pytest.mark.parametrize("arch", ["llama3_8b", "mamba2_130m",
                                  "kimi_k2_1t_a32b", "whisper_large_v3",
                                  "hymba_1p5b"])
def test_train_lowering_compiles(arch):
    cfg = get_smoke_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = dist.rules_for(cfg, mesh)
    opt = adamw(constant(1e-3))
    shape = _small_shape("train")
    with mesh, dist.use_mesh_rules(mesh, rules):
        params_sds, axes, opt_sds = abstract_state(cfg, opt)
        p_sh, o_sh, _ = state_shardings(cfg, mesh, params_sds, axes, opt_sds)
        batch_sds, batch_sh = train_batch_specs(cfg, shape, mesh)
        step = build_train_step(cfg, opt, microbatches=2)
        lowered = jax.jit(step,
                          in_shardings=(p_sh, o_sh, batch_sh, None),
                          out_shardings=(p_sh, o_sh, None)).lower(
            params_sds, opt_sds, batch_sds,
            jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    cost = hlo_analysis.cost_analysis_dict(compiled)
    assert cost.get("flops", 0) > 0
    rep = hlo_analysis.collective_report(compiled.as_text(), 1)
    assert rep.weighted_bytes >= 0


@pytest.mark.parametrize("arch", ["yi_6b", "hymba_1p5b"])
def test_serve_lowering_compiles(arch):
    cfg = get_smoke_config(arch)
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = dist.rules_for(cfg, mesh)
    with mesh, dist.use_mesh_rules(mesh, rules):
        params_sds, axes, _ = abstract_state(cfg, None)
        p_sh, _, _ = state_shardings(cfg, mesh, params_sds, axes, None)
        c_sds, c_sh = cache_specs(cfg, 4, 64, mesh)
        _, decode = build_serve_steps(cfg)
        lowered = jax.jit(decode,
                          in_shardings=(p_sh, None, c_sh, None),
                          out_shardings=(None, c_sh)).lower(
            params_sds, jax.ShapeDtypeStruct((4, 1), jnp.int32), c_sds,
            jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    assert hlo_analysis.cost_analysis_dict(compiled).get("flops", 0) > 0


def test_probe_config_scales_layers_only():
    cfg = get_smoke_config("whisper_large_v3")
    p = probe_config(cfg, 4)
    assert p.layers == 4 and p.encoder.layers == 4
    assert p.d_model == cfg.d_model and p.vocab == cfg.vocab


def test_skip_policy():
    long = SHAPES_BY_NAME["long_500k"]
    assert skip_reason(get_smoke_config("llama3_8b"), long)
    assert skip_reason(get_smoke_config("mamba2_130m"), long) is None
    assert skip_reason(get_smoke_config("hymba_1p5b"), long) is None
    assert skip_reason(get_smoke_config("llama3_8b"),
                       SHAPES_BY_NAME["train_4k"]) is None


def test_unrolled_forward_matches_scanned():
    """Unrolled and scanned layer stacks execute the same math; XLA fuses
    them differently so agreement is at bf16 rounding level, not bitwise."""
    import numpy as np
    from repro.models import forward, init_model
    cfg = get_smoke_config("llama3_8b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    a, _ = forward(params, cfg, tokens)
    b, _ = forward(params, cfg, tokens, unroll=True)
    af = np.asarray(a, np.float32)
    bf = np.asarray(b, np.float32)
    rel = np.abs(af - bf).max() / (np.abs(af).max() + 1e-9)
    assert rel < 0.02, rel
    # ranking-level agreement
    agree = (af.argmax(-1) == bf.argmax(-1)).mean()
    assert agree > 0.9, agree
