"""The references against the program, at small sizes on the CPU.

Run with an explicit path: ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m
pytest chipbench/tests``.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import serve                                                      # noqa: E402
from conftest import TINY_ENGINE, TINY_MODEL, TINY_PROGRAM        # noqa: E402
from reference import attn_mlp, hybrid                            # noqa: E402
from reference import weights as W                                # noqa: E402


def _reference_logits(model, seed, tokens):
    dm = W.dims(model)
    key = W.seed_key(seed)
    out = W.outside(dm, key)
    x = out["embed"][jnp.asarray(tokens)][None]
    with jax.default_matmul_precision("highest"):
        for i in range(dm["layers"]):
            x = attn_mlp.block(dm, W.layer(dm, key, i), x)
        return np.asarray(attn_mlp.logits(dm, out, x))[0]


def test_paged_prefill_and_decode_logits_match_the_reference():
    """Chunked paged prefill, then paged decode through the block pool,
    against the reference's full forward over the same tokens."""
    from repro.models import (init_paged_cache, paged_decode_step,
                              paged_prefill_chunk)
    cfg = serve.model_config({"program": TINY_PROGRAM})
    seed = 2 ** 31 + 77
    params = serve.program_params(cfg, TINY_MODEL, seed)
    eng = TINY_ENGINE
    ps = eng["page_size"]
    nblk = eng["max_len"] // ps
    cache = init_paged_cache(cfg, eng["num_blocks"], ps, 1, jnp.float32)
    table = np.zeros((1, nblk), np.int32)
    table[0, :6] = [5, 9, 2, 30, 11, 7]          # scattered physical blocks
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY_MODEL["vocab_size"], 70).astype(np.int32)
    got = []
    start = 0
    for c in (32, 32, 4):                        # the scheduler's chunking
        lg, cache = paged_prefill_chunk(params, cfg,
                                        jnp.asarray(toks[None, start:start + c]),
                                        cache, jnp.int32(start),
                                        jnp.asarray(table), jnp.int32(0))
        start += c
    got.append(np.asarray(lg)[0])
    for t in range(68, 70):                      # two decode steps
        lg, cache = paged_decode_step(params, cfg, jnp.asarray(toks[None, t:t + 1]),
                                      cache, jnp.asarray([t], jnp.int32),
                                      jnp.asarray(table))
        got.append(np.asarray(lg)[0])
    ref = _reference_logits(TINY_MODEL, seed, toks)
    want = ref[67:70]
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


def test_program_params_hold_the_benchmark_weights():
    cfg = serve.model_config({"program": TINY_PROGRAM})
    params = serve.program_params(cfg, TINY_MODEL, 3)
    dm = W.dims(TINY_MODEL)
    lay = W.layer(dm, W.seed_key(3), 1)
    np.testing.assert_array_equal(params["layers"]["attn"]["wq"][1], lay["wq"])
    np.testing.assert_array_equal(params["layers"]["attn"]["bv"][1], lay["bv"])
    np.testing.assert_array_equal(params["layers"]["mlp"]["wi"][1], lay["wu"])
    out = W.outside(dm, W.seed_key(3))
    np.testing.assert_array_equal(params["embed"]["out"], out["unembed"])


def test_weights_differ_by_seed_and_repeat_by_seed():
    dm = W.dims(TINY_MODEL)
    a = W.layer(dm, W.seed_key(2 ** 31 + 5), 0)["wq"]
    b = W.layer(dm, W.seed_key(2 ** 31 + 5), 0)["wq"]
    c = W.layer(dm, W.seed_key(2 ** 31 + 6), 0)["wq"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every weight is exact in bfloat16, so the served copy equals it
    np.testing.assert_array_equal(a, a.astype(jnp.bfloat16).astype(jnp.float32))


def test_hybrid_reference_matches_the_program_block():
    from repro.configs import get_smoke_config
    from repro.models import init_model
    from repro.models.transformer import block_apply
    cfg = dataclasses.replace(get_smoke_config("hymba-1.5b"),
                              dtype="float32")
    params, _ = init_model(jax.random.PRNGKey(4), cfg)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    # the program's init leaves the decay bias at a constant: vary it
    lp["ssm"]["a_bias"] = jnp.linspace(-1.0, 3.0, cfg.ssm.heads)
    S = 48                                       # past the 32-position window
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got, _, _ = block_apply(lp, x, cfg, positions=jnp.arange(S))
        dm = {"nh": cfg.heads, "nk": cfg.kv_heads, "hd": cfg.hd,
              "eps": cfg.norm_eps, "theta": cfg.rope_theta,
              "window": cfg.window, "ssm_heads": cfg.ssm.heads,
              "ssm_head_dim": cfg.ssm.head_dim}
        want = hybrid.block(dm, lp, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_control_rounds_to_float8():
    x = jnp.asarray([[1.0, 0.3, -0.71, 1e-3]])
    q = attn_mlp.fp8(x, -1)
    assert float(jnp.abs(q - x).max()) > 0
    assert float(jnp.abs(q - x).max()) < 0.07 * float(jnp.abs(x).max())
