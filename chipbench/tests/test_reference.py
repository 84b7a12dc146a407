"""The references against the program, at small sizes on the CPU.

Run with an explicit path: ``PYTHONPATH=src JAX_PLATFORMS=cpu python -m
pytest chipbench/tests``.
"""
from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import serve                                                      # noqa: E402
from conftest import (TINY_ENGINE, TINY_HYBRID, TINY_HYBRID_PROGRAM,  # noqa: E402
                      TINY_MODEL, TINY_PROGRAM)
from reference import attn_mlp, hybrid                            # noqa: E402
from reference import weights as W                                # noqa: E402


def _reference_logits(ref, model, seed, tokens):
    dm = ref.dims(model)
    key = W.seed_key(seed)
    out = ref.outside(dm, key)
    x = out["embed"][jnp.asarray(tokens)][None]
    with jax.default_matmul_precision("highest"):
        for i in range(dm["layers"]):
            x = ref.block(dm, ref.layer(dm, key, i), x)
        return np.asarray(ref.logits(dm, out, x))[0]


def _paged_logits(ref, model, program, seed, toks, chunks, table_blocks):
    """Chunked paged prefill of ``toks[:sum(chunks)]``, then one paged
    decode step a token through the rest: the logits of the prefill's last
    token and of each decoded one."""
    from repro.models import (init_paged_cache, paged_decode_step,
                              paged_prefill_chunk)
    cfg = serve.model_config({"program": program})
    params = serve.program_params(cfg, ref, model, seed)
    eng = TINY_ENGINE
    ps = eng["page_size"]
    nblk = eng["max_len"] // ps
    cache = init_paged_cache(cfg, eng["num_blocks"], ps, 1, jnp.float32)
    table = np.zeros((1, nblk), np.int32)
    table[0, :len(table_blocks)] = table_blocks     # scattered blocks
    got = []
    start = 0
    for c in chunks:                             # the scheduler's chunking
        lg, cache = paged_prefill_chunk(params, cfg,
                                        jnp.asarray(toks[None, start:start + c]),
                                        cache, jnp.int32(start),
                                        jnp.asarray(table), jnp.int32(0))
        start += c
    got.append(np.asarray(lg)[0])
    for t in range(start, len(toks)):            # decode steps
        lg, cache = paged_decode_step(params, cfg, jnp.asarray(toks[None, t:t + 1]),
                                      cache, jnp.asarray([t], jnp.int32),
                                      jnp.asarray(table))
        got.append(np.asarray(lg)[0])
    return np.stack(got)


def test_paged_prefill_and_decode_logits_match_the_reference():
    """Chunked paged prefill, then paged decode through the block pool,
    against the reference's full forward over the same tokens."""
    seed = 2 ** 31 + 77
    rng = np.random.default_rng(0)
    toks = rng.integers(0, TINY_MODEL["vocab_size"], 70).astype(np.int32)
    got = _paged_logits(attn_mlp, TINY_MODEL, TINY_PROGRAM, seed, toks,
                        (32, 32, 4), [5, 9, 2, 30, 11, 7])
    ref = _reference_logits(attn_mlp, TINY_MODEL, seed, toks)
    np.testing.assert_allclose(got, ref[67:70], atol=2e-4, rtol=2e-4)


def test_hybrid_paged_prefill_and_decode_match_the_reference():
    """The hybrid block through the same paged path (windowed attention
    over the pool, the SSD state carried across chunks and decode steps),
    past the 32-position window, against the hybrid reference."""
    seed = 2 ** 31 + 78
    rng = np.random.default_rng(1)
    toks = rng.integers(0, TINY_HYBRID["vocab_size"], 80).astype(np.int32)
    got = _paged_logits(hybrid, TINY_HYBRID, TINY_HYBRID_PROGRAM, seed, toks,
                        (32, 32, 8), [5, 9, 2, 30, 11, 7])
    ref = _reference_logits(hybrid, TINY_HYBRID, seed, toks)
    np.testing.assert_allclose(got, ref[71:80], atol=2e-4, rtol=2e-4)


def test_program_params_hold_the_benchmark_weights():
    cfg = serve.model_config({"program": TINY_PROGRAM})
    params = serve.program_params(cfg, attn_mlp, TINY_MODEL, 3)
    dm = attn_mlp.dims(TINY_MODEL)
    lay = attn_mlp.layer(dm, W.seed_key(3), 1)
    np.testing.assert_array_equal(params["layers"]["attn"]["wq"][1], lay["wq"])
    np.testing.assert_array_equal(params["layers"]["attn"]["bv"][1], lay["bv"])
    np.testing.assert_array_equal(params["layers"]["mlp"]["wi"][1], lay["wu"])
    out = W.outside(dm, W.seed_key(3))
    np.testing.assert_array_equal(params["embed"]["out"], out["unembed"])


def test_hybrid_program_params_hold_the_recipe():
    cfg = serve.model_config({"program": TINY_HYBRID_PROGRAM})
    params = serve.program_params(cfg, hybrid, TINY_HYBRID, 4)
    dm = hybrid.dims(TINY_HYBRID)
    lay = hybrid.layer(dm, W.seed_key(4), 1)
    np.testing.assert_array_equal(params["layers"]["ssm"]["wx"][1], lay["sx"])
    np.testing.assert_array_equal(params["layers"]["ssm"]["a_bias"][1],
                                  lay["s_bias"])
    np.testing.assert_array_equal(params["layers"]["lns"]["scale"][1],
                                  lay["lns"])
    np.testing.assert_array_equal(params["layers"]["attn"]["wq"][1], lay["wq"])
    # the attention + SwiGLU tensors are attn_mlp's recipe, the SSD's drawn
    # apart from them
    same = attn_mlp.layer(dm, W.seed_key(4), 1)
    assert all(np.array_equal(lay[k], v) for k, v in same.items())
    drawn = [np.asarray(v).ravel()[:8] for v in lay.values() if v.ndim == 2]
    assert len({a.tobytes() for a in drawn}) == len(drawn)


def test_weights_differ_by_seed_and_repeat_by_seed():
    dm = attn_mlp.dims(TINY_MODEL)
    a = attn_mlp.layer(dm, W.seed_key(2 ** 31 + 5), 0)["wq"]
    b = attn_mlp.layer(dm, W.seed_key(2 ** 31 + 5), 0)["wq"]
    c = attn_mlp.layer(dm, W.seed_key(2 ** 31 + 6), 0)["wq"]
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every weight is exact in bfloat16, so the served copy equals it
    np.testing.assert_array_equal(a, a.astype(jnp.bfloat16).astype(jnp.float32))


def test_hybrid_reference_matches_the_program_block():
    from repro.models.transformer import block_apply
    cfg = serve.model_config({"program": TINY_HYBRID_PROGRAM})
    dm = hybrid.dims(TINY_HYBRID)
    p = hybrid.layer(dm, W.seed_key(2 ** 31 + 4), 0)
    S = 48                                       # past the 32-position window
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got, _, _ = block_apply(hybrid.program_layer(dm, p), x, cfg,
                                positions=jnp.arange(S))
        want = hybrid.block(dm, p, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_hybrid_train_check_matches_the_program_trainer():
    """``train_check`` against the program's own trainer
    (``launch/train.py:build_trainer``) at a tiny size in float32: the loss
    of each of three steps, each leaf of the first gradient (AdamW's first
    moment after one step over ``1 - b1``) and each leaf's change."""
    from conftest import TINY_JOB, TINY_TRAIN
    import train as T
    from repro.data import DataConfig, SyntheticLM
    from repro.distributed import sharding as dist
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import build_trainer
    cfg = serve.model_config({"program": TINY_HYBRID_PROGRAM})
    seed, job = 2 ** 31 + 9, TINY_JOB
    ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=job["seq_len"],
                                global_batch=job["global_batch"], seed=seed))
    mesh = make_host_mesh()
    with mesh, dist.use_mesh_rules(mesh, dist.rules_for(cfg, mesh)):
        _, opt, step, (p_sh, _) = build_trainer(
            cfg, mesh, lr=job["schedule"]["lr"],
            total_steps=job["schedule"]["total_steps"], microbatches=2,
            seed=0)
        params = serve.program_params(cfg, hybrid, TINY_HYBRID, seed, p_sh)
        losses = []
        for i in range(3):
            params, opt, m = step(params, opt, ds.batch_at(i),
                                  jnp.asarray(i, jnp.int32))
            losses.append(float(m["loss"]))
            if i == 0:
                first = {k: v / 0.1 for k, v in T._norms(opt["m"]).items()}
        start = serve.program_params(cfg, hybrid, TINY_HYBRID, seed, p_sh)
        change = T._norms(jax.tree.map(jnp.subtract, params, start))
    want = hybrid.train_check(TINY_HYBRID, seed,
                              [ds.batch_at(i) for i in range(3)],
                              dict(TINY_TRAIN["optimizer"],
                                   **TINY_TRAIN["objective"],
                                   **job["schedule"]))
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    assert set(first) == set(want["grad_norms"]) == set(change)
    for k in first:
        np.testing.assert_allclose(first[k], want["grad_norms"][k],
                                   rtol=1e-4, err_msg=k)
        np.testing.assert_allclose(change[k], want["change_norms"][k],
                                   rtol=1e-4, err_msg=k)


def test_control_rounds_to_float8():
    x = jnp.asarray([[1.0, 0.3, -0.71, 1e-3]])
    q = attn_mlp.fp8(x, -1)
    assert float(jnp.abs(q - x).max()) > 0
    assert float(jnp.abs(q - x).max()) < 0.07 * float(jnp.abs(x).max())
    # its gradient passes straight through, unrounded
    g = jax.grad(lambda x: jnp.sum(attn_mlp.fp8(x, -1) * 1e-5))(x)
    np.testing.assert_array_equal(g, np.full(x.shape, 1e-5, np.float32))
