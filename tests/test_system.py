"""End-to-end behaviour: training descends, resumes exactly, serves, and the
paper's central claim (optimal parameters depend on input size) is visible
through the framework's own selection machinery."""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticLM
from repro.models import init_model
from repro.optim import adamw, constant, warmup_cosine
from repro.runtime import TrainController, build_train_step


def _setup(arch="llama3_8b", seed=0, lr=1e-3):
    cfg = get_smoke_config(arch)
    params, _ = init_model(jax.random.PRNGKey(seed), cfg)
    opt = adamw(warmup_cosine(lr, 5, 200))
    state = opt.init(params)
    step = jax.jit(build_train_step(cfg, opt, microbatches=2))
    ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8,
                                seed=seed))
    return cfg, params, opt, state, step, ds


def test_training_loss_decreases():
    cfg, params, opt, state, step, ds = _setup()
    losses = []
    for s in range(30):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
        params, state, m = step(params, state, batch, jnp.asarray(s))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])


@pytest.mark.slow
def test_resume_is_bit_exact(tmp_path):
    """Crash at step 12, restore at 10, replay: final loss must equal the
    uninterrupted run (stateless data + checkpointed state => exact)."""
    def build(ckpt_dir, fault):
        cfg, params, opt, state, step, ds = _setup(seed=3)

        def run_step(st, s):
            p, o = st
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(s).items()}
            p, o, m = step(p, o, batch, jnp.asarray(s))
            return (p, o), {"loss": float(m["loss"])}

        ctl = TrainController(run_step, CheckpointManager(str(ckpt_dir)),
                              ckpt_every=5, fault_hook=fault)
        return ctl, (params, state)

    ctl_ref, st0 = build(tmp_path / "ref", None)
    _, hist_ref = ctl_ref.run(st0, start_step=0, num_steps=15)

    fired = {"n": 0}

    def fault(step):
        if step == 12 and not fired["n"]:
            fired["n"] = 1
            raise RuntimeError("injected")

    ctl, st0b = build(tmp_path / "ft", fault)
    _, hist = ctl.run(st0b, start_step=0, num_steps=15)
    assert fired["n"] == 1
    final_ref = [h for h in hist_ref if h["step"] == 14][-1]["loss"]
    final_ft = [h for h in hist if h["step"] == 14][-1]["loss"]
    np.testing.assert_allclose(final_ft, final_ref, rtol=1e-6)


@pytest.mark.slow
def test_paper_claim_params_depend_on_input_size():
    """Table 1's headline: the best block parameters shift with input size.
    We assert the framework *can* express this: the offline selector returns
    size-dependent choices under a constrained machine."""
    from repro.core import MachineDescription, best_variant
    from repro.kernels.matmul import FAMILY

    tiny_vmem = MachineDescription(
        name="tiny", vmem_bytes=1 << 19, vreg_budget=512, num_cores=8,
        sublane=8, lane=128, mxu=128, hbm_bytes=1 << 30, hbm_bw=1e11,
        peak_flops_bf16=1e12, ici_bw=1e10)
    small = best_variant(FAMILY, tiny_vmem, {"M": 256, "N": 256, "K": 256})
    large = best_variant(FAMILY, tiny_vmem, {"M": 8192, "N": 8192, "K": 8192})
    # feasibility: each candidate satisfies the family's own VMEM counter
    # under its leaf's plan (cached and uncached leaves differ)
    for cand in (small, large):
        num, den = FAMILY.counter_value(cand.plan, "vmem_bytes")
        vmem = float(num.eval(cand.assignment)) / float(
            den.eval(cand.assignment) or 1)
        assert vmem <= (1 << 19), (cand.describe(), vmem)
    # size-dependence: the occupancy-driven score reshuffles the choice
    assert small.assignment != large.assignment or \
        small.leaf_index != large.leaf_index


@pytest.mark.slow
def test_quickstart_example_runs():
    import subprocess, sys
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "quickstart.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr


def test_init_params_matches_init_model_in_param_dtype():
    """The jitted init draws the same weights as init_model and stores the
    >=2-D ones in the config's param dtype (norm scales stay f32)."""
    import dataclasses
    from repro.models import init_params
    cfg = dataclasses.replace(get_smoke_config("qwen1.5-4b"),
                              param_dtype="bfloat16")
    got = init_params(jax.random.PRNGKey(3), cfg)
    want, _ = init_model(jax.random.PRNGKey(3), cfg)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        assert g.dtype == (jnp.bfloat16 if w.ndim >= 2 else jnp.float32)
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The env var wins and nothing else is set; without it the cache is
    one fixed directory in the checkout."""
    from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = enable_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert (CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
