"""Training launcher (runs for real on whatever devices exist).

On the CPU container this trains reduced configs end-to-end with the full
production stack — mesh + sharded train_step + stateless data pipeline +
async checkpointing + restart-on-failure — the same code path the 512-chip
job would take (only the mesh and config scale change).

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --smoke \
        --steps 100 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLM, DataConfig
from repro.distributed import sharding as dist
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.specs import grad_dtype_for, state_shardings, abstract_state
from repro.models import init_model
from repro.models.config import ModelConfig
from repro.optim import make_optimizer, warmup_cosine
from repro.runtime import TrainController, build_train_step

PyTree = Any


def build_trainer(cfg: ModelConfig, mesh, *, lr: float, total_steps: int,
                  microbatches: int, seed: int
                  ) -> Tuple[PyTree, PyTree, Callable, Tuple[PyTree, PyTree]]:
    """Sharded train state and the jitted step on ``mesh``.

    Call under ``with mesh, dist.use_mesh_rules(mesh, rules)``.  The init
    runs under jit with the state's shardings as ``out_shardings``, so every
    leaf is built in place on its devices: the whole f32 state never sits
    on one device first.  Returns ``(params, opt_state, step, (p_sh,
    o_sh))``; ``step(params, opt_state, batch, i)`` donates the state."""
    opt = make_optimizer(cfg.optimizer, warmup_cosine(lr, 10, total_steps))
    params_sds, axes, opt_sds = abstract_state(cfg, opt)
    p_sh, o_sh, _ = state_shardings(cfg, mesh, params_sds, axes, opt_sds)

    def init(key):
        params, _ = init_model(key, cfg)
        return params, opt.init(params)

    params, opt_state = jax.jit(init, out_shardings=(p_sh, o_sh))(
        jax.random.PRNGKey(seed))
    step_fn = build_train_step(cfg, opt, microbatches=microbatches,
                               grad_dtype=grad_dtype_for(cfg))
    jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None, None),
                     out_shardings=(p_sh, o_sh, None),
                     donate_argnums=(0, 1))
    return params, opt_state, jitted, (p_sh, o_sh)


def batch_at(cfg: ModelConfig, ds: SyntheticLM, step: int
             ) -> Dict[str, jax.Array]:
    """The stateless batch of ``step`` (plus stub encoder frames)."""
    batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
    if cfg.encoder is not None:
        batch["enc_embeds"] = jax.random.normal(
            jax.random.PRNGKey(step),
            (ds.cfg.global_batch, cfg.encoder.seq_len, cfg.d_model),
            jnp.float32)
    return batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", type=str, default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_host_mesh()
    rules = dist.rules_for(cfg, mesh)

    with mesh, dist.use_mesh_rules(mesh, rules):
        params, opt_state, jitted, (p_sh, o_sh) = build_trainer(
            cfg, mesh, lr=args.lr, total_steps=args.steps,
            microbatches=args.microbatches, seed=args.seed)
        ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                                    global_batch=args.global_batch,
                                    seed=args.seed))
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)

        def run_step(state, step):
            params, opt_state = state
            params, opt_state, metrics = jitted(
                params, opt_state, batch_at(cfg, ds, step),
                jnp.asarray(step, jnp.int32))
            return (params, opt_state), {k: float(v)
                                         for k, v in metrics.items()}

        # resume if a checkpoint exists
        start = 0
        restored_step, restored = ckpt.restore_latest((params, opt_state))
        if restored is not None:
            (params, opt_state) = jax.device_put(restored, (p_sh, o_sh))
            start = restored_step
            print(f"resumed from step {start}")

        ctl = TrainController(run_step, ckpt, ckpt_every=args.ckpt_every)
        t0 = time.time()
        (params, opt_state), hist = ctl.run(
            (params, opt_state), start_step=start, num_steps=args.steps)
        dt = time.time() - t0

    for h in hist[::max(1, len(hist) // (args.steps // args.log_every or 1))]:
        print(f"step {h['step']:5d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.3f}  {h['step_time_s']*1e3:.0f}ms")
    toks = args.steps * args.global_batch * args.seq_len
    print(f"done: {len(hist)} steps, {toks/dt:.0f} tok/s, "
          f"final loss {hist[-1]['loss']:.4f}")


if __name__ == "__main__":
    main()
