#!/usr/bin/env python
"""Bring-up smoke of the system on a TPU: proof that it runs, not a benchmark.

    python chip_smoke.py               # one chip: device, serve, kernels
    python chip_smoke.py --four-chips  # 2x2 host: sharded training only

One chip, in order:

* **device** — fails unless JAX's first device is a TPU;
* **serve** — Qwen1.5-4B at its published widths, weights stored in bf16,
  random weights from ``--seed``, served by the paged ``ServeEngine``
  (``max_batch=4``, ``max_len=1024``, no degradation, no monitor).  Every
  request must finish with tokens and no error, and the served greedy
  tokens must agree with the argmax of the dense ``forward`` over prompt +
  output on at least 90% of generated positions;
* **kernels** — each Pallas family's pick (``ops.select``) at one real
  shape, run compiled, against its ``kernels/ref.py`` oracle.

``--four-chips`` runs only the sharded path: a few train steps of
hymba-1.5b at published widths through ``launch/train.py``'s trainer over
every device, then the same config cut to 2 layers on the mesh and on one
device with the same batch, whose first-step loss and grad norm must agree.

The last stdout line is ``{"ok": true, "device": {...}}``; a failed phase
exits non-zero before it.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax                                                     # noqa: E402
import jax.numpy as jnp                                        # noqa: E402
import numpy as np                                             # noqa: E402

BF16_TOL = 2e-2            # max |out - ref| / max |ref| for every kernel
MIN_AGREEMENT = 0.9        # served vs dense greedy tokens


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(line: str) -> None:
    print(line, flush=True)


def peak_gb(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


# -- phases ---------------------------------------------------------------------

def device_phase(count: int):
    devices = jax.devices()
    d = devices[0]
    log(f"[device] {devices}")
    log(f"[device] platform={d.platform} kind={d.device_kind!r} "
        f"count={len(devices)}")
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    check(len(devices) >= count, f"{count} devices needed, {len(devices)} "
          f"found")
    return d


def serve_phase(cfg, *, seed: int, requests: int, prompt_lens, max_new: int,
                max_batch: int, max_len: int, check_requests: int) -> None:
    from repro.models import forward, init_params
    from repro.runtime import ServeEngine

    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(jax.random.PRNGKey(seed), cfg))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.layers} layers, d_model={cfg.d_model}, "
        f"{n_params / 1e9:.3f} B params in {cfg.param_dtype}, init "
        f"{time.perf_counter() - t0:.1f} s")
    eng = ServeEngine(cfg, params, max_batch=max_batch, max_len=max_len)
    log(f"[serve] engine: max_batch={max_batch} max_len={max_len} "
        f"page_size={eng.page_size} blocks={eng.pool.num_blocks} "
        f"machine={eng.machine.name}")

    rng = np.random.default_rng(seed)
    prompts = {}
    for _ in range(requests):
        prompt = rng.integers(0, cfg.vocab, int(rng.integers(*prompt_lens)),
                              dtype=np.int32)
        prompts[eng.submit(prompt, max_new=max_new)] = prompt

    t0 = time.perf_counter()
    done = eng.step()
    jax.block_until_ready(eng.cache)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    done += eng.run_until_drained(max_ticks=100_000)
    jax.block_until_ready(eng.cache)
    rest = time.perf_counter() - t0
    tokens = sum(len(r.out) for r in done)
    log(f"[serve] smoke timing, not a benchmark: first tick (compile + run) "
        f"{first:.2f} s; remaining {eng.sched.ticks - 1} ticks "
        f"{rest:.2f} s; {len(done)} requests, {tokens} tokens; peak device "
        f"memory {peak_gb(jax.devices()[0])}")
    check(len(done) == requests, f"{len(done)} of {requests} requests done")
    for r in done:
        check(r.error is None, f"request {r.rid} failed: {r.error!r}")
        check(len(r.out) > 0, f"request {r.rid} produced no tokens")

    dense = jax.jit(lambda p, t: jnp.argmax(forward(p, cfg, t)[0], -1))
    agree = total = 0
    for r in sorted(done, key=lambda r: r.rid)[:check_requests]:
        prompt = prompts[r.rid]
        seq = np.concatenate([prompt, np.asarray(r.out, np.int32)])
        pred = np.asarray(dense(params, jnp.asarray(seq[None])))[0]
        # position t predicts token t + 1: the generated tokens start at
        # position len(prompt)
        want = pred[len(prompt) - 1:len(seq) - 1]
        hits = int((want == np.asarray(r.out)).sum())
        agree, total = agree + hits, total + len(r.out)
        log(f"[serve] request {r.rid}: prompt {len(prompt)} tokens, "
            f"{hits}/{len(r.out)} generated tokens equal the dense argmax")
    check(total > 0 and agree / total >= MIN_AGREEMENT,
          f"served tokens agree with the dense forward on {agree}/{total} "
          f"positions, below {MIN_AGREEMENT:.0%}")
    log(f"[serve] PASS: greedy agreement {agree}/{total}")


def kernel_cases():
    """(family, data, run, reference, make_args) at the smoke's shapes, each
    run compiled; ``make_args(key)`` draws the operands (``jax.eval_shape``
    of it gives their shapes without allocating)."""
    from repro.kernels import ops, ref
    kw = {"impl": "pallas", "interpret": False}
    n = 4096

    def normals(*shapes, dtype=jnp.float32):
        def make(key):
            keys = jax.random.split(key, len(shapes))
            return tuple(jax.random.normal(k, s, dtype)
                         for k, s in zip(keys, shapes))
        return make

    def paged_args(key):
        # Qwen1.5-4B's decode attention at the serve phase's settings: 4
        # rows of ragged lengths over a pool of 4 x 64 scattered pages
        kq, kk, kv, kl, kt = jax.random.split(key, 5)
        pool = (20, 4 * 64 + 1, 16, 128)
        return (jax.random.normal(kq, (4, 20, 128), jnp.bfloat16),
                jax.random.normal(kk, pool, jnp.bfloat16),
                jax.random.normal(kv, pool, jnp.bfloat16),
                jax.random.randint(kl, (4,), 1, 1025),
                jax.random.permutation(kt, 4 * 64).reshape(4, 64) + 1)

    def ssd_args(key):
        x, a, b, c = normals((1024, 24, 64), (1024, 24), (1024, 24, 128),
                             (1024, 24, 128))(key)
        return x, jax.nn.sigmoid(a + 2.0), b * 0.1, c * 0.1

    return [
        # Qwen1.5-4B's MLP up-projection over 1024 tokens
        ("matmul", {"M": 1024, "N": 6912, "K": 2560},
         lambda a, b: ops.matmul(a, b, **kw), ref.matmul,
         normals((1024, 2560), (2560, 6912), dtype=jnp.bfloat16)),
        # Qwen1.5-4B's prefill attention: 20 heads x 1024 x 128
        ("flash_attention", {"SQ": 1024, "HD": 128},
         lambda q, k, v: ops.flash_attention(q, k, v, **kw),
         ref.flash_attention,
         normals(*[(20, 1024, 128)] * 3, dtype=jnp.bfloat16)),
        ("paged_attention", {"B": 4, "NK": 20, "GROUP": 1, "HD": 128,
                             "PS": 16, "NBLK": 64},
         lambda *a: ops.paged_attention(*a, **kw), ref.paged_attention,
         paged_args),
        # mamba2-130m: seq 1024, 24 heads x 64, state 128
        ("ssd_scan", {"SQ": 1024, "HD": 64, "STATE": 128},
         lambda *a: ops.ssd_scan(*a, **kw), ref.ssd_scan, ssd_args),
        ("matadd", {"M": n, "N": n},
         lambda a, b: ops.matadd(a, b, **kw), ref.matadd,
         normals((n, n), (n, n))),
        ("transpose", {"M": n, "N": n},
         lambda a: ops.transpose(a, **kw), ref.transpose, normals((n, n))),
        ("jacobi1d", {"N": 2 ** 20},
         lambda x: ops.jacobi1d(x, 4, **kw),
         lambda x: ref.jacobi1d(x, 4), normals((2 ** 20,))),
    ]


def kernel_phase() -> None:
    from repro.kernels import ops
    failed = []
    cases = kernel_cases()
    check(sorted(c[0] for c in cases) == sorted(ops.FAMILIES),
          f"the smoke's kernel cases do not cover {sorted(ops.FAMILIES)}")
    keys =jax.random.split(jax.random.PRNGKey(0), len(cases))
    for key, (family, data, run, reference, make_args) in zip(keys, cases):
        args = make_args(key)
        pick = ops.select(family, data)
        out = np.asarray(jax.jit(run)(*args), np.float32)
        want = np.asarray(jax.jit(reference)(*args), np.float32)
        err = float(np.abs(out - want).max())
        rel = err / max(float(np.abs(want).max()), 1e-30)
        ok = out.shape == want.shape and np.isfinite(out).all() \
            and rel <= BF16_TOL
        log(f"[kernels] {family} {dict(data)} pick={dict(pick.assignment)} "
            f"leaf={pick.leaf_index} compiled max_err={err:.3e} "
            f"rel={rel:.3e} {'PASS' if ok else 'FAIL'}")
        if not ok:
            failed.append(family)
    check(not failed, f"kernels outside tolerance of kernels/ref.py: {failed}")


def four_chip_phase(*, steps: int, seq_len: int, global_batch: int,
                    microbatches: int, seed: int) -> None:
    from repro.configs import get_config
    from repro.data import DataConfig, SyntheticLM
    from repro.distributed import sharding as dist
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import batch_at, build_trainer

    cfg = get_config("hymba-1.5b")
    ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                                global_batch=global_batch, seed=seed))

    def first_steps(cfg, mesh, n):
        with mesh, dist.use_mesh_rules(mesh, dist.rules_for(cfg, mesh)):
            params, opt_state, step, _ = build_trainer(
                cfg, mesh, lr=1e-3, total_steps=max(n, 2),
                microbatches=microbatches, seed=seed)
            out = []
            for i in range(n):
                t0 = time.perf_counter()
                params, opt_state, m = step(params, opt_state,
                                            batch_at(cfg, ds, i),
                                            jnp.asarray(i, jnp.int32))
                m = {k: float(v) for k, v in m.items()}
                m["seconds"] = time.perf_counter() - t0
                out.append(m)
            return out

    mesh = make_host_mesh()
    log(f"[train] {cfg.name}: {cfg.layers} layers at published widths on "
        f"mesh {dict(mesh.shape)}, FSDP={dist.uses_fsdp(cfg, mesh)}, "
        f"batch {global_batch}x{seq_len}, {microbatches} microbatches")
    hist = first_steps(cfg, mesh, steps)
    for i, m in enumerate(hist):
        log(f"[train] step {i}: loss {m['loss']:.5f} grad_norm "
            f"{m['grad_norm']:.5f} ({m['seconds']:.2f} s, step 0 includes "
            f"compile; smoke timing, not a benchmark)")
        check(np.isfinite(m["loss"]) and np.isfinite(m["grad_norm"]),
              f"step {i} is not finite")
    log("[train] peak device memory: "
        + ", ".join(peak_gb(d) for d in jax.devices()))

    small = dataclasses.replace(cfg, layers=2)
    many = first_steps(small, mesh, 1)[0]
    one = first_steps(small, make_host_mesh(devices=jax.devices()[:1]), 1)[0]
    for key in ("loss", "grad_norm"):
        rel = abs(many[key] - one[key]) / max(abs(one[key]), 1e-30)
        log(f"[train] 2 layers, first step {key}: {len(jax.devices())} "
            f"devices {many[key]:.6f}, one device {one[key]:.6f}, "
            f"rel diff {rel:.2e}")
        check(rel <= BF16_TOL, f"{key} differs between the mesh and one "
              f"device by {rel:.2e}")
    log("[train] PASS")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded training path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    try:
        device = device_phase(4 if args.four_chips else 1)
        cache = enable_compile_cache()
        warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        log(f"[setup] compile cache: {cache} ({warm} entries before this "
            f"run; compile seconds below are cold where that is 0)")
        if args.four_chips:
            four_chip_phase(steps=3, seq_len=128, global_batch=8,
                            microbatches=2, seed=args.seed)
        else:
            from repro.artifacts.dispatch import (DispatchCache,
                                                  set_default_cache)
            from repro.configs import get_config
            # a store-less cache: every pick is resolved cold from the
            # comprehensive trees, so no untracked ./artifacts is read
            set_default_cache(DispatchCache())
            log("[setup] kernel dispatch: cold resolution, no artifact "
                "store")
            cfg = dataclasses.replace(get_config("qwen1.5-4b"),
                                      param_dtype="bfloat16")
            serve_phase(cfg, seed=args.seed, requests=6,
                        prompt_lens=(64, 513), max_new=32, max_batch=4,
                        max_len=1024, check_requests=2)
            kernel_phase()
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
