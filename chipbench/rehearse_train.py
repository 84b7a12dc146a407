#!/usr/bin/env python3
"""Compile a configuration's train step for one chip or all four of a
described TPU v5e 2x2 host, without a chip, and print what
``memory_analysis()`` says each chip needs.

    JAX_PLATFORMS=cpu python chipbench/rehearse_train.py \\
        chipbench/configs/hymba-1.5b.json --seq 4096 --batch 16 \\
        --remat dots,full --microbatches 1,2,4 [--chips 1]

One line per (remat, microbatches): argument, output, alias and temporary
bytes of the step on one chip, and seconds to compile.  The state is laid
out as ``launch/train.py:build_trainer`` lays it (FSDP over ``data`` on
four chips).  It compiles at the real size: about a minute a step.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    ap.add_argument("--seq", type=int, required=True)
    ap.add_argument("--batch", type=int, required=True)
    ap.add_argument("--remat", default="none,dots,full")
    ap.add_argument("--microbatches", default="1")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=4)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import serve
    from repro.distributed import sharding as dist
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import (abstract_state, grad_dtype_for,
                                    state_shardings)
    from repro.optim import make_optimizer, warmup_cosine
    from repro.runtime import build_train_step

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads(Path(args.config).read_text())
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_mesh((args.chips, 1), ("data", "model"),
                     devices=topo.devices[:args.chips])
    for remat in args.remat.split(","):
        cfg = dataclasses.replace(serve.model_config(conf), remat=remat)
        opt = make_optimizer(cfg.optimizer, warmup_cosine(1e-4, 10, 100))
        params_sds, axes, opt_sds = abstract_state(cfg, opt)
        p_sh, o_sh, rules = state_shardings(cfg, mesh, params_sds, axes,
                                            opt_sds)
        put = lambda t, s: jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            t, s)
        rows = NamedSharding(mesh, P("data"))
        tok = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32,
                                   sharding=rows)

        def compile_step(step, *shapes):
            with mesh, dist.use_mesh_rules(mesh, rules):
                return jax.jit(step, in_shardings=(p_sh, o_sh, None, None),
                               out_shardings=(p_sh, o_sh, None),
                               donate_argnums=(0, 1)).lower(*shapes).compile()

        for mb in (int(m) for m in args.microbatches.split(",")):
            step = build_train_step(cfg, opt, microbatches=mb,
                                    grad_dtype=grad_dtype_for(cfg))
            t0 = time.perf_counter()
            try:
                c = compile_step(step, put(params_sds, p_sh),
                                 put(opt_sds, o_sh),
                                 {"tokens": tok, "labels": tok},
                                 jax.ShapeDtypeStruct((), jnp.int32))
            except Exception as e:                 # the compiler refused it
                print(json.dumps({"remat": remat, "microbatches": mb,
                                  "refused": str(e).splitlines()[0][:300]}),
                      flush=True)
                continue
            m = c.memory_analysis()
            print(json.dumps({
                "remat": remat, "microbatches": mb,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "compile_s": round(time.perf_counter() - t0, 1)}),
                flush=True)


if __name__ == "__main__":
    main()
