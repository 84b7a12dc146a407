#!/usr/bin/env python3
"""Compile a configuration's serving steps for one chip of a described TPU
v5e, without a chip, and print what ``memory_analysis()`` says they need.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py chipbench/configs/yi-6b.json

Prints the bytes of the weights, of the KV pool at the file's
``num_blocks``, and the argument and temporary bytes of the decode step and
of the largest prefill chunk, so ``num_blocks`` can be set from the HBM the
weights leave.  It compiles at the real size: about a minute a step.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(path: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import serve
    from repro.models import init_model, init_paged_cache
    from repro.runtime.serving import engine_steps

    jax.config.update("jax_enable_compilation_cache", False)
    conf = json.loads(Path(path).read_text())
    eng = conf["engine"]
    cfg = serve.model_config(conf)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = on_chip(jax.eval_shape(lambda k: init_model(k, cfg)[0],
                                    jax.random.PRNGKey(0)))
    B, page = eng["max_batch"], eng["page_size"]
    nblk = -(-eng["max_len"] // page)
    cache = on_chip(jax.eval_shape(lambda: init_paged_cache(
        cfg, eng["num_blocks"], page, B)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    nbytes = lambda t: sum(x.size * x.dtype.itemsize
                           for x in jax.tree.leaves(t))
    prefill, decode = engine_steps(cfg)
    report = {"weights_bytes": nbytes(params), "kv_pool_bytes": nbytes(cache),
              "num_blocks": eng["num_blocks"]}
    dec = jax.jit(decode, donate_argnums=(2,)).lower(
        params, i32(B, 1), cache, i32(B), i32(B, nblk),
        jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one)).compile()
    pre = jax.jit(prefill, donate_argnums=(2,)).lower(
        params, i32(1, eng["prefill_chunk"]), cache, i32(), i32(1, nblk),
        i32()).compile()
    for name, c in (("decode", dec), ("prefill", pre)):
        m = c.memory_analysis()
        report[name] = {"argument_bytes": m.argument_size_in_bytes,
                        "output_bytes": m.output_size_in_bytes,
                        "alias_bytes": m.alias_size_in_bytes,
                        "temp_bytes": m.temp_size_in_bytes}
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
