"""Sharding rules, ZeRO-1 extension, and int8 compressed all-reduce."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as dist
from repro.launch.mesh import make_host_mesh, make_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_spec_basic_rules():
    cfg = get_config("llama3-8b")
    mesh = _mesh11()
    rules = dist.rules_for(cfg, mesh)
    assert rules["ff"] == "model"
    assert rules["embed"] is None                 # not an FSDP arch
    spec = dist.spec_for(("embed", "ff"), rules)
    assert spec == P(None, "model")


def test_spec_dedup_and_divisibility():
    cfg = get_config("kimi-k2-1t-a32b")           # FSDP arch
    mesh = _mesh11()
    rules = dist.rules_for(cfg, mesh)
    # expert gets 'data'; the FSDP embed entry must not reuse it
    with dist.use_mesh_rules(mesh, rules):
        spec = dist.spec_for(("expert", "embed", "ff"), rules,
                             (384, 7168, 2048))
    flat = []
    for e in spec:
        flat += list(e) if isinstance(e, tuple) else [e]
    dup = [a for a in flat if a is not None]
    assert len(dup) == len(set(dup)), spec


def test_spec_nondivisible_falls_back():
    cfg = get_config("mamba2-130m")
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = dict(dist.rules_for(cfg, mesh))
    rules["vocab"] = "model"
    with dist.use_mesh_rules(mesh, rules):
        # vocab 50280 % 1 == 0 on a 1-device mesh: kept
        s1 = dist.spec_for(("vocab", "embed"), rules, (50280, 768))
        assert s1 == P("model")


def test_fsdp_on_pure_data_parallel_meshes():
    """Weights shard over the batch axes where no "model" axis can hold
    them (hymba-1.5b's f32 train state on a v5e 2x2), and only there."""
    from jax.sharding import AbstractMesh
    cfg = get_config("hymba-1.5b")
    dp4 = AbstractMesh((4, 1), ("data", "model"))
    assert dist.uses_fsdp(cfg, dp4)
    assert dist.rules_for(cfg, dp4)["embed"] == ("data",)
    assert not dist.uses_fsdp(cfg, AbstractMesh((2, 2), ("data", "model")))
    assert not dist.uses_fsdp(cfg, _mesh11())
    assert dist.uses_fsdp(get_config("kimi-k2-1t-a32b"), _mesh11())


def test_constrain_noop_without_mesh():
    x = jax.numpy.ones((4, 4))
    y = dist.constrain(x, ("batch", None))
    assert y is x


def test_zero1_extends_largest_replicated_dim():
    mesh = _mesh11()
    sh = NamedSharding(mesh, P(None, "model"))
    leaf = jax.ShapeDtypeStruct((8, 4), jax.numpy.float32)
    from repro.launch.specs import _zero1_one
    out = _zero1_one(sh, leaf, mesh)
    assert out.spec == P("data", "model")


def test_state_shardings_cover_optimizer_tree():
    from repro.launch.specs import abstract_state, state_shardings
    from repro.optim import adamw, constant
    cfg = get_config("yi-6b")
    mesh = _mesh11()
    opt = adamw(constant(1e-3))
    params_sds, axes, opt_sds = abstract_state(cfg, opt)
    p_sh, o_sh, _ = state_shardings(cfg, mesh, params_sds, axes, opt_sds)
    n_p = len(jax.tree.leaves(p_sh, is_leaf=lambda t: isinstance(t, NamedSharding)))
    n_o = len(jax.tree.leaves(o_sh, is_leaf=lambda t: isinstance(t, NamedSharding)))
    assert n_o == 2 * n_p                      # m and v per param


COMPRESS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np, jax, jax.numpy as jnp
    from repro.distributed import compressed_psum_pod
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    key = jax.random.PRNGKey(0)
    grads = {"w": jax.random.normal(key, (64, 64)),
             "b": jax.random.normal(jax.random.PRNGKey(1), (17,))}
    out = compressed_psum_pod(grads, mesh, jax.random.PRNGKey(2))
    # reference: n_pods * grads (each pod holds the same replicated values)
    for k in grads:
        want = 2.0 * np.asarray(grads[k])
        got = np.asarray(out[k])
        rel = np.abs(got - want).mean() / (np.abs(want).mean() + 1e-9)
        assert rel < 0.02, (k, rel)
    print("COMPRESS_OK", rel)
""")


def test_compressed_psum_pod_numerics():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", COMPRESS_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr
