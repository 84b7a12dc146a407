"""The hand-written all-to-all MoE path must equal the dense GShard path.

Runs in a subprocess with 8 forced host devices so the shard_map actually
exchanges data over a (2x2x2) pod x data x model mesh.
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from repro.distributed import sharding as dist
    from repro.models.config import ModelConfig, MoEConfig
    from repro.models.moe import init_moe, moe_block
    from repro.models.moe_a2a import moe_block_a2a
    from repro.launch.mesh import make_mesh

    cfg = ModelConfig(
        name="a2a-test", layers=1, d_model=32, heads=4, kv_heads=2,
        d_ff=48, vocab=64, block="attn_moe",
        moe=MoEConfig(num_experts=6, top_k=2, d_ff_expert=48,
                      capacity_factor=64.0))     # dropless => paths agree

    mesh = make_mesh((2, 4), ("data", "model"))
    rules = dist.rules_for(cfg, mesh)
    p, _ = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 32), jnp.float32)

    with mesh, dist.use_mesh_rules(mesh, rules):
        y_ref, aux_ref = jax.jit(
            lambda p, x: moe_block(p, x, cfg, group_size=8))(p, x)
        y_a2a, aux_a2a = jax.jit(
            lambda p, x: moe_block_a2a(p, x, cfg, group_size=8))(p, x)

    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_a2a), float(aux_ref), rtol=1e-3)

    # gradients flow and match through the a2a schedule
    def loss(fn):
        def f(p):
            y, aux = fn(p, x, cfg, group_size=8)
            return jnp.sum(y * y) + 0.01 * aux
        return f
    with mesh, dist.use_mesh_rules(mesh, rules):
        g_ref = jax.jit(jax.grad(loss(moe_block)))(p)
        g_a2a = jax.jit(jax.grad(loss(moe_block_a2a)))(p)
    for k in ("router", "wi", "wg", "wo"):
        np.testing.assert_allclose(np.asarray(g_a2a[k]),
                                   np.asarray(g_ref[k]),
                                   rtol=2e-3, atol=2e-3)
    print("A2A_OK")
""")


def test_moe_a2a_matches_dense():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "A2A_OK" in r.stdout, r.stdout + r.stderr
