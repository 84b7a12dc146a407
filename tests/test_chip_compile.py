"""AOT compiles for one chip of a described TPU v5e 2x2: what the chip's
compiler refuses (tiling, scoped VMEM, HBM) fails here, with no chip.

Covers every Pallas family's pick at the shapes ``chip_smoke.py`` runs
(the head-major ``ssd_scan`` among them) and Qwen1.5-4B's jitted paged
decode step at published widths, which must fit the chip's 16 GB and read
the pool through the ``paged_attention`` kernel, as it does on a TPU.  The
topology is described only inside the module fixture below, never while
a module is imported: only one process may load the TPU compiler's
library, and the fixture skips where it cannot be loaded.
"""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.ops import FAMILIES
from repro.models import init_model, init_paged_cache
from repro.runtime.serving import engine_steps

ROOT = os.path.join(os.path.dirname(__file__), "..")
HBM_BYTES = 16e9


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_pick_compiles_for_v5e(one_chip, family):
    cases = {c[0]: c for c in _chip_smoke().kernel_cases()}
    _, _, run, _, make_args = cases[family]
    args = _on(one_chip, jax.eval_shape(make_args, jax.random.PRNGKey(0)))
    hlo = jax.jit(run).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo            # the Pallas kernel is there


def test_qwen_paged_decode_step_fits_one_v5e(one_chip, monkeypatch):
    """The engine's decode step at the smoke's settings: bf16 weights,
    max_batch 4, max_len 1024, page size 16.  The CPU backend this
    compiles from would route the attention read to the gather path; on
    the TPU it goes to the kernel, so the test resolves ``impl`` as a TPU
    process does."""
    monkeypatch.setattr(ops, "resolve_impl", lambda impl: "pallas")
    cfg = dataclasses.replace(get_config("qwen1.5-4b"),
                              param_dtype="bfloat16")
    batch, max_len, page = 4, 1024, 16
    nblk = max_len // page
    params = _on(one_chip, jax.eval_shape(lambda k: init_model(k, cfg)[0],
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: init_paged_cache(cfg, batch * nblk + 1, page, batch)))

    def spec(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _, decode = engine_steps(cfg)
    compiled = jax.jit(decode, donate_argnums=(2,)).lower(
        params, spec((batch, 1)), cache, spec((batch,)),
        spec((batch, nblk)), spec((batch,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()   # paged_attention
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert m.argument_size_in_bytes > 9e9      # ~7.9 GB weights + 1.7 GB KV
    assert need < HBM_BYTES, need
