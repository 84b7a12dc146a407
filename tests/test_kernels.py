"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.matmul import FAMILY as MATMUL, pallas_matmul
from repro.kernels.flash_attention import FAMILY as FLASH
from repro.kernels.ssd_scan import ssd_chunk


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


# ---------------------------------------------------------------------------
# matmul — paper Fig. 3/4 kernel, full parametric sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (256, 512, 384),
                                   (300, 200, 150), (64, 1024, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes(M, K, N, dtype):
    a = _rand(0, (M, K), dtype)
    b = _rand(1, (K, N), dtype)
    out = ops.matmul(a, b, impl="pallas", interpret=True)
    want = ref.matmul(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("bm,bn,bk,s,cached", [
    (8, 128, 128, 1, True), (16, 128, 128, 2, True),
    (32, 128, 256, 4, False), (8, 128, 128, 8, True),
    (64, 256, 128, 1, False),
])
def test_matmul_all_block_params(bm, bn, bk, s, cached):
    """Every (block-format, grain, caching) leaf computes the same product —
    paper code-soundness (Def 2 ii) for the matmul family."""
    a = _rand(2, (256, 384))
    b = _rand(3, (384, 256))
    out = pallas_matmul(a, b, bm=bm, bn=bn, bk=bk, s=s, cached=cached,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# matadd — paper Fig. 1/2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,N", [(128, 128), (257, 511), (1024, 256)])
def test_matadd(M, N):
    a = _rand(4, (M, N))
    b = _rand(5, (M, N))
    out = ops.matadd(a, b, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a + b), rtol=1e-6)


# ---------------------------------------------------------------------------
# jacobi1d — paper Fig. 7 / Table 2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,steps", [(1026, 1), (4098, 4), (32770, 2)])
def test_jacobi1d(n, steps):
    x = _rand(6, (n,))
    out = ops.jacobi1d(x, steps, impl="pallas", interpret=True)
    want = ref.jacobi1d(x, steps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# transpose — paper Fig. 8 / Table 3
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("M,N", [(128, 128), (512, 256), (300, 700)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_transpose(M, N, dtype):
    a = _rand(7, (M, N), dtype)
    out = ops.transpose(a, impl="pallas", interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a).T)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h,s,d", [(2, 256, 64), (4, 512, 128), (1, 128, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(h, s, d, causal):
    q = _rand(8, (h, s, d))
    k = _rand(9, (h, s, d))
    v = _rand(10, (h, s, d))
    out = ops.flash_attention(q, k, v, causal=causal, impl="pallas",
                              interpret=True)
    want = ref.flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_window():
    q = _rand(11, (2, 512, 64))
    k = _rand(12, (2, 512, 64))
    v = _rand(13, (2, 512, 64))
    out = ops.flash_attention(q, k, v, causal=True, window=128,
                              impl="pallas", interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# SSD scan (mamba2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seq,heads,hd,state", [
    (256, 2, 32, 16), (512, 4, 64, 32), (128, 1, 64, 64)])
def test_ssd_scan(seq, heads, hd, state):
    x = _rand(14, (seq, heads, hd))
    a = jax.nn.sigmoid(_rand(15, (seq, heads))) * 0.9 + 0.05
    b = _rand(16, (seq, heads, state))
    c = _rand(17, (seq, heads, state))
    out = ops.ssd_scan(x, a, b, c, impl="pallas", interpret=True)
    want = ref.ssd_scan(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("chunk", [64, 128, 256])
def test_pallas_ssd_scan_every_chunk(chunk):
    """Every chunk variant of the head-major kernel, over several heads and
    several chunks with a ragged tail (padding), matches the sequential
    oracle in interpret mode."""
    from repro.kernels.ssd_scan import pallas_ssd_scan
    seq, heads, hd, state = 600, 3, 64, 128
    x = _rand(23, (seq, heads, hd))
    a = jax.nn.sigmoid(_rand(24, (seq, heads))) * 0.9 + 0.05
    b = _rand(25, (seq, heads, state)) * 0.3
    c = _rand(26, (seq, heads, state)) * 0.3
    out = pallas_ssd_scan(x, a, b, c, chunk=chunk, interpret=True)
    want = ref.ssd_scan(x, a, b, c)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ssd_chunk_equals_stepwise():
    """The matmul-form chunk recurrence == naive per-token recurrence."""
    C, hd, st_ = 64, 16, 8
    x = np.asarray(_rand(18, (C, hd)))
    a = np.asarray(jax.nn.sigmoid(_rand(19, (C,))))
    b = np.asarray(_rand(20, (C, st_)))
    c = np.asarray(_rand(21, (C, st_)))
    S = np.asarray(_rand(22, (st_, hd))) * 0.1
    y, S_new = ssd_chunk(jnp.asarray(x), jnp.asarray(a), jnp.asarray(b),
                         jnp.asarray(c), jnp.asarray(S))
    # naive recurrence
    S_ref = S.copy()
    y_ref = np.zeros((C, hd), np.float32)
    for t in range(C):
        S_ref = a[t] * S_ref + np.outer(b[t], x[t])
        y_ref[t] = c[t] @ S_ref
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(S_new), S_ref, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# selection coherence: CPU tests take the same decision path as TPU builds
# ---------------------------------------------------------------------------

def test_selected_variant_is_feasible_and_deterministic():
    from repro.core import TPU_V5E, best_variant
    c1 = best_variant(MATMUL, TPU_V5E, {"M": 2048, "N": 2048, "K": 2048})
    c2 = best_variant(MATMUL, TPU_V5E, {"M": 2048, "N": 2048, "K": 2048})
    assert c1.assignment == c2.assignment
    # the chosen block parameters satisfy the leaf constraints
    C = c1.plan and None
    bm, bn, bk, s = (c1.assignment[k] for k in ("bm", "bn", "bk", "s"))
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
    # VMEM constraint holds under v5e binding
    vmem = 2 * 2 * (bm * bk + bk * bn * s) + 4 * bm * bn * s * 2
    assert vmem <= TPU_V5E.vmem_bytes


def test_machine_resolved_from_device_kind():
    """A v5e binds TPU_V5E, the CPU backend binds the modelled TPU_V5E, and
    a TPU kind with no description raises instead of borrowing v5e's
    limits."""
    from types import SimpleNamespace
    from repro.core.params import TPU_V5E, default_machine, machine_for
    assert machine_for(jax.devices()[0]) is TPU_V5E        # CPU backend
    assert default_machine() is TPU_V5E
    assert machine_for(SimpleNamespace(platform="tpu",
                                       device_kind="TPU v5 lite")) is TPU_V5E
    with pytest.raises(ValueError, match="TPU v9 unknown"):
        machine_for(SimpleNamespace(platform="tpu",
                                    device_kind="TPU v9 unknown"))
