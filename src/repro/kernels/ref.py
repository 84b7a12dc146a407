"""Pure-jnp oracles for every Pallas kernel family.

Each function is the semantic ground truth the per-kernel allclose sweeps in
``tests/test_kernels.py`` compare against (any leaf variant of the
comprehensive tree must match these — code soundness, Def. 2 (ii)).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul(a: jax.Array, b: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """C = A @ B with f32 accumulation (paper Fig. 3/4)."""
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32)
                      ).astype(out_dtype)


def matadd(a: jax.Array, b: jax.Array) -> jax.Array:
    """C = A + B (paper Fig. 1/2)."""
    return a + b


def jacobi1d(a: jax.Array, steps: int) -> jax.Array:
    """1D Jacobi with fixed boundary (paper Fig. 7).

    ``a`` has length n; interior points are averaged over the 3-stencil for
    ``steps`` time iterations; boundary values stay fixed.
    """
    def one(x):
        inner = (x[:-2] + x[1:-1] + x[2:]) / 3
        return x.at[1:-1].set(inner)

    for _ in range(steps):
        a = one(a)
    return a


def transpose(a: jax.Array) -> jax.Array:
    """B = A^T (paper Fig. 8)."""
    return a.T


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None) -> jax.Array:
    """Softmax attention oracle.  q,k,v: [heads, seq, head_dim]."""
    hd = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    logits = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    sq, sk = q.shape[-2], k.shape[-2]
    idx_q = jnp.arange(sq)[:, None] + (sk - sq)   # align ends (KV cache decode)
    idx_k = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= idx_k <= idx_q
    if window is not None:
        mask &= idx_k > (idx_q - window)
    logits = jnp.where(mask[None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", probs, v.astype(jnp.float32)
                      ).astype(q.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    lengths: jax.Array, block_tables: jax.Array,
                    scale: float | None = None) -> jax.Array:
    """Paged decode attention by gathering every row's whole block table.

    q: (B, nh, hd); k_pages, v_pages: (nk, num_pages, page_size, hd);
    lengths: (B,); block_tables: (B, nblk).  Row ``b`` attends to its
    logical positions ``0..lengths[b]-1``; GQA by head grouping, f32
    softmax."""
    B, nh, hd = q.shape
    nk, _, ps, _ = k_pages.shape
    nblk = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    k = k_pages[:, block_tables].reshape(nk, B, nblk * ps, hd)
    v = v_pages[:, block_tables].reshape(nk, B, nblk * ps, hd)
    qf = q.astype(jnp.float32).reshape(B, nk, nh // nk, hd)
    logits = jnp.einsum("bhgd,hbkd->bhgk", qf, k.astype(jnp.float32)) * scale
    live = jnp.arange(nblk * ps)[None, :] < lengths[:, None]    # (B, Sk)
    logits = jnp.where(live[:, None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgk,hbkd->bhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, nh, hd).astype(q.dtype)


def ssd_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array
             ) -> jax.Array:
    """Mamba-2 SSD (state-space dual) sequential oracle.

    x: [seq, heads, head_dim]   input
    a: [seq, heads]             per-step log-decay (a_t in (0,1) after exp)
    b: [seq, heads, state]      input projection
    c: [seq, heads, state]      output projection
    Recurrence per head:  S_t = a_t * S_{t-1} + b_t ⊗ x_t ;  y_t = c_t · S_t
    """
    seq, heads, hd = x.shape
    state = b.shape[-1]

    def step(S, inp):
        x_t, a_t, b_t, c_t = inp
        S = a_t[:, None, None] * S + jnp.einsum("hs,hd->hsd", b_t, x_t)
        y = jnp.einsum("hs,hsd->hd", c_t, S)
        return S, y

    S0 = jnp.zeros((heads, state, hd), jnp.float32)
    _, y = jax.lax.scan(step, S0, (x.astype(jnp.float32),
                                   a.astype(jnp.float32),
                                   b.astype(jnp.float32),
                                   c.astype(jnp.float32)))
    return y.astype(x.dtype)
