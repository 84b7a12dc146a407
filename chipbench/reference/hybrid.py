"""Plain float32 reference of the repo's hybrid block (its Hymba-1.5B).

One block, as the program defines it: attention and a state-space (SSD)
path read the same input, each through its own RMSNorm, and their outputs
are added to the residual; a SwiGLU MLP follows.

* attention: rotary embeddings, grouped-query heads, a causal window of
  ``window`` positions;
* SSD (Mamba-2 with one group): per head ``h`` and step ``t``,
  ``a = sigmoid(x W_a + bias)``, ``S_t = a S_{t-1} + B_t x_t^T`` and
  ``y_t = C_t S_t``, with ``B`` and ``C`` shared by the heads; written as
  the plain recurrence, one step at a time.

Departures from the published Hymba, which the program makes and this
reference follows: the two paths are summed (Hymba averages their
normalized outputs with learned scales), there are no meta tokens and no
key/value sharing across layers, every layer is windowed (Hymba keeps three
global layers), and the SSD path has no convolution, no output gate and no
skip term.

Parameters are the program's names for one layer (``ln1``, ``attn``,
``lns``, ``ssm``, ``ln2``, ``mlp``) in float32; ``dims`` as
``weights.dims`` plus ``window``, ``ssm_heads``, ``ssm_head_dim``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attn_mlp import HIGHEST, causal_attention, mm, rmsnorm, rope


def ssd(p: dict, x: jax.Array, heads: int, head_dim: int) -> jax.Array:
    """x: (B, S, d) normalized input -> (B, S, d)."""
    B, S, _ = x.shape
    xi = mm(x, p["wx"]).reshape(B, S, heads, head_dim)
    b, c = mm(x, p["wb"]), mm(x, p["wc"])                  # (B, S, N)
    a = jax.nn.sigmoid(mm(x, p["wa"]) + p["a_bias"])       # (B, S, H)

    def step(state, t):
        xt, bt, ct, at = t                                 # (B,H,hd) (B,N)..
        state = at[..., None, None] * state + jnp.einsum(
            "bn,bhd->bhnd", bt, xt, precision=HIGHEST)
        return state, jnp.einsum("bn,bhnd->bhd", ct, state, precision=HIGHEST)

    state = jnp.zeros((B, heads, b.shape[-1], head_dim), jnp.float32)
    seq = (xi.transpose(1, 0, 2, 3), b.transpose(1, 0, 2),
           c.transpose(1, 0, 2), a.transpose(1, 0, 2))
    _, y = jax.lax.scan(step, state, seq)                  # (S, B, H, hd)
    return mm(y.transpose(1, 0, 2, 3).reshape(B, S, heads * head_dim),
              p["wo"])


def block(dm: dict, p: dict, x: jax.Array) -> jax.Array:
    B, S, _ = x.shape
    nh, nk, hd = dm["nh"], dm["nk"], dm["hd"]
    pos = jnp.arange(S)
    h = rmsnorm(x, p["ln1"]["scale"], dm["eps"])
    at = p["attn"]
    q = rope(mm(h, at["wq"]).reshape(B, S, nh, hd), pos, dm["theta"])
    k = rope(mm(h, at["wk"]).reshape(B, S, nk, hd), pos, dm["theta"])
    v = mm(h, at["wv"]).reshape(B, S, nk, hd)
    att = mm(causal_attention(q, k, v, window=dm["window"]), at["wo"])
    s = ssd(p["ssm"], rmsnorm(x, p["lns"]["scale"], dm["eps"]),
            dm["ssm_heads"], dm["ssm_head_dim"])
    x = x + att + s
    h = rmsnorm(x, p["ln2"]["scale"], dm["eps"])
    m = p["mlp"]
    g = mm(h, m["wg"])
    return x + mm(g * jax.nn.sigmoid(g) * mm(h, m["wi"]), m["wo"])
