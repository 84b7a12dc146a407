"""Plain float32 reference of the dense decoder of Llama and Qwen2.

It covers Yi-6B (Llama: grouped-query attention, no biases) and Qwen1.5-4B
(Qwen2: multi-head attention with q/k/v biases), as their published
``config.json`` and modelling code describe them: pre-norm RMSNorm blocks,
rotary embeddings on the rotate-half convention, causal softmax attention
with ``num_attention_heads // num_key_value_heads`` query heads to a key/value
head, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``), a final RMSNorm and an
untied output head.  No cache, no batching tricks, no kernels.

Departures from the published models: the weights are random (drawn by
``weights.py``), and the context is cut to the serving window (``max_len``).

Every matrix product runs at ``Precision.HIGHEST`` under
``jax.default_matmul_precision("highest")``, since a TPU otherwise multiplies
float32 in bfloat16.  The pass is blocked so it fits one chip at published
widths: layer by layer with each layer's weights drawn when it is reached,
attention in blocks of query rows, and logits in blocks of positions.

``low=True`` is the control: every projection takes float8 (e4m3) operands,
weights scaled per output column and activations per row, which is the next
precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 512              # query rows per attention block
LOGIT_BLOCK = 512          # positions per block of logits


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(x: jax.Array, w: jax.Array, low: bool = False) -> jax.Array:
    if low:
        x, w = fp8(x, -1), fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (B, S, H, hd); rotate-half pairs (i, i + hd/2)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, window=None):
    """q: (B, S, nh, hd); k, v: (B, S, nk, hd) -> (B, S, nh * hd).  With
    ``window``, a query sees only the ``window`` latest positions."""
    B, S, nh, hd = q.shape
    nk = k.shape[2]
    g = nh // nk
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    qs = q.reshape(B, S // qb, qb, nk, g, hd).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(S)

    def block(args):
        qc, start = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc, k,
                       precision=HIGHEST) / np.sqrt(hd)
        qpos = start + jnp.arange(qb)
        seen = kpos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (qs, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, nh * hd)


def block(dm: dict, p: dict, x: jax.Array, low: bool = False) -> jax.Array:
    """One decoder layer over x: (B, S, d), positions 0..S-1."""
    B, S, _ = x.shape
    nh, nk, hd = dm["nh"], dm["nk"], dm["hd"]
    pos = jnp.arange(S)
    h = rmsnorm(x, p["ln1"], dm["eps"])
    q, k, v = mm(h, p["wq"], low), mm(h, p["wk"], low), mm(h, p["wv"], low)
    if dm["bias"]:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(B, S, nh, hd), pos, dm["theta"])
    k = rope(k.reshape(B, S, nk, hd), pos, dm["theta"])
    a = causal_attention(q, k, v.reshape(B, S, nk, hd))
    x = x + mm(a, p["wo"], low)
    h = rmsnorm(x, p["ln2"], dm["eps"])
    g = mm(h, p["wg"], low)
    return x + mm(g * jax.nn.sigmoid(g) * mm(h, p["wu"], low), p["wd"], low)


def logits(dm: dict, out: dict, x: jax.Array, low: bool = False) -> jax.Array:
    return mm(rmsnorm(x, out["ln_f"], dm["eps"]), out["unembed"], low)


@functools.lru_cache(maxsize=None)
def _programs(frozen: tuple):
    dm = dict(frozen)

    def gap(ref, tok):
        return ref.max(-1) - jnp.take_along_axis(ref, tok[..., None], -1)[..., 0]

    def gaps(out, x, nxt):
        """Per position: the reference's best logit less that of the next
        token."""
        return gap(logits(dm, out, x), nxt)

    def gaps_low(out, x, xl, nxt):
        """As ``gaps``, and the same less the logit of the control's first
        choice."""
        ref = logits(dm, out, x)
        return gap(ref, nxt), gap(ref, logits(dm, out, xl, low=True).argmax(-1))

    return {
        "outside": jax.jit(lambda key: W.outside(dm, key)),
        "layer": jax.jit(lambda key, i: W.layer(dm, key, i)),
        "block": jax.jit(lambda p, x: block(dm, p, x)),
        "block_low": jax.jit(lambda p, x: block(dm, p, x, low=True)),
        "gaps": jax.jit(gaps),
        "gaps_low": jax.jit(gaps_low),
    }


def served_gaps(model: dict, seed: int, tokens: np.ndarray,
                control: bool = False):
    """Teacher-forced pass over ``tokens`` (K, T) with the weights of ``seed``.

    Returns ``(gap, gap_low)``, each (K, T): at position t, the reference's
    best logit less its logit of ``tokens[:, t + 1]`` (the last column is 0),
    and, with ``control``, less its logit of the control's first choice
    (else ``None``).  T must be a multiple of the blocks."""
    dm = W.dims(model)
    fn = _programs(tuple(sorted(dm.items())))
    key = W.seed_key(seed)
    K, T = tokens.shape
    with jax.default_matmul_precision("highest"):
        out = fn["outside"](key)
        x = out["embed"][jnp.asarray(tokens)]
        xl = x
        for i in range(dm["layers"]):
            p = fn["layer"](key, jnp.int32(i))
            x = fn["block"](p, x)
            if control:
                xl = fn["block_low"](p, xl)
        nxt = np.concatenate([tokens[:, 1:], tokens[:, :1]], 1)
        gap = np.zeros((K, T), np.float32)
        gap_low = np.zeros((K, T), np.float32)
        for k in range(K):
            for s in range(0, T, LOGIT_BLOCK):
                e = min(T, s + LOGIT_BLOCK)
                tok = jnp.asarray(nxt[k:k + 1, s:e])
                if control:
                    g, gl = fn["gaps_low"](out, x[k:k + 1, s:e],
                                           xl[k:k + 1, s:e], tok)
                    gap_low[k, s:e] = np.asarray(gl)[0]
                else:
                    g = fn["gaps"](out, x[k:k + 1, s:e], tok)
                gap[k, s:e] = np.asarray(g)[0]
    gap[:, -1] = gap_low[:, -1] = 0.0
    return gap, (gap_low if control else None)
