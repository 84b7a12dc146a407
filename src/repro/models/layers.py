"""Functional model layers (no framework deps; pjit/GSPMD-friendly).

Every ``init_*`` returns ``(params, axes)`` — two parallel pytrees, the
second holding *logical axis names* per parameter dimension.  The
distributed layer maps logical axes -> mesh axes (MaxText-style rules), so
the same model code runs on 1 CPU device and on the 512-chip mesh.

Attention/SSD hot-paths route through the comprehensive-tree kernels on TPU
(`repro.kernels.ops`) and through equivalent einsum math elsewhere; both are
validated against `repro.kernels.ref` oracles.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from ..kernels import ops
from ..kernels.ssd_scan import ssd_chunk

Params = Dict[str, Any]
Axes = Dict[str, Any]
NEG_INF = -1e30

# When True, inner lax.scan loops (SSD chunk scan, blocked-attention q loop)
# are unrolled at trace time.  Only the roofline probes set this: XLA's
# cost_analysis counts a while body once, so probes must make every loop
# body explicit to measure true per-layer FLOPs/bytes (DESIGN.md §8).
_UNROLL_INNER = False


def set_unroll_inner(value: bool) -> None:
    global _UNROLL_INNER
    _UNROLL_INNER = bool(value)


def _inner_scan(body, carry, xs, length: int):
    if not _UNROLL_INNER:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(length):
        x_i = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys) if ys else None
    return carry, ys


def _norm_init(key, shape, scale=1.0, dtype=jnp.float32):
    fan_in = shape[0] if len(shape) > 1 else 1
    return jax.random.normal(key, shape, dtype) * (scale / max(1, fan_in) ** 0.5)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int) -> Tuple[Params, Axes]:
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": ("embed",)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float,
         compute_dtype=None) -> jax.Array:
    """x: (..., S, H, hd); positions: (..., S).

    Angles are always f32; with ``compute_dtype`` the cos/sin tables are
    cast before the elementwise rotation so the (B,S,H,hd)-sized
    intermediates stay in the compute dtype instead of f32 (the
    'rope_compute' perf flag — halves rope HBM traffic; cos/sin in bf16
    carry ~4e-3 relative error on the rotation, fine for training)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs      # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]                            # (..., S, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    if compute_dtype is not None:
        cos = cos.astype(compute_dtype)
        sin = sin.astype(compute_dtype)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA + causal/window masks + optional KV cache)
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    d, nh, nk, hd = cfg.d_model, cfg.heads, cfg.kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": _norm_init(ks[0], (d, nh * hd)),
        "wk": _norm_init(ks[1], (d, nk * hd)),
        "wv": _norm_init(ks[2], (d, nk * hd)),
        "wo": _norm_init(ks[3], (nh * hd, d)),
    }
    a = {
        "wq": ("embed", "q_proj"),
        "wk": ("embed", "kv_proj"),
        "wv": ("embed", "kv_proj"),
        "wo": ("q_proj", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh * hd,), jnp.float32)
        p["bk"] = jnp.zeros((nk * hd,), jnp.float32)
        p["bv"] = jnp.zeros((nk * hd,), jnp.float32)
        a["bq"], a["bk"], a["bv"] = ("q_proj",), ("kv_proj",), ("kv_proj",)
    return p, a


def _sdpa(q, k, v, *, causal: bool, window: Optional[int],
          q_positions, k_positions, flags: Tuple[str, ...] = ()) -> jax.Array:
    """q: (B,Sq,nh,hd) k/v: (B,Sk,nk,hd); GQA by head grouping; f32 softmax.

    Positions may be shared (1D) or per-row (2D, continuous batching where
    each sequence in the decode pool sits at its own offset).

    perf flags:
      attn_q_heads — repeat K/V to the query-head count and contract over a
        single head axis: the head dim is then nh (divisible by the model
        axis on every assigned arch) instead of nk, so GSPMD shards the
        scores/probs tensors instead of replicating them when nk < mesh.
      probs_bf16 — probabilities leave the f32 softmax in compute dtype,
        halving the largest attention tensors; PV accumulates in f32.
    """
    B, Sq, nh, hd = q.shape
    nk = k.shape[2]
    group = nh // nk
    qp = q_positions if q_positions.ndim == 2 else q_positions[None]
    kp = k_positions if k_positions.ndim == 2 else k_positions[None]
    qi = qp[:, :, None]                # (B|1, Sq, 1)
    ki = kp[:, None, :]                # (B|1, 1, Sk)
    mask = ki >= 0                     # ring slots that were never written
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    mask = jnp.broadcast_to(mask, (mask.shape[0], Sq, k.shape[1]))

    if "attn_q_heads" in flags and group > 1:
        kq = jnp.repeat(k, group, axis=2)          # (B,Sk,nh,hd)
        vq = jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kq,
                            preferred_element_type=jnp.float32) / (hd ** 0.5)
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if "probs_bf16" in flags:
            probs = probs.astype(q.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, vq,
                             preferred_element_type=jnp.float32)
        else:
            out = jnp.einsum("bhqk,bkhd->bqhd", probs,
                             vq.astype(jnp.float32))
        return out.astype(q.dtype)

    qf = q.astype(jnp.float32).reshape(B, Sq, nk, group, hd)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf) / (hd ** 0.5)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if "probs_bf16" in flags:
        probs = probs.astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                         preferred_element_type=jnp.float32)
    else:
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, nh, hd).astype(q.dtype)


def paged_kernel_serves(cfg: ModelConfig, *, decode: bool,
                        pool_dtype) -> bool:
    """Whether a paged attention call reads the pool through the
    ``paged_attention`` kernel: a decode step (one token per row, per-row
    index) over a bf16 pool, in a layer with no sliding window, on a
    backend where ``"auto"`` resolves to Pallas (a TPU).  Prefill chunks,
    windowed layers, float32 pools and the CPU gather instead."""
    return (decode and cfg.window is None
            and jnp.dtype(pool_dtype) == jnp.bfloat16
            and ops.resolve_impl("auto") == "pallas")


def attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array,
              cache: Optional[Dict[str, jax.Array]] = None,
              cache_index: Optional[jax.Array] = None,
              causal: bool = True,
              context: Optional[jax.Array] = None,
              precomputed_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
              return_kv: bool = False,
              block_tables: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- (or cross-, when ``context`` given) attention.

    cache: {"k","v"} of shape (B, S_max, nk, hd); cache_index: scalar int —
    new k/v are written at [cache_index : cache_index+Sq].
    precomputed_kv: projected (k, v) (B,Sk,nk,hd) — whisper decode reuses the
    cross K/V cached at prefill and skips the projections.
    return_kv: return the projected (k, v) instead of a cache dict (the
    whisper prefill writes them into the cross cache).
    block_tables: (B, nblk) int32 — *paged* KV cache.  The cache leaves are
    then head-major block pools of shape (nk, num_blocks, page_size, hd)
    shared by every sequence, and row ``b``'s logical block ``j`` lives in
    physical block ``block_tables[b, j]``.  Unallocated entries may point
    anywhere (conventionally the engine's garbage block 0): their logical
    positions lie beyond the row's ``cache_index`` and are never read.  A
    decode step that :func:`paged_kernel_serves` attends through the
    ``paged_attention`` kernel, which reads only each row's live pages;
    every other paged call gathers the rows' whole tables.
    """
    B, Sq, d = x.shape
    nh, nk, hd = cfg.heads, cfg.kv_heads, cfg.hd
    src = x if context is None else context
    q = jnp.einsum("bsd,dh->bsh", x, p["wq"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, Sq, nh, hd)
    if precomputed_kv is not None:
        k, v = precomputed_kv
        k = k.astype(x.dtype)
        v = v.astype(x.dtype)
    else:
        k = jnp.einsum("bsd,dh->bsh", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dh->bsh", src, p["wv"].astype(x.dtype))
        if cfg.qkv_bias:
            k = k + p["bk"].astype(x.dtype)
            v = v + p["bv"].astype(x.dtype)
        k = k.reshape(B, -1, nk, hd)
        v = v.reshape(B, -1, nk, hd)

    if context is None and precomputed_kv is None:   # rope: self-attn only
        rope_dt = x.dtype if "rope_compute" in cfg.perf_flags else None
        q = rope(q, positions, cfg.rope_theta, compute_dtype=rope_dt)
        k = rope(k, positions, cfg.rope_theta, compute_dtype=rope_dt)
    elif precomputed_kv is not None:
        pass                                          # cross-attn: no rope

    new_cache = None
    out = None
    if cache is not None and block_tables is not None:
        # Paged KV pool (serving): scatter this call's K/V into the rows'
        # physical blocks, then read them back.  Works for both the per-row
        # decode step (cache_index (B,), Sq == 1) and the batch-1
        # chunked-prefill step (scalar cache_index, Sq == chunk).  Window
        # semantics come from the sdpa mask, not a ring buffer — the pool
        # is position-exact.
        ps = cache["k"].shape[2]
        idxv = (cache_index if jnp.ndim(cache_index) == 1
                else jnp.broadcast_to(cache_index, (B,)))
        ptok = idxv[:, None] + jnp.arange(Sq)[None]          # (B,Sq) logical
        phys = jnp.take_along_axis(block_tables, ptok // ps, axis=1)
        pslot = ptok % ps
        ck = cache["k"].at[:, phys, pslot].set(
            k.transpose(2, 0, 1, 3).astype(cache["k"].dtype))
        cv = cache["v"].at[:, phys, pslot].set(
            v.transpose(2, 0, 1, 3).astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        decode = Sq == 1 and jnp.ndim(cache_index) == 1
        if paged_kernel_serves(cfg, decode=decode, pool_dtype=ck.dtype):
            out = ops.paged_attention(q[:, 0], ck, cv, idxv + 1,
                                      block_tables)
        else:
            # gather each row's whole logical view for the sdpa read
            nblk = block_tables.shape[1]

            def view(pool):
                return (pool[:, block_tables].transpose(1, 2, 3, 0, 4)
                        .reshape(B, nblk * ps, nk, hd).astype(x.dtype))

            k_att, v_att = view(ck), view(cv)
            k_positions = jnp.arange(nblk * ps)
    elif cache is not None:
        k_len = cache["k"].shape[1]
        ring = cfg.window is not None and k_len <= cfg.window
        vec_idx = cache_index is not None and jnp.ndim(cache_index) == 1
        if ring:
            # Ring buffer of size W: token t lives at slot t % W.  Slot j
            # currently holds token  t_last - ((t_last - j) mod W); negative
            # values mean "never written" and are masked out.  This keeps the
            # long-context decode cache at O(window), not O(S_max).
            idxv = jnp.broadcast_to(cache_index, (B,))
            if Sq >= k_len:
                # prefill longer than the window: only the last W tokens
                # matter (distinct slots; avoids duplicate-index scatter)
                kw_, vw_ = k[:, -k_len:], v[:, -k_len:]
                slots = (idxv[:, None] + Sq - k_len +
                         jnp.arange(k_len)[None]) % k_len
            else:
                kw_, vw_ = k, v
                slots = (idxv[:, None] + jnp.arange(Sq)[None]) % k_len
            rows = jnp.arange(B)[:, None]
            ck = cache["k"].at[rows, slots].set(kw_.astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slots].set(vw_.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            if Sq > 1:
                # prefill: attend in-sequence (chunked windowed path); the
                # ring is only written for the decode steps that follow.
                k_att, v_att, k_positions = k, v, positions
            else:
                t_last = idxv[:, None] + Sq - 1                  # (B,1)
                k_positions = t_last - ((t_last - jnp.arange(k_len)[None])
                                        % k_len)                 # (B,W)
                k_att, v_att = ck.astype(x.dtype), cv.astype(x.dtype)
        elif vec_idx:
            # continuous batching: every pool row sits at its own offset
            rows = jnp.arange(B)[:, None]
            slots = cache_index[:, None] + jnp.arange(Sq)[None]
            ck = cache["k"].at[rows, slots].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slots].set(v.astype(cache["v"].dtype))
            new_cache = {"k": ck, "v": cv}
            k_att, v_att = ck.astype(x.dtype), cv.astype(x.dtype)
            k_positions = jnp.arange(k_len)
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, cache_index, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, cache_index, 0, 0))
            new_cache = {"k": ck, "v": cv}
            if cfg.window is not None and cfg.window < k_len:
                # sliding window over a full-length cache: slice the last
                # `window` rows so decode cost is O(window), not O(S_max).
                w = cfg.window
                start = jnp.clip(cache_index + Sq - w, 0, k_len - w)
                k_att = jax.lax.dynamic_slice(ck, (0, start, 0, 0),
                                              (B, w, nk, hd))
                v_att = jax.lax.dynamic_slice(cv, (0, start, 0, 0),
                                              (B, w, nk, hd))
                k_positions = start + jnp.arange(w)
            else:
                k_att, v_att = ck.astype(x.dtype), cv.astype(x.dtype)
                k_positions = jnp.arange(k_len)
    else:
        k_att, v_att = k, v
        k_positions = (positions
                       if context is None and precomputed_kv is None
                       else jnp.arange(k.shape[1]))
    cross = context is not None or precomputed_kv is not None
    if out is None:
        if cache is not None and "kv_cache_hd" in cfg.perf_flags:
            # the cache is head_dim-sharded; matching q makes GSPMD compute
            # the QK contraction distributed (partial scores + ~65MB
            # all-reduce) instead of all-gathering the ~1GB K cache per
            # layer (§Perf C2)
            from ..distributed import sharding as dist
            q = dist.constrain(q, ("batch", None, None, "kv_hd"))
        out = sdpa_auto(q, k_att, v_att,
                        causal=causal and not cross,
                        window=cfg.window if not cross else None,
                        q_positions=positions, k_positions=k_positions,
                        flags=cfg.perf_flags)
    out = out.reshape(B, Sq, nh * hd)
    proj = jnp.einsum("bsh,hd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return proj, (k, v)
    return proj, new_cache


def _sdpa_chunked(q, k, v, *, causal: bool, window: Optional[int],
                  q_positions, k_positions, q_block: int = 1024,
                  flags: Tuple[str, ...] = ()) -> jax.Array:
    """Flash-style blocked attention in pure XLA (lax.scan over Q blocks).

    Keeps peak memory at O(q_block × S_k) instead of O(S_q × S_k) so the
    32k-prefill cells lower with realistic (flash-equivalent) HBM traffic.
    Windowed attention additionally slices only the K rows a Q block can see,
    making the whole pass O(S·W) — the sub-quadratic path the hybrid archs
    use for long contexts.  Perf flags as in :func:`_sdpa`.
    """
    B, Sq, nh, hd = q.shape
    Sk, nk = k.shape[1], k.shape[2]
    group = nh // nk
    q_heads = "attn_q_heads" in flags and group > 1
    qb = min(q_block, Sq)
    nq = -(-Sq // qb)
    Sqp = nq * qb
    qp = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, 0)))
    qpos = jnp.pad(q_positions, (0, Sqp - Sq), constant_values=-1)
    if q_heads:
        kf = jnp.repeat(k, group, axis=2)      # (B,Sk,nh,hd) compute dtype
        vf = jnp.repeat(v, group, axis=2)
    else:
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)

    if window is not None:
        kw = window + qb                       # rows a q-block can see

    def body(_, inp):
        qc, qp_c, qstart = inp                 # (B,qb,nh,hd), (qb,), scalar
        nh_k = nh if q_heads else nk
        if window is not None:
            start = jnp.clip(qstart - window + 1, 0, max(Sk - kw, 0))
            kc = jax.lax.dynamic_slice(kf, (0, start, 0, 0),
                                       (B, min(kw, Sk), nh_k, hd))
            vc = jax.lax.dynamic_slice(vf, (0, start, 0, 0),
                                       (B, min(kw, Sk), nh_k, hd))
            kp = start + jnp.arange(min(kw, Sk))
            kp = jnp.take(k_positions, kp, axis=0) \
                if k_positions.shape[0] == Sk else kp
        else:
            kc, vc, kp = kf, vf, k_positions
        mask = jnp.ones((qb, kp.shape[0]), bool)
        qi = qp_c[:, None]
        ki = kp[None, :]
        mask &= ki >= 0
        if causal:
            mask &= ki <= qi
        if window is not None:
            mask &= ki > qi - window
        mask &= qi >= 0                        # padded q rows
        if q_heads:
            scores = jnp.einsum("bqhd,bkhd->bhqk", qc, kc,
                                preferred_element_type=jnp.float32) \
                / (hd ** 0.5)
            scores = jnp.where(mask[None, None], scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            if "probs_bf16" in flags:
                probs = probs.astype(q.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, vc,
                             preferred_element_type=jnp.float32)
            return None, out.astype(q.dtype)
        qf = qc.astype(jnp.float32).reshape(B, qb, nk, group, hd)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kc) / (hd ** 0.5)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if "probs_bf16" in flags:
            probs = probs.astype(q.dtype)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vc,
                         preferred_element_type=jnp.float32)
        return None, out.reshape(B, qb, nh, hd).astype(q.dtype)

    xs = (qp.reshape(B, nq, qb, nh, hd).transpose(1, 0, 2, 3, 4),
          qpos.reshape(nq, qb),
          jnp.arange(nq) * qb)
    _, outs = _inner_scan(body, None, xs, nq)
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sqp, nh, hd)
    return out[:, :Sq].astype(q.dtype)


# XLA attention dispatch: dense for short sequences, blocked beyond this.
CHUNKED_SDPA_THRESHOLD = 4096


def sdpa_auto(q, k, v, *, causal: bool, window: Optional[int],
              q_positions, k_positions,
              flags: Tuple[str, ...] = ()) -> jax.Array:
    if q.shape[1] >= CHUNKED_SDPA_THRESHOLD or (
            window is not None and q.shape[1] > window):
        return _sdpa_chunked(q, k, v, causal=causal, window=window,
                             q_positions=q_positions,
                             k_positions=k_positions, flags=flags)
    return _sdpa(q, k, v, causal=causal, window=window,
                 q_positions=q_positions, k_positions=k_positions,
                 flags=flags)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"wi": _norm_init(ks[0], (d, f)), "wg": _norm_init(ks[1], (d, f)),
         "wo": _norm_init(ks[2], (f, d))}
    a = {"wi": ("embed", "ff"), "wg": ("embed", "ff"), "wo": ("ff", "embed")}
    return p, a


def mlp(p: Params, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype))
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(x.dtype))
    h = jax.nn.silu(g) * h
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# SSD (Mamba-2) block
# ---------------------------------------------------------------------------

def init_ssm(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    """Mamba-2 SSD projections.  B and C are shared across heads
    (ngroups=1, as in the paper) — (d, state), not (d, heads*state)."""
    s = cfg.ssm
    d = cfg.d_model
    di = s.heads * s.head_dim
    ks = jax.random.split(key, 5)
    p = {
        "wx": _norm_init(ks[0], (d, di)),
        "wb": _norm_init(ks[1], (d, s.state)),
        "wc": _norm_init(ks[2], (d, s.state)),
        "wa": _norm_init(ks[3], (d, s.heads), scale=0.1),
        "wo": _norm_init(ks[4], (di, d)),
        "a_bias": jnp.full((s.heads,), 2.0, jnp.float32),
    }
    a = {
        "wx": ("embed", "ssm_inner"), "wb": ("embed", "ssm_bc"),
        "wc": ("embed", "ssm_bc"), "wa": ("embed", "ssm_heads"),
        "wo": ("ssm_inner", "embed"), "a_bias": ("ssm_heads",),
    }
    return p, a


def ssm_decays(p: Params, x: jax.Array, s) -> jax.Array:
    """Per-token decay a_t in (0,1): sigmoid(x·wa + bias)."""
    logit = jnp.einsum("bsd,dh->bsh", x.astype(jnp.float32),
                       p["wa"].astype(jnp.float32)) + p["a_bias"]
    return jax.nn.sigmoid(logit)


def ssm_block(p: Params, x: jax.Array, cfg: ModelConfig, *,
              state: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """SSD block; ``state`` (B, heads, state, hd) enables O(1) decode.

    Training path runs the chunked matmul-form scan (`ssd_chunk`, shared with
    the Pallas kernel).  Decode path applies one recurrence step.
    """
    s = cfg.ssm
    B, S, d = x.shape
    xi = jnp.einsum("bsd,di->bsi", x, p["wx"].astype(x.dtype))
    xi = xi.reshape(B, S, s.heads, s.head_dim)
    # B/C shared across heads (ngroups=1): project once, broadcast to heads
    b1 = jnp.einsum("bsd,dn->bsn", x, p["wb"].astype(x.dtype))
    c1 = jnp.einsum("bsd,dn->bsn", x, p["wc"].astype(x.dtype))
    b = jnp.broadcast_to(b1[:, :, None, :], (B, S, s.heads, s.state))
    c = jnp.broadcast_to(c1[:, :, None, :], (B, S, s.heads, s.state))
    a = ssm_decays(p, x, s)                                   # (B,S,H)

    if state is not None and S == 1:
        # one-step recurrence: S_t = a*S + b⊗x ; y = c·S
        xf = xi[:, 0].astype(jnp.float32)                     # (B,H,hd)
        bf = b[:, 0].astype(jnp.float32)                      # (B,H,st)
        cf = c[:, 0].astype(jnp.float32)
        af = a[:, 0]                                          # (B,H)
        new_state = af[..., None, None] * state + \
            jnp.einsum("bhs,bhd->bhsd", bf, xf)
        y = jnp.einsum("bhs,bhsd->bhd", cf, new_state)[:, None]
        y = y.astype(x.dtype)
        new_state_out = new_state
    else:
        # chunked scan over the sequence (matmul form, shared with kernel)
        ck = min(s.chunk, S)
        Sp = -(-S // ck) * ck
        pad = Sp - S
        xi_p = jnp.pad(xi, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a_p = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        b_p = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c_p = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        nchunks = Sp // ck

        def chunk_body(S_prev, inp):
            xc, ac, bc, cc = inp                              # (B,ck,H,*)
            def per_bh(Sp_bh, x_bh, a_bh, b_bh, c_bh):
                return ssd_chunk(x_bh, a_bh, b_bh, c_bh, Sp_bh)
            # vmap over batch and heads
            f = jax.vmap(jax.vmap(
                lambda S0, xx, aa, bb, cc2: ssd_chunk(xx, aa, bb, cc2, S0)))
            y, S_new = f(S_prev,
                         xc.transpose(0, 2, 1, 3).astype(jnp.float32),
                         ac.transpose(0, 2, 1).astype(jnp.float32),
                         bc.transpose(0, 2, 1, 3).astype(jnp.float32),
                         cc.transpose(0, 2, 1, 3).astype(jnp.float32))
            return S_new, y                                   # y: (B,H,ck,hd)

        S0 = (state if state is not None
              else jnp.zeros((B, s.heads, s.state, s.head_dim), jnp.float32))
        xs = (xi_p.reshape(B, nchunks, ck, s.heads, s.head_dim).transpose(1, 0, 2, 3, 4),
              a_p.reshape(B, nchunks, ck, s.heads).transpose(1, 0, 2, 3),
              b_p.reshape(B, nchunks, ck, s.heads, s.state).transpose(1, 0, 2, 3, 4),
              c_p.reshape(B, nchunks, ck, s.heads, s.state).transpose(1, 0, 2, 3, 4))
        S_fin, ys = _inner_scan(chunk_body, S0, xs, nchunks)
        y = ys.transpose(1, 0, 3, 2, 4).reshape(B, Sp, s.heads, s.head_dim)
        y = y[:, :S].astype(x.dtype)
        new_state_out = S_fin

    y = y.reshape(B, S, s.heads * s.head_dim)
    out = jnp.einsum("bsi,id->bsd", y, p["wo"].astype(x.dtype))
    return out, new_state_out


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    ks = jax.random.split(key, 2)
    p = {"tok": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model),
                                  jnp.float32) * 0.02,
         "out": _norm_init(ks[1], (cfg.d_model, cfg.vocab))}
    a = {"tok": ("vocab", "embed"), "out": ("embed", "vocab")}
    return p, a


def embed(p: Params, tokens: jax.Array, dtype) -> jax.Array:
    return p["tok"].astype(dtype)[tokens]


def unembed(p: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,dv->bsv", x, p["out"].astype(x.dtype))
