"""Model assembly: blocks -> stacked layers (lax.scan) -> full LM.

Covers all four block families of the assigned architectures:

  attn_mlp  — dense GQA transformer (yi, llama3, qwen, granite, chameleon)
  attn_moe  — GQA + mixture-of-experts FFN (kimi-k2, llama4-scout)
  ssm       — attention-free Mamba-2/SSD (mamba2-130m)
  hybrid    — parallel attention + SSD heads (hymba)

plus the whisper encoder-decoder (self + cross attention; audio frontend is a
stub: ``encode`` consumes precomputed frame embeddings).

Parameters are *stacked over layers* so the forward pass is a single
``lax.scan`` — the compiled HLO contains each layer body once, which keeps
dry-run compile times bounded and makes per-layer roofline extraction exact
(DESIGN.md §8).  ``cfg.remat`` wraps the scanned body in ``jax.checkpoint``.

Every ``init_*`` returns ``(params, axes)``; axes leaves are tuples of
logical axis names consumed by :mod:`repro.distributed.sharding`.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from .config import ModelConfig
from .moe import init_moe, moe_block

Params = Dict[str, Any]
Axes = Dict[str, Any]
PyTree = Any


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _has_attn(cfg: ModelConfig) -> bool:
    return cfg.block in ("attn_mlp", "attn_moe", "hybrid")


def _has_ssm(cfg: ModelConfig) -> bool:
    return cfg.block in ("ssm", "hybrid")


def _has_mlp(cfg: ModelConfig) -> bool:
    return cfg.block in ("attn_mlp", "hybrid") or (
        cfg.block == "ssm" and cfg.d_ff > 0)


def init_layer(key, cfg: ModelConfig, *, cross: bool = False
               ) -> Tuple[Params, Axes]:
    """One decoder block (``cross=True`` adds whisper cross-attention)."""
    ks = iter(jax.random.split(key, 8))
    p: Params = {}
    a: Axes = {}
    if _has_attn(cfg):
        p["ln1"], a["ln1"] = L.init_rmsnorm(cfg.d_model)
        p["attn"], a["attn"] = L.init_attention(next(ks), cfg)
    if _has_ssm(cfg):
        p["lns"], a["lns"] = L.init_rmsnorm(cfg.d_model)
        p["ssm"], a["ssm"] = L.init_ssm(next(ks), cfg)
    if cross:
        p["lnx"], a["lnx"] = L.init_rmsnorm(cfg.d_model)
        p["xattn"], a["xattn"] = L.init_attention(next(ks), cfg)
    if _has_mlp(cfg):
        p["ln2"], a["ln2"] = L.init_rmsnorm(cfg.d_model)
        p["mlp"], a["mlp"] = L.init_mlp(next(ks), cfg)
    if cfg.block == "attn_moe":
        p["ln2"], a["ln2"] = L.init_rmsnorm(cfg.d_model)
        p["moe"], a["moe"] = init_moe(next(ks), cfg)
    return p, a


def _stack_init(key, n: int, init_fn) -> Tuple[Params, Axes]:
    """vmap an init over n layer keys; prepend the "layers" logical axis."""
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, axes = init_fn(key)
    axes = jax.tree.map(lambda t: ("layers",) + tuple(t), axes,
                        is_leaf=lambda t: isinstance(t, tuple))
    return params, axes


def init_model(key, cfg: ModelConfig) -> Tuple[Params, Axes]:
    k_emb, k_layers, k_enc = jax.random.split(key, 3)
    p: Params = {}
    a: Axes = {}
    p["embed"], a["embed"] = L.init_embed(k_emb, cfg)
    cross = cfg.encoder is not None
    p["layers"], a["layers"] = _stack_init(
        k_layers, cfg.layers, functools.partial(init_layer, cfg=cfg,
                                                cross=cross))
    p["ln_f"], a["ln_f"] = L.init_rmsnorm(cfg.d_model)
    if cfg.encoder is not None:
        enc_cfg = cfg  # encoder blocks share dims with the decoder backbone
        p["enc_layers"], a["enc_layers"] = _stack_init(
            k_enc, cfg.encoder.layers,
            functools.partial(init_layer, cfg=enc_cfg, cross=False))
        p["enc_ln_f"], a["enc_ln_f"] = L.init_rmsnorm(cfg.d_model)
    if cfg.param_dtype == "bfloat16":
        # bf16 weight storage (norm scales stay f32 for stability)
        p = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16) if x.ndim >= 2 else x, p)
    return p, a


def init_params(key, cfg: ModelConfig) -> Params:
    """:func:`init_model`'s parameters, built under jit.

    Each leaf is drawn in f32 and cast to ``cfg.param_dtype`` inside one
    compiled program, so a bf16 config never holds its whole f32 tree on
    the device (Qwen1.5-4B's would take the whole of a 16 GB chip)."""
    return jax.jit(lambda k: init_model(k, cfg)[0])(key)


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Block application (train / prefill / decode share this body)
# ---------------------------------------------------------------------------

def block_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                positions: jax.Array,
                enc_out: Optional[jax.Array] = None,
                cache: Optional[Dict[str, jax.Array]] = None,
                cache_index: Optional[jax.Array] = None,
                causal: bool = True,
                block_tables: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Apply one block.  Returns (x, aux_loss, new_cache).

    ``new_cache`` mirrors the input ``cache`` pytree exactly (untouched keys
    pass through) so lax.scan / lax.while decode loops keep a stable carry
    structure.
    """
    from ..distributed import sharding as dist
    x = dist.constrain(x, ("batch", "seq", None))
    aux = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, jax.Array] = dict(cache) if cache is not None else {}

    def _residual(y):
        # 'barrier_bf16' perf flag: pin the TP all-reduce of each block
        # output at bf16 — without the barrier XLA hoists the consumer's
        # f32 upcast above the all-reduce, doubling wire bytes (§Perf A2)
        if "barrier_bf16" in cfg.perf_flags:
            return jax.lax.optimization_barrier(y)
        return y

    if cfg.block == "hybrid":
        # parallel attention + SSD heads on the same normalized input
        att, kv = L.attention(
            p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cfg,
            positions=positions,
            cache=({"k": cache["k"], "v": cache["v"]} if cache else None),
            cache_index=cache_index, causal=causal,
            block_tables=block_tables)
        ssm_state = cache.get("ssm") if cache else None
        ssd, new_state = L.ssm_block(
            p["ssm"], L.rmsnorm(p["lns"], x, cfg.norm_eps), cfg,
            state=ssm_state)
        x = x + _residual(att) + _residual(ssd)
        if kv is not None:
            new_cache.update(kv)
        if cache is not None and new_state is not None:
            new_cache["ssm"] = new_state
    elif _has_attn(cfg):
        att, kv = L.attention(
            p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cfg,
            positions=positions,
            cache=({"k": cache["k"], "v": cache["v"]} if cache else None),
            cache_index=cache_index, causal=causal,
            block_tables=block_tables)
        x = x + _residual(att)
        if kv is not None:
            new_cache.update(kv)
    elif _has_ssm(cfg):
        ssm_state = cache.get("ssm") if cache else None
        ssd, new_state = L.ssm_block(
            p["ssm"], L.rmsnorm(p["lns"], x, cfg.norm_eps), cfg,
            state=ssm_state)
        x = x + _residual(ssd)
        if cache is not None and new_state is not None:
            new_cache["ssm"] = new_state

    if "xattn" in p:  # whisper cross-attention
        if cache is not None and "ck" in cache and enc_out is None:
            # decode: K/V over the encoder output were cached at prefill
            xa, _ = L.attention(
                p["xattn"], L.rmsnorm(p["lnx"], x, cfg.norm_eps), cfg,
                positions=positions, causal=False,
                precomputed_kv=(cache["ck"], cache["cv"]))
        else:
            xa, ckv = L.attention(
                p["xattn"], L.rmsnorm(p["lnx"], x, cfg.norm_eps), cfg,
                positions=positions, causal=False, context=enc_out,
                return_kv=True)
            if cache is not None:
                ck, cv = ckv
                new_cache["ck"] = ck.astype(cache["ck"].dtype)
                new_cache["cv"] = cv.astype(cache["cv"].dtype)
        x = x + _residual(xa)

    if "moe" in p:
        moe_fn = moe_block
        if "moe_a2a" in cfg.perf_flags:
            from ..distributed import sharding as _dist
            mesh = _dist.current_mesh()
            T = x.shape[0] * x.shape[1]
            if mesh is not None and "data" in mesh.axis_names:
                import numpy as _np
                n_dev = int(_np.prod([mesh.shape[a]
                                      for a in ("data", "model")
                                      if a in mesh.axis_names]))
                if T % n_dev == 0 and T // n_dev >= 1:
                    from .moe_a2a import moe_block_a2a
                    moe_fn = moe_block_a2a
        y, aux_moe = moe_fn(p["moe"], L.rmsnorm(p["ln2"], x, cfg.norm_eps),
                            cfg)
        x = x + _residual(y)
        aux = aux + aux_moe
    elif "mlp" in p:
        x = x + _residual(L.mlp(p["mlp"], L.rmsnorm(p["ln2"], x, cfg.norm_eps)))

    return x, aux, (new_cache if new_cache else None)


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Stacked-over-layers decode cache.

    Windowed archs get a ring buffer of size ``min(window, max_len)`` —
    long-context decode memory is O(window).  SSM blocks carry a recurrent
    state instead of (or, for hybrids, in addition to) KV rows.
    """
    Lc = cfg.layers
    c: Dict[str, jax.Array] = {}
    if _has_attn(cfg):
        W = min(cfg.window, max_len) if cfg.window else max_len
        kv_shape = (Lc, batch, W, cfg.kv_heads, cfg.hd)
        c["k"] = jnp.zeros(kv_shape, dtype)
        c["v"] = jnp.zeros(kv_shape, dtype)
    if _has_ssm(cfg):
        s = cfg.ssm
        c["ssm"] = jnp.zeros((Lc, batch, s.heads, s.state, s.head_dim),
                             jnp.float32)
    if cfg.encoder is not None:
        enc_S = cfg.encoder.seq_len
        c["ck"] = jnp.zeros((Lc, batch, enc_S, cfg.kv_heads, cfg.hd), dtype)
        c["cv"] = jnp.zeros((Lc, batch, enc_S, cfg.kv_heads, cfg.hd), dtype)
    return c


def cache_spec_axes(cfg: ModelConfig) -> Dict[str, Tuple]:
    """Logical axes of each cache leaf (for sharding).

    With the 'kv_cache_hd' perf flag the head_dim carries the "kv_hd"
    logical axis: when kv_heads is not divisible by the model axis (yi=4,
    llama3/kimi=8, hymba=5 on a 16-way axis) spec_for drops the kv_heads
    entry and the cache shards evenly on head_dim instead of replicating —
    16x less cache memory per device; attention contracts hd with a small
    per-layer all-reduce (EXPERIMENTS.md §Perf, decode cells)."""
    hd_ax = "kv_hd" if "kv_cache_hd" in cfg.perf_flags else None
    out: Dict[str, Tuple] = {}
    if _has_attn(cfg):
        out["k"] = ("layers", "batch", None, "kv_heads", hd_ax)
        out["v"] = ("layers", "batch", None, "kv_heads", hd_ax)
    if _has_ssm(cfg):
        out["ssm"] = ("layers", "batch", "ssm_heads", None, None)
    if cfg.encoder is not None:
        out["ck"] = ("layers", "batch", None, "kv_heads", hd_ax)
        out["cv"] = ("layers", "batch", None, "kv_heads", hd_ax)
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        # "full" still saves the named MoE block outputs: they are small
        # ((g,t,d), same scale as the residual stream) and skipping their
        # recompute removes the out-projection all-reduce from the backward
        # pass (6.5TB/step on kimi-k2; EXPERIMENTS.md §Perf iter B5)
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.save_only_these_names(
                "moe_out"))
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    return fn


def _scan_layers(body, carry, xs, n: int, *, unroll: bool = False):
    """lax.scan over stacked layers, or a python loop when ``unroll``.

    The unrolled form exists for the roofline probes: ``cost_analysis``
    counts a while body once, so an unrolled L=2 lowering plus the scanned
    full lowering solve for (fixed, per-layer) costs exactly (DESIGN.md §8).
    """
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    ys = []
    for i in range(n):
        layer = jax.tree.map(lambda x: x[i], xs)
        carry, y = body(carry, layer)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return carry, ys


def encode(params: Params, cfg: ModelConfig, enc_embeds: jax.Array, *,
           unroll: bool = False) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (B, S_enc, d)."""
    x = enc_embeds.astype(_dtype(cfg))
    positions = jnp.arange(x.shape[1])

    def body(carry, lp):
        y, _, _ = block_apply(lp, carry, cfg, positions=positions,
                              causal=False)
        return y, None

    x, _ = _scan_layers(_remat(body, cfg), x, params["enc_layers"],
                        cfg.encoder.layers, unroll=unroll)
    return L.rmsnorm(params["enc_ln_f"], x, cfg.norm_eps)


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def forward(params: Params, cfg: ModelConfig, tokens: jax.Array, *,
            enc_embeds: Optional[jax.Array] = None,
            patch_embeds: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            unroll: bool = False,
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward (training / prefill without cache).

    Returns (logits (B,S,V), aux_loss scalar).

    - ``enc_embeds``  (whisper): precomputed audio frame embeddings.
    - ``patch_embeds`` (chameleon): precomputed VQ patch embeddings fused
      over the first P token positions (early fusion).
    """
    B, S = tokens.shape
    dtype = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dtype)
    if patch_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, patch_embeds.astype(dtype), (0, 0, 0))
    if positions is None:
        positions = jnp.arange(S)

    enc_out = None
    if cfg.encoder is not None:
        assert enc_embeds is not None, "whisper needs encoder embeddings"
        enc_out = encode(params, cfg, enc_embeds, unroll=unroll)

    def body(carry, lp):
        y, aux = carry
        y, aux_l, _ = block_apply(lp, y, cfg, positions=positions,
                                  enc_out=enc_out, causal=True)
        return (y, aux + aux_l), None

    (x, aux), _ = _scan_layers(_remat(body, cfg),
                               (x, jnp.zeros((), jnp.float32)),
                               params["layers"], cfg.layers, unroll=unroll)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x)
    return logits, aux


def prefill(params: Params, cfg: ModelConfig, tokens: jax.Array,
            cache: Dict[str, jax.Array], *,
            enc_embeds: Optional[jax.Array] = None,
            patch_embeds: Optional[jax.Array] = None,
            unroll: bool = False,
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill: full forward that also fills the decode cache.

    Returns (last-token logits (B,V), new cache).  The cache index after
    prefill is ``tokens.shape[1]`` (callers track it).
    """
    B, S = tokens.shape
    dtype = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dtype)
    if patch_embeds is not None:
        x = jax.lax.dynamic_update_slice(
            x, patch_embeds.astype(dtype), (0, 0, 0))
    positions = jnp.arange(S)
    enc_out = None
    if cfg.encoder is not None:
        assert enc_embeds is not None
        enc_out = encode(params, cfg, enc_embeds, unroll=unroll)

    idx0 = jnp.zeros((), jnp.int32)

    def body(carry, xs):
        y, aux = carry
        lp, lc = xs
        y, aux_l, nc = block_apply(lp, y, cfg, positions=positions,
                                   enc_out=enc_out, cache=lc,
                                   cache_index=idx0, causal=True)
        return (y, aux + aux_l), nc

    (x, _), new_cache = _scan_layers(
        _remat(body, cfg), (x, jnp.zeros((), jnp.float32)),
        (params["layers"], cache), cfg.layers, unroll=unroll)
    x = L.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    logits = L.unembed(params["embed"], x)[:, 0]
    return logits, new_cache


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Dict[str, jax.Array], cache_index: jax.Array, *,
                unroll: bool = False,
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step: ``tokens`` (B, 1) -> (logits (B,V), new cache).

    ``cache_index`` may be a scalar (lockstep batch decode — the dry-run
    serve shapes) or an (B,) vector (continuous batching: each pool row at
    its own offset).
    """
    B, S = tokens.shape
    assert S == 1
    dtype = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dtype)
    if jnp.ndim(cache_index) == 1:
        positions = cache_index[:, None] + jnp.arange(S)[None]
    else:
        positions = cache_index + jnp.arange(S)

    def body(carry, xs):
        lp, lc = xs
        y, _, nc = block_apply(lp, carry, cfg, positions=positions,
                               cache=lc, cache_index=cache_index,
                               causal=True)
        return y, nc

    x, new_cache = _scan_layers(body, x, (params["layers"], cache),
                                cfg.layers, unroll=unroll)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Paged (block-pool) serving path
# ---------------------------------------------------------------------------

def init_paged_cache(cfg: ModelConfig, num_blocks: int, page_size: int,
                     batch: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Block-pool decode cache for the paged serving engine.

    Attention K/V live in a shared pool of ``num_blocks`` fixed-size blocks
    of ``page_size`` token positions each — requests own non-contiguous
    block lists (their *block table*), so memory scales with live tokens,
    not ``max_batch × max_len``.  Block 0 is conventionally the garbage
    block (never allocated; dead decode rows write there).  SSM recurrent
    state is O(1) per sequence and stays per-slot, keyed by decode row.
    Encoder-decoder configs are not served by the paged engine (the CLI
    rejects them too).
    """
    if cfg.encoder is not None:
        raise ValueError("paged serving does not support encoder-decoder "
                         "configs")
    Lc = cfg.layers
    c: Dict[str, jax.Array] = {}
    if _has_attn(cfg):
        # head-major: one page of one KV head is one (page_size, hd) tile
        shape = (Lc, cfg.kv_heads, num_blocks, page_size, cfg.hd)
        c["k"] = jnp.zeros(shape, dtype)
        c["v"] = jnp.zeros(shape, dtype)
    if _has_ssm(cfg):
        s = cfg.ssm
        c["ssm"] = jnp.zeros((Lc, batch, s.heads, s.state, s.head_dim),
                             jnp.float32)
    return c


def paged_copy_block(cache: Dict[str, jax.Array], src: jax.Array,
                     dst: jax.Array) -> Dict[str, jax.Array]:
    """Copy-on-write duplication: copy physical KV block ``src`` into
    ``dst`` across every layer, for both K and V pool leaves.

    The serving engine calls this before a tick writes into a block whose
    refcount is above one (prefix-shared with another sequence or pinned
    by the prefix index): the writer gets a private copy, other owners
    keep reading the original.  Per-slot SSM state is not paged and never
    shared, so only the block-pool leaves move.  ``src``/``dst`` are
    scalar block ids — shape-stable, so the jit'd copy compiles once.
    """
    out = dict(cache)
    for key in ("k", "v"):
        if key in cache:
            out[key] = cache[key].at[:, :, dst].set(
                jax.lax.dynamic_index_in_dim(cache[key], src, axis=2,
                                             keepdims=False))
    return out


def paged_prefill_chunk(params: Params, cfg: ModelConfig, tokens: jax.Array,
                        cache: Dict[str, jax.Array], cache_index: jax.Array,
                        block_table: jax.Array, slot: jax.Array, *,
                        unroll: bool = False,
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One chunk of a paged prefill: ``tokens`` (1, C) at logical offset
    ``cache_index`` of the sequence whose block table is ``block_table``
    (1, nblk) and whose decode-pool row (SSM state) is ``slot``.

    Chunks carry no padding (the engine quantizes chunk lengths instead),
    so the recurrent SSM state threads exactly and the returned last-token
    logits of the *final* chunk equal whole-prompt prefill's.  Returns
    (last-token logits (1, V), new cache); the caller tracks the index.
    """
    B, S = tokens.shape
    dtype = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dtype)
    positions = cache_index + jnp.arange(S)
    has_ssm = _has_ssm(cfg)

    def body(carry, xs):
        lp, lc = xs
        lc_in = dict(lc)
        if has_ssm:
            lc_in["ssm"] = jax.lax.dynamic_slice_in_dim(
                lc["ssm"], slot, 1, axis=0)
        y, _, nc = block_apply(lp, carry, cfg, positions=positions,
                               cache=lc_in, cache_index=cache_index,
                               causal=True, block_tables=block_table)
        if has_ssm:
            nc["ssm"] = jax.lax.dynamic_update_slice_in_dim(
                lc["ssm"], nc["ssm"], slot, axis=0)
        return y, nc

    x, new_cache = _scan_layers(_remat(body, cfg), x,
                                (params["layers"], cache), cfg.layers,
                                unroll=unroll)
    x = L.rmsnorm(params["ln_f"], x[:, -1:], cfg.norm_eps)
    logits = L.unembed(params["embed"], x)[:, 0]
    return logits, new_cache


def paged_decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                      cache: Dict[str, jax.Array], cache_index: jax.Array,
                      block_tables: jax.Array, *,
                      ssm_mask: Optional[jax.Array] = None,
                      unroll: bool = False,
                      ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step over the paged pool: ``tokens`` (B, 1) with per-row
    ``cache_index`` (B,) and ``block_tables`` (B, nblk).

    Dead rows point their whole table at the garbage block (0) with index
    0; their writes land there and their logits are ignored by the engine.
    KV writes of non-decoding rows are harmless (garbage block), but the
    recurrent SSM state is per-slot and *would* absorb their garbage step —
    ``ssm_mask`` (B,) bool keeps the old state for rows not decoding (dead
    slots, and slots whose chunked prefill is still in flight).
    """
    B, S = tokens.shape
    assert S == 1
    dtype = _dtype(cfg)
    x = L.embed(params["embed"], tokens, dtype)
    positions = cache_index[:, None] + jnp.arange(S)[None]

    def body(carry, xs):
        lp, lc = xs
        y, _, nc = block_apply(lp, carry, cfg, positions=positions,
                               cache=lc, cache_index=cache_index,
                               causal=True, block_tables=block_tables)
        if ssm_mask is not None and "ssm" in nc:
            keep = ssm_mask[:, None, None, None]
            nc["ssm"] = jnp.where(keep, nc["ssm"], lc["ssm"])
        return y, nc

    x, new_cache = _scan_layers(body, x, (params["layers"], cache),
                                cfg.layers, unroll=unroll)
    x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    logits = L.unembed(params["embed"], x)[:, 0]
    return logits, new_cache
