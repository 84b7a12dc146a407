"""Plain float32 reference of the dense decoder of Llama and Qwen2.

It covers Yi-6B (Llama: grouped-query attention, no biases) and Qwen1.5-4B
(Qwen2: multi-head attention with q/k/v biases), as their published
``config.json`` and modelling code describe them: pre-norm RMSNorm blocks,
rotary embeddings on the rotate-half convention, causal softmax attention
with ``num_attention_heads // num_key_value_heads`` query heads to a key/value
head, a SwiGLU MLP (``down(silu(gate(x)) * up(x))``), a final RMSNorm and an
untied output head.  No cache, no batching tricks, no kernels.

Departures from the published models: the weights are random (drawn by
:func:`layer` and ``weights.outside``), and the context is cut to the
serving window (``max_len``).

Every matrix product runs at ``Precision.HIGHEST`` under
``jax.default_matmul_precision("highest")``, since a TPU otherwise multiplies
float32 in bfloat16.  The pass is blocked so it fits one chip at published
widths: layer by layer with each layer's weights drawn when it is reached,
attention in blocks of query rows, and logits in blocks of positions.

``low=True`` is the control: every projection takes float8 (e4m3) operands,
weights scaled per output column and activations per row, which is the next
precision below the bfloat16 the configurations state.

This module is also the architecture's whole interface to the harness, the
one a configuration file names with ``"reference": "attn_mlp"``; every
reference module gives the same names:

* ``dims(model)``: the sizes, read from the configuration's published keys;
* the weight recipe ``layer(dm, key, i)`` and ``outside(dm, key)``;
* ``program_layer(dm, p)``: one layer's tensors as the program's layer tree;
* ``block(dm, p, x, low)``: one layer of the plain forward pass;
* ``served_gaps(model, seed, tokens, control)``: the serving comparison;
* ``train_check(model, seed, batches, job, control, devices)``: the
  training comparison (``training.py``);
* the work count, ``step(dm, rows, logit_rows)`` for a serving step and
  ``train_step(dm, batch, seq_len)`` for a training step.
"""
from __future__ import annotations

import functools
import sys
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W
from .weights import _normal, _norm_scale, outside     # noqa: F401

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0
Q_BLOCK = 512              # query rows per attention block
LOGIT_BLOCK = 512          # positions per block of logits


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    nh = model["num_attention_heads"]
    return {
        "d": d, "nh": nh, "nk": model.get("num_key_value_heads", nh),
        "hd": model.get("head_dim") or d // nh,
        "f": model["intermediate_size"], "vocab": model["vocab_size"],
        "layers": model["num_hidden_layers"],
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
        "bias": bool(model.get("qkv_bias", False)),
    }


def layer(dm: dict, key: jax.Array, i) -> dict:
    """Layer ``i``'s tensors (float32, bfloat16-exact).  ``i`` may be traced."""
    d, nh, nk, hd, f = dm["d"], dm["nh"], dm["nk"], dm["hd"], dm["f"]
    ks = jax.random.split(jax.random.fold_in(key, i), 12)
    p = {
        "ln1": _norm_scale(ks[0], d),
        "wq": _normal(ks[1], (d, nh * hd), d ** -0.5),
        "wk": _normal(ks[2], (d, nk * hd), d ** -0.5),
        "wv": _normal(ks[3], (d, nk * hd), d ** -0.5),
        "wo": _normal(ks[4], (nh * hd, d), (nh * hd) ** -0.5),
        "ln2": _norm_scale(ks[5], d),
        "wg": _normal(ks[6], (d, f), d ** -0.5),
        "wu": _normal(ks[7], (d, f), d ** -0.5),
        "wd": _normal(ks[8], (f, d), f ** -0.5),
    }
    if dm["bias"]:
        p["bq"] = _normal(ks[9], (nh * hd,), W.BIAS_SCALE)
        p["bk"] = _normal(ks[10], (nk * hd,), W.BIAS_SCALE)
        p["bv"] = _normal(ks[11], (nk * hd,), W.BIAS_SCALE)
    return p


def program_layer(dm: dict, p: dict) -> dict:
    """One layer's tensors under the program's names (its ``attn_mlp``
    block: ``ln1``, ``attn``, ``ln2``, ``mlp``)."""
    attn = {k: p[k] for k in ("wq", "wk", "wv", "wo")}
    if dm["bias"]:
        attn.update(bq=p["bq"], bk=p["bk"], bv=p["bv"])
    return {"ln1": {"scale": p["ln1"]}, "attn": attn,
            "ln2": {"scale": p["ln2"]},
            "mlp": {"wi": p["wu"], "wg": p["wg"], "wo": p["wd"]}}


def _round_fp8(x: jax.Array, axis: int) -> jax.Array:
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``.  Its
    gradient passes straight through: the control trains on a rounded
    forward pass, and its gradients themselves are not rounded."""
    return _round_fp8(x, axis)


fp8.defvjp(lambda x, axis: (_round_fp8(x, axis), None),
           lambda axis, _, g: (g,))


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


def train_check(model: dict, seed: int, batches, job: dict,
                control: bool = False, devices=None) -> dict:
    """The training comparison (:func:`training.train_check`)."""
    from . import training
    return training.train_check(sys.modules[__name__], model, seed, batches,
                                job, control, devices)


@functools.lru_cache(maxsize=None)
def _programs(arch, frozen: tuple):
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
        "outside": jax.jit(lambda key: arch.outside(dm, key)),
        "layer": jax.jit(lambda key, i: arch.layer(dm, key, i)),
        "block": jax.jit(lambda p, x: arch.block(dm, p, x)),
        "block_low": jax.jit(lambda p, x: arch.block(dm, p, x, low=True)),
        "gaps": jax.jit(gaps),
        "gaps_low": jax.jit(gaps_low),
    }


def teacher_forced_gaps(arch, model: dict, seed: int, tokens: np.ndarray,
                        control: bool = False):
    """Teacher-forced pass of the architecture ``arch`` (a reference module)
    over ``tokens`` (K, T) with the weights of ``seed``.

    Returns ``(gap, gap_low)``, each (K, T): at position t, the reference's
    best logit less its logit of ``tokens[:, t + 1]`` (the last column is 0),
    and, with ``control``, less its logit of the control's first choice
    (else ``None``).  T must be a multiple of the blocks."""
    dm = arch.dims(model)
    fn = _programs(arch, tuple(sorted(dm.items())))
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


def served_gaps(model: dict, seed: int, tokens: np.ndarray,
                control: bool = False):
    """:func:`teacher_forced_gaps` of this architecture."""
    return teacher_forced_gaps(sys.modules[__name__], model, seed, tokens,
                               control)


# -- the work count ----------------------------------------------------------
#
# What a step has to do is counted from the work alone, never from how the
# program does it (see ``chipbench/work.py``): every weight is read once a
# serving step, each row reads the keys and values of its ``start`` earlier
# positions and writes its ``n`` new ones, and attention covers each new
# token's causal prefix.

PARAM_BYTES = 2            # bfloat16 weights, as the configurations serve
KV_BYTES = 2               # bfloat16 key/value cache


def layer_matmul_params(dm: dict) -> int:
    d, nh, nk, hd, f = dm["d"], dm["nh"], dm["nk"], dm["hd"], dm["f"]
    return d * nh * hd + 2 * d * nk * hd + nh * hd * d + 3 * d * f


def weight_bytes(dm: dict) -> int:
    """Bytes of the weights one step reads: every layer, the final norm and
    the output head (the embedding is gathered by row, counted per token)."""
    d, nh, nk, hd = dm["d"], dm["nh"], dm["nk"], dm["hd"]
    per_layer = layer_matmul_params(dm) + 2 * d
    if dm["bias"]:
        per_layer += nh * hd + 2 * nk * hd
    return PARAM_BYTES * (dm["layers"] * per_layer + d + d * dm["vocab"])


def kv_bytes_per_position(dm: dict) -> int:
    return dm["layers"] * 2 * dm["nk"] * dm["hd"] * KV_BYTES


def step(dm: dict, rows: Iterable[Tuple[int, int]], logit_rows: int
         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step over ``rows`` of (start, n); the output
    head runs for ``logit_rows`` tokens (one per decode row, one per prefill
    chunk)."""
    L, nh, hd, d = dm["layers"], dm["nh"], dm["hd"], dm["d"]
    flops = 2.0 * d * dm["vocab"] * logit_rows
    nbytes = float(weight_bytes(dm))
    kv = kv_bytes_per_position(dm)
    mm = layer_matmul_params(dm) * L
    for start, n in rows:
        # QK^T and PV: 2 * hd each, per head, per (query, key) pair
        pairs = n * start + n * (n + 1) // 2
        flops += 2.0 * mm * n + 4.0 * L * nh * hd * pairs
        nbytes += kv * (start + n) + PARAM_BYTES * d * n
    return flops, nbytes


def train_step(dm: dict, batch: int, seq_len: int) -> float:
    """FLOPs of one training step over ``batch`` sequences of ``seq_len``:
    the forward pass and its backward (twice the forward), with attention
    over each token's causal prefix."""
    L = dm["layers"]
    tokens = batch * seq_len
    pairs = batch * seq_len * (seq_len + 1) // 2
    fwd = (2.0 * (L * layer_matmul_params(dm) + dm["d"] * dm["vocab"])
           * tokens + 4.0 * L * dm["nh"] * dm["hd"] * pairs)
    return 3.0 * fwd
