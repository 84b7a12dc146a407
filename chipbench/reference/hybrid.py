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
global layers), the SSD path has no convolution, no output gate and no
skip term, and the output head is not tied to the embedding.

The interface is ``attn_mlp``'s (see its docstring): the same names, with
the attention + SwiGLU tensors of ``attn_mlp.layer`` plus ``lns`` and the
SSD tensors, drawn from ``fold_in`` data of their own.  The pass is blocked
to fit: attention in blocks of query rows over the keys of their window,
the recurrence in chunks, each recomputed for its gradient.
"""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from . import attn_mlp
from .attn_mlp import (HIGHEST, Q_BLOCK, fp8, logits, mm,  # noqa: F401
                       rmsnorm, rope)
from .weights import _bf16, _normal, _norm_scale, outside          # noqa: F401

#: ``fold_in`` data of the SSD tensors (``attn_mlp.layer`` folds in ``i``)
_SSD = 1_000_004
SSD_CHUNK = 64             # recurrence steps recomputed together


def dims(model: dict) -> dict:
    dm = attn_mlp.dims(model)
    dm.update(window=int(model["sliding_window"]),
              ssm_heads=int(model["ssd_heads"]),
              ssm_head_dim=int(model["ssd_head_dim"]),
              ssm_state=int(model["mamba_d_state"]))
    return dm


def layer(dm: dict, key: jax.Array, i) -> dict:
    """Layer ``i``'s tensors (float32, bfloat16-exact): ``attn_mlp.layer``'s,
    ``lns``, and the SSD's ``sx`` (input), ``sb``/``sc`` (B and C), ``sa``
    and ``s_bias`` (decay), ``so`` (output)."""
    d, H, hd, N = dm["d"], dm["ssm_heads"], dm["ssm_head_dim"], dm["ssm_state"]
    p = attn_mlp.layer(dm, key, i)
    ks = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, _SSD), i),
                          7)
    p.update(
        lns=_norm_scale(ks[0], d),
        sx=_normal(ks[1], (d, H * hd), d ** -0.5),
        sb=_normal(ks[2], (d, N), d ** -0.5),
        sc=_normal(ks[3], (d, N), d ** -0.5),
        sa=_normal(ks[4], (d, H), 0.1 * d ** -0.5),
        # decays sigmoid(2 + N(0, 1)): memories of 2 to about 50 steps
        s_bias=_bf16(2.0 + jax.random.normal(ks[5], (H,))),
        so=_normal(ks[6], (H * hd, d), (N * H * hd) ** -0.5))
    return p


def program_layer(dm: dict, p: dict) -> dict:
    """One layer's tensors under the program's names (its ``hybrid``
    block: ``attn_mlp``'s plus ``lns`` and ``ssm``)."""
    t = attn_mlp.program_layer(dm, p)
    t.update(lns={"scale": p["lns"]},
             ssm={"wx": p["sx"], "wb": p["sb"], "wc": p["sc"], "wa": p["sa"],
                  "a_bias": p["s_bias"], "wo": p["so"]})
    return t


def windowed_attention(q, k, v, window: int, low: bool = False):
    """q: (B, S, nh, hd); k, v: (B, S, nk, hd) -> (B, S, nh * hd).  A query
    sees itself and the ``window - 1`` positions before it.  Each block of
    query rows reads only the keys of its window, and is recomputed for
    its gradient.  ``low``: queries, keys, values and probabilities in
    float8, as the control computes every product the program computes in
    bfloat16."""
    if low:
        q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, -1)
    B, S, nh, hd = q.shape
    nk = k.shape[2]
    g = nh // nk
    qb = min(Q_BLOCK, S)
    assert S % qb == 0, (S, qb)
    band = window + qb
    pad = ((0, 0), (window, 0), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)     # key j sits at j + window
    qs = q.reshape(B, S // qb, qb, nk, g, hd).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def blk(qc, start):
        kc = jax.lax.dynamic_slice_in_dim(kp, start, band, 1)
        vc = jax.lax.dynamic_slice_in_dim(vp, start, band, 1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc,
                       precision=HIGHEST) / np.sqrt(hd)
        qpos = start + jnp.arange(qb)
        kpos = start - window + jnp.arange(band)
        seen = ((kpos[None, :] <= qpos[:, None])
                & (kpos[None, :] > qpos[:, None] - window)
                & (kpos[None, :] >= 0))
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = fp8(p, -1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, vc, precision=HIGHEST)

    out = jax.lax.map(lambda a: blk(*a), (qs, jnp.arange(S // qb) * qb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, nh * hd)


def ssd(dm: dict, p: dict, x: jax.Array, low: bool = False) -> jax.Array:
    """x: (B, S, d) normalized input -> (B, S, d)."""
    B, S, _ = x.shape
    H, hd = dm["ssm_heads"], dm["ssm_head_dim"]
    xi = mm(x, p["sx"], low).reshape(B, S, H, hd)
    b, c = mm(x, p["sb"], low), mm(x, p["sc"], low)            # (B, S, N)
    a = jax.nn.sigmoid(mm(x, p["sa"], low) + p["s_bias"])       # (B, S, H)
    if low:                      # the recurrence's operands, as ``mm``'s
        xi, b, c = fp8(xi, -1), fp8(b, -1), fp8(c, -1)

    def step(state, t):
        xt, bt, ct, at = t                                 # (B,H,hd) (B,N)..
        state = at[..., None, None] * state + jnp.einsum(
            "bn,bhd->bhnd", bt, xt, precision=HIGHEST)
        return state, jnp.einsum("bn,bhnd->bhd", ct, state, precision=HIGHEST)

    @jax.checkpoint
    def chunk(state, seq):
        return jax.lax.scan(step, state, seq)

    T = math.gcd(S, SSD_CHUNK)
    state = jnp.zeros((B, H, b.shape[-1], hd), jnp.float32)
    seq = (xi.transpose(1, 0, 2, 3), b.transpose(1, 0, 2),
           c.transpose(1, 0, 2), a.transpose(1, 0, 2))
    seq = jax.tree.map(lambda z: z.reshape((S // T, T) + z.shape[1:]), seq)
    _, y = jax.lax.scan(chunk, state, seq)               # (S/T, T, B, H, hd)
    y = y.reshape(S, B, H * hd).transpose(1, 0, 2)
    return mm(y, p["so"], low)


def block(dm: dict, p: dict, x: jax.Array, low: bool = False) -> jax.Array:
    """One hybrid layer over x: (B, S, d), positions 0..S-1."""
    B, S, _ = x.shape
    nh, nk, hd = dm["nh"], dm["nk"], dm["hd"]
    pos = jnp.arange(S)
    h = rmsnorm(x, p["ln1"], dm["eps"])
    q = rope(mm(h, p["wq"], low).reshape(B, S, nh, hd), pos, dm["theta"])
    k = rope(mm(h, p["wk"], low).reshape(B, S, nk, hd), pos, dm["theta"])
    v = mm(h, p["wv"], low).reshape(B, S, nk, hd)
    att = mm(windowed_attention(q, k, v, dm["window"], low), p["wo"], low)
    s = ssd(dm, p, rmsnorm(x, p["lns"], dm["eps"]), low)
    x = x + att + s
    h = rmsnorm(x, p["ln2"], dm["eps"])
    g = mm(h, p["wg"], low)
    return x + mm(g * jax.nn.sigmoid(g) * mm(h, p["wu"], low), p["wd"], low)


def served_gaps(model: dict, seed: int, tokens: np.ndarray,
                control: bool = False):
    """``attn_mlp.teacher_forced_gaps`` of this architecture."""
    return attn_mlp.teacher_forced_gaps(sys.modules[__name__], model, seed,
                                        tokens, control)


def train_check(model: dict, seed: int, batches, job: dict,
                control: bool = False, devices=None) -> dict:
    """The training comparison (:func:`training.train_check`)."""
    from . import training
    return training.train_check(sys.modules[__name__], model, seed, batches,
                                job, control, devices)


# -- the work count (as ``attn_mlp``'s, with the window and the SSD) --------

STATE_BYTES = 4            # the SSD state is kept in float32


def layer_matmul_params(dm: dict) -> int:
    d, H, hd, N = dm["d"], dm["ssm_heads"], dm["ssm_head_dim"], dm["ssm_state"]
    return (attn_mlp.layer_matmul_params(dm)
            + d * H * hd + 2 * d * N + d * H + H * hd * d)


def _ssd_flops(dm: dict) -> int:
    """Recurrence FLOPs of one token in one layer: ``B x^T`` into the state
    and ``C S`` out of it."""
    return 4 * dm["ssm_heads"] * dm["ssm_state"] * dm["ssm_head_dim"]


def window_pairs(start: int, n: int, window: int) -> int:
    """(query, key) pairs of ``n`` new tokens from position ``start``, each
    seeing itself and at most ``window - 1`` earlier positions."""
    return sum(min(t + 1, window) for t in range(start, start + n))


def step(dm: dict, rows, logit_rows: int):
    """(FLOPs, bytes) of one serving step over ``rows`` of (start, n): the
    weights once, each row's keys and values inside its window, its SSD
    state read and written, attention over the window."""
    L, nh, hd, d, W = dm["layers"], dm["nh"], dm["hd"], dm["d"], dm["window"]
    flops = 2.0 * d * dm["vocab"] * logit_rows
    nbytes = float(attn_mlp.PARAM_BYTES * (
        dm["layers"] * (layer_matmul_params(dm) + 3 * d + dm["ssm_heads"])
        + d + d * dm["vocab"]))
    kv = attn_mlp.kv_bytes_per_position(dm)
    state = (L * dm["ssm_heads"] * dm["ssm_state"] * dm["ssm_head_dim"]
             * STATE_BYTES)
    mm_ = layer_matmul_params(dm) * L
    for start, n in rows:
        pairs = window_pairs(start, n, W)
        flops += (2.0 * mm_ * n + 4.0 * L * nh * hd * pairs
                  + L * _ssd_flops(dm) * n)
        nbytes += (kv * (min(start, W - 1) + n) + 2 * state
                   + attn_mlp.PARAM_BYTES * d * n)
    return flops, nbytes


def train_step(dm: dict, batch: int, seq_len: int) -> float:
    """FLOPs of one training step over ``batch`` sequences of ``seq_len``:
    the forward pass and its backward (twice the forward); attention counts
    only the pairs inside each query's window."""
    L = dm["layers"]
    tokens = batch * seq_len
    pairs = batch * window_pairs(0, seq_len, dm["window"])
    fwd = (2.0 * (L * layer_matmul_params(dm) + dm["d"] * dm["vocab"])
           * tokens + 4.0 * L * dm["nh"] * dm["hd"] * pairs
           + L * _ssd_flops(dm) * tokens)
    return 3.0 * fwd

