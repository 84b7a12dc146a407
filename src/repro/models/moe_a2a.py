"""Hand-written all-to-all MoE dispatch (shard_map) — the 'moe_a2a' flag.

EXPERIMENTS.md §Perf B shows GSPMD lowers the GShard dispatch to full
all-gathers (~5x the intrinsic dispatch bytes on kimi-k2).  This module
writes the collective schedule by hand, the way DeepSpeed-MoE / MaxText
expert-parallel paths do:

  * experts are distributed over ALL mesh devices (data x model), padded up
    to a multiple of the device count (kimi: 384 -> 512, 2 per device;
    phantom experts receive no tokens and their capacity rows are zeros);
  * each device routes its own token groups locally, builds the dispatched
    tensor (G_local, E, C, d), and a single `lax.all_to_all` over
    (data, model) exchanges it for (G, E_local, C, d): every device then
    holds ALL token groups for ITS experts;
  * the expert FFN is fully local — d and f are unsharded, so there is no
    TP all-reduce on the k*cf-inflated tensor at all;
  * a second all_to_all brings expert outputs home; combine is local.

Wire bytes per device per call ~= 2 x |dispatched tensor| x (n-1)/n — the
intrinsic top-k dispatch cost, nothing else.

Constraints: token count per device must be >= 1 group (decode-sized
batches fall back to the dense GShard path), and E must divide by the
device count after padding.  Gradient flow works through shard_map +
all_to_all (both differentiable).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .moe import capacity

Params = Dict[str, Any]


def _routing(xg, router, E, k, C, dtype):
    """Local GShard routing: returns (dispatch, combine, probs) for one
    shard's groups.  xg: (G_l, gsz, d)."""
    logits = jnp.einsum("gtd,de->gte", xg, router.astype(dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, E, dtype=jnp.float32)
    G_l, gsz = xg.shape[0], xg.shape[1]
    flat = onehot.reshape(G_l, gsz * k, E)
    pos = jnp.cumsum(flat, axis=1) - flat
    pos = pos.reshape(G_l, gsz, k, E)
    pos_k = jnp.sum(pos * onehot, axis=-1)
    fits = (pos_k < C) & (jnp.sum(onehot, -1) > 0)
    pos_oh = jax.nn.one_hot(pos_k.astype(jnp.int32), C,
                            dtype=jnp.float32) * fits[..., None]
    dispatch = jnp.einsum("gtke,gtkc->gtec", onehot, pos_oh)
    combine = jnp.einsum("gtke,gtkc,gtk->gtec", onehot, pos_oh, gates)
    return dispatch, combine, probs


def moe_block_a2a(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  group_size: int = 1024) -> Tuple[jax.Array, jax.Array]:
    """Drop-in replacement for moe_block using explicit all_to_all.

    Requires an active mesh with a 'data' axis; otherwise (and for
    decode-sized token counts) the caller should use the dense path.
    """
    from ..distributed import sharding as dist
    mesh = dist.current_mesh()
    m = cfg.moe
    B, S, d = x.shape
    E, k = m.num_experts, m.top_k
    T = B * S

    axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]
    E_store = p["wi"].shape[0]
    if E_store > E and E_store % n_dev == 0:
        # weights stored pre-padded in the a2a layout (init_moe under the
        # flag): zero weight resharding inside the shard_map — the fix for
        # §Perf iter B6's 33.8 GB/layer/mb regression
        E_pad = E_store
        pre_padded = True
    else:
        E_pad = -(-E // n_dev) * n_dev
        pre_padded = False
    E_l = E_pad // n_dev

    # groups: one shard of tokens per device along 'data'; the 'model'
    # ranks subdivide those groups so the a2a runs over both axes
    n_data = mesh.shape.get("data", 1)
    n_model = mesh.shape.get("model", 1)
    gsz = min(group_size, max(1, T // n_dev))
    G = T // gsz
    assert T % gsz == 0 and G % n_dev == 0, (
        f"moe_a2a needs tokens to tile over {n_dev} devices: T={T} gsz={gsz}")
    C = capacity(gsz, E, k, m.capacity_factor)

    xg = x.reshape(G, gsz, d)

    def local(xg_l, router, wi_l, wg_l, wo_l):
        # xg_l: (G/n_dev, gsz, d); w*_l: (E_l, d, f) own experts
        G_l = xg_l.shape[0]
        dtype = xg_l.dtype
        dispatch, combine, probs = _routing(xg_l, router, E, k, C, dtype)
        # pad expert dim to E_pad (phantom experts receive no tokens)
        pad = E_pad - E
        disp_p = jnp.pad(dispatch, ((0, 0), (0, 0), (0, pad), (0, 0)))
        xin = jnp.einsum("gtec,gtd->gecd", disp_p.astype(dtype), xg_l)
        # exchange: split the expert dim n_dev-ways, concat on groups —
        # every device then holds ALL token groups for ITS E_l experts
        xin = jax.lax.all_to_all(xin, axes, split_axis=1, concat_axis=0,
                                 tiled=True)            # (G, E_l, C, d)
        h = jnp.einsum("gecd,edf->gecf", xin, wi_l.astype(dtype))
        g = jnp.einsum("gecd,edf->gecf", xin, wg_l.astype(dtype))
        h = jax.nn.silu(g) * h
        out = jnp.einsum("gecf,efd->gecd", h, wo_l.astype(dtype))
        # inverse exchange: outputs come home, experts re-concatenate
        out = jax.lax.all_to_all(out, axes, split_axis=0, concat_axis=1,
                                 tiled=True)            # (G_l, E_pad, C, d)
        out = out[:, :E]
        y_l = jnp.einsum("gtec,gecd->gtd", combine.astype(dtype), out)
        # load-balance stats (global means via psum over all axes)
        ft = jnp.mean(jax.nn.one_hot(
            jnp.argmax(probs, -1), E, dtype=jnp.float32), axis=(0, 1))
        fp = jnp.mean(probs, axis=(0, 1))
        ft = jax.lax.pmean(ft, axes)
        fp = jax.lax.pmean(fp, axes)
        aux = E * jnp.sum(ft * fp)
        return y_l, aux

    # weights: experts padded then split over (data, model)
    def pad_w(w):
        if pre_padded:
            return w
        return jnp.pad(w, ((0, E_pad - E), (0, 0), (0, 0)))

    espec = P(axes if len(axes) > 1 else axes[0])
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axes if len(axes) > 1 else axes[0], None, None),
                  P(None, None),
                  P(*espec, None, None), P(*espec, None, None),
                  P(*espec, None, None)),
        out_specs=(P(axes if len(axes) > 1 else axes[0], None, None), P()),
        check_vma=False)
    y, aux = fn(xg, p["router"],
                pad_w(p["wi"]), pad_w(p["wg"]), pad_w(p["wo"]))
    return y.reshape(B, S, d), aux
