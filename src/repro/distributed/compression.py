"""Gradient compression: int8 ring all-reduce over the pod axis.

Inter-pod links are the slowest tier (DCI < ICI), so the pure-DP gradient
all-reduce across pods is the natural compression target.  We quantize each
block to int8 with a per-tensor f32 scale (stochastic rounding to keep the
estimator unbiased), run a ring exchange over the pod axis inside
``shard_map``, and dequantize.  4x fewer bytes on the slow links for <1%
gradient RMS error (tests/test_distributed.py checks the numerics).

The public entry is :func:`compressed_psum_pod`, used by the train-step
builder when ``grad_compression="int8"``.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def _quantize(x: jax.Array, key: jax.Array):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    y = x / scale
    # stochastic rounding: unbiased under expectation
    noise = jax.random.uniform(key, y.shape, jnp.float32, -0.5, 0.5)
    q = jnp.clip(jnp.round(y + noise), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def _ring_allreduce_int8(x: jax.Array, key: jax.Array, axis: str
                         ) -> jax.Array:
    """All-reduce of f32 ``x`` over ``axis`` moving int8 on the wire."""
    # jax.lax.axis_size is not available on every supported jax; psum of 1
    # over the axis is the portable spelling of the same number.
    n = int(jax.lax.psum(1, axis))
    idx = jax.lax.axis_index(axis)
    q, scale = _quantize(x, jax.random.fold_in(key, idx))
    acc = _dequantize(q, scale)           # own (quantized) contribution
    perm = [(i, (i + 1) % n) for i in range(n)]
    cur_q, cur_s = q, scale
    for _ in range(n - 1):
        cur_q = jax.lax.ppermute(cur_q, axis, perm)
        cur_s = jax.lax.ppermute(cur_s, axis, perm)
        acc = acc + _dequantize(cur_q, cur_s)
    return acc


def compressed_psum_pod(grads: PyTree, mesh: Mesh, key: jax.Array) -> PyTree:
    """psum over the 'pod' axis with int8 wire format.

    Input grads must already be summed within each pod (the usual GSPMD
    all-reduce over 'data'/'model'); this handles only the inter-pod hop.
    Leaves keep their sharding over the other axes (``P`` below only names
    the pod axis; shard_map treats the rest as replicated-per-shard).
    """
    if "pod" not in mesh.axis_names:
        return grads

    def one(leaf_key, g):
        spec = P(*(("pod",) + (None,) * (g.ndim - 1))) if g.ndim else P()
        # grads are replicated over pod on entry -> use P() in/out with the
        # reduction done on fully-addressable shards
        fn = jax.shard_map(
            functools.partial(_ring_allreduce_int8, axis="pod"),
            mesh=mesh, in_specs=(P(), P()), out_specs=P(),
            check_vma=False)
        return fn(g.astype(jnp.float32), leaf_key).astype(g.dtype)

    leaves, treedef = jax.tree.flatten(grads)
    keys = jax.random.split(key, len(leaves))
    out = [one(k, g) for k, g in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, out)
