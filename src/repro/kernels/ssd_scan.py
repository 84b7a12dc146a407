"""Parametric Mamba-2 SSD chunked scan (state-space duality, arXiv 2405.21060).

The SSD insight: a selective-state-space recurrence over a chunk of length C
equals a (C×C) masked "attention" matmul (intra-chunk, MXU-friendly) plus a
rank-`state` carry between chunks.  Chunk length is the program parameter the
comprehensive tree optimizes — exactly the paper's granularity knob, with VMEM
as the binding resource (the (C×C) score tile + state carry must fit).

Grid layout: (heads, n_chunks) with the chunk axis innermost; TPU executes the
grid sequentially, so the inter-chunk state lives in VMEM scratch across grid
steps (same mechanism as the k-accumulation in matmul).  Operands are laid
out head-major so each block is a 2-D (chunk, ·) tile.
"""
from __future__ import annotations

import functools
from typing import Callable, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.counters import Counter, performance, resource
from ..core.plan import KernelPlan, ParamDomain
from ..core.polynomial import Poly, V
from ..core.strategies import Strategy
from .instantiate_cache import CachedInstantiationMixin


def ssd_chunk(xc, ac, bc, cc, S_prev):
    """One chunk of the SSD recurrence in matmul form (the XLA path of
    models/; :func:`_ssd_kernel` computes the same in 2-D tiles).

    xc: (C, hd)  ac: (C,)  bc/cc: (C, state)  S_prev: (state, hd)
    Returns (y: (C, hd), S_new: (state, hd)).  All f32.
    """
    C = xc.shape[0]
    la = jnp.log(ac)                                   # a in (0, 1)
    cum = jnp.cumsum(la)                               # (C,)
    # L[t, i] = exp(cum[t] - cum[i]) for i <= t else 0; mask BEFORE exp so the
    # (positive) upper-triangle differences can never overflow to inf.
    diff = cum[:, None] - cum[None, :]
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    L = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
    scores = (cc @ bc.T) * L                           # (C, C)
    y_intra = scores @ xc                              # (C, hd)
    y_inter = (cc * jnp.exp(cum)[:, None]) @ S_prev    # (C, hd)
    a_tot = jnp.exp(cum[-1])
    w = jnp.exp(cum[-1] - cum)                         # decay to chunk end
    S_new = a_tot * S_prev + (bc * w[:, None]).T @ xc  # (state, hd)
    return y_intra + y_inter, S_new


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, y_ref, state_ref):
    """One (head, chunk) grid step of :func:`ssd_chunk`, written in the ops
    the TPU compiler lowers: 2-D tiles and matmuls only.  The prefix sums
    of the log-decays come from a lower-triangular ones matmul, and the
    transposed copy ``cum[i]`` from a ones-by-diagonal matmul, so the
    kernel needs no 1-D vectors, cumsum or transposes."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    hi = jax.lax.Precision.HIGHEST
    xc = x_ref[...].astype(jnp.float32)                # (C, hd)
    la = la_ref[...].astype(jnp.float32)               # (C, 1) log a
    bc = b_ref[...].astype(jnp.float32)                # (C, state)
    cc = c_ref[...].astype(jnp.float32)
    C, st = bc.shape
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    tril = (row >= col).astype(jnp.float32)
    diag = (row == col).astype(jnp.float32)
    cum_t = jnp.dot(tril, jnp.broadcast_to(la, (C, C)),
                    precision=hi)                      # [t, i] = cum[t]
    cum_i = jnp.dot(jnp.ones((C, C), jnp.float32), diag * cum_t,
                    precision=hi)                      # [t, i] = cum[i]
    # mask BEFORE exp so the upper triangle cannot overflow to inf
    L = jnp.exp(jnp.where(row >= col, cum_t - cum_i, -jnp.inf))
    scores = jax.lax.dot_general(cc, bc, (((1,), (1,)), ((), ())),
                                 precision=hi) * L     # (C, C)
    cum_s = jnp.dot(tril, jnp.broadcast_to(la, (C, st)),
                    precision=hi)                      # [t, s] = cum[t]
    S_prev = state_ref[...]
    y = (jnp.dot(scores, xc, precision=hi)
         + jnp.dot(cc * jnp.exp(cum_s), S_prev, precision=hi))
    w = jnp.exp(cum_s[C - 1:C, :] - cum_s)             # decay to chunk end
    state_ref[...] = (jnp.exp(jnp.sum(la)) * S_prev
                      + jax.lax.dot_general(bc * w, xc,
                                            (((0,), (0,)), ((), ())),
                                            precision=hi))
    y_ref[...] = y.astype(y_ref.dtype)


def pallas_ssd_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
                    *, chunk: int, interpret: bool = False) -> jax.Array:
    """x: (seq, heads, hd); a: (seq, heads); b,c: (seq, heads, state).

    The operands go head-major into the kernel, so every block's last two
    dims are (chunk, hd), (chunk, 1) or (chunk, state): the TPU tiling rule
    wants them divisible by (8, 128) or equal to the array's."""
    seq, heads, hd = x.shape
    state = b.shape[-1]
    ck = min(chunk, seq)
    seq_p = -(-seq // ck) * ck
    pad = seq_p - seq
    # pad with a=1 (identity decay, log 0), x=0 so padding contributes
    # nothing
    xh = jnp.pad(x, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)
    lah = jnp.log(jnp.pad(a, ((0, pad), (0, 0)), constant_values=1.0)
                  ).T[:, :, None]
    bh = jnp.pad(b, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)
    ch = jnp.pad(c, ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)

    y = pl.pallas_call(
        _ssd_kernel,
        grid=(heads, seq_p // ck),
        in_specs=[
            pl.BlockSpec((None, ck, hd), lambda h, j: (h, j, 0)),
            pl.BlockSpec((None, ck, 1), lambda h, j: (h, j, 0)),
            pl.BlockSpec((None, ck, state), lambda h, j: (h, j, 0)),
            pl.BlockSpec((None, ck, state), lambda h, j: (h, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, ck, hd), lambda h, j: (h, j, 0)),
        out_shape=jax.ShapeDtypeStruct((heads, seq_p, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((state, hd), jnp.float32)],
        interpret=interpret,
    )(xh, lah, bh, ch)
    return y.transpose(1, 0, 2)[:seq]


class SsdScanFamily(CachedInstantiationMixin):
    name = "ssd_scan"

    def initial_plan(self) -> KernelPlan:
        return KernelPlan(
            family=self.name,
            flags={"granularity_level": 0},
            program_params={
                "chunk": ParamDomain("chunk", (64, 128, 256), align=8),
            },
        )

    def counters(self) -> Sequence[Counter]:
        return [
            resource("vmem_bytes", "V", ("reduce_chunk",),
                     "x/b/c blocks + (C,C) score tile + state carry"),
            resource("vreg_pressure", "G", ()),
            performance("occupancy", "P_occ", ("reduce_chunk",)),
        ]

    def strategies(self) -> Sequence[Strategy]:
        def reduce_chunk(plan: KernelPlan):
            if plan.flags.get("granularity_level", 0) >= 1:
                return None
            p = plan.with_flag("granularity_level", 1, "reduce chunk")
            p.program_params["chunk"] = ParamDomain("chunk", (64,), align=8)
            return p

        return [Strategy("reduce_chunk", reduce_chunk)]

    def counter_value(self, plan: KernelPlan, counter: str
                      ) -> Tuple[Poly, Poly]:
        C, hd, st = V("chunk"), V("HD"), V("STATE")
        one = Poly.const(1)
        if counter == "vmem_bytes":
            blocks = 2 * 4 * (C * hd + C + 2 * C * st)     # dbl-buffered f32
            tile = 4 * (C * C + st * hd + C * hd)
            return blocks + tile, one
        if counter == "vreg_pressure":
            return C * C / (8 * 128) + st * hd / (8 * 128), one
        if counter == "occupancy":
            return V("CORES") * C, V("SQ")
        raise KeyError(counter)

    def score(self, plan: KernelPlan, v: Mapping[str, int]) -> float:
        C = v["chunk"]
        sq = v.get("SQ", 4096)
        # bigger chunks amortize the state carry but grow the C^2 tile
        mxu_fill = min(1.0, C / 128)
        carry_amort = C / (C + v.get("STATE", 64))
        return mxu_fill * carry_amort * min(1.0, sq / C / 8)

    def _build(self, plan: KernelPlan, assignment: Mapping[str, int],
               interpret: bool = False) -> Callable:
        return functools.partial(pallas_ssd_scan,
                                 chunk=int(assignment["chunk"]),
                                 interpret=interpret)


FAMILY = SsdScanFamily()
