"""Parametric paged-attention decode: each row reads only its live pages.

The serving engine keeps K/V in a shared pool of fixed-size pages, laid out
head-major ``(nk, num_pages, page_size, hd)`` so that one page of one KV
head is one whole ``(16, 128)`` bf16 tile.  A decode row ``b`` of length
``lengths[b]`` owns the pages ``block_tables[b, :ceil(length / page_size)]``;
the rest of its table is never addressed.

The kernel walks that table in HBM: the grid runs over rows and over groups
of KV heads, and inside a grid step a loop DMAs ``pages_per_block`` pages at a
time into double-buffered VMEM, for ``kv_heads`` heads per DMA, and stops at
the row's last live page.  A page past the row's length costs neither a DMA
nor a grid step, so a dead row (length 1) reads one page.  GQA runs in the
kernel: the ``group`` query heads of a KV head share its pages.  Scores come
from bf16 operands with float32 accumulation; the online softmax, the
probabilities and the P·V accumulation stay float32.

``pages_per_block`` and ``kv_heads`` are the program parameters the
comprehensive tree resolves per machine and shape.  ``kv_heads`` is a cap:
the kernel steps over the largest divisor of NK not above it, so every pick
is valid for every head count.  A tail block shorter than
``pages_per_block`` reads only its live pages and masks the rest of its
block, so the block need not divide the table.
"""
from __future__ import annotations

import functools
import math
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

NEG_INF = -1e30
_MIB = 1 << 20

# Napkin costs of the score model, in bytes of HBM traffic they are worth at
# the v5e's 819 GB/s: starting one DMA (~0.05 us), the latency of a page in
# the first, unoverlapped block of a grid step (~0.12 us), one pass of the
# block loop (~0.16 us) and one grid step (~0.35 us).  The page latency and
# the block pass are fitted to every candidate timed at both cells' decode
# shapes on a v5e (PERF.md, section 6).
_DMA_COST = 41_000
_PAGE_LATENCY = 98_000
_BLOCK_COST = 131_000
_STEP_COST = 287_000


def heads_per_step(nk: int, cap: int) -> int:
    """The largest divisor of ``nk`` not above ``cap`` (at least 1)."""
    return max(d for d in range(1, min(nk, max(cap, 1)) + 1) if nk % d == 0)


def _paged_kernel(lengths_ref, tables_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
                  ppb: int, hps: int, nblk: int, ps: int, scale: float):
    b = pl.program_id(0)
    h0 = pl.program_id(1) * hps
    length = lengths_ref[b]
    npages = (length + ps - 1) // ps
    nblocks = (npages + ppb - 1) // ppb
    bk = ppb * ps

    def page_copies(blk, slot, j):
        page = tables_ref[b * nblk + blk * ppb + j]
        return (pltpu.make_async_copy(k_hbm.at[pl.ds(h0, hps), page],
                                      kbuf.at[slot, :, j], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pl.ds(h0, hps), page],
                                      vbuf.at[slot, :, j], sems.at[1, slot]))

    def live(blk):
        return jnp.minimum(ppb, npages - blk * ppb)

    def start(blk, slot):
        def body(j, c):
            for cp in page_copies(blk, slot, j):
                cp.start()
            return c
        jax.lax.fori_loop(0, live(blk), body, 0)

    def wait(blk, slot):
        def body(j, c):
            for cp in page_copies(blk, slot, j):
                cp.wait()
            return c
        jax.lax.fori_loop(0, live(blk), body, 0)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    start(0, 0)

    def block(blk, c):
        slot = blk % 2

        @pl.when(blk + 1 < nblocks)
        def _prefetch():
            start(blk + 1, 1 - slot)

        wait(blk, slot)
        # pages past the row's length were not read this block: their
        # slots hold stale data, masked out of both products
        kpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        vpos = blk * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        for h in range(hps):
            q = q_ref[h]                                    # (group, hd)
            k = kbuf[slot, h].reshape(bk, -1)               # (bk, hd)
            if k.dtype != q.dtype:
                k = k.astype(q.dtype)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(kpos < length, s, NEG_INF)        # (group, bk)
            m_prev = m_ref[h][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[h][:, :1] * corr + jnp.sum(p, axis=-1,
                                                    keepdims=True)
            v = vbuf[slot, h].reshape(bk, -1).astype(jnp.float32)
            v = jnp.where(vpos < length, v, 0.0)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)         # (group, hd)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])
        return c

    jax.lax.fori_loop(0, nblocks, block, 0)
    for h in range(hps):
        l = l_ref[h][:, :1]
        o_ref[h] = (acc_ref[h] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def vmem_bytes(*, ppb, hps, group, hd, ps, q_itemsize: int = 2,
               kv_itemsize: int = 2):
    """VMEM the kernel holds: double-buffered K and V page blocks, the
    pipelined q and out blocks, the f32 accumulators and softmax state, and
    one head's f32 score row and upcast V block.  Integers give bytes;
    symbols (:class:`Poly`) give the family's ``vmem_bytes`` counter."""
    bk = ppb * ps
    pages = 2 * 2 * hps * bk * hd * kv_itemsize
    qo = 2 * 2 * hps * group * hd * q_itemsize
    state = 4 * hps * group * (hd + 2 * 128)
    tile = 4 * (2 * group * bk + bk * hd)
    return pages + qo + state + tile


def pallas_paged_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, lengths: jax.Array,
                           block_tables: jax.Array, *, pages_per_block: int,
                           kv_heads: int, scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, nh, hd); k_pages, v_pages: (nk, num_pages, page_size, hd);
    lengths: (B,) tokens each row attends to (positions ``0..length-1``);
    block_tables: (B, nblk) physical page of each logical page.  Returns
    (B, nh, hd) in q's dtype."""
    B, nh, hd = q.shape
    nk, _, ps, _ = k_pages.shape
    nblk = block_tables.shape[1]
    group = nh // nk
    hps = heads_per_step(nk, kv_heads)
    ppb = max(1, min(pages_per_block, nblk))
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    tables = block_tables.astype(jnp.int32).reshape(-1)
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, nblk * ps)
    qg = q.reshape(B, nk, group, hd)
    need = vmem_bytes(ppb=ppb, hps=hps, group=group, hd=hd, ps=ps,
                      q_itemsize=q.dtype.itemsize,
                      kv_itemsize=k_pages.dtype.itemsize)
    qspec = pl.BlockSpec((None, hps, group, hd),
                         lambda b, g, lens, tabs: (b, g, 0, 0))
    out = pl.pallas_call(
        functools.partial(_paged_kernel, ppb=ppb, hps=hps, nblk=nblk,
                          ps=ps, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk // hps),
            in_specs=[qspec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=qspec,
            scratch_shapes=[
                pltpu.VMEM((2, hps, ppb, ps, hd), k_pages.dtype),
                pltpu.VMEM((2, hps, ppb, ps, hd), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hps, group, 128), jnp.float32),
                pltpu.VMEM((hps, group, 128), jnp.float32),
                pltpu.VMEM((hps, group, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(16 * _MIB, need + need // 4 + 4 * _MIB)),
        name="paged_attention",
        interpret=interpret,
    )(lengths, tables, qg, k_pages, v_pages)
    return out.reshape(B, nh, hd)


class PagedAttentionFamily(CachedInstantiationMixin):
    name = "paged_attention"

    def initial_plan(self) -> KernelPlan:
        return KernelPlan(
            family=self.name,
            flags={"granularity_level": 0},
            program_params={
                "pages_per_block": ParamDomain(
                    "pages_per_block", (1, 2, 4, 8, 16, 32)),
                "kv_heads": ParamDomain("kv_heads", (1, 2, 4, 8, 16, 32)),
            },
        )

    def counters(self) -> Sequence[Counter]:
        return [
            resource("vmem_bytes", "V", ("reduce_block",),
                     "double-buffered K/V page blocks + q/out blocks + "
                     "f32 accumulators"),
            performance("page_fill", "P_occ", (),
                        "a page block no longer than the table"),
        ]

    def strategies(self) -> Sequence[Strategy]:
        def reduce_block(plan: KernelPlan):
            if plan.flags.get("granularity_level", 0) >= 1:
                return None
            p = plan.with_flag("granularity_level", 1,
                               "reduce page block and heads per step")
            p.program_params["pages_per_block"] = ParamDomain(
                "pages_per_block", (1, 2, 4))
            p.program_params["kv_heads"] = ParamDomain("kv_heads", (1, 2))
            return p

        return [Strategy("reduce_block", reduce_block)]

    def counter_value(self, plan: KernelPlan, counter: str
                      ) -> Tuple[Poly, Poly]:
        one = Poly.const(1)
        if counter == "vmem_bytes":
            return vmem_bytes(ppb=V("pages_per_block"), hps=V("kv_heads"),
                              group=V("GROUP"), hd=V("HD"), ps=V("PS")), one
        if counter == "page_fill":
            return V("pages_per_block"), V("NBLK")
        raise KeyError(counter)

    def score(self, plan: KernelPlan, v: Mapping[str, int]) -> float:
        """Ideal HBM bytes over modelled cost, for rows half-way through
        their table: every live page's bytes, plus the first block of each
        grid step (its DMAs are not overlapped), DMA starts, block-loop
        passes and grid steps, each in bytes it is worth."""
        B, nk, ps, hd = v["B"], v["NK"], v["PS"], v["HD"]
        ppb = v["pages_per_block"]
        hps = heads_per_step(nk, v["kv_heads"])
        pages = max(1, v["NBLK"] // 2)
        steps = B * (nk // hps)
        page_bytes = hps * ps * hd * 2 * 2              # K and V, bf16
        ideal = steps * pages * page_bytes
        cost = (ideal + steps * min(ppb, pages) * (page_bytes + _PAGE_LATENCY)
                + steps * pages * 2 * _DMA_COST
                + steps * math.ceil(pages / ppb) * _BLOCK_COST
                + steps * _STEP_COST)
        return ideal / cost

    def _build(self, plan: KernelPlan, assignment: Mapping[str, int],
               interpret: bool = False) -> Callable:
        return functools.partial(
            pallas_paged_attention,
            pages_per_block=int(assignment["pages_per_block"]),
            kv_heads=int(assignment["kv_heads"]), interpret=interpret)


FAMILY = PagedAttentionFamily()
