"""Public jit'd wrappers: every call goes through the comprehensive tree.

``impl`` resolution:
  "pallas"  — instantiate the selected leaf's Pallas kernel (TPU target; on
              CPU pass ``interpret=True``, which tests do).
  "xla"     — the pure-jnp oracle path (used by the model stack on the CPU
              container and by the dry-run, where Pallas cannot lower).
  "auto"    — pallas on TPU backends, xla elsewhere.

The *selection* (which leaf, which block sizes) is identical for both impls,
so CPU tests exercise the same decision path the TPU build would take.

Warm-path fast lane: each pallas op builds its data mapping as an items
tuple and calls ``DispatchCache.warm_callable`` — one lock-free dict lookup
returning the pre-built kernel callable when the triple was frozen
(``DispatchCache.freeze``, fed by serving warm-up), else a locked LRU
resolve plus the family's *memoized* ``instantiate``.  Either way the
steady state performs zero ``pallas_call``/partial rebuilds and hands jax
an identity-stable callable, so jit tracing keys do not churn
(``get_default_cache`` itself is a lock-free read once installed).
"""
from __future__ import annotations

from typing import Mapping, Optional

import jax

from ..artifacts.dispatch import get_default_cache
from ..core.params import MachineDescription, default_machine
from ..core.select import Candidate
from . import ref
from .flash_attention import FAMILY as FLASH_FAMILY
from .jacobi1d import FAMILY as JACOBI_FAMILY
from .matadd import FAMILY as MATADD_FAMILY
from .matmul import FAMILY as MATMUL_FAMILY
from .paged_attention import FAMILY as PAGED_FAMILY
from .ssd_scan import FAMILY as SSD_FAMILY
from .transpose import FAMILY as TRANSPOSE_FAMILY

FAMILIES = {f.name: f for f in (MATMUL_FAMILY, MATADD_FAMILY, JACOBI_FAMILY,
                                TRANSPOSE_FAMILY, FLASH_FAMILY, SSD_FAMILY,
                                PAGED_FAMILY)}


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def select(family_name: str, data: Mapping[str, int],
           machine: Optional[MachineDescription] = None) -> Candidate:
    """Resolve the kernel variant through the process-wide DispatchCache,
    for ``machine`` or, by default, the machine this process runs on
    (:func:`repro.core.params.default_machine`).

    Steady-state (the serving hot path) this is one lock-free frozen-plan
    lookup when the triple was frozen at warm-up, else one LRU lookup; a
    full miss falls back to the precompiled per-machine dispatch artifact,
    and only a shape never compiled offline pays for tree enumeration."""
    return get_default_cache().best_variant(FAMILIES[family_name],
                                            machine or default_machine(),
                                            data)


# -- matmul -------------------------------------------------------------------

def matmul(a: jax.Array, b: jax.Array, *, impl: str = "auto",
           machine: Optional[MachineDescription] = None,
           interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.matmul(a, b)
    M, K = a.shape
    N = b.shape[1]
    fn = get_default_cache().warm_callable(
        MATMUL_FAMILY, machine or default_machine(),
        (("M", M), ("N", N), ("K", K)), interpret)
    return fn(a, b)


# -- matadd -------------------------------------------------------------------

def matadd(a: jax.Array, b: jax.Array, *, impl: str = "auto",
           machine: Optional[MachineDescription] = None,
           interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.matadd(a, b)
    M, N = a.shape
    fn = get_default_cache().warm_callable(
        MATADD_FAMILY, machine or default_machine(), (("M", M), ("N", N)),
        interpret)
    return fn(a, b)


# -- jacobi1d -------------------------------------------------------------------

def jacobi1d(x: jax.Array, steps: int, *, impl: str = "auto",
             machine: Optional[MachineDescription] = None,
             interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.jacobi1d(x, steps)
    (n,) = x.shape
    fn = get_default_cache().warm_callable(
        JACOBI_FAMILY, machine or default_machine(), (("N", n),), interpret)
    return fn(x, steps)


# -- transpose -----------------------------------------------------------------

def transpose(a: jax.Array, *, impl: str = "auto",
              machine: Optional[MachineDescription] = None,
              interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.transpose(a)
    M, N = a.shape
    fn = get_default_cache().warm_callable(
        TRANSPOSE_FAMILY, machine or default_machine(), (("M", M), ("N", N)),
        interpret)
    return fn(a)


# -- flash attention -----------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    impl: str = "auto",
                    machine: Optional[MachineDescription] = None,
                    interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.flash_attention(q, k, v, causal=causal, window=window)
    h, sq, d = q.shape
    fn = get_default_cache().warm_callable(
        FLASH_FAMILY, machine or default_machine(), (("SQ", sq), ("HD", d)),
        interpret)
    return fn(q, k, v, causal=causal, window=window)


# -- paged attention (decode) ---------------------------------------------------

def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    lengths: jax.Array, block_tables: jax.Array, *,
                    impl: str = "auto",
                    machine: Optional[MachineDescription] = None,
                    interpret: bool = False) -> jax.Array:
    """One decode token per row over a head-major page pool: q (B, nh, hd),
    pools (nk, num_pages, page_size, hd), lengths (B,), block_tables
    (B, nblk)."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.paged_attention(q, k_pages, v_pages, lengths, block_tables)
    B, nh, hd = q.shape
    nk, _, ps, _ = k_pages.shape
    fn = get_default_cache().warm_callable(
        PAGED_FAMILY, machine or default_machine(),
        (("B", B), ("NK", nk), ("GROUP", nh // nk), ("HD", hd), ("PS", ps),
         ("NBLK", block_tables.shape[1])), interpret)
    return fn(q, k_pages, v_pages, lengths, block_tables)


# -- SSD scan --------------------------------------------------------------------

def ssd_scan(x: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, *,
             impl: str = "auto",
             machine: Optional[MachineDescription] = None,
             interpret: bool = False) -> jax.Array:
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.ssd_scan(x, a, b, c)
    seq, heads, hd = x.shape
    state = b.shape[-1]
    fn = get_default_cache().warm_callable(
        SSD_FAMILY, machine or default_machine(),
        (("SQ", seq), ("HD", hd), ("STATE", state)), interpret)
    return fn(x, a, b, c)
