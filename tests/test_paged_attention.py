"""The ``paged_attention`` family: the decode kernel against its gather
oracle (interpret mode), the case discussion's picks, and the routing that
sends only paged decode steps over bf16 pools on Pallas to the kernel."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import PAPER_M2050, TPU_V5E
from repro.core.select import enumerate_candidates
from repro.kernels import ops, ref
from repro.kernels.paged_attention import (FAMILY, heads_per_step,
                                           pallas_paged_attention,
                                           vmem_bytes)
from repro.models import (init_model, init_paged_cache, paged_decode_step,
                          paged_prefill_chunk)
from repro.models.layers import paged_kernel_serves

PS = 16
NBLK = 34                        # 544 positions: every block size is ragged


def _operands(nh, nk, lengths, *, seed=0, hd=128, pool=48):
    """bf16 q and pools, and block tables whose live pages are scattered
    over the pool; rows 0 and 1 share (repeat) one physical page."""
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, nh, hd)).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (nk, pool, PS, hd)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (nk, pool, PS, hd)).astype(jnp.bfloat16)
    rng = np.random.default_rng(seed)
    tables = np.stack([rng.permutation(np.arange(1, pool))[:NBLK]
                       for _ in range(B)]).astype(np.int32)
    tables[1, 0] = tables[0, 0]
    return q, kp, vp, jnp.asarray(lengths, jnp.int32), jnp.asarray(tables)


@pytest.mark.parametrize("ppb", FAMILY.initial_plan()
                         .program_params["pages_per_block"].candidates)
@pytest.mark.parametrize("nh,nk,cap", [(2, 2, 2), (16, 2, 1)],
                         ids=["mha", "gqa8"])
def test_kernel_matches_gather_oracle(nh, nk, cap, ppb):
    # a dead row (1), exactly a page, one past a page-block boundary, and
    # the whole table
    lengths = [1, PS, min(ppb * PS + 1, NBLK * PS - 3), NBLK * PS]
    q, kp, vp, lens, tables = _operands(nh, nk, lengths, seed=ppb)
    got = pallas_paged_attention(q, kp, vp, lens, tables,
                                 pages_per_block=ppb, kv_heads=cap,
                                 interpret=True)
    want = ref.paged_attention(q, kp, vp, lens, tables)
    assert got.shape == want.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=1e-2)


def test_ops_dispatch_runs_the_pick_in_interpret_mode():
    q, kp, vp, lens, tables = _operands(16, 2, [3, 300])
    got = ops.paged_attention(q, kp, vp, lens, tables, impl="pallas",
                              interpret=True)
    want = ops.paged_attention(q, kp, vp, lens, tables, impl="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=1e-2)


SHAPES = {
    "qwen1.5-4b": {"B": 8, "NK": 20, "GROUP": 1, "HD": 128, "PS": 16,
                   "NBLK": 256},
    "yi-6b": {"B": 16, "NK": 4, "GROUP": 8, "HD": 128, "PS": 16,
              "NBLK": 128},
    "short": {"B": 4, "NK": 8, "GROUP": 4, "HD": 64, "PS": 16, "NBLK": 3},
}


@pytest.mark.parametrize("machine", [TPU_V5E, PAPER_M2050],
                         ids=lambda m: m.name)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_pick_fits_vmem_and_covers_the_table(shape, machine):
    """Every candidate the case discussion admits fits the machine's VMEM
    as the kernel allocates it, and its page block is no longer than the
    table (a tail block that does not divide it is masked)."""
    data = SHAPES[shape]
    cands = enumerate_candidates(FAMILY, machine, data)
    assert cands, (shape, machine.name)
    for c in cands:
        ppb, cap = c.assignment["pages_per_block"], c.assignment["kv_heads"]
        hps = heads_per_step(data["NK"], cap)
        assert data["NK"] % hps == 0 and hps <= cap
        assert ppb <= data["NBLK"]
        need = vmem_bytes(ppb=ppb, hps=hps, group=data["GROUP"],
                          hd=data["HD"], ps=data["PS"])
        assert need <= machine.vmem_bytes, (c.describe(), need)


def test_pick_grows_with_the_heads_a_page_dma_can_carry():
    """MHA (20 KV heads) moves more heads per DMA than GQA (4)."""
    qwen = ops.select("paged_attention", SHAPES["qwen1.5-4b"], TPU_V5E)
    yi = ops.select("paged_attention", SHAPES["yi-6b"], TPU_V5E)
    assert heads_per_step(20, qwen.assignment["kv_heads"]) > 4
    assert heads_per_step(4, yi.assignment["kv_heads"]) == 4


def _cfg(arch="yi_6b"):
    return get_smoke_config(arch)


def _on_tpu(monkeypatch):
    """Resolve ``impl="auto"`` as a TPU process does."""
    monkeypatch.setattr(ops, "resolve_impl", lambda impl: "pallas")


@pytest.mark.parametrize("case,kw,serves", [
    ("decode", {}, True),
    ("prefill", {"decode": False}, False),
    ("windowed", {"cfg": "hymba_1p5b"}, False),
    ("float32_pool", {"pool_dtype": jnp.float32}, False),
    ("cpu", {"tpu": False}, False),
])
def test_routing_sends_only_paged_decode_on_pallas_to_the_kernel(
        monkeypatch, case, kw, serves):
    kw = dict(kw)
    cfg = _cfg(kw.pop("cfg", "yi_6b"))
    assert jax.default_backend() == "cpu"
    if kw.pop("tpu", True):
        _on_tpu(monkeypatch)
    args = {"decode": True, "pool_dtype": jnp.bfloat16, **kw}
    assert paged_kernel_serves(cfg, **args) is serves, case


def _decode_logits(cfg, params, monkeypatch, kernel):
    """Prefill a 21-token prompt into scattered pages, then three decode
    steps of a two-row batch whose second row is dead."""
    if kernel:
        _on_tpu(monkeypatch)
        monkeypatch.setattr(ops, "paged_attention", functools.partial(
            ops.paged_attention, interpret=True))
    ps, nblk = 4, 8
    cache = init_paged_cache(cfg, 20, ps, 2)
    table = np.zeros((2, nblk), np.int32)
    table[0, :7] = [9, 3, 17, 5, 11, 2, 14]
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab, 24).astype(np.int32)
    _, cache = paged_prefill_chunk(params, cfg, jnp.asarray(toks[None, :21]),
                                   cache, jnp.int32(0),
                                   jnp.asarray(table[:1]), jnp.int32(0))
    out = []
    for t in range(21, 24):
        lg, cache = paged_decode_step(
            params, cfg, jnp.asarray([[toks[t]], [0]], jnp.int32), cache,
            jnp.asarray([t, 0], jnp.int32), jnp.asarray(table))
        out.append(np.asarray(lg[0], np.float32))
    return np.stack(out)


def test_decode_step_through_the_kernel_equals_the_gather_path(monkeypatch):
    cfg = _cfg()
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    want = _decode_logits(cfg, params, monkeypatch, kernel=False)
    got = _decode_logits(cfg, params, monkeypatch, kernel=True)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_pool_is_head_major_and_no_larger():
    cfg = _cfg()
    cache = init_paged_cache(cfg, 9, 4, 2)
    L, nk, hd = cfg.layers, cfg.kv_heads, cfg.hd
    assert cache["k"].shape == cache["v"].shape == (L, nk, 9, 4, hd)
    assert cache["k"].nbytes == L * 9 * 4 * nk * hd * 2
