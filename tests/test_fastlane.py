"""Warm-path fast lane: instantiation-cache identity + frozen dispatch plans.

Acceptance properties (ISSUE 4):

- ``instantiate`` returns an *identical callable object* across repeated
  resolutions of the same triple — the property that stabilizes jit keys;
- keying is exact: same assignment with ``interpret=True`` vs ``False`` and
  differing plan flags (``vmem_cache``) yield *distinct* cached callables;
- frozen parity: with and without ``freeze()``, every family resolves the
  same candidate for every warm-up triple;
- ``get_default_cache`` picks up an artifact dir that appears *after* the
  first cold dispatch (store snapshotting regression).
"""
import pytest

from repro.artifacts import ArtifactStore, DispatchCache, compile_family
from repro.artifacts.dispatch import get_default_cache, set_default_cache
from repro.core import TPU_V5E, best_variant
from repro.core.select import STATS
from repro.kernels.ops import FAMILIES

#: One serving-representative triple per family (mirrors benchmarks).
SHAPES = {
    "matmul": {"M": 512, "N": 512, "K": 512},
    "matadd": {"M": 512, "N": 512},
    "jacobi1d": {"N": 2048},
    "transpose": {"M": 512, "N": 512},
    "flash_attention": {"SQ": 256, "HD": 64},
    "ssd_scan": {"SQ": 256, "HD": 64, "STATE": 64},
    "paged_attention": {"B": 4, "NK": 4, "GROUP": 2, "HD": 64, "PS": 16,
                        "NBLK": 8},
}


@pytest.fixture(autouse=True)
def _isolate_default_cache():
    set_default_cache(DispatchCache())
    yield
    set_default_cache(None)


# ---------------------------------------------------------------------------
# Instantiation cache: identity + keying
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fname", sorted(SHAPES), ids=str)
def test_instantiate_identity_across_resolutions(fname):
    """Repeated resolutions of the same triple return the SAME object."""
    fam, data = FAMILIES[fname], SHAPES[fname]
    cache = DispatchCache()
    c1 = cache.best_variant(fam, TPU_V5E, data)
    c2 = cache.best_variant(fam, TPU_V5E, data)
    assert c1 == c2
    f1 = fam.instantiate(c1.plan, c1.assignment, interpret=True,
                         leaf_index=c1.leaf_index)
    f2 = fam.instantiate(c2.plan, c2.assignment, interpret=True,
                         leaf_index=c2.leaf_index)
    assert f1 is f2


def test_instantiate_key_interpret_mode():
    fam, data = FAMILIES["matmul"], SHAPES["matmul"]
    cand = best_variant(fam, TPU_V5E, data, use_cache=False)
    fi = fam.instantiate(cand.plan, cand.assignment, interpret=True)
    fc = fam.instantiate(cand.plan, cand.assignment, interpret=False)
    assert fi is not fc
    assert fam.instantiate(cand.plan, cand.assignment, interpret=True) is fi


def test_instantiate_key_plan_flags():
    """Same assignment under different plan flags => distinct callables."""
    fam = FAMILIES["matmul"]
    cand = best_variant(fam, TPU_V5E, SHAPES["matmul"], use_cache=False)
    plan = cand.plan
    assert plan.flags.get("vmem_cache", True)
    uncached_plan = plan.with_flag("vmem_cache", False)
    f_cached = fam.instantiate(plan, cand.assignment, interpret=True)
    f_uncached = fam.instantiate(uncached_plan, cand.assignment,
                                 interpret=True)
    assert f_cached is not f_uncached


def test_instantiate_zero_rebuilds_when_warm():
    """Steady-state op calls never invoke the kernel builder again."""
    fam, data = FAMILIES["matadd"], SHAPES["matadd"]
    cache = DispatchCache()
    cand = cache.best_variant(fam, TPU_V5E, data)
    fam.instantiate(cand.plan, cand.assignment, interpret=True,
                    leaf_index=cand.leaf_index)          # build once
    misses_before = fam.instantiation_cache.misses
    for _ in range(50):
        c = cache.best_variant(fam, TPU_V5E, data)
        fam.instantiate(c.plan, c.assignment, interpret=True,
                        leaf_index=c.leaf_index)
    assert fam.instantiation_cache.misses == misses_before


def test_instantiate_fresh_bypasses_cache():
    fam = FAMILIES["transpose"]
    cand = best_variant(fam, TPU_V5E, SHAPES["transpose"], use_cache=False)
    a = fam.instantiate_fresh(cand.plan, cand.assignment, True)
    b = fam.instantiate_fresh(cand.plan, cand.assignment, True)
    assert a is not b                     # the pre-fast-lane behaviour


# ---------------------------------------------------------------------------
# Frozen dispatch plans
# ---------------------------------------------------------------------------

def _freeze_all(cache):
    return cache.freeze([(FAMILIES[f], TPU_V5E, d)
                         for f, d in SHAPES.items()])


def test_frozen_parity_all_families():
    """Acceptance: freeze() changes the cost of a lookup, never its answer."""
    frozen_cache = DispatchCache()
    plain_cache = DispatchCache()
    _freeze_all(frozen_cache)
    for fname, data in SHAPES.items():
        fam = FAMILIES[fname]
        via_frozen = frozen_cache.best_variant(fam, TPU_V5E, data)
        via_tiers = plain_cache.best_variant(fam, TPU_V5E, data)
        cold = best_variant(fam, TPU_V5E, data, use_cache=False)
        assert via_frozen == via_tiers == cold
        # the observability lookup sees the same snapshot (and counts)
        ent = frozen_cache.frozen_entry(fam.name, TPU_V5E.name, data)
        assert ent is not None and ent.candidate == via_frozen
        assert ent.source in ("measured", "symbolic", "cold")
    assert frozen_cache.stats.frozen_hits == 2 * len(SHAPES)
    assert frozen_cache.frozen_entry("matmul", TPU_V5E.name,
                                     {"M": 7, "N": 7, "K": 7}) is None


def test_frozen_resolution_skips_lru_and_enumeration():
    cache = DispatchCache()
    _freeze_all(cache)
    STATS.reset()
    before = cache.stats.memory_hits
    for fname, data in SHAPES.items():
        cache.best_variant(FAMILIES[fname], TPU_V5E, data)
    assert STATS.enumerate_calls == 0            # no tree search
    assert cache.stats.memory_hits == before     # not even the LRU
    assert cache.stats.frozen_hits >= len(SHAPES)


def test_warm_callable_identity_and_parity():
    """The ops-layer fast lane returns the frozen, memoized callable."""
    cache = DispatchCache()
    plan = _freeze_all(cache)
    for fname, data in SHAPES.items():
        fam = FAMILIES[fname]
        items = tuple(data.items())
        f1 = cache.warm_callable(fam, TPU_V5E, items, True)
        f2 = cache.warm_callable(fam, TPU_V5E, items, True)
        assert f1 is f2
        ent = plan.get(fam.name, TPU_V5E.name, data)
        assert ent is not None and f1 is ent.fns[1]
        # and identical to what a direct memoized instantiate returns
        cand = ent.candidate
        assert f1 is fam.instantiate(cand.plan, cand.assignment,
                                     interpret=True,
                                     leaf_index=cand.leaf_index)


def test_warm_callable_item_order_insensitive():
    cache = DispatchCache()
    _freeze_all(cache)
    data = SHAPES["matmul"]
    fam = FAMILIES["matmul"]
    fwd = cache.warm_callable(fam, TPU_V5E, tuple(data.items()), False)
    rev = cache.warm_callable(fam, TPU_V5E,
                              tuple(reversed(list(data.items()))), False)
    assert fwd is rev


def test_warm_callable_miss_falls_back_to_tiers():
    """An unfrozen triple still resolves (cache-miss-never-error) and the
    returned callable is the memoized one (stable identity on repeat)."""
    cache = DispatchCache()
    _freeze_all(cache)
    items = (("M", 384), ("N", 384), ("K", 384))   # never frozen
    f1 = cache.warm_callable(FAMILIES["matmul"], TPU_V5E, items, True)
    f2 = cache.warm_callable(FAMILIES["matmul"], TPU_V5E, items, True)
    assert f1 is f2
    assert cache.stats.memory_hits >= 1            # served by the LRU tier


def test_late_store_attach_refreezes_stale_cold_snapshots(tmp_path):
    """A frozen plan must not pin pre-artifact cold picks forever: attaching
    a store re-freezes the plan's own warm-up triples against the new
    tables (same candidate by parity, fresh source), and an explicit
    re-freeze also resolves through the tiers, never the old plan."""
    fam, data = FAMILIES["matmul"], SHAPES["matmul"]
    cache = DispatchCache()
    cache.freeze([(fam, TPU_V5E, data)])
    assert cache.frozen_plan.get(fam.name, TPU_V5E.name,
                                 data).source == "cold"
    store = ArtifactStore(tmp_path)
    compile_family(fam, store, machines=[TPU_V5E], shapes=[dict(data)])
    cache.attach_store(store)                    # tables appear later
    ent = cache.frozen_plan.get(fam.name, TPU_V5E.name, data)
    assert ent.source == "symbolic"              # auto-refrozen, not pinned
    assert ent.candidate == best_variant(fam, TPU_V5E, data,
                                         use_cache=False)
    # explicit re-freeze equally re-reads the tables (never the old plan)
    cache.freeze([(fam, TPU_V5E, data)])
    assert cache.frozen_plan.get(fam.name, TPU_V5E.name,
                                 data).source == "symbolic"


def test_unfreeze_wins_over_inflight_refreeze():
    """The generation guard: a freeze carrying a stale unfreeze generation
    (attach_store's re-freeze racing an explicit unfreeze) must not
    resurrect the dropped plan."""
    fam, data = FAMILIES["matmul"], SHAPES["matmul"]
    cache = DispatchCache()
    plan = cache.freeze([(fam, TPU_V5E, data)])
    stale_gen = cache._unfreeze_gen
    cache.unfreeze()                             # explicit drop
    out = cache.freeze(plan.triples, _expect_unfreeze_gen=stale_gen)
    assert cache.frozen_plan is None and out is None
    # a current-generation freeze still publishes
    cache.freeze(plan.triples)
    assert cache.frozen_plan is not None


def test_freeze_is_monotonic_and_unfreeze_drops():
    cache = DispatchCache()
    cache.freeze([(FAMILIES["matmul"], TPU_V5E, SHAPES["matmul"])])
    cache.freeze([(FAMILIES["matadd"], TPU_V5E, SHAPES["matadd"])])
    plan = cache.frozen_plan
    assert len(plan) == 2                          # merged, not replaced
    assert plan.get("matmul", TPU_V5E.name, SHAPES["matmul"]) is not None
    cache.unfreeze()
    assert cache.frozen_plan is None
    # tiers still serve after unfreeze
    assert cache.best_variant(FAMILIES["matmul"], TPU_V5E,
                              SHAPES["matmul"]) is not None


def test_ops_warm_path_zero_rebuilds():
    """End to end through the public op: repeated calls build nothing."""
    import jax
    import numpy as np
    from repro.kernels import ops, ref
    from repro.runtime.serving import warm_kernel_dispatch  # noqa: F401
    fam = FAMILIES["matmul"]
    a = jax.random.normal(jax.random.PRNGKey(0), (128, 128))
    b = jax.random.normal(jax.random.PRNGKey(1), (128, 128))
    out = ops.matmul(a, b, impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul(a, b)),
                               rtol=1e-4, atol=1e-3)
    misses_before = fam.instantiation_cache.misses
    enumerate_before = STATS.enumerate_calls
    for _ in range(5):
        ops.matmul(a, b, impl="pallas", interpret=True)
    assert fam.instantiation_cache.misses == misses_before
    assert STATS.enumerate_calls == enumerate_before


def test_serving_warmup_feeds_frozen_plan():
    """warm_kernel_dispatch(freeze=True) populates the process cache's
    frozen plan with every reported pick, at parity with the picks."""
    from repro.configs import get_smoke_config
    from repro.plans import op_label
    from repro.runtime.serving import warm_kernel_dispatch
    cfg = get_smoke_config("llama3_8b")
    picks = warm_kernel_dispatch(cfg, max_len=128)
    cache = get_default_cache()
    plan = cache.frozen_plan
    assert plan is not None and len(plan) == len(picks)
    hd = cfg.hd
    data = {"SQ": 128, "HD": hd}
    ent = plan.get("flash_attention", TPU_V5E.name, data)
    assert ent is not None
    label = op_label("flash_attention", data)
    assert ent.candidate == picks[label]["candidate"]
    # freeze=False leaves the plan untouched
    set_default_cache(DispatchCache())
    warm_kernel_dispatch(cfg, max_len=128, freeze=False)
    assert get_default_cache().frozen_plan is None


# ---------------------------------------------------------------------------
# get_default_cache store snapshotting (satellite regression)
# ---------------------------------------------------------------------------

def test_default_cache_attaches_store_appearing_later(tmp_path, monkeypatch):
    """An artifact dir compiled AFTER the first cold dispatch must be seen:
    the auto-created default re-probes while store-less and serves tier-2
    hits once tables exist."""
    art = tmp_path / "artifacts"
    monkeypatch.setenv("REPRO_ARTIFACT_DIR", str(art))
    set_default_cache(None)                       # re-arm the env probe
    fam, data = FAMILIES["matmul"], SHAPES["matmul"]
    cache = get_default_cache()
    assert cache.store is None                    # dir does not exist yet
    cache.best_variant(fam, TPU_V5E, data)        # first dispatch: cold
    assert cache.stats.cold_builds == 1

    compile_family(fam, ArtifactStore(art), machines=[TPU_V5E],
                   shapes=[{"M": 1024, "N": 1024, "K": 1024}, dict(data)])
    # a NEW shape (LRU miss) must now come from the disk artifact
    cand = get_default_cache().best_variant(fam, TPU_V5E,
                                            {"M": 1024, "N": 1024, "K": 1024})
    assert cache.stats.disk_hits == 1
    assert cache.store is not None
    assert cand == best_variant(fam, TPU_V5E,
                                {"M": 1024, "N": 1024, "K": 1024},
                                use_cache=False)
    # ... and the attach unpinned the pre-store LRU entry: the ORIGINAL
    # shape re-resolves against the table instead of replaying its cold
    # answer forever
    again = get_default_cache().best_variant(fam, TPU_V5E, data)
    assert cache.stats.disk_hits == 2
    assert again == best_variant(fam, TPU_V5E, data, use_cache=False)


def test_explicit_cache_store_is_never_overridden(tmp_path, monkeypatch):
    """A cache installed via set_default_cache keeps its (lack of) store
    even when an artifact dir exists — test isolation depends on it."""
    art = tmp_path / "artifacts"
    fam = FAMILIES["matmul"]
    compile_family(fam, ArtifactStore(art), machines=[TPU_V5E],
                   shapes=[SHAPES["matmul"]])
    monkeypatch.setenv("REPRO_ARTIFACT_DIR", str(art))
    mine = DispatchCache()
    set_default_cache(mine)
    got = get_default_cache()
    got.best_variant(fam, TPU_V5E, SHAPES["matmul"])
    assert got is mine and got.store is None
    assert got.stats.cold_builds == 1             # not a disk hit
