"""Old-vs-new cold-path parity: the vectorized ``enumerate_candidates``
must return the identical candidate set — same assignments, same leaf
indices, same enumeration order, scores within 1e-9 — as the
``use_compiled=False`` reference path, across every registered family.

The deterministic sweep runs in the fast tier; the hypothesis property test
additionally fuzzes data shapes, machines, and the ``max_per_leaf``
truncation cap.
"""
import pytest

from repro.core import PAPER_M2050, TPU_V5E
from repro.core.select import enumerate_candidates
from repro.kernels.ops import FAMILIES

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # property test skipped; deterministic one runs
    HAVE_HYPOTHESIS = False

# data-parameter names per family (matches artifacts.compile grids)
DIMS = {
    "matmul": ("M", "N", "K"),
    "matadd": ("M", "N"),
    "transpose": ("M", "N"),
    "jacobi1d": ("N",),
    "flash_attention": ("SQ", "HD"),
    "ssd_scan": ("SQ", "HD", "STATE"),
    "paged_attention": ("B", "NK", "GROUP", "HD", "PS", "NBLK"),
}
DIM_VALUES = (1, 7, 127, 128, 500, 1024, 4096, 100000)
MACHINES = (TPU_V5E, PAPER_M2050)


def _assert_parity(family, machine, data, max_per_leaf=512):
    fast = enumerate_candidates(family, machine, data,
                                max_per_leaf=max_per_leaf, use_compiled=True)
    ref = enumerate_candidates(family, machine, data,
                               max_per_leaf=max_per_leaf, use_compiled=False)
    assert ([(c.leaf_index, c.assignment) for c in fast]
            == [(c.leaf_index, c.assignment) for c in ref])
    for f, r in zip(fast, ref):
        assert abs(f.score - r.score) <= 1e-9, (f, r)
    return fast


def test_all_families_covered_by_dims():
    assert set(DIMS) == set(FAMILIES)


@pytest.mark.parametrize("name", sorted(DIMS))
@pytest.mark.parametrize("machine", MACHINES, ids=lambda m: m.name)
def test_parity_default_shape(name, machine):
    data = {d: v for d, v in zip(DIMS[name],
                                 (1024, 512, 512, 128, 16, 64))}
    cands = _assert_parity(FAMILIES[name], machine, data)
    if machine is TPU_V5E:
        assert cands, f"no candidates for {name} on {machine.name}"


@pytest.mark.parametrize("name", sorted(DIMS))
def test_parity_truncation_cap(name):
    data = {d: v for d, v in zip(DIMS[name], (2048, 128, 256, 128, 16, 64))}
    _assert_parity(FAMILIES[name], TPU_V5E, data, max_per_leaf=5)


@pytest.mark.parametrize("chunk", [1, 3, 7])
def test_parity_across_chunk_boundaries(monkeypatch, chunk):
    """Chunked screening (bounded memory + early exit) must not change the
    candidate sequence, whatever the chunk size."""
    from repro.core import select
    monkeypatch.setattr(select, "_SCREEN_CHUNK", chunk)
    data = {"M": 1024, "N": 1024, "K": 1024}
    _assert_parity(FAMILIES["matmul"], TPU_V5E, data, max_per_leaf=512)
    _assert_parity(FAMILIES["matmul"], TPU_V5E, data, max_per_leaf=4)


if HAVE_HYPOTHESIS:

    @pytest.mark.parametrize("name", sorted(DIMS))
    @settings(max_examples=4, deadline=None)
    @given(data=st.data())
    def test_parity_property(name, data):
        shape = {d: data.draw(st.sampled_from(DIM_VALUES), label=d)
                 for d in DIMS[name]}
        machine = data.draw(st.sampled_from(MACHINES))
        cap = data.draw(st.sampled_from([512, 5]))
        _assert_parity(FAMILIES[name], machine, shape, max_per_leaf=cap)
