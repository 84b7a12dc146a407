"""Invariants of Algorithm 1/2 (paper Definition 2 + Lemmas 1-4)."""
from fractions import Fraction

import pytest

from repro.core import (Constraint, ConstraintSystem, Verdict,
                        comprehensive_optimization, comprehensive_tree,
                        initial_quintuple, tree_report, V)
from repro.core.counters import CounterKind
from repro.kernels.flash_attention import FAMILY as FLASH
from repro.kernels.jacobi1d import FAMILY as JACOBI
from repro.kernels.matadd import FAMILY as MATADD
from repro.kernels.matmul import FAMILY as MATMUL
from repro.kernels.paged_attention import FAMILY as PAGED
from repro.kernels.ssd_scan import FAMILY as SSD
from repro.kernels.transpose import FAMILY as TRANSPOSE

FAMILIES = [MATMUL, MATADD, JACOBI, TRANSPOSE, FLASH, SSD, PAGED]


@pytest.fixture(scope="module", params=FAMILIES, ids=lambda f: f.name)
def family(request):
    return request.param


@pytest.fixture(scope="module")
def leaves(family):
    return comprehensive_tree(family)


def test_tree_nonempty(leaves):
    assert len(leaves) >= 2          # at least one accept/refuse fork


def test_constraint_soundness(leaves):
    """Def 2 (i): every kept system is consistent (never provably empty)."""
    for leaf in leaves:
        assert leaf.constraints.check() is not Verdict.INCONSISTENT


def test_lemma1_height_bound(family, leaves):
    """Lemma 1: #applied strategies + #constraints bounded by w(s+t).

    Each leaf's path length = number of accept edges (= evaluated counters,
    re-pushed after refuses) + refuse edges (<= w).  We check the recipe
    length |λ| <= w and constraint count <= axioms + 2*w(s+t)."""
    w = len(family.strategies())
    s_t = len(family.counters())
    for leaf in leaves:
        assert len(leaf.applied) <= w
        assert len(leaf.constraints) <= 4 + s_t + 2 * w * (s_t + 1)


def test_lemma2_strategies_explored(family, leaves):
    """Lemma 2 (pruned-tree form): some leaf applies no strategy, and the
    FIRST σ-strategy of every counter appears in some recipe.

    (Lemma 2 guarantees every strategy subset labels a path of the
    *unpruned* tree; consistency pruning legitimately removes paths whose
    extra strategy level cannot change the counter — e.g. transpose's cse_2
    after cse_1, exactly the paper's R3/R6 contradiction discard.)"""
    recipes = [set(l.applied) for l in leaves]
    assert set() in recipes                      # the all-accept path
    applied_anywhere = set().union(*recipes)
    initially_applicable = {
        s.name for s in family.strategies()
        if s(family.initial_plan()) is not None}
    for c in family.counters():
        firsts = [n for n in c.sigma if n in initially_applicable]
        if firsts:
            assert firsts[0] in applied_anywhere, \
                f"{firsts[0]} (first σ({c.name})) never explored"


def test_optimality_fixpoint(family, leaves):
    """Def 2 (iv): for each counter, some leaf is a fix-point of every
    strategy in σ(counter) — no strategy can improve it further."""
    for counter in family.counters():
        found = False
        for leaf in leaves:
            plan = leaf.plan
            fixpoint = True
            for s in family.strategies():
                if s.name not in counter.sigma:
                    continue
                transformed = s(plan)
                if transformed is None:
                    continue           # idempotence: not applicable again
                before = counter.evaluate(family, plan)
                after = counter.evaluate(family, transformed)
                if (before[0] * after[1]) != (after[0] * before[1]):
                    fixpoint = False
                    break
            if fixpoint:
                found = True
                break
        assert found, f"no optimal leaf for counter {counter.name}"


def test_coverage_on_concrete_machines(family, leaves):
    """Def 2 (iii): concrete machine+data bindings leave >= 1 live leaf."""
    from repro.core.params import TPU_V5E, PAPER_M2050
    data_samples = [
        {"M": 1024, "N": 1024, "K": 1024, "SQ": 1024, "HD": 128,
         "STATE": 64, "T": 4},
        {"M": 8192, "N": 8192, "K": 8192, "SQ": 8192, "HD": 64,
         "STATE": 128, "T": 8},
    ]
    for machine in (TPU_V5E,):
        binding = machine.bindings()
        for data in data_samples:
            live = 0
            for leaf in leaves:
                C = leaf.constraints.subs({**binding, **data})
                if C.check() is not Verdict.INCONSISTENT:
                    live += 1
            assert live >= 1, (machine.name, data)


def test_idempotence_of_strategies(family):
    """σ-strategies are idempotent on plans (paper assumption)."""
    plan = family.initial_plan()
    for s in family.strategies():
        once = s(plan)
        if once is None:
            continue
        twice = s(once)
        assert twice is None, f"{s.name} is not idempotent"


def test_report_smoke(family, leaves):
    rep = tree_report(leaves)
    assert "case 1" in rep and family.name in rep
