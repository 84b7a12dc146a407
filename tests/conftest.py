"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests must see the
real single CPU device; only the dry-run subprocesses force 512 devices.

Determinism: every randomized test draws from the ``rng`` fixture (or an
explicitly seeded generator) — never the global ``np.random`` state — so
the suite is safe under test-order randomization (``pytest-randomly`` or
``pytest -p no:randomly`` both yield identical results; no test may depend
on RNG state another test advanced).  The fake-clock/skewed-timer fixtures
below are the drift-injection half of ``tests/test_adaptive.py``: they
fabricate deterministic wall-clock measurements so adaptive-serving tests
never time real kernels.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)

#: One seed for every randomized fixture; change in one place to shake the
#: whole suite.
TEST_SEED = 1234


def pytest_configure(config):
    # Registered in pyproject.toml too; re-register here so the marker is
    # known even when pytest is invoked from outside the repo root.  The CI
    # fast tier deselects these with ``-m "not slow"``; the nightly job runs
    # the full suite with ``-m "slow or not slow"``.
    config.addinivalue_line(
        "markers",
        "slow: long-running model/system tests "
        "(excluded from the CI fast tier via -m 'not slow')")


@pytest.fixture
def rng():
    """Deterministic per-test RNG — the only sanctioned randomness source
    for randomized tests (drift workloads, reservoir sampling, fuzzed
    shapes)."""
    return np.random.default_rng(TEST_SEED)


class FakeClock:
    """A controllable monotonic clock for timing-dependent tests."""

    def __init__(self, start: float = 0.0):
        self.now = float(start)

    def advance(self, dt: float) -> None:
        self.now += float(dt)

    def __call__(self) -> float:
        return self.now


@pytest.fixture
def fake_clock():
    return FakeClock()


class CountingClock(FakeClock):
    """A clock that advances ``step`` seconds on every read, so the time
    the engine measures is a count of its own clock reads: independent of
    how busy the host is, and the same on every run."""

    def __init__(self, step: float = 1e-4):
        super().__init__()
        self.step = step

    def __call__(self) -> float:
        self.now += self.step
        return self.now


@pytest.fixture
def counting_clock():
    return CountingClock()


class SkewedTimer:
    """A deterministic ``repro.tuning.measure.Timer`` whose measurements
    are dictated per candidate — the drift-injection harness.

    ``skews`` maps a candidate key (``repro.runtime.monitor.cand_key``) to
    the seconds-per-repeat it should "measure"; ``default`` covers every
    other candidate.  Re-skew mid-test (``timer.skews[key] = ...``) to
    fabricate a traffic shift.  Tiny seeded jitter keeps medians honest
    without ever reordering candidates."""

    def __init__(self, default: float = 1e-3, jitter: float = 0.0,
                 seed: int = TEST_SEED):
        self.default = float(default)
        self.jitter = float(jitter)
        self.skews = {}
        self.calls = []                      # (family, cand_key, data)
        self._rng = np.random.default_rng(seed)

    def __call__(self, family, plan, assignment, data, cfg):
        key = tuple(sorted((k, int(v)) for k, v in assignment.items()))
        base = None
        for (leaf, asg), secs in self.skews.items():
            if asg == key:
                base = float(secs)
                break
        if base is None:
            base = self.default
        self.calls.append((family.name, key, dict(data)))
        out = []
        for _ in range(max(1, cfg.iters)):
            j = (self._rng.uniform(-self.jitter, self.jitter)
                 if self.jitter else 0.0)
            out.append(base * (1.0 + j))
        return out


@pytest.fixture
def skewed_timer():
    return SkewedTimer()
