"""Timing harness for dispatch-table candidates (the measurement half of
KLARAPTOR-style calibration).

Given a compiled dispatch table (:mod:`repro.artifacts.compile`), this
module re-runs the top-k pre-ranked candidates of every data-shape bucket as
*actual kernels* — ``family.instantiate(plan, assignment)`` under ``jax``,
interpreted only where the backend is the CPU (:func:`interpret_pallas`) so
the same harness runs on the CPU CI container and times compiled kernels on
a TPU — and records a trimmed-mean wall time per candidate.

Invariants:

- **deterministic inputs** — operand tensors are derived from a PRNG key
  seeded by ``(family, bucket, cfg.seed)``, so two runs time identical work;
- **measurement never invents candidates** — only entries already present
  in the table (hence already feasibility-checked offline) are timed;
- **failure is data, not an error** — a candidate that fails to instantiate
  or run records ``us=None`` and keeps its symbolic rank; the sweep
  continues (the cache-miss-never-error policy, applied to measurement).

Interpreted-Pallas timings are *relative* quality signals (the paper's
case-discussion experiments use the same reasoning): they order variants by
executed work on this host, they are not TPU microseconds.  The calibration
layer treats them as an opaque monotone cost, so swapping in a real-TPU
timer changes numbers, not code paths.
"""
from __future__ import annotations

import re
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, \
    Tuple

import numpy as np

from ..core.plan import FamilySpec, KernelPlan

_BUCKET_PART = re.compile(r"^([A-Za-z_]+?)(\d+)$")


def parse_bucket_key(key: str) -> Dict[str, int]:
    """Inverse of :func:`repro.artifacts.dispatch.bucket_key`.

    Relies on the repo-wide convention that data-parameter names contain no
    trailing digits (``M``, ``N``, ``K``, ``SQ``, ``HD``, ``STATE``); the
    bucket grammar is ``<name><pow2>`` joined by ``|``.
    """
    out: Dict[str, int] = {}
    for part in key.split("|"):
        m = _BUCKET_PART.match(part)
        if m is None:
            raise ValueError(f"unparseable bucket part {part!r} in {key!r}")
        out[m.group(1)] = int(m.group(2))
    return out


def clamp_data(data: Mapping[str, int], max_dim: int) -> Dict[str, int]:
    """Clamp each dim to ``max_dim`` (keeps powers of two powers of two)."""
    return {k: min(int(v), max_dim) for k, v in data.items()}


# Per family: the smallest data dims at which a set of candidate assignments
# runs without padding, i.e. every block extent fits inside its data dim.
# Measuring below these floors would rank candidates by *padding overhead*
# that does not exist at the bucket's true shape.
def _block_minima(family_name: str,
                  assignments: Sequence[Mapping[str, int]]
                  ) -> Dict[str, int]:
    req: Dict[str, int] = {}

    def need(dim: str, value: int) -> None:
        req[dim] = max(req.get(dim, 1), int(value))

    for a in assignments:
        if family_name == "matmul":
            need("M", a["bm"]); need("K", a["bk"]); need("N", a["bn"] * a["s"])
        elif family_name in ("matadd", "transpose"):
            need("M", a["bm"]); need("N", a["bn"] * a["s"])
        elif family_name == "jacobi1d":
            need("N", a["B"] * a["s"] + 2)
        elif family_name == "flash_attention":
            need("SQ", max(a["bq"], a["bkv"]))
        elif family_name == "ssd_scan":
            need("SQ", a["chunk"])
    return req


def measure_shape(family_name: str, data: Mapping[str, int],
                  assignments: Sequence[Mapping[str, int]],
                  max_dim: int) -> Dict[str, int]:
    """The shape a bucket is measured at: dims clamped to ``max_dim``, but
    never below the block extents of the candidates being compared.

    Interpreted Pallas pays per grid step on the host CPU, so measuring a
    4096^3 matmul bucket verbatim is infeasible.  A naive clamp, though,
    can shrink a dim *below* a candidate's block size — the kernel then
    pads, and the measured order reflects padding waste the true bucket
    shape never pays.  Flooring each dim at the candidates' block minima
    keeps every candidate in its real blocking regime, so the relative
    order transfers; a bucket whose true dims are already below a block
    extent is measured verbatim (padding there is what serving would pay).
    Real-TPU timer runs can set ``max_dim`` high enough to make this a
    no-op.
    """
    req = _block_minima(family_name, assignments)
    return {k: min(int(v), max(max_dim, req.get(k, 1)))
            for k, v in data.items()}


@dataclass(frozen=True)
class MeasureConfig:
    iters: int = 3          # timed repeats per candidate
    warmup: int = 1         # untimed runs (jit/interpreter warm-up)
    trim: int = 1           # repeats dropped from each end before the mean
    max_dim: int = 256      # clamp_data bound for measured shapes
    top_k: int = 8          # candidates measured per bucket (prefix of table)
    seed: int = 0           # base PRNG seed (mixed with family+bucket)


def interpret_pallas() -> bool:
    """Pallas is interpreted only on a CPU backend: on a TPU the harness
    times the compiled kernel, never the interpreter."""
    import jax
    return jax.default_backend() == "cpu"


@dataclass
class MeasuredSample:
    """One (bucket, candidate) timing — the unit calibrate/compact consume."""

    bucket: str
    entry_index: int                  # position in the bucket's symbolic list
    leaf_index: int
    assignment: Dict[str, int]
    score: float                      # symbolic model score (from the table)
    data: Dict[str, int]              # the (possibly clamped) measured shape
    us: Optional[float]               # trimmed-mean microseconds; None=failed
    repeats: List[float] = field(default_factory=list)


def _seed_for(family_name: str, bucket: str, base: int) -> int:
    return zlib.crc32(f"{family_name}|{bucket}|{base}".encode()) & 0x7FFFFFFF


def _build_inputs(family_name: str, data: Mapping[str, int], seed: int
                  ) -> Tuple[Tuple[Any, ...], Dict[str, Any]]:
    """Deterministic operand tensors for one family at one data shape."""
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)

    def normal(k, shape, dtype=jnp.float32):
        return jax.random.normal(k, shape, dtype)

    if family_name == "matmul":
        k1, k2 = jax.random.split(key)
        M, N, K = data["M"], data["N"], data["K"]
        return (normal(k1, (M, K), jnp.bfloat16),
                normal(k2, (K, N), jnp.bfloat16)), {}
    if family_name == "matadd":
        k1, k2 = jax.random.split(key)
        M, N = data["M"], data["N"]
        return (normal(k1, (M, N)), normal(k2, (M, N))), {}
    if family_name == "transpose":
        return (normal(key, (data["M"], data["N"])),), {}
    if family_name == "jacobi1d":
        return (normal(key, (data["N"],)), 4), {}
    if family_name == "flash_attention":
        k1, k2, k3 = jax.random.split(key, 3)
        sq, hd = data["SQ"], data["HD"]
        shape = (1, sq, hd)
        return (normal(k1, shape, jnp.bfloat16),
                normal(k2, shape, jnp.bfloat16),
                normal(k3, shape, jnp.bfloat16)), {"causal": True}
    if family_name == "ssd_scan":
        k1, k2, k3, k4 = jax.random.split(key, 4)
        sq, hd, st = data["SQ"], data["HD"], data["STATE"]
        heads = 1
        a = jax.nn.sigmoid(normal(k2, (sq, heads)))       # decay in (0, 1)
        return (normal(k1, (sq, heads, hd)), a,
                normal(k3, (sq, heads, st)),
                normal(k4, (sq, heads, st))), {}
    raise KeyError(f"no input builder for family {family_name!r}")


def default_timer(family: FamilySpec, plan: KernelPlan,
                  assignment: Mapping[str, int], data: Mapping[str, int],
                  cfg: MeasureConfig) -> List[float]:
    """Run the candidate kernel; return per-repeat wall times in seconds.

    Raises on instantiation/execution failure — ``measure_table`` converts
    that into a ``us=None`` sample.
    """
    import time

    import jax
    fn = family.instantiate(plan, dict(assignment),
                            interpret=interpret_pallas())
    seed = _seed_for(family.name, repr(sorted(data.items())), cfg.seed)
    args, kwargs = _build_inputs(family.name, data, seed)
    for _ in range(max(0, cfg.warmup)):
        jax.block_until_ready(fn(*args, **kwargs))
    out = []
    for _ in range(max(1, cfg.iters)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        out.append(time.perf_counter() - t0)
    return out


def trimmed_mean_us(repeats: Sequence[float], trim: int) -> float:
    """Trimmed mean (seconds -> microseconds); robust to scheduler noise."""
    xs = sorted(float(r) for r in repeats)
    if trim > 0 and len(xs) > 2 * trim:
        xs = xs[trim:-trim]
    return float(np.mean(xs) * 1e6)


Timer = Callable[[FamilySpec, KernelPlan, Mapping[str, int],
                  Mapping[str, int], MeasureConfig], List[float]]


def measure_table(family: FamilySpec, table: Mapping[str, Any],
                  cfg: MeasureConfig = MeasureConfig(),
                  timer: Optional[Timer] = None,
                  progress: Optional[Callable[[str], None]] = None
                  ) -> List[MeasuredSample]:
    """Time the top-``cfg.top_k`` candidates of every bucket in ``table``.

    ``timer`` is injectable (tests use a deterministic fake; a TPU host can
    supply a non-interpreted one); the default runs
    real/interpreted Pallas via :func:`default_timer`.
    """
    from ..artifacts import serde
    timer = timer or default_timer
    samples: List[MeasuredSample] = []
    leaves = serde.table_leaves(table)
    for bucket in sorted(table.get("buckets", {})):
        entries = table["buckets"][bucket]
        measured_entries = entries[:cfg.top_k]
        try:
            data = measure_shape(
                family.name, parse_bucket_key(bucket),
                [{k: int(v) for k, v in e["assignment"].items()}
                 for e in measured_entries], cfg.max_dim)
        except (KeyError, TypeError, ValueError):
            continue                          # unparseable bucket: skip
        for pos, entry in enumerate(measured_entries):
            leaf = leaves.get(int(entry["leaf_index"]))
            if leaf is None:
                continue
            asg = {k: int(v) for k, v in entry["assignment"].items()}
            if progress:
                progress(f"{family.name}/{bucket}#{pos} {asg}")
            try:
                repeats = timer(family, leaf.plan, asg, data, cfg)
                us: Optional[float] = trimmed_mean_us(repeats, cfg.trim)
            except Exception:                 # noqa: BLE001 — failure is data
                repeats, us = [], None
            samples.append(MeasuredSample(
                bucket=bucket, entry_index=pos,
                leaf_index=int(entry["leaf_index"]), assignment=asg,
                score=float(entry["score"]), data=dict(data), us=us,
                repeats=[float(r) for r in repeats]))
    return samples
