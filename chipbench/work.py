"""Operations and bytes of one model step, from shapes and from the work.

A step computes ``n`` new tokens for each of its rows, the new tokens of a
row sitting at positions ``start .. start + n - 1``.  What it has to do is
counted from that alone, never from how the program does it: every weight
is read once per step, each row reads the keys and values of its
``start`` earlier positions and writes its ``n`` new ones, and attention
covers each new token's causal prefix.  A gather of a whole block table,
or any other work beyond this, does not count, so a
roofline share read against this count says how far the program is from
what the step needs.

``dims`` are those of :func:`reference.weights.dims`.
"""
from __future__ import annotations

from typing import Iterable, Tuple

PARAM_BYTES = 2            # bfloat16 weights, as the configurations serve
KV_BYTES = 2               # bfloat16 key/value cache


def layer_matmul_params(dm: dict) -> int:
    d, nh, nk, hd, f = dm["d"], dm["nh"], dm["nk"], dm["hd"], dm["f"]
    return d * nh * hd + 2 * d * nk * hd + nh * hd * d + 3 * d * f


def weight_bytes(dm: dict) -> int:
    """Bytes of the weights one step reads: every layer, the final norm and
    the output head (the embedding is gathered by row, counted per token)."""
    d, nh, nk, hd = dm["d"], dm["nh"], dm["nk"], dm["hd"]
    per_layer = layer_matmul_params(dm) + 2 * d
    if dm["bias"]:
        per_layer += nh * hd + 2 * nk * hd
    return PARAM_BYTES * (dm["layers"] * per_layer + d + d * dm["vocab"])


def kv_bytes_per_position(dm: dict) -> int:
    return dm["layers"] * 2 * dm["nk"] * dm["hd"] * KV_BYTES


def step(dm: dict, rows: Iterable[Tuple[int, int]], logit_rows: int
         ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one step over ``rows`` of (start, n); the output
    head runs for ``logit_rows`` tokens (one per decode row, one per prefill
    chunk)."""
    L, nh, hd, d = dm["layers"], dm["nh"], dm["hd"], dm["d"]
    flops = 2.0 * d * dm["vocab"] * logit_rows
    nbytes = float(weight_bytes(dm))
    kv = kv_bytes_per_position(dm)
    mm = layer_matmul_params(dm) * L
    for start, n in rows:
        # QK^T and PV: 2 * hd each, per head, per (query, key) pair
        pairs = n * start + n * (n + 1) // 2
        flops += 2.0 * mm * n + 4.0 * L * nh * hd * pairs
        nbytes += kv * (start + n) + PARAM_BYTES * d * n
    return flops, nbytes


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes / peak["bytes_per_s"])
