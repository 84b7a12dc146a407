"""Operations and bytes of one model step, from shapes and from the work.

A step computes ``n`` new tokens for each of its rows, the new tokens of a
row sitting at positions ``start .. start + n - 1``.  What it has to do is
counted from that alone, never from how the program does it: every weight
is read once per step, each row reads the state of its ``start`` earlier
positions and writes its ``n`` new ones, and attention covers each new
token's causal prefix (or its window).  A gather of a whole block table,
recomputation, or any other work beyond this, does not count, so a
roofline share read against this count says how far the program is from
what the step needs.

Each architecture counts its own work in its reference module
(``reference/<module>.py``: ``step`` for a serving step, ``train_step`` for
a training step); this module holds what every count shares.
"""
from __future__ import annotations


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / peak["flops"], nbytes / peak["bytes_per_s"])
