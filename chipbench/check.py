"""Whether what the window produced is right: the comparison behind
``correct``.

For a serving cell: once the window has closed, a sample of the finished
requests, drawn from the seed and always holding the longest, is run
through the configuration's float32 reference (the module its
``"reference"`` names) over prompt plus served tokens.  The
number compared is the widest gap by which a served token's reference logit
lies below the reference's best at that position: 0 where every served
token is the reference's first choice, small where bfloat16 rounding flips
a near-tie, large where the program computes something else.
"""
from __future__ import annotations

from typing import List

import numpy as np


def sample(finished: list, seed: int, k: int) -> list:
    """``k`` finished requests: the longest, then others in an order drawn
    from the seed."""
    if not finished:
        return []
    size = [len(s.prompt) + len(s.req.out) for s in finished]
    first = int(np.argmax(size))
    rest = [i for i in range(len(finished)) if i != first]
    rng = np.random.default_rng([int(seed), 7])
    order = [first] + [rest[i] for i in rng.permutation(len(rest))]
    return [finished[i] for i in order[:k]]


def served_tokens(chosen: list, k: int, length: int):
    """(k, length) tokens (prompt then served output, zero padded) and the
    mask of positions whose next token was served."""
    tokens = np.zeros((k, length), np.int32)
    mask = np.zeros((k, length), bool)
    for row, s in enumerate(chosen):
        seq = np.concatenate([s.prompt, np.asarray(s.req.out, np.int32)])
        tokens[row, :len(seq)] = seq
        mask[row, len(s.prompt) - 1:len(seq) - 1] = True
    return tokens, mask


def logit_gaps(ref, model: dict, seed: int, chosen: list, k: int,
               length: int, control: bool = False):
    """Widest gap of the served tokens and, with ``control``, of the
    control's first choices, over the sampled positions; and the number of
    served tokens compared."""
    tokens, mask = served_tokens(chosen, k, length)
    gap, gap_low = ref.served_gaps(model, seed, tokens, control)
    widest = float(gap[mask].max()) if mask.any() else float("nan")
    low = (float(gap_low[mask].max()) if control and mask.any() else None)
    return widest, low, int(mask.sum())


def verdict(checks: dict) -> bool:
    """Every number compared within its limit; a number that is missing or
    not finite fails."""
    return all(c["value"] is not None and np.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in checks.values())


def lines(checks: dict) -> List[str]:
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in checks.items()]


def failed(served: list) -> int:
    return sum(1 for s in served if s.req is not None
               and s.req.error is not None)


def finished(served: list) -> list:
    return [s for s in served if s.req is not None and s.req.done
            and s.req.error is None and s.req.out]
