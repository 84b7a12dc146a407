"""The one generator of training mixes: a synthetic language of Zipf
unigrams mixed with a bigram rule, drawn from the seed.

A mix names it with ``"generator": "synthetic"`` and gives ``seq_len``,
``global_batch`` and ``data`` (``zipf_alpha``, ``bigram_fraction``).
:func:`dataset` returns an object whose ``batch_at(step)`` is a pure
function of (seed, step): ``tokens`` and ``labels``, each (global_batch,
seq_len) int32, the labels the tokens shifted by one.  Every seed gets
batches of the same shape and the same mix of unigram and bigram
positions.

It is the benchmark's own copy of the program's ``repro.data.SyntheticLM``
(the same numbers for the same settings), so that a change to the program
cannot change what the benchmark feeds it.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


class Synthetic:
    def __init__(self, vocab: int, seq_len: int, global_batch: int,
                 seed: int, zipf_alpha: float = 1.2,
                 bigram_fraction: float = 0.5):
        self.vocab, self.seq_len = vocab, seq_len
        self.global_batch, self.seed = global_batch, seed
        self.bigram_fraction = bigram_fraction
        probs = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_alpha)
        self.probs = probs / probs.sum()
        # the bigram rule t -> (a t + c) mod vocab, a odd
        rng = np.random.default_rng(seed ^ 0x5EED)
        self.succ_mul = int(rng.integers(3, 97)) * 2 + 1
        self.succ_add = int(rng.integers(0, vocab))

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        out = np.empty((self.global_batch, self.seq_len + 1), np.int64)
        for r in range(self.global_batch):
            rng = np.random.default_rng((self.seed, step, r))
            seq = rng.choice(self.vocab, size=self.seq_len + 1, p=self.probs)
            bigram = rng.random(self.seq_len) < self.bigram_fraction
            # a bigram position follows the token before it, itself maybe
            # a bigram position: the chain runs from the last free token
            for t in np.flatnonzero(bigram):
                seq[t + 1] = (seq[t] * self.succ_mul + self.succ_add) \
                    % self.vocab
            out[r] = seq
        out = out.astype(np.int32)
        return {"tokens": out[:, :-1], "labels": out[:, 1:]}


def dataset(traffic: dict, seed: int, vocab: int) -> Synthetic:
    return Synthetic(vocab, traffic["seq_len"], traffic["global_batch"], seed,
                     **traffic.get("data", {}))
