"""The benchmark's traffic: a Zipf-weighted order-2 Markov token stream.

A copy of the program's ``SyntheticLM`` (``src/repro/data/pipeline.py``),
kept here so that no later change to the program can change the yardstick.
It has real sequential structure, so the loss falls and the gradient
entropy evolves as in training on text, and it is deterministic by seed.
"""
from __future__ import annotations

import numpy as np


class SyntheticLM:
    """Order-2 Markov chain with Zipf marginals, deterministic by seed."""

    def __init__(self, vocab_size: int, seq_len: int, batch_size: int,
                 seed: int, zipf_a: float = 1.3, n_buckets: int = 64) -> None:
        self.vocab_size = vocab_size
        self.seq_len = seq_len
        self.batch_size = batch_size
        rng = np.random.default_rng(seed)
        ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
        base = 1.0 / ranks ** zipf_a
        self._cdf = np.cumsum(base / base.sum())
        self._n_buckets = n_buckets
        # each (prev-token bucket) permutes the marginal: a cheap stand-in
        # for bigram structure
        self._perms = np.stack(
            [rng.permutation(vocab_size) for _ in range(n_buckets)])
        self._rng = np.random.default_rng(seed + 1)

    def batch(self) -> dict[str, np.ndarray]:
        """One (batch, seq_len) batch of tokens and next-token labels."""
        B, T, V = self.batch_size, self.seq_len + 1, self.vocab_size
        draws = self._rng.random((B, T))
        seqs = np.empty((B, T), np.int64)
        prev = np.zeros(B, np.int64)
        for t in range(T):
            buckets = (prev * 2654435761) % self._n_buckets
            idx = np.minimum(np.searchsorted(self._cdf, draws[:, t]), V - 1)
            prev = self._perms[buckets, idx]
            seqs[:, t] = prev
        return {"tokens": seqs[:, :-1].astype(np.int32),
                "labels": seqs[:, 1:].astype(np.int32)}
