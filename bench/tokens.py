"""Synthetic token batches from ``--seed``.

The arithmetic of ``repro.data.SyntheticLMDataset``, copied so that the
yardstick does not move with the program: each row starts at a random token
and follows ``next = (a * tok + b) % vocab``, replaced by a random token with
probability ``NOISE``; ``a`` and ``b`` come from the seed. Step ``i``'s batch
is a function of ``(seed, i)`` alone, so the reference sees the same rows.

A map whose rows fall into a short cycle repeats one token hundreds of times
in a batch; that changes the step's work from seed to seed and, through the
program's bfloat16 accumulation of a repeated token's embedding gradient,
its numbers (PERF.md, Open question 1). Such an ``(a, b)`` is drawn again
until no token fills more than ``MAX_REPEAT`` places of the first batch.
"""
from __future__ import annotations

import numpy as np

NOISE = 0.05  # SyntheticLMDataset's default
MAX_REPEAT = 16  # places one token may fill in the first batch


class TokenStream:
    def __init__(self, vocab: int, seq: int, batch: int, seed: int, noise: float = NOISE):
        self.vocab, self.seq, self.batch_rows, self.seed, self.noise = vocab, seq, batch, seed, noise
        rng = np.random.default_rng(seed)
        while True:
            self.a = int(rng.integers(2, max(3, vocab - 1)))
            self.b = int(rng.integers(1, vocab))
            if np.bincount(self.batch(0)[0].ravel()).max() <= MAX_REPEAT:
                break

    def batch(self, step: int) -> tuple:
        """-> (inputs, labels), int32 ``[batch, seq]`` each."""
        rng = np.random.default_rng((self.seed, step))
        B, S = self.batch_rows, self.seq
        x = np.empty((B, S + 1), np.int64)
        x[:, 0] = rng.integers(0, self.vocab, B)
        noise = rng.random((B, S)) < self.noise
        rnd = rng.integers(0, self.vocab, (B, S))
        for t in range(S):
            x[:, t + 1] = np.where(noise[:, t], rnd[:, t], (self.a * x[:, t] + self.b) % self.vocab)
        return x[:, :-1].astype(np.int32), x[:, 1:].astype(np.int32)
