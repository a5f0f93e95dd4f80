"""Traffic generation from a seed: the one generator every mix reads.

Training feeds come from ``lm_token_ring``, a copy of the program's
``data/synthetic.lm_token_stream`` (Zipf unigrams plus a bigram chain),
kept here so the yardstick cannot move with the program.
"""
from __future__ import annotations

import numpy as np


def lm_token_ring(vocab_size: int, batch: int, seq_len: int, seed: int,
                  n: int) -> list:
    """``n`` batches of {"tokens", "labels"} int32 [batch, seq_len], as
    numpy arrays on the host; every row of every batch differs."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    unigram = (1.0 / ranks) / np.sum(1.0 / ranks)
    shift = rng.integers(1, vocab_size)
    out = []
    for _ in range(n):
        base = rng.choice(vocab_size, size=(batch, seq_len + 1), p=unigram)
        # 50% of positions continue a deterministic bigram chain
        cont = rng.random((batch, seq_len)) < 0.5
        for t in range(1, seq_len + 1):
            nxt = (base[:, t - 1] + shift) % vocab_size
            base[:, t] = np.where(cont[:, t - 1], nxt, base[:, t])
        out.append({"tokens": base[:, :-1].astype(np.int32),
                    "labels": base[:, 1:].astype(np.int32)})
    return out
