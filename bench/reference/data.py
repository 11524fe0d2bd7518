"""The token rows a job trains on, made from the seed.

The rows follow the recipe the program's synthetic pipeline documents: row
``i`` of the stream is a random walk over the vocabulary (steps drawn from
[-32, 32], a random start, taken mod the vocabulary), drawn from a
generator seeded with ``(seed << 32) ^ i``, and rows are consumed in order
across steps and rescales.  Labels are the tokens themselves; the loss
predicts each next token.
"""
from __future__ import annotations

import numpy as np

VIRTUAL_ROWS = 1 << 20          # the stream wraps after this many rows


def row(seed: int, vocab: int, seq_len: int, i: int) -> np.ndarray:
    rng = np.random.default_rng((seed << 32) ^ (i % VIRTUAL_ROWS))
    steps = rng.integers(-32, 33, size=seq_len)
    return np.mod(np.cumsum(steps) + rng.integers(0, vocab),
                  vocab).astype(np.int32)


def rows(seed: int, vocab: int, seq_len: int, start: int,
         count: int) -> np.ndarray:
    """Rows ``start .. start + count - 1`` of the stream, shape
    ``(count, seq_len)``."""
    return np.stack([row(seed, vocab, seq_len, start + k)
                     for k in range(count)])
