"""Training batches from the seed: ``(B, S)`` tokens and next-token targets.

Every row of every step is its own draw, so no two rows of a run repeat.
Tokens are uniform over the vocabulary: the benchmark times the step and
checks its arithmetic, and neither depends on what the tokens say.
"""

from __future__ import annotations

import numpy as np


def batch(seed: int, step: int, rows: int, seq: int, vocab: int) -> dict:
    rng = np.random.default_rng([seed, 7, step])
    x = rng.integers(0, vocab, (rows, seq + 1), dtype=np.int64).astype(np.int32)
    return {"tokens": x[:, :-1], "targets": x[:, 1:]}
