"""The one token generator: batches of a training job, drawn from the seed.

A traffic file fixes the shapes (``seq_len``, ``global_batch``) and the
marginal of the token ids (``token_skew``: an id is ``floor(V * u**skew)``
for uniform ``u``, so low ids are more frequent, as in natural text).  Every
seed gives the same sizes; only the ids differ.  Row ``i`` of stream ``s``
depends on ``(seed, s, i)`` alone, so the training feed (stream 0) and the
held-out eval batches (stream 1) never share a row, and the reference can
draw the same batches again after the run.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

TRAIN_STREAM, EVAL_STREAM = 0, 1


class Batches:
    """The feed a ``Trainer`` reads: ``get()`` for training batches,
    ``next_batch()`` for eval batches; ``batch_at(i)`` draws batch ``i``
    again without moving the cursor."""

    def __init__(self, vocab_size: int, traffic: dict, seed: int,
                 stream: int = TRAIN_STREAM):
        self.vocab_size = int(vocab_size)
        self.seq_len = int(traffic["seq_len"])
        self.global_batch = int(traffic["global_batch"])
        self.skew = float(traffic["token_skew"])
        self.seed, self.stream = int(seed), int(stream)
        self.cursor = 0

    def batch_at(self, index: int) -> Dict[str, np.ndarray]:
        rows = np.empty((self.global_batch, self.seq_len + 1), np.int32)
        for r in range(self.global_batch):
            rng = np.random.default_rng(
                [self.seed, self.stream, index * self.global_batch + r])
            u = rng.random(self.seq_len + 1)
            rows[r] = np.minimum((self.vocab_size * u ** self.skew)
                                 .astype(np.int64), self.vocab_size - 1)
        return {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    def next_batch(self) -> Dict[str, np.ndarray]:
        batch = self.batch_at(self.cursor)
        self.cursor += 1
        return batch

    get = next_batch

    def state(self) -> dict:
        return {"cursor": self.cursor, "seed": self.seed}

    def restore(self, state: dict) -> None:
        self.cursor = int(state["cursor"])
