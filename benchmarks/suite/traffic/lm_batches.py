"""Training batches: distinct ``[rows, seq]`` token blocks, uniform over
the vocabulary, drawn on the host from the seed. Parameters: ``rows``,
``seq``."""

import numpy as np


class Batches:
    def __init__(self, params, seed, vocab_size):
        self.rows, self.seq = int(params["rows"]), int(params["seq"])
        self.vocab_size = int(vocab_size)
        self.rng = np.random.default_rng(seed)

    @property
    def tokens_per_batch(self):
        return self.rows * self.seq

    def next(self):
        return {"input_ids": self.rng.integers(
            0, self.vocab_size, (self.rows, self.seq), dtype=np.int32)}


def make(params, seed, vocab_size, **_):
    return Batches(params, seed, vocab_size)
