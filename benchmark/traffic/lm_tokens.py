"""Language-model training batches: token ids uniform over the vocabulary,
every row different, drawn from the seed. The cell's file gives ``micro``
and ``seq``."""

import numpy as np


def micro_batches(seed, cell, size):
    rng = np.random.default_rng([seed, 1])
    while True:
        ids = rng.integers(
            0, size["vocab_size"], (cell["micro"] * cell["chips"], cell["seq"]),
            dtype=np.int32)
        yield {"input_ids": ids}


def tokens_per_micro_batch(cell):
    return cell["micro"] * cell["chips"] * cell["seq"]
