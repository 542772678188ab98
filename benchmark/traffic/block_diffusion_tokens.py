"""Block-diffusion training batches from the seed: a clean row of token ids
uniform over the vocabulary slice's rows under the mask id (its last row),
every row different; one noise level ``t_b`` a block of ``block_length``
positions, uniform on ``[t_min, 1]`` (both the configuration's, ``assumed``);
each position replaced by the mask id with probability ``t_b``; the loss
weight ``1 / t_b`` where it was replaced, else 0. The program and the
reference are handed the same three arrays, so both see the same masks.

``1 / t_b`` is rounded to a number bfloat16 holds and ``t_b`` is then its
inverse: the engine casts floating batch leaves to the compute dtype on the
way to the model, and the weights survive that unchanged. The cell's file
gives ``micro`` and ``seq``; ``seq`` and the tokens counted are the DATA
tokens of a row (L), not the 2 L positions the stack runs on."""

import ml_dtypes
import numpy as np


def micro_batches(seed, cell, size):
    rng = np.random.default_rng([seed, 3])
    rows, seq = cell["micro"] * cell["chips"], cell["seq"]
    block, mask_id = int(size["block_length"]), int(size["vocab_size"]) - 1
    while True:
        clean = rng.integers(0, mask_id, (rows, seq), dtype=np.int32)
        level = rng.uniform(size["t_min"], 1.0, (rows, seq // block))
        weight = (1.0 / level).astype(ml_dtypes.bfloat16).astype(np.float32)
        weight = np.repeat(weight, block, axis=1)
        masked = rng.random((rows, seq)) < 1.0 / weight
        yield {
            "noisy_ids": np.where(masked, mask_id, clean).astype(np.int32),
            "clean_ids": clean,
            "loss_weights": np.where(masked, weight, 0.0).astype(np.float32),
        }


def tokens_per_micro_batch(cell):
    return cell["micro"] * cell["chips"] * cell["seq"]
