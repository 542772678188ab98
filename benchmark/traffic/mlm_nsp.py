"""BERT pre-training batches from the seed: uniform token ids, no padding,
two segments split at a random position, ``mlm_share`` of the positions
carrying their own id as the masked-LM label (-1 elsewhere), a random
next-sentence label. The inputs at the labelled positions are not replaced
by a mask token: that changes what is learned, not what is computed."""

import numpy as np


def micro_batches(seed, cell, size):
    rng = np.random.default_rng([seed, 2])
    rows, seq = cell["micro"] * cell["chips"], cell["seq"]
    while True:
        ids = rng.integers(0, size["vocab_size"], (rows, seq), dtype=np.int32)
        split = rng.integers(1, seq, (rows, 1))
        types = (np.arange(seq)[None, :] >= split).astype(np.int32)
        labelled = rng.random((rows, seq)) < cell["mlm_share"]
        labelled[:, 0] = True   # at least one label in every row
        yield {
            "input_ids": ids,
            "attention_mask": np.ones((rows, seq), np.int32),
            "token_type_ids": types,
            "masked_lm_labels": np.where(labelled, ids, -1).astype(np.int32),
            "next_sentence_label": rng.integers(0, 2, (rows,), dtype=np.int32),
        }


def tokens_per_micro_batch(cell):
    return cell["micro"] * cell["chips"] * cell["seq"]
