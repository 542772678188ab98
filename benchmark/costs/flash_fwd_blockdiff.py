"""Operations and bytes of one call of the flash-attention forward kernel
under the block-diffusion mask, from the cell's shapes.

One call covers one micro-batch on one chip: B = micro rows of 2 L positions
(``seq`` = L data tokens; the row is ``[noisy ; clean]``), H query heads of
width D. The mask allows ``L^2 + L block_length`` query-key pairs a head a
row (``pairs``: noisy -> own block ``L B``, noisy -> earlier clean blocks
``L (L - B) / 2``, clean -> clean up to its block's end ``L (L + B) / 2``),
2D operations each for q.k and for p.v. The ALLOWED pairs are counted,
whatever the kernel visits: a walk that skips nothing reads half the share,
and none can read over 100%. q, k, v read and the output written once in
bf16 over the 2 L positions, plus the float32 log-sum-exp row. Grouped-query
heads count their k and v once a QUERY head: the kernels get them
repeated."""


def pairs(length, block):
    return length * length + length * block


def per_call(cell, size):
    b, length = cell["micro"], cell["seq"]
    h, d = size["num_attention_heads"], size["head_dim"]
    flops = b * h * pairs(length, int(size["block_length"])) * 4 * d
    nbytes = b * h * (4 * 2 * length * d * 2 + 2 * length * 4)
    return flops, nbytes
