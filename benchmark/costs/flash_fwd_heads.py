"""Operations and bytes of one call of the causal flash-attention forward
kernel for a configuration that states its heads and their width
(``num_attention_heads``, ``head_dim``), from the cell's shapes.

``costs/flash_fwd.py``'s count with the head width read and not derived: one
call covers one micro-batch on one chip, B = micro rows, H query heads, S
positions, D per head; the lower triangle's S(S+1)/2 query-key pairs a head,
2D operations each for q.k and for p.v; q, k, v read and the output written
once in bf16, plus the float32 log-sum-exp row. Grouped-query heads count
their k and v once a QUERY head: the kernels get them repeated."""


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h, d = size["num_attention_heads"], size["head_dim"]
    pairs = s * (s + 1) // 2
    flops = b * h * pairs * 4 * d
    nbytes = b * h * (4 * s * d * 2 + s * 4)
    return flops, nbytes
