"""Operations and bytes of one call of the flash-attention forward kernel
under the band (``window=W``: a query sees its last W keys, itself among
them), from the cell's shapes.

One call covers one micro-batch of a SLIDING layer on one chip: B = micro
rows, H = ``sliding_attention_heads`` query heads of width D, S positions. The
band allows ``S W - W (W - 1) / 2`` query-key pairs a head a row (``pairs``:
W keys a query but for the first W - 1 queries, which have fewer), 2D
operations each for q.k and for p.v. The ALLOWED pairs are counted, whatever
the kernel's walk visits (at 512-wide sub-tiles it visits about twice as
many), so no walk can read over 100%. q, k, v read and the output written
once in bf16, plus the float32 log-sum-exp row. Grouped-query heads count
their k and v once a QUERY head: the kernels get them repeated."""


def pairs(seq, window):
    window = min(window, seq)
    return seq * window - window * (window - 1) // 2


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h, d = int(size["sliding_attention_heads"]), size["head_dim"]
    flops = b * h * pairs(s, int(size["sliding_window"])) * 4 * d
    nbytes = b * h * (4 * s * d * 2 + s * 4)
    return flops, nbytes
