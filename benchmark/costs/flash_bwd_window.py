"""Operations and bytes of one call of the fused flash-attention backward
kernel (it runs as ``flash_bwd_dkv``) under the band, from the cell's shapes.

The allowed pairs of ``costs/flash_fwd_window.py``, 10 D operations each: the
five products a score sub-tile takes (k q^T again, v dO^T, and one each into
dv, dk and dq), 2D apiece. Bytes in bf16: q, k, v and dO read, dq, dk and dv
written, plus the float32 log-sum-exp and delta rows. What the walk visits
beyond the allowed pairs is not counted, so no implementation reads over
100%."""

from .flash_fwd_window import pairs


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h, d = int(size["sliding_attention_heads"]), size["head_dim"]
    flops = b * h * pairs(s, int(size["sliding_window"])) * 10 * d
    nbytes = b * h * (7 * s * d * 2 + 2 * s * 4)
    return flops, nbytes
