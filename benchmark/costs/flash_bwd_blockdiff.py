"""Operations and bytes of one call of the fused flash-attention backward
kernel (it runs as ``flash_bwd_dkv``) under the block-diffusion mask, from
the cell's shapes.

The allowed pairs of ``costs/flash_fwd_blockdiff.py``, 10 D operations each:
the five products a score sub-tile takes (k q^T again, v dO^T, and one each
into dv, dk and dq), 2D apiece. Bytes over the 2 L positions, bf16: q, k, v
and dO read, dq, dk and dv written, plus the float32 log-sum-exp and delta
rows. What the walk visits beyond the allowed pairs is not counted, so no
implementation reads over 100%."""

from .flash_fwd_blockdiff import pairs


def per_call(cell, size):
    b, length = cell["micro"], cell["seq"]
    h, d = size["num_attention_heads"], size["head_dim"]
    flops = b * h * pairs(length, int(size["block_length"])) * 10 * d
    nbytes = b * h * (7 * 2 * length * d * 2 + 2 * 2 * length * 4)
    return flops, nbytes
