"""Operations and bytes of one call of the fused flash-attention backward
kernel (it runs as ``flash_bwd_dkv``) at a latent mixer's unequal widths,
from the cell's shapes.

The allowed pairs of ``costs/flash_fwd_mla.py`` (the mean over a micro-step's
calls), and the five products a score sub-tile takes: k q^T again and the two
into dk and dq contract or produce Dqk lanes, v dO^T and the one into dv Dv
lanes: ``2 (3 Dqk + 2 Dv)`` operations a pair. Bytes in bf16: q, k, v and dO
read, dq, dk and dv written (q, k, dq and dk at Dqk lanes a head, v, dO and
dv at Dv), plus the float32 log-sum-exp and delta rows. What the walk visits
beyond the allowed pairs is not counted, so no implementation reads over
100%."""

from .flash_fwd_mla import mean_pairs, widths


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h, dqk, dv = widths(size)
    flops = b * h * mean_pairs(cell, size) * 2 * (3 * dqk + 2 * dv)
    nbytes = b * h * (s * (4 * dqk + 3 * dv) * 2 + 2 * s * 4)
    return flops, nbytes
