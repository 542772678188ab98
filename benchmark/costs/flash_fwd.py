"""Operations and bytes of one call of the causal flash-attention forward
kernel, from the cell's shapes.

One call covers one micro-batch on one chip: B = micro rows, H heads, S
positions, D per head. Causal attention needs the lower triangle only:
S(S+1)/2 query-key pairs per head, each 2D operations for q.k and 2D for
p.v. The exponentials and the running maximum are not counted (the matrix
unit's peak is the yardstick). Bytes: q, k, v read and the output written
once, in bf16, plus the float32 log-sum-exp row; a kernel that reads k and
v more than once moves more than this.
"""


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h = size["n_head"]
    d = size["n_embd"] // h
    pairs = s * (s + 1) // 2
    flops = b * h * pairs * 4 * d
    nbytes = b * h * (4 * s * d * 2 + s * 4)
    return flops, nbytes
