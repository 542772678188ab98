"""Operations and bytes of the chunked gated delta rule (everything under
the ``gdn_delta_rule`` scope) for one training window of the cell, from its
shapes.

Per chunk of C positions and value head, with d_k lanes a key and d_v a
value, the chunked form multiplies: K K^T and Q K^T (C x C x d_k each, once a
KEY head, so 1/r of each a value head); the unit lower-triangular inverse as
the program forms it, log2(C) - 1 squarings and as many products of C x C x
C; T (beta exp(c) K) and T (beta V) (C x C x d_k, C x C x d_v); three
products with the state (C x d_k x d_v each: the corrections' reading of it,
the queries' reading, the chunk's own addition); tril(Q K^T) U (C x C x
d_v). The backward pass is two products for each of these but the inverse,
whose backward is two C x C x C products (dA = -T^T dT T^T). Each product is
counted ONCE at the matrix unit's bf16 rate, also those of the inverse that
run in float32 at full precision; what per-layer remat and the segments'
checkpoint compute again is not counted, so the share cannot reach 100%.

Bytes: q and k [tokens, H_k d_k], v and o [tokens, H_v d_v] in bf16 and g,
beta [tokens, H_v] in float32, read or written once forward and twice
backward (the inputs again, then their gradients); one float32 state [d_k,
d_v] a chunk and value head, written once forward and read once backward. A
form that keeps the state on the chip between chunks and never writes it
moves less than this; one that writes the C x C arrays moves more."""

import math


def per_window(cell, size):
    tokens = cell["micro"] * cell["seq"]
    every = int(size["full_attention_interval"])
    layers = sum((i + 1) % every != 0
                 for i in range(int(size["num_hidden_layers"])))
    passes = cell["accum"] * layers
    hk, hv = size["linear_num_key_heads"], size["linear_num_value_heads"]
    dk, dv = size["linear_key_head_dim"], size["linear_value_head_dim"]
    c = int(size["gdn_chunk"])
    chunks = cell["micro"] * -(-cell["seq"] // c)
    inverse = 2 * (int(math.log2(c)) - 1) * c ** 3
    other = (2 * c * c * dk * hk / hv + c * c * dk + c * c * dv
             + 3 * c * dk * dv + c * c * dv)
    products = (inverse + other) + (2 * c ** 3 + 2 * other)
    flops = passes * chunks * hv * 2 * products
    rows = tokens * (2 * hk * dk * 2 + 2 * hv * dv * 2 + 2 * hv * 4)
    states = chunks * hv * dk * dv * 4
    nbytes = passes * (3 * rows + 2 * states)
    return flops, nbytes
