"""Operations and bytes of the grouped products over the HELD SiLU-gated
experts (everything under ``moe_experts`` in the gated experts sublayer), for
one training window of the cell, from its shapes.

The count is the EXPECTATION, as ``costs/moe_experts.py``'s: a token chooses
``num_experts_per_tok`` of the ``experts_routed_over`` experts and finds on
average k * held / routed of those held here (10 * 32 / 512 = 0.625 in the
cell); ``moe/local_assignments`` gives a run's real number. Each assignment
is one row through three products forward (gate, up: hidden x intermediate;
down: intermediate x hidden) and six backward (two for each): 9 products of
2 * hidden * intermediate operations. What per-layer remat computes again,
and the forward that the backward of the grouped loop repeats, is not
counted, so the share cannot reach 100% under remat. Bytes: per layer and
micro-step, the held weights read once forward and once backward in bf16 and
their float32 gradient written once, and each assignment's hidden-wide row
read and written forward and backward, in bf16; a loop that reads an
expert's weights once per tile moves more."""


def per_window(cell, size):
    tokens = cell["micro"] * cell["seq"]
    passes = cell["accum"] * int(size["num_hidden_layers"])
    held = size["num_experts"]
    hidden, inter = size["hidden_size"], size["moe_intermediate_size"]
    assignments = tokens * size["num_experts_per_tok"] * held / size[
        "experts_routed_over"]
    flops = passes * assignments * 9 * 2 * hidden * inter
    weights = held * 3 * hidden * inter
    nbytes = passes * (weights * (2 + 2 + 4) + assignments * hidden * 2 * 4)
    return flops, nbytes
