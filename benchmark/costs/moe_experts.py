"""Operations and bytes of the grouped products over the HELD experts of
the latent mixture of experts, for one training window of the cell, from
its shapes.

The count is the EXPECTATION: a token chooses ``num_experts_per_tok`` of
the ``experts_routed_over`` experts, so it finds on average k * held /
routed of the experts held here (16 * 22 / 512 = 0.69 in the cell); the
program's counter ``moe/local_assignments`` gives the real number of a run
(PERF.md puts it beside this). Each assignment is one row through two
products, latent x intermediate and intermediate x latent, forward, and
through the four products of their backward pass (two for the rows, two
for the weights): 6 products of 2 * latent * intermediate operations.
What per-layer remat computes again, and the forward that the backward of
the grouped loop repeats, is not counted, so the share cannot reach 100%
under remat. Bytes: per E layer and micro-step, the held weights read once
forward and once backward and their float32 gradient written once, and
each assignment's latent row read and written forward and backward, in
bf16; a loop that reads an expert's weights once per tile moves more."""


def per_window(cell, size):
    tokens = cell["micro"] * cell["seq"]
    passes = cell["accum"] * str(int(size["layer_kinds"])).count("2")
    held = size["n_routed_experts"]
    latent, inter = size["moe_latent_size"], size["moe_intermediate_size"]
    assignments = tokens * size["num_experts_per_tok"] * held / size[
        "experts_routed_over"]
    flops = passes * assignments * 6 * 2 * latent * inter
    weights = held * 2 * latent * inter
    nbytes = passes * (weights * (2 + 2 + 4) + assignments * latent * 2 * 4)
    return flops, nbytes
