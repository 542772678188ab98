"""Operations and bytes of the grouped products over the HELD SiLU-gated
experts (everything under ``moe_experts``) for one training window of a
block-diffusion cell, from its shapes.

``costs/gated_experts.py``'s count over the positions the stack really runs
on: a row of ``seq`` = L data tokens goes through every layer as ``[noisy ;
clean]``, 2 L positions, and each of them is routed. That file counts ``micro
* seq`` and would halve the share here."""

from . import gated_experts


def per_window(cell, size):
    return gated_experts.per_window({**cell, "seq": 2 * cell["seq"]}, size)
