"""Operations and bytes of one call of the flash-attention forward kernel at
a latent mixer's UNEQUAL widths (q and k of ``qk_nope_head_dim +
qk_rope_head_dim`` lanes, v and the context of ``v_head_dim``), from the
cell's shapes.

One call covers one micro-batch of one latent mixer on one chip: B = micro
rows, H = ``num_attention_heads`` heads, S positions, causal. The ALLOWED
pairs are counted, ``S (S + 1) / 2`` a head and row, whatever the kernel's
walk visits (at 512-wide sub-tiles it visits about 6% more), so no walk can
read over 100%: 2 Dqk operations a pair for q.k and 2 Dv for p.v. A
micro-step makes ``num_hidden_layers + num_nextn_predict_layers`` calls of one
shape; the module's runs over S positions of which the last has no target, so
its call counts the pairs of S - 1 positions, and ``per_call`` is the mean
over the calls (the reader divides by the calls' mean time). Bytes in bf16: q,
k and v read and the context written once, plus the float32 log-sum-exp row;
k is counted at its full Dqk lanes a head, as the kernel reads it (the one
rotated key part arrives repeated over the heads)."""


def pairs(seq):
    return seq * (seq + 1) // 2


def widths(size):
    """(heads, q/k lanes, v lanes)."""
    return (int(size["num_attention_heads"]),
            int(size["qk_nope_head_dim"] + size["qk_rope_head_dim"]),
            int(size["v_head_dim"]))


def mean_pairs(cell, size):
    """Allowed pairs a head and row, averaged over a micro-step's calls: the
    main stack's at S positions, the modules' at S - 1."""
    layers = int(size["num_hidden_layers"])
    modules = int(size.get("num_nextn_predict_layers", 0))
    s = cell["seq"]
    return (layers * pairs(s) + modules * pairs(s - 1)) / (layers + modules)


def per_call(cell, size):
    b, s = cell["micro"], cell["seq"]
    h, dqk, dv = widths(size)
    flops = b * h * mean_pairs(cell, size) * 2 * (dqk + dv)
    nbytes = b * h * (s * (2 * dqk + 2 * dv) * 2 + s * 4)
    return flops, nbytes
