"""What the float32 follower keeps on the device (ISSUE 49): its one program
that holds activations takes the gradient sum donated and hands it back in
the same buffers, so that parameters and sum are all that outlives a block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import ops
from benchmark.reference import train as follower

CELLS = ["gpt2-large.train-seq1024", "bert-large.pretrain-seq128"]
SEED = 4900000007


@pytest.mark.parametrize("cell_name", CELLS)
def test_a_blocks_step_writes_the_sum_in_place(cell_name):
    cell, config, _bench = harness.load_cell(cell_name)
    cell.update(cell["toy"])
    size = harness.sizes(config, True)
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    batch = next(gen.micro_batches(SEED, cell, size))
    rows = {k: v[:1] for k, v in batch.items()}
    params = ref.init_params(ops.seed_key(SEED), size)
    n_bytes = sum(x.nbytes for x in params.values())
    weights = tuple(jnp.float32(1.0 / c) for c in ref.counts(batch))
    step = follower.block_step(ref, size, ops.make_dot("float32"))
    compiled = step.lower(params, params, rows, weights).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= n_bytes

    zeros = {k: jnp.zeros_like(x) for k, x in params.items()}
    before = sum(x.nbytes for x in jax.live_arrays())
    acc, loss = compiled(params, zeros, rows, weights)
    assert all(x.is_deleted() for x in zeros.values())   # donated
    assert all(x.dtype == jnp.float32 for x in acc.values())
    assert np.isfinite(float(loss))
    # the sum took its donated buffers' place: nothing of a gradient's size
    # is alive beside it once the block is done
    assert sum(x.nbytes for x in jax.live_arrays()) - before < 0.1 * n_bytes

