"""Blocked (fused) LM-head cross-entropy.

The naive tied-head loss materializes ``[B, S, vocab]`` logits (bf16
~0.4 GB and an fp32 softmax copy ~1.6 GB at GPT-2 bench shapes) — the
single biggest transient in the GPT-2 step and a large slice of the MFU
gap (VERDICT r02).  This version streams the tokens through the head in
``block_rows``-sized SEQUENCE chunks under ``lax.scan``, one
``jax.custom_vjp`` behind both public functions:

  no gradient asked (evaluation, ``jax.eval_shape``): per chunk, logits =
            x_chunk @ W^T on the MXU, fp32 logsumexp reduced immediately;
            only the scalar partial sums persist. ONE product a chunk.
  forward:  the same walk, and while the chunk's logits are in hand their
            gradient ``g = (softmax - onehot) * weight * valid`` and the
            two products it feeds, ``dx_chunk = g @ W`` and ``dW += g^T @
            x_chunk``: THREE products a chunk, the residuals ``dx``, ``dW``
            (and ``dweights``) — peak extra memory is ONE chunk's logits,
            never the whole [B, S, V] plane.
  backward: a scale. Everything the gradient needs from the logits is known
            in the forward; what arrives later is a scalar, the loss's
            cotangent (loss scale, 1 / accumulation steps) over the count
            of positions. No product, no ``exp``, nothing run again.

``g`` is left unscaled — O(1), not divided by the count nor multiplied by a
loss scale before the products — so that ``dx`` and ``dW`` hold in the
compute dtype the magnitudes that a dynamic loss scale (fp16) then lifts,
as it lifts every other gradient of the step.

Chunking the SEQUENCE dim (not flattened rows) keeps the batch dim whole,
so under a dp-sharded mesh every chunk's GEMM stays sharded over the data
axis — flattened-row chunks would put each chunk on a single shard and
serialize the mesh.

Same semantics as models/bert.cross_entropy_ignore_index: mean over
positions whose label is not an ignore value.
"""

import functools

import jax
import jax.numpy as jnp


def _sequence_blocks(hidden, labels, block_rows):
    """``hidden`` [..., B, T, H] and ``labels`` [B, T] as blocks of
    ``block_rows`` sequence positions for ``lax.scan`` to walk: ``(xs
    [nb, ..., B, block, H], ls [nb, B, block], pos [nb, B, block], T)``."""
    B, T, H = hidden.shape[-3:]
    lead = hidden.shape[:-3]
    block = min(block_rows, T)
    nb = -(-T // block)
    pad = nb * block - T
    if pad:
        # pad positions are masked BY INDEX in the chunk body (pos >= T),
        # not by a sentinel label value — so an explicit ignore_values=()
        # (count every real label) stays correct and label-0 padding is
        # never mistaken for a real target
        hidden = jnp.concatenate(
            [hidden, jnp.zeros(lead + (B, pad, H), hidden.dtype)], axis=-2
        )
        labels = jnp.concatenate(
            [labels, jnp.zeros((B, pad), labels.dtype)], axis=1
        )
    # [nb, ..., B, block, ...] so lax.scan walks sequence chunks
    xs = jnp.moveaxis(hidden.reshape(lead + (B, nb, block, H)), -3, 0)
    ls = labels.reshape(B, nb, block).transpose(1, 0, 2)
    pos = jnp.broadcast_to(
        jnp.arange(nb * block, dtype=jnp.int32).reshape(nb, 1, block),
        (nb, B, block),
    )
    return xs, ls, pos, T


def _sequence_of_blocks(ys, T):
    """``_sequence_blocks`` back: [nb, ..., B, block, H] as [..., B, T, H]."""
    ys = jnp.moveaxis(ys, 0, -3)
    B, nb, block, H = ys.shape[-4:]
    return ys.reshape(ys.shape[:-4] + (B, nb * block, H))[..., :T, :]


def _walk(hiddens, word_table, labels, weights, block_rows, ignore_values,
          with_gradient):
    """The one scan behind both losses, over ``hiddens`` [R, B, T, H] and
    ``weights`` [R, B, T] in blocks of sequence positions: ``(num, den)``,
    the counted positions' ``sum_r weights[r] * nll_r`` added up and their
    count; with ``with_gradient`` also the gradient of ``num`` (NOT of
    ``num / den``) to ``(hiddens, word_table, weights)``, each chunk's part
    taken from the chunk's one logits plane."""
    xs, ls, pos, T = _sequence_blocks(hiddens, labels, block_rows)
    ws, _, _, _ = _sequence_blocks(weights[..., None], labels, block_rows)

    def chunk(carry, inputs):
        num, den, d_table = carry
        x, w, l, p_idx = inputs
        w = w[..., 0]
        valid = p_idx < T
        for iv in ignore_values:
            valid &= l != iv
        safe = jnp.broadcast_to(jnp.where(valid, l, 0), x.shape[:-1])
        # the logits in the compute dtype (MXU), the log-sum-exp in float32
        logits = x @ word_table.T  # [R, B, block, V]
        picked = jnp.take_along_axis(
            logits, safe[..., None], axis=-1)[..., 0].astype(jnp.float32)
        m = jnp.max(logits, axis=-1).astype(jnp.float32)
        e = jnp.exp(logits.astype(jnp.float32) - m[..., None])
        z = jnp.sum(e, axis=-1)
        nll = jnp.log(z) + m - picked
        num = num + jnp.sum(jnp.where(valid, jnp.sum(nll * w, axis=0), 0.0))
        den = den + jnp.sum(valid.astype(jnp.int32))
        if not with_gradient:
            return (num, den, d_table), None
        # (softmax - onehot) * weight * valid in float32, cast where
        # autodiff casts the logits' cotangent: at the products' door
        row = jnp.where(valid, w, 0.0)
        target = safe[..., None] == jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, logits.ndim - 1)
        g = (e * (row / z)[..., None]
             - jnp.where(target, row[..., None], 0.0)).astype(logits.dtype)
        d_x = (g @ word_table).astype(x.dtype)
        d_table = d_table + jnp.einsum(
            "...v,...h->vh", g, x, preferred_element_type=d_table.dtype)
        d_w = jnp.where(valid, nll, 0.0).astype(weights.dtype)
        return (num, den, d_table), (d_x, d_w[..., None])

    # the table's gradient adds up in the table's dtype, as the scan's
    # transposition added its cotangent up: float32 fits every cell and is
    # 0.2-3.8 ms a micro-step slower (PERF.md section 6, PR 42)
    d_table = jnp.zeros_like(word_table) if with_gradient else None
    (num, den, d_table), ys = jax.lax.scan(
        chunk, (jnp.float32(0.0), jnp.int32(0), d_table), (xs, ws, ls, pos))
    if not with_gradient:
        return (num, den), None
    d_hiddens, d_weights = (_sequence_of_blocks(y, T) for y in ys)
    return (num, den), (d_hiddens, d_table, d_weights[..., 0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _head_loss(hiddens, word_table, labels, weights, block_rows,
               ignore_values):
    """``num / den`` of ``_walk``. Called for its value alone it runs one
    product a chunk and keeps nothing; under ``jax.grad`` the forward rule
    below runs in its place."""
    (num, den), _ = _walk(hiddens, word_table, labels, weights, block_rows,
                          ignore_values, with_gradient=False)
    return num / jnp.maximum(den, 1).astype(jnp.float32)


def _head_loss_fwd(hiddens, word_table, labels, weights, block_rows,
                   ignore_values):
    (num, den), gradient = _walk(hiddens, word_table, labels, weights,
                                 block_rows, ignore_values,
                                 with_gradient=True)
    count = jnp.maximum(den, 1).astype(jnp.float32)
    return num / count, (gradient, count)


def _head_loss_bwd(block_rows, ignore_values, residuals, ct):
    (d_hiddens, d_table, d_weights), count = residuals
    scale = ct / count
    d_hiddens, d_table, d_weights = (
        (d.astype(jnp.float32) * scale).astype(d.dtype)
        for d in (d_hiddens, d_table, d_weights))
    return d_hiddens, d_table, None, d_weights


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "ignore_values")
)
def blocked_lm_head_loss(
    hidden, word_table, labels, block_rows=512, ignore_values=(-1, -100)
):
    """Mean CE of ``hidden @ word_table.T`` against ``labels``:
    ``weighted_lm_head_loss`` of one state under weights of 1, the same
    body. Forward under ``jax.grad``: three products a chunk, the gradient
    kept as the residual; backward: a scale (module docstring).

    Args:
      hidden: [B, T, H] activations (typically already shifted for
        next-token prediction).
      word_table: [V, H] tied embedding/LM-head table.
      labels: [B, T] integer labels.
      block_rows: sequence positions per chunk; the only [B, block, V]
        buffer alive.
      ignore_values: labels to exclude from the mean.
    """
    return _head_loss(
        hidden[None], word_table, labels,
        jnp.ones((1,) + labels.shape, jnp.float32), block_rows, ignore_values)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "ignore_values")
)
def weighted_lm_head_loss(
    hiddens, word_table, labels, weights, block_rows=512,
    ignore_values=(-1, -100),
):
    """``blocked_lm_head_loss`` for R states of the same positions, each
    position's R losses weighted: the mean over the counted positions of
    ``sum_r weights[r] * nll_r``, ``nll_r`` the CE of ``hiddens[r] @
    word_table.T`` against ``labels``.

    Args:
      hiddens: [R, B, T, H]; a chunk's R x B x block rows go through the
        head in ONE product, so the table is read once a chunk whatever R.
      weights: [R, B, T] float32, differentiable (a looped model's exit
        distribution, models/hybrid.py).

    Under ``jax.grad`` the forward takes the gradient to ``hiddens``,
    ``word_table`` and ``weights`` chunk by chunk, from each chunk's one
    logits plane (three products a chunk where a checkpointed chunk ran
    four), left UNSCALED in the compute dtype so that an fp16 recipe's loss
    scale lifts what it lifts everywhere else; the backward multiplies the
    three by the loss's cotangent over the count, and runs nothing again.
    """
    return _head_loss(
        hiddens, word_table, labels, weights, block_rows, ignore_values)


def exit_log_probs(gate_logits):
    """Log of a looped model's exit distribution from its gate's logits
    [R, ...]: ``lambda_t = sigmoid(z_t)``; pass t < R is left with
    probability ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` and the last
    takes what is left, ``p_R = prod_{j<R} (1 - lambda_j)`` (its own gate is
    not read). In log space, so that a saturated gate gives a large finite
    log and ``p log p`` stays 0 there; ``exp`` of the result sums to 1 over
    the first axis."""
    z = gate_logits.astype(jnp.float32)
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay], axis=0)
    return before.at[:-1].add(jax.nn.log_sigmoid(z[:-1]))
