"""Follow the first steps of a training run in plain float32.

The reference model gives the numerators of its loss's terms over some rows
(``loss_sums``) and their denominators over a micro-batch (``counts``);
this accumulates the gradient of the window's loss block of rows by block
of rows, and applies the optimizer the configuration states, written out
here (Adam: Kingma & Ba 2014 with bias correction, decoupled decay; LAMB:
You et al. 2019 with the trust ratio clamped as DeepSpeed's FusedLamb
does). The window's loss is the mean over its micro-batches of each
micro-batch's own mean loss: gradient accumulation as the program does it.

What lives where. On the device: the float32 parameters, the gradient sum
(donated to every block's step and written in place) and, inside that step,
the block's gradient piece by piece with the activations: 8 bytes a
parameter between blocks, 12 and the activations at a step's end, as the
compiler counts them (``compile_described.py`` prints the peak; PERF.md
section 6, PR 49, has what a sum kept on the host does and does not save).
On the host (numpy): the moments between steps, which visit the device a
leaf at a time for the update.

The sum's order is fixed: float32 adds of the blocks' gradients, block
after block in the order of the rows, from zeros at the start of a step. A
float32 sum depends on its order, and every limit of every cell was set
from readings that added in this one.
"""

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(model, flat):
    """{name: float32 [layers] or [1]}: a leaf that holds one slice per layer
    gives one norm per layer, any other leaf one. A model may name GROUPS of
    leaves whose norm is taken together: a leaf of two numbers, such as the
    bias of BERT's next-sentence head, has a gradient that cancels to almost
    nothing on some seeds, and its own norm then measures rounding only."""
    flat = dict(flat)
    out = {}
    for group, members in getattr(model, "GROUPS", {}).items():
        out[group] = jnp.sqrt(sum(
            jnp.sum(jnp.square(flat.pop(m).astype(jnp.float32)))
            for m in members))[None]
    for name, x in flat.items():
        x = x.astype(jnp.float32)
        if model.stacked(name):
            out[name] = jnp.sqrt(jnp.sum(
                jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
    return out


def host(norms):
    return {k: np.asarray(v) for k, v in norms.items()}


def _update(kind, o):
    def adam(p, g, m, v, t):
        m = o["b1"] * m + (1.0 - o["b1"]) * g
        v = o["b2"] * v + (1.0 - o["b2"]) * g * g
        u = (m / (1.0 - o["b1"] ** t)) / (
            jnp.sqrt(v / (1.0 - o["b2"] ** t)) + o["eps"])
        u = u + o["weight_decay"] * p
        if kind == "lamb":
            wn, un = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(u * u))
            ratio = jnp.where(
                (wn > 0) & (un > 0),
                jnp.clip(wn / un, o["min_coeff"], o["max_coeff"]), 1.0)
            u = ratio * u
        return p - o["lr"] * u, m, v

    if kind not in ("adam", "lamb"):
        raise ValueError(f"no reference optimizer {kind!r}")
    return jax.jit(adam, donate_argnums=(0, 2, 3))


def block_step(model, cfg, dot):
    """Jitted ``(params, sum, rows, weights) -> (sum + gradient, loss)`` over
    one block of rows, the sum donated: the follower's one program that holds
    activations, which ``compile_described.py`` compiles at real size."""
    def block_loss(p, rows, weights):
        sums = model.loss_sums(p, rows, cfg, dot)
        return sum(s * w for s, w in zip(sums, weights))

    def accumulate(p, acc, rows, weights):
        loss, g = jax.value_and_grad(block_loss)(p, rows, weights)
        return jax.tree_util.tree_map(jnp.add, acc, g), loss

    return jax.jit(accumulate, donate_argnums=(1,))


def follow(model, cfg, make_params, steps, optimizer, dot, block_rows):
    """``steps``: one list of micro-batches ({name: numpy array}) per step;
    ``make_params()`` gives the seeded weights, anew at each call. Returns
    the loss of each step, the per-leaf norms of the first step's gradient,
    and the per-leaf norms of the parameters' change after the first step
    and after the last."""
    params = make_params()
    names = sorted(params)

    def change():
        start = make_params()
        return host(leaf_norms(
            model, {k: params[k] - start[k] for k in names}))

    accumulate = block_step(model, cfg, dot)
    update = _update(optimizer["type"], optimizer)
    moments = None
    losses, first_grad, first_change = [], None, None
    for t, micro_batches in enumerate(steps, start=1):
        acc = {k: jnp.zeros_like(params[k]) for k in names}
        loss = 0.0
        for batch in micro_batches:
            n = next(iter(batch.values())).shape[0]
            weights = tuple(
                jnp.float32(1.0 / (c * len(micro_batches)))
                for c in model.counts(batch))
            for lo in range(0, n, block_rows):
                rows = {k: v[lo:lo + block_rows] for k, v in batch.items()}
                acc, part = accumulate(params, acc, rows, weights)
                loss = loss + part
        losses.append(float(loss))
        if first_grad is None:
            first_grad = host(leaf_norms(model, acc))
        new_moments = {}
        for k in names:
            if moments is None:
                m, v = jnp.zeros_like(acc[k]), jnp.zeros_like(acc[k])
            else:
                m, v = (jnp.asarray(x) for x in moments[k])
            params[k], m, v = update(
                params[k], acc.pop(k), m, v, jnp.float32(t))
            if t < len(steps):
                new_moments[k] = (np.asarray(m), np.asarray(v))
            del m, v
        moments = new_moments
        if first_change is None:
            first_change = change()
    return losses, first_grad, first_change, change()
