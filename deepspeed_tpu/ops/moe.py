"""Mixture-of-Experts with expert parallelism, the GSPMD way.

Beyond-reference capability (v0.2.0 has no MoE; SURVEY §2.4 lists EP as
absent). Built as the GShard/GSPMD einsum pattern rather than a port of
torch all-to-all MoE: the router produces one-hot dispatch/combine
tensors, token->expert movement is two einsums whose operands carry
sharding constraints — experts sharded over the mesh's ``data`` axis (the
standard expert=data layout), tokens sharded over the same axis on the
group dim — and XLA inserts the all-to-alls over ICI. No hand-written
collectives, and the whole layer stays differentiable/jit-friendly
(static capacity, dropped-token semantics).

Router: top-2 gating with the Switch/GShard load-balancing auxiliary loss
(mean gate fraction x mean dispatch fraction x E), capacity
``capacity_factor * S * K / E`` tokens per expert per group; overflow
tokens fall through to the residual path (standard MoE semantics).

A second path lives at the end of this file: ``latent_moe_mixer``, the
expert-SHARE layer of a latent mixture of experts that drops no token
(sigmoid scores, sorted assignments, Pallas kernels over the tiles in use).
"""

import dataclasses
import functools
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.constants import DATA_AXIS
from ..utils import device


@dataclasses.dataclass(unsafe_hash=True)
class MoEConfig:
    n_experts: int = 8
    # top-k routing (1 = Switch, 2 = GShard default)
    top_k: int = 2
    capacity_factor: float = 1.25
    # weight of the load-balancing aux loss added via ``aux_loss`` output
    aux_loss_weight: float = 1e-2
    # experts shard over this mesh axis (expert parallelism); the
    # conventional choice is the data axis — each dp rank hosts E/dp experts
    expert_axis: str = DATA_AXIS


def top_k_gating(logits, k, capacity):
    """GShard-style top-k gating.

    Args:
      logits: [G, S, E] router logits (G token groups, S tokens, E experts).
      k: how many experts per token.
      capacity: max tokens per (group, expert).

    Returns:
      dispatch: [G, S, E, C] one-hot dispatch mask (0/1, float32).
      combine: [G, S, E, C] combine weights (gate prob at the dispatched
        slot, 0 elsewhere).  For k > 1 the selected gates are renormalized
        by their sum (GShard semantics: the expert branch keeps unit mass
        instead of being attenuated by the sub-1 top-k softmax mass); k = 1
        keeps the raw prob (Switch semantics).
      aux_loss: scalar load-balancing loss (mean_gates . mean_dispatch * E).
    """
    G, S, E = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # [G,S,E]

    # aux loss uses the FIRST choice's dispatch fraction (Switch eq. 4):
    # E * sum_e(mean-gate_e * dispatch-fraction_e), averaged over groups;
    # == 1 at perfect balance
    top1 = jnp.argmax(gates, axis=-1)  # [G,S]
    top1_1h = jax.nn.one_hot(top1, E, dtype=jnp.float32)
    aux_loss = E * jnp.mean(
        jnp.sum(jnp.mean(gates, axis=1) * jnp.mean(top1_1h, axis=1), axis=-1)
    )

    dispatch = jnp.zeros((G, S, E, capacity), jnp.float32)
    combine = jnp.zeros((G, S, E, capacity), jnp.float32)
    remaining = gates
    # running per-expert fill count, carried across the k choices so the
    # second choice respects slots taken by first choices
    fill = jnp.zeros((G, E), jnp.int32)
    topk_mass = jnp.zeros((G, S), jnp.float32)
    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)  # [G,S]
        choice_1h = jax.nn.one_hot(choice, E, dtype=jnp.float32)
        gate_val = jnp.sum(remaining * choice_1h, axis=-1)  # [G,S]
        # position of each token within its chosen expert's queue:
        # tokens earlier in the group claim earlier slots
        pos_in_expert = (
            jnp.cumsum(choice_1h, axis=1) - choice_1h
        )  # [G,S,E] count of same-expert tokens before this one
        pos = jnp.einsum("gse,gse->gs", pos_in_expert, choice_1h)
        pos = pos + jnp.take_along_axis(
            fill.astype(jnp.float32), choice, axis=1
        )
        keep = pos < capacity  # dropped tokens fall through to residual
        pos_1h = jax.nn.one_hot(
            jnp.where(keep, pos, capacity).astype(jnp.int32),
            capacity, dtype=jnp.float32,
        )  # [G,S,C] (overflow maps past the last slot -> all-zero row)
        d = choice_1h[..., None] * pos_1h[:, :, None, :]  # [G,S,E,C]
        dispatch = dispatch + d
        combine = combine + d * gate_val[..., None, None]
        fill = fill + jnp.sum(
            (choice_1h * keep[..., None]).astype(jnp.int32), axis=1
        )
        topk_mass = topk_mass + gate_val
        remaining = remaining * (1.0 - choice_1h)  # mask the chosen expert
    if k > 1:
        combine = combine / jnp.maximum(topk_mass, 1e-9)[..., None, None]
    return dispatch, combine, aux_loss


class MoEMLP(nn.Module):
    """Expert-parallel FFN: ``[G, S, M] -> [G, S, M]`` plus an aux loss.

    Expert weights are stored stacked ``[E, M, I]``/``[E, I, M]`` and
    sharded over ``cfg.expert_axis``; the dispatch/combine einsums carry
    sharding constraints so GSPMD materializes the token all-to-all over
    ICI (the einsum MoE of the GShard paper, TPU-native).
    """

    hidden: int
    intermediate: int
    cfg: MoEConfig
    mesh: Optional[object] = None
    initializer_range: float = 0.02

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        M, I, E = self.hidden, self.intermediate, cfg.n_experts
        G, S, _ = x.shape
        init = nn.initializers.normal(stddev=self.initializer_range)
        wg = self.param("gate_w", init, (M, E), jnp.float32)
        wi = self.param("expert_in_w", init, (E, M, I), x.dtype)
        bi = self.param("expert_in_b", nn.initializers.zeros, (E, I), x.dtype)
        wo = self.param("expert_out_w", init, (E, I, M), x.dtype)
        bo = self.param("expert_out_b", nn.initializers.zeros, (E, M), x.dtype)

        capacity = max(1, int(cfg.capacity_factor * S * cfg.top_k / E))
        logits = x.astype(jnp.float32) @ wg  # router in fp32
        dispatch, combine, aux = top_k_gating(logits, cfg.top_k, capacity)
        dispatch = dispatch.astype(x.dtype)
        combine = combine.astype(x.dtype)

        def shard(t, spec):
            if self.mesh is None:
                return t
            if dict(self.mesh.shape).get(cfg.expert_axis, 1) == 1:
                return t
            return jax.lax.with_sharding_constraint(
                t, NamedSharding(self.mesh, spec)
            )

        # tokens -> expert queues: [G,S,E,C] x [G,S,M] -> [E,G,C,M]
        expert_in = jnp.einsum("gsec,gsm->egcm", dispatch, x)
        expert_in = shard(expert_in, P(cfg.expert_axis))
        h = jnp.einsum("egcm,emi->egci", expert_in, wi) + bi[:, None, None, :]
        h = nn.gelu(h, approximate=True)
        out = jnp.einsum("egci,eim->egcm", h, wo) + bo[:, None, None, :]
        out = shard(out, P(cfg.expert_axis))
        # expert queues -> tokens (weighted by gate prob; dropped tokens
        # receive zeros and ride the residual connection)
        y = jnp.einsum("gsec,egcm->gsm", combine, out)
        return y, cfg.aux_loss_weight * aux


def moe_leaf_spec(names, leaf, expert_axis=DATA_AXIS):
    """PartitionSpec for one MoE param leaf (by its path names):
    expert-stacked weights shard their E axis over ``expert_axis`` (dim 0
    standalone, dim 1 under a scanned stack's leading ``layers`` axis);
    the router gate is replicated."""
    if any(n and n.startswith("expert_") for n in names):
        base_nd = 3 if any(
            n in ("expert_in_w", "expert_out_w") for n in names
        ) else 2
        if leaf.ndim == base_nd:  # [E, ...]
            return P(expert_axis, *([None] * (leaf.ndim - 1)))
        # scanned: [L, E, ...]
        return P(None, expert_axis, *([None] * (leaf.ndim - 2)))
    return P()


def moe_partition_specs(params, expert_axis=DATA_AXIS):
    """PartitionSpecs for a param tree containing MoEMLP subtrees; non-MoE
    params come back replicated."""

    def spec_for(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        return moe_leaf_spec(names, leaf, expert_axis)

    return jax.tree_util.tree_map_with_path(spec_for, params)


class DeepSpeedMoETransformerLayer(nn.Module):
    """Transformer block whose FFN sublayer is an expert-parallel MoE.

    Attention sublayer, LN order, dropout and residual structure are the
    fused layer's (ops/transformer.py:transformer_block_apply with
    ``ffn_fn`` swapped); returns ``(hidden, aux_loss)`` — callers (the
    GPT-2 MoE stack) accumulate the router losses into the objective.
    """

    config: object  # DeepSpeedTransformerConfig
    moe: MoEConfig
    causal: bool = False
    use_flash: bool = True
    mesh: Optional[object] = None

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, train: bool = True):
        from .transformer import transformer_block_apply

        cfg = self.config
        if cfg.use_remat:
            # raw jax.checkpoint around a closure that calls a flax
            # submodule (the MoE) would re-enter module scopes; use
            # nn.remat at the stack level instead if needed
            raise ValueError(
                "DeepSpeedMoETransformerLayer does not support the layer "
                "memory modes; leave remat flags off for MoE layers"
            )
        from .transformer import TRANSFORMER_PARAM_LAYOUT

        H = cfg.hidden_size
        dtype = hidden_states.dtype
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        # attention + norm params from the shared layout; the FFN entries
        # (inter_*/output_*) are replaced by the MoE's expert weights
        shapes = {"H": H, "3H": 3 * H, "I": cfg.intermediate}
        makers = {
            "init": (init, dtype),
            "zeros": (nn.initializers.zeros, dtype),
            "ones32": (nn.initializers.ones, jnp.float32),
            "zeros32": (nn.initializers.zeros, jnp.float32),
        }
        p = {
            name: self.param(
                name, makers[kind][0],
                tuple(shapes[d] for d in dims), makers[kind][1],
            )
            for name, dims, kind in TRANSFORMER_PARAM_LAYOUT
            if not name.startswith(("inter_", "output_"))
        }
        moe = MoEMLP(
            hidden=H, intermediate=cfg.intermediate, cfg=self.moe,
            mesh=self.mesh, initializer_range=cfg.initializer_range,
            name="moe",
        )
        need_rng = train and (
            cfg.attn_dropout_ratio > 0 or cfg.hidden_dropout_ratio > 0
        )
        rng = self.make_rng("dropout") if need_rng else None
        return transformer_block_apply(
            cfg, p, hidden_states, attention_mask,
            causal=self.causal, use_flash=self.use_flash, mesh=self.mesh,
            train=train, dropout_rng=rng, ffn_fn=moe,
        )


# ----------------------------------------------------------------------
# Latent mixture of experts that drops no token (the expert-SHARE layer).
#
# The layer is told which experts it HOLDS (``offset`` .. ``offset + held``)
# out of those it ROUTES OVER: it scores every token against all of them,
# takes the top-k, and computes its own experts' part of the result. What
# the experts held elsewhere would add is left out, and nothing here stands
# in for the exchange that would fetch it. There is no capacity: the
# assignments to held experts are sorted by expert (ONE single-operand sort
# of keys ``expert * tokens + token``), each expert's run is cut into tiles
# of rows, and the tiles IN USE go a chunk at a time through one gather of
# their rows, one call of a Pallas kernel that multiplies each tile by its
# expert's weights, and one scatter-add of the results — work goes with the
# real group sizes, and only the key array has the worst-case size.
# Everything that carries a gradient outside the kernels is elementwise
# over [tokens, experts]: no static-size scatter in a backward.
# ----------------------------------------------------------------------


def level_selection_scores(positions, routed):
    """[len(positions), routed] float32: a fixed pseudo-random number for
    every (position in the sequence, expert), far apart (integers below
    2^24, typical gap 2^15). Added to the selection scores they decide the
    top-k alone: every position picks a fixed random-looking set, every
    expert takes tokens * k / routed of them give or take a few percent,
    whatever the weights are and however training moves them."""
    cell = positions.astype(jnp.uint32)[:, None] * jnp.uint32(routed) \
        + jnp.arange(routed, dtype=jnp.uint32)[None, :]
    h = cell * jnp.uint32(2654435761)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return ((h ^ (h >> 16)) >> 8).astype(jnp.float32)


def route_sigmoid_topk(x, router_w, router_bias, top_k, scale, level=None):
    """Sigmoid scores in float32 over all routed experts; the top-k of
    ``score + bias`` (the bias chooses, takes no gradient and weighs
    nothing); weights ``scale * s_e / sum_chosen s``. ``level``: each row's
    position in its sequence, to force a level selection
    (``level_selection_scores`` joins the bias); the weights are the
    router's own either way. x [T, E] -> (chosen [T, k] int32, weights
    [T, routed] float32, 0 where not chosen)."""
    scores = jax.nn.sigmoid(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32))
    selection = scores + jax.lax.stop_gradient(router_bias.astype(jnp.float32))
    if level is not None:
        selection = selection + level_selection_scores(level, scores.shape[-1])
    chosen, picked = _top_k_of(selection, scores, top_k)
    return chosen, scale * picked / jnp.sum(picked, axis=-1, keepdims=True)


def _top_k_of(selection, scores, top_k):
    """The top-k of ``selection`` a row, and ``scores`` where chosen, 0
    elsewhere."""
    _, chosen = jax.lax.top_k(selection, top_k)
    experts = jnp.arange(scores.shape[-1], dtype=chosen.dtype)
    mask = jnp.any(chosen[:, :, None] == experts[None, None, :], axis=1)
    return chosen, jnp.where(mask, scores, 0.0)


def plan_held_rows(chosen, held, offset, tile):
    """Sort the assignments to held experts by expert, tokens in order
    inside each. Returns a dict: ``keys`` — ``expert * tokens + token`` for
    every held assignment in sorted order, then the sentinel ``held *
    tokens`` (static length: tokens x k, plus one tile so that a tile's
    slice never runs off the end);
    ``tile_expert``, ``tile_first`` (where in ``keys`` the tile's rows
    start) and ``tile_rows`` (how many are real), one entry for each tile
    that could be in use; ``n_tiles``, the tiles in use. Beside it,
    ``sizes`` [held]: each held expert's load."""
    tokens = chosen.shape[0]
    if (held + 1) * tokens >= 2 ** 31:
        raise ValueError(
            f"{held} held experts x {tokens} tokens overflow the int32 keys")
    local = chosen - offset
    sentinel = held * tokens
    token = jnp.arange(tokens, dtype=jnp.int32)[:, None]
    keys = jnp.where(
        (local >= 0) & (local < held), local * tokens + token, sentinel)
    keys = jnp.sort(keys.reshape(-1).astype(jnp.int32))
    bounds = jnp.arange(held + 1, dtype=jnp.int32) * tokens
    starts = jnp.searchsorted(keys, bounds).astype(jnp.int32)
    sizes = starts[1:] - starts[:-1]
    tile_starts = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(-(-sizes // tile)).astype(jnp.int32)])
    max_tiles = -(-keys.shape[0] // tile) + held
    ids = jnp.arange(max_tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_starts[1:], ids, side="right"),
        held - 1).astype(jnp.int32)
    tile_first = starts[tile_expert] + (ids - tile_starts[tile_expert]) * tile
    tile_rows = jnp.clip(starts[tile_expert + 1] - tile_first, 0, tile)
    return {
        "keys": jnp.concatenate(
            [keys, jnp.full((tile,), sentinel, jnp.int32)]),
        "tile_expert": tile_expert, "tile_first": tile_first,
        "tile_rows": tile_rows, "n_tiles": tile_starts[held],
    }, sizes


def route_softmax_topk(x, router_w, top_k, level=None):
    """Softmax in float32 over all routed experts; the top-k of it; weights
    ``p_e / sum_chosen p`` (the sum runs over all k chosen, held here or
    not). ``level`` as in ``route_sigmoid_topk``. x [T, E] -> (chosen [T, k]
    int32, weights [T, routed] float32, 0 where not chosen)."""
    probs = jax.nn.softmax(
        jnp.dot(x, router_w, preferred_element_type=jnp.float32), axis=-1)
    selection = probs
    if level is not None:
        selection = probs + level_selection_scores(level, probs.shape[-1])
    chosen, picked = _top_k_of(selection, probs, top_k)
    return chosen, picked / jnp.sum(picked, axis=-1, keepdims=True)


# The two expert forms of the grouped products, each as (the hidden
# activation from the tile's rows times the expert's input matrices, its
# backward: the gradient of each of those products from the hidden one's):
# ``relu2`` is ``relu(x W1)^2 W2`` over ``mats = (W1, W2)``; ``swiglu`` is
# ``(silu(x Wg) * (x Wu)) Wd`` over ``(Wg, Wu, Wd)``. All float32.
def _relu2(a):
    r = jnp.maximum(a, 0)
    return r * r


def _relu2_bwd(a, dh):
    return (dh * 2.0 * jnp.maximum(a, 0),)


def _swiglu(a, b):
    return jax.nn.silu(a) * b


def _swiglu_bwd(a, b, dh):
    sig = jax.nn.sigmoid(a)
    return dh * b * sig * (1.0 + a * (1.0 - sig)), dh * a * sig


EXPERT_FORMS = {"relu2": (_relu2, _relu2_bwd),
                "swiglu": (_swiglu, _swiglu_bwd)}

# ----------------------------------------------------------------------
# The kernels. One walk serves both forms, forward and backward: a grid
# step is one tile of the plan, its expert's matrices picked out of the
# stacked ``[held, ...]`` arrays by the prefetched ``tile_expert`` (a
# block stays in VMEM while consecutive tiles name the same expert, so an
# expert's weights cross HBM once a pass), and an inner loop over blocks of
# the intermediate width keeps every activation of the tile in VMEM.
# Products take the rows' dtype with float32 accumulation. The backward
# computes the tile's hidden activation again rather than keep any, and
# adds an expert's weight gradient up in float32 scratch over that
# expert's consecutive tiles: written once, in the weights' dtype.
# ----------------------------------------------------------------------
_VMEM_LIMIT = 100 * 2 ** 20  # of v5e's 128 MiB: an expert's matrices whole
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), preferred_element_type=jnp.float32)


def _in_blocks(size, sizes, body):
    """``body(slice)`` over ``size`` rows or columns in blocks of the first
    of ``sizes`` that divides it (all of a toy size at once): a loop, not
    unrolled code."""
    block = next((b for b in sizes if size % b == 0), size)

    def step(i, _):
        body(pl.ds(pl.multiple_of(i * block, block), block))

    jax.lax.fori_loop(0, size // block, step, None)


# whole 128-lane blocks of the intermediate width a step of the inner loop
_WIDTH_BLOCKS = (512, 384, 256, 128)


def _ffn_fwd_kernel(expert_ref, meta_ref, x_ref, wt_ref, *refs, form):
    """``moe_ffn_fwd``: y = weight * expert(x) for one tile's rows."""
    *w_in, w_out, y_ref = refs
    act = EXPERT_FORMS[form][0]

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _tile():
        x = x_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

        def block(cols):
            h = act(*(_dot(x, w[:, cols], _NN) for w in w_in))
            y_ref[...] += _dot(h.astype(x.dtype), w_out[cols, :], _NN)

        _in_blocks(w_out.shape[0], _WIDTH_BLOCKS, block)
        y_ref[...] *= wt_ref[...]


def _ffn_bwd_kernel(expert_ref, meta_ref, x_ref, g_ref, wt_ref, *refs, form):
    """``moe_ffn_bwd``: from one tile's rows x, the gradient g at their
    tokens and their routing weights: dx, the weights' gradient (the sum
    over the hidden width of h * (g W_out^T): the product h W_out is never
    formed) and the tile's part of every matrix's gradient, added up in
    float32 scratch over the expert's consecutive tiles and written in the
    matrix's dtype at the last of them. ``refs``: the matrices; the sums
    that the chunk before left open (float32, in HBM); the gradients'
    buffers (aliased to the outputs, not read); dx, dwt, the gradients, the
    sums left open (aliased to the ones read); the scratch sums; a
    semaphore. ``meta``: tiles in use, the expert whose run the chunk before
    left open (-1: none), whether a chunk follows."""
    *refs, sem = refs
    n = (len(refs) - 2) // 6
    (*w_in, w_out), open_in, _ = (refs[i * n:(i + 1) * n] for i in range(3))
    dx_ref, dwt_ref = refs[3 * n:3 * n + 2]
    dws, open_out, accs = (
        refs[3 * n + 2 + i * n:3 * n + 2 + (i + 1) * n] for i in range(3))
    *acc_in, acc_out = accs
    act, act_bwd = EXPERT_FORMS[form]
    t, in_use = pl.program_id(0), meta_ref[0]
    live = t < in_use
    e = expert_ref[t]
    first = live & ((t == 0) | (e != expert_ref[jnp.maximum(t - 1, 0)]))
    last = live & ((t == in_use - 1) | (e != expert_ref[
        jnp.minimum(t + 1, pl.num_programs(0) - 1)]))
    resumed = (t == 0) & (e == meta_ref[1])

    def each(fn, *refs_of_mats):
        for group in zip(*refs_of_mats):
            _in_blocks(
                group[0].shape[1], (128,), functools.partial(fn, *group))

    def copies(sources, targets):
        for source, target in zip(sources, targets):
            copy = pltpu.make_async_copy(source, target, sem)
            copy.start()
            copy.wait()

    @pl.when(first & ~resumed)
    def _start():
        def zero(acc, rows):
            acc[0, rows, :] = jnp.zeros(
                (rows.size, acc.shape[2]), jnp.float32)

        each(zero, accs)

    @pl.when(live & resumed)
    def _resume():
        copies(open_in, accs)

    @pl.when(live)
    def _tile():
        x, g, wt = x_ref[...], g_ref[...], wt_ref[...]
        gx = g.astype(x.dtype)
        dy = (g * wt).astype(x.dtype)
        dx_ref[...] = jnp.zeros_like(dx_ref)
        dwt_ref[...] = jnp.zeros_like(dwt_ref)

        def block(cols):
            pre = [_dot(x, w[:, cols], _NN) for w in w_in]
            h = act(*pre).astype(x.dtype)
            dh = _dot(gx, w_out[cols, :], _NT)
            dwt_ref[...] += jnp.sum(
                h.astype(jnp.float32) * dh, axis=1, keepdims=True)
            acc_out[0, cols, :] += _dot(h, dy, _TN)
            for w, acc, dp in zip(w_in, acc_in, act_bwd(*pre, dh * wt)):
                dp = dp.astype(x.dtype)
                acc[0, :, cols] += _dot(x, dp, _TN)
                dx_ref[...] += _dot(dp, w[:, cols], _NT)

        _in_blocks(w_out.shape[0], _WIDTH_BLOCKS, block)

    @pl.when(last)
    def _write():
        def cast(dw, acc, rows):
            dw[0, rows, :] = acc[0, rows, :].astype(dw.dtype)

        each(cast, dws, accs)

    @pl.when(last & (t == in_use - 1) & (meta_ref[2] > 0))
    def _leave_open():
        copies(accs, open_out)


def _ffn_call(kernel, name, form, expert, meta, rows, mats, out_rows,
              grads=(), left_open=(), part=(0, 1)):
    """One chunk of tiles through a kernel. ``rows``: the arrays that hold
    a tile's rows ``[tiles * stride, width]``; ``out_rows``: the widths and
    dtypes of such results; ``grads``: arrays like ``mats`` in which the
    kernel writes the gradients of the experts whose runs end in this
    chunk, and ``left_open`` the float32 sums ``[1, ...]`` of the run a
    chunk's end cuts, both in place. ``part = (at, parts)``: the call takes
    part ``at`` of ``parts`` of every matrix's intermediate width (the last
    axis of the input matrices, the first of the output one) and of every
    gradient, and ``left_open`` holds that part's sums (``_width_parts``)."""
    tiles = expert.shape[0]
    stride = rows[0].shape[0] // tiles
    at, parts = part
    if device.on_tpu() and any(
            width % 128 for width in (
                *mats[0].shape[1:], mats[0].shape[2] // parts)):
        raise ValueError(
            "on the chip the grouped expert kernels take an expert's "
            "matrices in whole 128-lane blocks, not "
            f"{tuple(mats[0].shape[1:])} in {parts} part(s)")

    def at_tile(t, meta):
        return jnp.maximum(jnp.minimum(t, meta[0] - 1), 0)

    def tile_spec(width):
        return pl.BlockSpec(
            (stride, width), lambda t, e, m: (at_tile(t, m), 0))

    def expert_specs(like, lead=None):
        """One spec a matrix: the intermediate width is the last axis of the
        input matrices and the first of the output one, the last of them."""
        specs = []
        for i, mat in enumerate(like):
            down, across = mat.shape[1:]
            specs.append(pl.BlockSpec(
                (lead, down // parts, across),
                lambda t, e, m: (e[at_tile(t, m)], at, 0))
                if i == len(like) - 1 else pl.BlockSpec(
                (lead, down, across // parts),
                lambda t, e, m: (e[at_tile(t, m)], 0, at)))
        return specs

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    outs = [jax.ShapeDtypeStruct((tiles * stride, w), d) for w, d in out_rows]
    n_in = 2 + len(rows) + len(mats)
    return pl.pallas_call(
        functools.partial(kernel, form=form),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[tile_spec(r.shape[1]) for r in rows]
            + expert_specs(mats)
            + [in_hbm] * (len(left_open) + len(grads)),
            out_specs=[tile_spec(w) for w, _ in out_rows]
            + expert_specs(grads, 1) + [in_hbm] * len(left_open),
            scratch_shapes=[
                pltpu.VMEM(m.shape, jnp.float32) for m in left_open]
            + [pltpu.SemaphoreType.DMA(())] * bool(grads)),
        out_shape=outs + [
            jax.ShapeDtypeStruct(m.shape, m.dtype)
            for m in (*grads, *left_open)],
        input_output_aliases={
            **{n_in + len(left_open) + i: len(outs) + i
               for i in range(len(grads))},
            **{n_in + i: len(outs) + len(grads) + i
               for i in range(len(left_open))}},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=not device.on_tpu(), name=name,
    )(expert, meta, *rows, *mats, *left_open, *grads)


# What the backward kernel holds of ONE expert in VMEM: its matrices and their
# gradients' output blocks, two buffers each, and the float32 sums. Above this
# an expert is taken in parts of its intermediate width, one call of the
# kernel a part: the gated FFN and its backward are sums over that width, so
# the parts' dx and routing-weight gradients add up and each part writes its
# own columns of the matrices' gradients. 3 x 2048 x 768 (56.6 MB) stays whole;
# 3 x 3072 x 1024 (113 MB, over the chip's 128 MiB with the rows) takes two.
_BWD_EXPERT_BUDGET = 64 * 2 ** 20


def _width_parts(mats):
    """Parts of the intermediate width in which ``moe_ffn_bwd`` takes an
    expert's matrices: the fewest (a power of two, whole 128-lane blocks each
    on the chip) under ``_BWD_EXPERT_BUDGET``."""
    held, width = mats[0].shape[0], mats[-1].shape[1]
    need = sum(m.size // held for m in mats) * (
        4 * mats[0].dtype.itemsize + 4)
    parts, lanes = 1, 128 if device.on_tpu() else 1
    while need > parts * _BWD_EXPERT_BUDGET \
            and width % (2 * parts * lanes) == 0:
        parts *= 2
    return parts


def _row_stride(tile, dtype):
    """Rows a tile takes in the kernels' buffers: ``tile`` rounded up to
    whole sublane groups of the rows' dtype."""
    group = 8 * 4 // jnp.dtype(dtype).itemsize
    return -(-tile // group) * group


def _chunk_of_tiles(c, chunk_tiles, tile, stride, plan, weights_t):
    """Chunk ``c`` of the plan's tiles laid out ``stride`` rows a tile:
    each tile's expert; for the kernels (tiles in use, the expert of the
    tile before the chunk or -1, whether tiles are left for a next chunk);
    and a row's key (``held * tokens``: no row), token (``tokens``: no row)
    and routing weight."""
    held, tokens = weights_t.shape
    ids = c * chunk_tiles + jnp.arange(chunk_tiles, dtype=jnp.int32)
    expert, first, rows = (
        jnp.take(plan[name], ids, mode="fill", fill_value=0)
        for name in ("tile_expert", "tile_first", "tile_rows"))
    j = jnp.arange(stride, dtype=jnp.int32)
    real = (j < rows[:, None]) & (ids < plan["n_tiles"])[:, None]
    # a tile's keys lie side by side: a slice a tile, not a gather a row
    sorted_keys = jnp.pad(plan["keys"], (0, stride - tile))
    keys = jax.vmap(lambda at: jax.lax.dynamic_slice(
        sorted_keys, (at,), (stride,)))(first)
    key = jnp.where(real, keys, held * tokens).reshape(-1)
    tok = jnp.where(real, keys - expert[:, None] * tokens, tokens).reshape(-1)
    wt = jnp.take(weights_t.reshape(-1), key, mode="fill", fill_value=0)
    left = plan["n_tiles"] - c * chunk_tiles
    meta = jnp.stack([
        jnp.clip(left, 0, chunk_tiles),
        jnp.where(c > 0, plan["tile_expert"][c * chunk_tiles - 1], -1),
        left > chunk_tiles]).astype(jnp.int32)
    return expert, meta, key, tok, wt[:, None]


def _rows_of(a, tok):
    """``a[tok]`` for a chunk's rows. Where ``tok`` says no row (``tokens``)
    this takes the last token's: its routing weight is 0, so whatever the
    kernels compute from it is 0 or is dropped, and a gather that need not
    fill is up to 1.7 times as fast."""
    return jnp.take(a, tok, axis=0, mode="clip")


def _replicated(fn, mesh):
    """``fn`` on every device of ``mesh`` over whole operands: a kernel is
    not partitioned, so more than one device each computes all of it."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(
        fn, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def grouped_expert_ffn(u, mats, weights_t, plan, tile, form="relu2",
                       chunk_tiles=None, mesh=None):
    """sum over held assignments of ``weight * expert_e(u[token])`` at the
    token's row, the expert in one of ``EXPERT_FORMS``. u [T, L]; ``mats``:
    the held experts' matrices, each [held, ...] (``relu2``: w1 [held, L, F],
    w2 [held, F, L]; ``swiglu``: wg and wu [held, L, F], wd [held, F, L]);
    weights_t [held, T] float32 (0 where the token did not choose the
    expert); plan: ``plan_held_rows``. ``chunk_tiles``: the tiles whose
    rows are gathered, multiplied (one call of a kernel) and combined at a
    time; a loop takes as many such chunks as the tiles in use need (None:
    one chunk of the worst case). -> [T, L] float32."""
    return _grouped_fwd(u, mats, weights_t, plan, tile, form, chunk_tiles,
                        mesh)[0]


def _chunks(plan, chunk_tiles):
    chunk_tiles = min(chunk_tiles or 2 ** 31, plan["tile_expert"].shape[0])
    return chunk_tiles, -(-plan["n_tiles"] // chunk_tiles)


def _grouped_fwd(u, mats, weights_t, plan, tile, form, chunk_tiles, mesh):
    stride = _row_stride(tile, u.dtype)

    def forward(u, mats, weights_t, plan):
        tiles, n_chunks = _chunks(plan, chunk_tiles)

        def chunk(c, out):
            expert, meta, _, tok, wt = _chunk_of_tiles(
                c, tiles, tile, stride, plan, weights_t)
            x = _rows_of(u, tok)
            y, = _ffn_call(
                _ffn_fwd_kernel, "moe_ffn_fwd", form, expert, meta, (x, wt),
                mats, [(u.shape[1], jnp.float32)])
            return out.at[tok].add(y, mode="drop")

        return jax.lax.fori_loop(
            0, n_chunks, chunk, jnp.zeros(u.shape, jnp.float32))

    out = _replicated(forward, mesh)(u, mats, weights_t, plan)
    return out, (u, mats, weights_t, plan)


def _grouped_bwd(tile, form, chunk_tiles, mesh, res, g):
    u, mats, weights_t, plan = res
    stride = _row_stride(tile, u.dtype)

    def backward(u, mats, weights_t, plan, g):
        tiles, n_chunks = _chunks(plan, chunk_tiles)

        def chunk(c, carry):
            du, dmats, left_open, dwt = carry
            expert, meta, key, tok, wt = _chunk_of_tiles(
                c, tiles, tile, stride, plan, weights_t)
            x, gy = _rows_of(u, tok), _rows_of(g, tok)
            dx = dw_rows = None
            for at in range(parts):
                dx_part, dw_part, *dws = _ffn_call(
                    _ffn_bwd_kernel, "moe_ffn_bwd", form, expert, meta,
                    (x, gy, wt), mats,
                    [(u.shape[1], jnp.float32), (1, jnp.float32)], dmats,
                    left_open[at], part=(at, parts))
                dmats = tuple(dws[:len(mats)])
                left_open = (*left_open[:at], tuple(dws[len(mats):]),
                             *left_open[at + 1:])
                dx = dx_part if dx is None else dx + dx_part
                dw_rows = dw_part if dw_rows is None else dw_rows + dw_part
            # the keys are sorted and no two alike: one write an assignment
            dwt = dwt.at[key].add(
                dw_rows[:, 0], mode="drop", indices_are_sorted=True,
                unique_indices=True)
            return du.at[tok].add(dx, mode="drop"), dmats, left_open, dwt

        # a part's sums: the input matrices' columns, the output one's rows
        parts = _width_parts(mats)
        open_shapes = [
            (1, m.shape[1], m.shape[2] // parts) for m in mats[:-1]] + [
            (1, mats[-1].shape[1] // parts, mats[-1].shape[2])]
        # an expert that no tile names keeps these zeros
        du, dmats, _, dwt = jax.lax.fori_loop(0, n_chunks, chunk, (
            jnp.zeros(u.shape, jnp.float32),
            tuple(jnp.zeros_like(m) for m in mats),
            tuple(tuple(jnp.zeros(shape, jnp.float32) for shape in open_shapes)
                  for _ in range(parts)),
            jnp.zeros((weights_t.size,), jnp.float32)))
        return du.astype(u.dtype), dmats, dwt.reshape(weights_t.shape)

    du, dmats, dwt = _replicated(backward, mesh)(u, mats, weights_t, plan, g)
    return du, dmats, dwt, jax.tree_util.tree_map(lambda _: None, plan)


grouped_expert_ffn.defvjp(_grouped_fwd, _grouped_bwd)


def _route_and_plan(xt, seq, route, held, offset, tile, force_level):
    """What both expert layers do under ``moe_route``: choose, plan the held
    rows, count. ``route(xt, level) -> (chosen, weights)``. Returns the
    held experts' weights [held, T], the plan, the tiles a chunk of the
    grouped products takes, and the counters."""
    chosen, weights = route(
        xt, jnp.arange(xt.shape[0]) % seq if force_level else None)
    plan, sizes = plan_held_rows(chosen, held, offset, tile)
    chosen, plan = jax.tree_util.tree_map(
        lambda a: checkpoint_name(a, "moe_plan"), (chosen, plan))
    # what the shapes give in expectation, and one tile an expert for the
    # runs' ends: a level router fills one chunk, a skewed one takes more
    chunk_tiles, n_chunks = _chunks(plan, held - (
        -chosen.size * held // (weights.shape[1] * tile)))
    in_use = jnp.arange(plan["tile_rows"].shape[0]) < plan["n_tiles"]
    local = chosen - offset
    is_held = (local >= 0) & (local < held)
    counters = {
        "moe/local_assignments": jnp.sum(sizes),
        "moe/tokens_without_held_expert": jnp.sum(
            ~jnp.any(is_held, axis=-1)).astype(jnp.int32),
        "moe/max_expert_load": jnp.max(sizes),
        # held assignments that no tile in use has a row for
        "moe/overflow": jnp.sum(is_held).astype(jnp.int32)
        - jnp.sum(jnp.where(in_use, plan["tile_rows"], 0)),
        "moe/tiles": plan["n_tiles"],
        "moe/chunks": n_chunks,
    }
    return weights[:, offset:offset + held].T, plan, chunk_tiles, counters


def latent_moe_mixer(p, x, *, top_k, scale, held, offset, tile,
                     force_level=False, mesh=None):
    """One latent mixture-of-experts mixer over normalized ``x`` [B, S, E]
    -> (out [B, S, E], counters). ``p``: router [E, routed], router_bias
    [routed], down [E, L], up [L, E], w1 [held, L, F], w2 [held, F, L],
    shared_w1 [E, Fs], shared_w2 [Fs, E]. The router and the shared expert
    read ``x``; the routed experts live in the latent ``x W_down``. A token
    none of whose chosen experts is held gets the shared expert only.
    ``force_level``: the selection is forced level (for measurements with
    weights that no balance rule has trained; ``level_selection_scores``)."""
    b, s, e = x.shape
    xt = x.reshape(b * s, e)
    with jax.named_scope("moe_route"):
        weights_t, plan, chunk_tiles, counters = _route_and_plan(
            xt, s, lambda xt, level: route_sigmoid_topk(
                xt, p["router"], p["router_bias"], top_k, scale, level=level),
            held, offset, tile, force_level)
    with jax.named_scope("moe_shared"):
        u = xt @ p["down"]
        shared = _relu2(xt @ p["shared_w1"]) @ p["shared_w2"]
    with jax.named_scope("moe_experts"):
        routed = grouped_expert_ffn(
            u, (p["w1"], p["w2"]), weights_t, plan, tile, "relu2",
            chunk_tiles, mesh)
    # kept with the plan: all that backward reads of the forward is this
    # operand of ``up``'s product, so remat runs no kernel again for it
    routed = checkpoint_name(routed.astype(x.dtype), "moe_plan")
    with jax.named_scope("moe_shared"):
        out = routed @ p["up"] + shared
    return out.reshape(b, s, e), counters


def gated_moe_mixer(p, x, *, top_k, held, offset, tile, force_level=False,
                    scale=1.0, route="softmax", mesh=None):
    """One mixture of SiLU-gated experts at the model's own width, dropping
    no token, over normalized ``x`` [B, S, E] -> (out [B, S, E], counters).
    ``p``: router [E, routed], wg and wu [held, E, F], wd [held, F, E],
    shared_wg and shared_wu [E, Fs], shared_wd [Fs, E], shared_gate [E, 1].
    ``route`` ``"softmax"``: ``route_softmax_topk``, no selection bias;
    ``"sigmoid"``: ``route_sigmoid_topk`` under the leaf router_bias
    [routed], which chooses and takes no gradient. ``scale`` multiplies the
    routed experts' weighted sum either way (a family's routed scaling
    factor, on the experts' output). The shared expert is weighed by
    ``sigmoid(x shared_gate)``, or added as it is where the family has no
    ``shared_gate`` leaf. A family without a shared expert has no
    ``shared_*`` leaves and the layer is the routed sum alone (no
    ``moe_shared`` scope opens). The same plan, loop and counters as
    ``latent_moe_mixer``."""
    b, s, e = x.shape
    xt = x.reshape(b * s, e)
    with jax.named_scope("moe_route"):
        routes = {
            "softmax": lambda xt, level: route_softmax_topk(
                xt, p["router"], top_k, level=level),
            "sigmoid": lambda xt, level: route_sigmoid_topk(
                xt, p["router"], p["router_bias"], top_k, 1.0, level=level)}
        weights_t, plan, chunk_tiles, counters = _route_and_plan(
            xt, s, routes[route], held, offset, tile, force_level)
        if scale != 1.0:
            weights_t = weights_t * scale
    with jax.named_scope("moe_experts"):
        routed = grouped_expert_ffn(
            xt, (p["wg"], p["wu"], p["wd"]), weights_t, plan, tile, "swiglu",
            chunk_tiles, mesh)
    if "shared_wg" not in p:
        return routed.astype(x.dtype).reshape(b, s, e), counters
    with jax.named_scope("moe_shared"):
        if "shared_gate" in p:
            gate = jax.nn.sigmoid(jnp.dot(
                xt, p["shared_gate"], preferred_element_type=jnp.float32))
        shared = (jax.nn.silu(xt @ p["shared_wg"]) * (xt @ p["shared_wu"])) \
            @ p["shared_wd"]
        shared = shared.astype(jnp.float32)
        out = routed + (gate * shared if "shared_gate" in p else shared)
    return out.astype(x.dtype).reshape(b, s, e), counters
