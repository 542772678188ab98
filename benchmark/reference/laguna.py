"""The ``laguna`` stack (Laguna-S-2.1, ``config.json`` on Hugging Face) in
plain float32 ``jax.numpy``: a token table, layers of TWO residual sublayers
(attention, then a dense FFN or routed experts, each behind its own norm), a
final norm and an untied head.

The equations, with ``rms(x; g) = x / sqrt(mean(x^2) + eps) g`` (plain gain,
eps ``rms_norm_eps``). Layer ``l``: ``h = x + Attn_l(rms(x; g_a))``, ``y = h
+ FFN_l(rms(h; g_b))``; after the last layer ``rms(.; g_f)``, then the head.

* ``Attn_l`` is FULL where ``l % full_attention_period == 0`` (the published
  ``layer_types``: full, then three sliding, repeated) and SLIDING elsewhere,
  with its own number of query heads H (``num_attention_heads`` where full,
  ``sliding_attention_heads`` where sliding: the published
  ``num_attention_heads_per_layer``) on the same kv heads of width D: ``q =
  x^ W_q`` (H x D), ``k = x^ W_k``, ``v = x^ W_v`` (kv heads x D), no bias, no
  q/k norm; rotary on q and k; kv head j serves query heads ``j H / kv ..``;
  ``softmax(q k^T / sqrt(D) + mask) v``; each head's context times
  ``sigmoid((x^ W_gate)_h)``, ONE gate a head and position (``gating``
  ``per-head``), read from the sublayer's own normed input; ``W_o``.
  - mask: query i sees key j iff ``j <= i`` (full), iff ``i - sliding_window
    < j <= i`` (sliding): a comparison of the position indices.
  - rotary, sliding: half-split pairing on all D lanes, base
    ``sliding_rope_theta``, plain. Full: on the first ``full_rotary_lanes``
    lanes (``partial_rotary_factor`` 0.5), base ``full_rope_theta``, YaRN
    (``yarn_inverse_frequencies``, from its closed form), cosine and sine
    times ``yarn_attention_factor``; the lanes after pass through unscaled.
* ``FFN_l``, ``l < leading_dense_layers`` (``mlp_only_layers`` [0]): ``(silu(h^
  W_g) * h^ W_u) W_d`` at ``intermediate_size``. Else routed experts: ``p =
  softmax(h^ W_r)`` over ALL the experts routed over, float32, no soft cap; T
  = the ``num_experts_per_tok`` largest; ``w_e = p_e / sum_T p``
  (``norm_topk_prob``); ``moe_routed_scaling_factor sum_{e in T, held} w_e
  expert_e(h^)``, the weight on the experts' OUTPUT, plus ``shared(h^)`` with
  no gate; experts and the shared one SiLU-gated as the dense FFN. Only the
  experts HELD here (``expert_offset`` .. ``+ num_experts``) add to the sum:
  what the absent chips' experts would add is left out, as in the program
  (the share is cut as ``nemotron_h`` cuts it: a slice of the routing
  weights' columns); the weights' denominator runs over all chosen.

Departures from the published description (also under ``assumed`` in the
configuration's file): the harness hands a reference NUMBERS only, so the
per-layer lists arrive as ``full_attention_period``, ``sliding_attention_heads``
and ``leading_dense_layers`` and ``rope_parameters``' numbers as keys of their
own; ``router_force_level`` (a fixed pseudo-random table over (position,
expert) joins the SELECTION: ``nemotron_h.level_scores``; the weights are the
router's own softmax either way; routing and one expert are ``qwen3_next``'s
own functions: the same equations); no auxiliary loss; weights are random: N(0,
``initializer_range``) matrices, gains 1 + N; same numbers, less memory: each
sublayer under ``jax.checkpoint``, attention over blocks of ``BLOCK`` query
rows with the mask of a block made inside it, the head's log-likelihood over
blocks of ``HEAD_BLOCK`` positions, the held experts one at a time.

Every matrix product goes through the ``dot`` it is handed. Imports nothing
of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from .qwen3_next import gated_ffn, layer_params, routing_weights

BLOCK = 128         # query positions per block of attention
HEAD_BLOCK = 1024   # positions per block of the head


def layer_kinds(cfg):
    """[(attention kind, FFN kind)] a layer: ('full' | 'win', 'ffn' | 'moe')."""
    every, dense = int(cfg["full_attention_period"]), int(
        cfg["leading_dense_layers"])
    return [("win" if i % every else "full", "ffn" if i < dense else "moe")
            for i in range(int(cfg["num_hidden_layers"]))]


def heads(cfg, kind):
    return int(cfg["sliding_attention_heads" if kind == "win"
                   else "num_attention_heads"])


def shapes(cfg):
    e, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    held, over = cfg["num_experts"], cfg["experts_routed_over"]
    kinds = layer_kinds(cfg)
    out = {"embed": (v, e), "head": (v, e), "norm_f.g": (e,)}
    for kind in ("full", "win"):
        n, h = sum(a == kind for a, _ in kinds), heads(cfg, kind)
        out.update({
            f"{kind}.norm.g": (n, e), f"{kind}.wq": (n, e, h * d),
            f"{kind}.wk": (n, e, kv), f"{kind}.wv": (n, e, kv),
            f"{kind}.wg": (n, e, h), f"{kind}.wo": (n, h * d, e)})
    n = sum(b == "ffn" for _, b in kinds)
    out.update({
        "ffn.norm.g": (n, e), "ffn.wg": (n, e, cfg["intermediate_size"]),
        "ffn.wu": (n, e, cfg["intermediate_size"]),
        "ffn.wd": (n, cfg["intermediate_size"], e)})
    n = sum(b == "moe" for _, b in kinds)
    out.update({
        "moe.norm.g": (n, e), "moe.router": (n, e, over),
        "moe.wg": (n, held, e, f), "moe.wu": (n, held, e, f),
        "moe.wd": (n, held, f, e), "moe.shared_wg": (n, e, fs),
        "moe.shared_wu": (n, e, fs), "moe.shared_wd": (n, fs, e)})
    return {k: s for k, s in out.items() if 0 not in s[:1]}


def stacked(name):
    """Leaves that hold one slice per layer OF THEIR KIND on the first axis."""
    return name.split(".")[0] in ("full", "win", "ffn", "moe")


def init_params(key, cfg):
    sh = shapes(cfg)
    return ops.seeded_normals(
        key, sh, cfg["initializer_range"],
        {k: 1.0 for k in sh if k.endswith(".g")})


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def yarn_inverse_frequencies(cfg):
    """[full_rotary_lanes / 2] float32 from YaRN's closed form (arXiv:
    2309.00071, section 3.2), on the host in float64. With n lanes, base b
    and L original positions, pair i turns ``r_i = L b^(-2i/n) / 2 pi`` times
    over L; the pair at which it turns r times is ``d(r) = n ln(L / (2 pi r))
    / (2 ln b)``. ``gamma_i = clip((i - floor d(beta_fast)) / (ceil
    d(beta_slow) - floor d(beta_fast)), 0, 1)`` (both dimensions kept within
    0 .. n - 1) and the frequency is ``b^(-2i/n) ((1 - gamma_i) + gamma_i /
    factor)``: fast pairs keep theirs, slow ones are interpolated."""
    n, base = int(cfg["full_rotary_lanes"]), float(cfg["full_rope_theta"])
    length = float(cfg["yarn_original_positions"])

    def pair_at(turns):
        return n * np.log(length / (2 * np.pi * turns)) / (2 * np.log(base))

    low = max(np.floor(pair_at(cfg["yarn_beta_fast"])), 0.0)
    high = min(np.ceil(pair_at(cfg["yarn_beta_slow"])), n - 1.0)
    i = np.arange(n // 2, dtype=np.float64)
    gamma = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = base ** (-2.0 * i / n)
    return np.asarray(
        plain * ((1.0 - gamma) + gamma / float(cfg["yarn_factor"])), np.float32)


def plain_inverse_frequencies(cfg):
    d = int(cfg["head_dim"])
    return np.asarray(
        float(cfg["sliding_rope_theta"]) ** (-np.arange(0, d, 2) / d),
        np.float32)


def rotary(x, inv, factor):
    """Half-split rotary on the first 2 len(inv) lanes of x [B, H, S, D],
    cosine and sine times ``factor``; the other lanes as they are."""
    half = inv.shape[0]
    angle = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def attn(p, x, cfg, dot, kind):
    """x [B, S, E], normed -> [B, S, E]; ``kind`` 'full' or 'win'."""
    b, s, _ = x.shape
    hq, hkv, d = heads(cfg, kind), cfg["num_key_value_heads"], cfg["head_dim"]
    q = dot(x, p["wq"], ops.X_W).reshape(b, s, hq, d).transpose(0, 2, 1, 3)
    k = dot(x, p["wk"], ops.X_W).reshape(b, s, hkv, d).transpose(0, 2, 1, 3)
    v = dot(x, p["wv"], ops.X_W).reshape(b, s, hkv, d).transpose(0, 2, 1, 3)
    if kind == "full":
        inv, factor = yarn_inverse_frequencies(cfg), jnp.float32(
            cfg["yarn_attention_factor"])
        reach = s                      # every earlier key
    else:
        inv, factor = plain_inverse_frequencies(cfg), jnp.float32(1.0)
        reach = int(cfg["sliding_window"])
    q, k = rotary(q, inv, factor), rotary(k, inv, factor)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
    cols = jnp.arange(s)[None, :]

    def rows(args):
        q_rows, first = args
        at = first + jnp.arange(q_rows.shape[2])[:, None]
        seen = (cols <= at) & (cols > at - reach)
        bias = jnp.where(seen, 0.0, -1e30).astype(jnp.float32)[None, None]
        return ops.attention(dot, q_rows, k, v, bias)

    if s % BLOCK == 0 and s > BLOCK:
        blocks = q.reshape(b, hq, s // BLOCK, BLOCK, -1).transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(
            jax.checkpoint(rows), (blocks, jnp.arange(0, s, BLOCK)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(q.shape)
    else:
        ctx = rows((q, 0))
    gate = jax.nn.sigmoid(dot(x, p["wg"], ops.X_W))          # [B, S, H]
    ctx = ctx * gate.transpose(0, 2, 1)[..., None]
    return dot(ops.merge_heads(ctx), p["wo"], ops.X_W)


def dense_ffn(p, x, cfg, dot):
    return gated_ffn(x, p["wg"], p["wu"], p["wd"], dot)


def experts(p, x, cfg, dot):
    lo = cfg.get("expert_offset", 0)
    weights = cfg["moe_routed_scaling_factor"] * routing_weights(
        p, x, cfg, dot)[..., lo:lo + p["wg"].shape[0]]

    def expert(acc, inp):
        wg, wu, wd, w = inp
        return acc + w[..., None] * gated_ffn(x, wg, wu, wd, dot), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(x),
        (p["wg"], p["wu"], p["wd"], jnp.moveaxis(weights, -1, 0)))
    return routed + gated_ffn(
        x, p["shared_wg"], p["shared_wu"], p["shared_wd"], dot)


MIXERS = {
    "full": lambda p, x, cfg, dot: attn(p, x, cfg, dot, "full"),
    "win": lambda p, x, cfg, dot: attn(p, x, cfg, dot, "win"),
    "ffn": dense_ffn, "moe": experts,
}


def hidden(params, tokens, cfg, dot):
    """[B, S] token ids -> [B, S, E] after the final norm."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    seen = dict.fromkeys(MIXERS, 0)
    for pair in layer_kinds(cfg):
        for kind in pair:
            def sublayer(x, p, mixer=MIXERS[kind]):
                return x + mixer(p, rms(x, p["norm.g"], eps), cfg, dot)

            x = jax.checkpoint(sublayer)(
                x, layer_params(params, kind, seen[kind]))
            seen[kind] += 1
    return rms(x, params["norm_f.g"], eps)


def logits(params, tokens, cfg, dot):
    return dot(hidden(params, tokens, cfg, dot), params["head"].T, ops.X_W)


def counts(batch):
    """Denominators of the loss's terms over a whole micro-batch (host)."""
    ids = batch["input_ids"]
    return (ids.shape[0] * (ids.shape[1] - 1),)


def loss_sums(params, batch, cfg, dot):
    """Numerators of the loss's terms over some rows of a micro-batch:
    next-token negative log-likelihood, summed over rows and positions."""
    ids = batch["input_ids"]
    b, s = ids.shape
    x = hidden(params, ids, cfg, dot)
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], 1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)

    def block(args):
        x_rows, label_rows, weight = args
        lg = dot(x_rows, params["head"].T, ops.X_W)
        return jnp.sum(ops.nll(lg, label_rows) * weight)

    if s % HEAD_BLOCK == 0 and s > HEAD_BLOCK:
        n = s // HEAD_BLOCK
        parts = jax.lax.map(jax.checkpoint(block), (
            x.reshape(b, n, HEAD_BLOCK, -1).swapaxes(0, 1),
            labels.reshape(b, n, HEAD_BLOCK).swapaxes(0, 1),
            jnp.broadcast_to(
                counted.reshape(n, 1, HEAD_BLOCK), (n, b, HEAD_BLOCK))))
        return (jnp.sum(parts),)
    return (block((x, labels, counted)),)
