"""The ``sdar_moe`` stack (SDAR-30B-A3B-Chat, ``config.json`` on Hugging
Face) and its block-diffusion training objective in plain float32
``jax.numpy``: a token table, identical layers of TWO residual sublayers
(attention, then routed experts, each behind its own norm), a final norm and
an untied head.

The equations, with ``rms(x; g) = x / sqrt(mean(x^2) + eps) g`` (plain gain,
eps ``rms_norm_eps``). A layer: ``h = x + Attn(rms(x; g_a))``, ``y = h +
Experts(rms(h; g_b))``; after the last layer ``rms(.; g_f)``, then the head.

* ``Attn``: ``q = x^ W_q`` (heads x D), ``k = x^ W_k``, ``v = x^ W_v`` (kv
  heads x D each), no bias; ``q <- rms(q; g_q)``, ``k <- rms(k; g_k)`` over
  the D lanes of each head (one gain of D for all heads), THEN rotary on all
  D lanes (half-split pairing: lane i with lane i + D/2; ``rope_theta``; no
  scaling) at the position ids given; kv head j serves query heads ``j n ..
  j n + n - 1``, ``n = heads / kv heads``; ``softmax(q k^T / sqrt(D) + mask)
  v``; ``W_o``.
* ``Experts``: ``p = softmax(x^ W_r)`` over ALL the experts routed over, in
  float32; the chosen set T = the ``num_experts_per_tok`` largest; ``w_e =
  p_e / sum_T p`` (``norm_topk_prob``); ``sum_{e in T, held} w_e W_d,e
  (silu(W_g,e x^) * W_u,e x^)``. No shared expert. Only the experts HELD
  here (``expert_offset`` .. ``+ num_experts``) add to the sum: what the
  absent chips' experts would add is left out, as in the program; the
  weights' denominator runs over all chosen, held here or not.
* The objective (block diffusion with an absorbing mask; block length
  ``block_length`` = B, a row of L tokens ``x0``): the traffic generator
  draws a level ``t_b`` for each block and replaces each of its positions
  by the mask id with probability ``t_b``, giving ``xt``, and hands over
  ``noisy_ids`` = xt, ``clean_ids`` = x0 and ``loss_weights`` (``1 / t_b``
  where the position was replaced, else 0). The stack runs on the row ``[xt
  ; x0]`` of 2 L positions, both halves with position ids 0..L-1. With
  ``blk(i) = i // B``, query i, key j (``mask_allowed``): noisy -> noisy iff
  ``blk(i) == blk(j)``; noisy -> clean iff ``blk(j) < blk(i)``; clean ->
  clean iff ``blk(j) <= blk(i)``; clean -> noisy never. The head reads the
  NOISY half only; position i's logits are scored against token i (no
  shift): a micro-batch's loss is ``sum_i loss_weights[i] nll(logits_i,
  x0_i) / (rows L)``.

Departures from the published description (also under ``assumed`` in the
configuration's file):

* ``block_length``, the schedule and the mask id are not in ``config.json``:
  the configuration's file states them and the generator applies them; this
  file sees the three arrays only;
* ``router_force_level`` (the benchmark's configuration sets it): a fixed
  pseudo-random table over (position in the 2 L row, expert) joins the
  SELECTION, with gaps so wide that it decides the top-k alone
  (``nemotron_h.level_scores``); the weights are the router's own softmax
  either way. The routing (``routing_weights``) and one expert
  (``gated_ffn``) are ``qwen3_next``'s own functions: the same equations;
* no auxiliary loss (the config carries no coefficient);
* weights are random: N(0, ``initializer_range``) matrices, gains 1 + N;
* same numbers, less memory: each sublayer under ``jax.checkpoint``;
  attention over blocks of ``BLOCK`` query positions, the mask of a block
  made from ``iota`` inside it; the head's log-likelihood over blocks of
  ``HEAD_BLOCK`` positions; the held experts one at a time.

Every matrix product goes through the ``dot`` it is handed. Imports nothing
of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from .qwen3_next import gated_ffn, layer_params, routing_weights

BLOCK = 256         # query positions per block of attention
HEAD_BLOCK = 1024   # positions per block of the head


def shapes(cfg):
    e, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {
        "embed": (v, e), "head": (v, e), "norm_f.g": (e,),
        "attn.norm.g": (n, e), "attn.wq": (n, e, hq * d),
        "attn.wk": (n, e, hkv * d), "attn.wv": (n, e, hkv * d),
        "attn.q_norm.g": (n, d), "attn.k_norm.g": (n, d),
        "attn.wo": (n, hq * d, e),
        "moe.norm.g": (n, e), "moe.router": (n, e, cfg["experts_routed_over"]),
        "moe.wg": (n, held, e, f), "moe.wu": (n, held, e, f),
        "moe.wd": (n, held, f, e),
    }


def stacked(name):
    """Leaves that hold one slice per layer on the first axis."""
    return name.split(".")[0] in ("attn", "moe")


def init_params(key, cfg):
    sh = shapes(cfg)
    return ops.seeded_normals(
        key, sh, cfg["initializer_range"],
        {k: 1.0 for k in sh if k.endswith(".g")})


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary_frequencies(cfg):
    """[D / 2] float32, made on the host in float64."""
    d = cfg["head_dim"]
    return np.asarray(
        float(cfg["rope_theta"]) ** (-np.arange(0, d, 2) / d), np.float32)


def rotary(x, positions, inv):
    """Half-split rotary on every lane of x [B, H, S, D] at ``positions``
    [S]."""
    half = inv.shape[0]
    angle = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mask_allowed(rows, cols, length, block):
    """Which of the keys ``cols`` each of the queries ``rows`` may see:
    positions of the 2 ``length`` row ``[noisy ; clean]``, as integer arrays
    that broadcast against each other."""
    q_clean, k_clean = rows >= length, cols >= length
    q_blk, k_blk = (rows % length) // block, (cols % length) // block
    return jnp.where(
        k_clean, jnp.where(q_clean, k_blk <= q_blk, k_blk < q_blk),
        ~q_clean & (k_blk == q_blk))


def attn(p, x, cfg, dot):
    """x [B, 2 L, E], normed -> [B, 2 L, E]."""
    b, s, _ = x.shape
    length, block = s // 2, cfg["block_length"]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = dot(x, p["wq"], ops.X_W).reshape(b, s, hq, d)
    k = dot(x, p["wk"], ops.X_W).reshape(b, s, hkv, d)
    v = dot(x, p["wv"], ops.X_W).reshape(b, s, hkv, d)
    positions, inv = jnp.arange(s) % length, rotary_frequencies(cfg)
    q = rotary(rms(q, p["q_norm.g"], eps).transpose(0, 2, 1, 3), positions, inv)
    k = rotary(rms(k, p["k_norm.g"], eps).transpose(0, 2, 1, 3), positions, inv)
    k, v = (jnp.repeat(t, hq // hkv, axis=1)
            for t in (k, v.transpose(0, 2, 1, 3)))
    cols = jnp.arange(s)[None, :]

    def rows(args):
        q_rows, first = args
        seen = mask_allowed(
            first + jnp.arange(q_rows.shape[2])[:, None], cols, length, block)
        bias = jnp.where(seen, 0.0, -1e30).astype(jnp.float32)[None, None]
        return ops.attention(dot, q_rows, k, v, bias)

    if s % BLOCK == 0 and s > BLOCK:
        blocks = q.reshape(b, hq, s // BLOCK, BLOCK, -1).transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(
            jax.checkpoint(rows), (blocks, jnp.arange(0, s, BLOCK)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(q.shape)
    else:
        ctx = rows((q, 0))
    return dot(ops.merge_heads(ctx), p["wo"], ops.X_W)


def experts(p, x, cfg, dot):
    lo = cfg.get("expert_offset", 0)
    weights = routing_weights(p, x, cfg, dot)[..., lo:lo + p["wg"].shape[0]]

    def expert(acc, inp):
        wg, wu, wd, w = inp
        return acc + w[..., None] * gated_ffn(x, wg, wu, wd, dot), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(x),
        (p["wg"], p["wu"], p["wd"], jnp.moveaxis(weights, -1, 0)))
    return routed


MIXERS = {"attn": attn, "moe": experts}


def hidden(params, tokens, cfg, dot):
    """[B, 2 L] token ids of rows ``[noisy ; clean]`` -> [B, 2 L, E] after
    the final norm."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    for i in range(int(cfg["num_hidden_layers"])):
        for kind in ("attn", "moe"):
            def sublayer(x, p, mixer=MIXERS[kind]):
                return x + mixer(p, rms(x, p["norm.g"], eps), cfg, dot)

            x = jax.checkpoint(sublayer)(x, layer_params(params, kind, i))
    return rms(x, params["norm_f.g"], eps)


def counts(batch):
    """Denominators of the loss's terms over a whole micro-batch (host):
    every position of every row, masked or not."""
    return (batch["clean_ids"].size,)


def loss_sums(params, batch, cfg, dot):
    """Numerators of the loss's terms over some rows of a micro-batch: the
    weighted negative log-likelihood of the clean token at each position of
    the noisy half, summed over rows and positions."""
    noisy, clean = batch["noisy_ids"], batch["clean_ids"]
    weights = jnp.asarray(batch["loss_weights"], jnp.float32)
    b, length = clean.shape
    x = hidden(
        params, jnp.concatenate([noisy, clean], axis=1), cfg, dot)[:, :length]

    def block(args):
        x_rows, label_rows, weight = args
        lg = dot(x_rows, params["head"].T, ops.X_W)
        return jnp.sum(ops.nll(lg, label_rows) * weight)

    if length % HEAD_BLOCK == 0 and length > HEAD_BLOCK:
        n = length // HEAD_BLOCK
        parts = jax.lax.map(jax.checkpoint(block), (
            x.reshape(b, n, HEAD_BLOCK, -1).swapaxes(0, 1),
            clean.reshape(b, n, HEAD_BLOCK).swapaxes(0, 1),
            weights.reshape(b, n, HEAD_BLOCK).swapaxes(0, 1)))
        return (jnp.sum(parts),)
    return (block((x, clean, weights)),)
