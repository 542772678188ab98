"""The ``joyai_llm_flash`` stack (JoyAI-LLM-Flash, ``config.json`` on Hugging
Face) in plain float32 ``jax.numpy``: a token table, layers of TWO residual
sublayers (latent attention, then a dense FFN or routed experts, each behind
its own norm), a final norm, an untied head, and one multi-token-prediction
module on the same table and head.

The equations, with ``rms(x; g) = x / sqrt(mean(x^2) + eps) g`` (plain gain,
eps ``rms_norm_eps``). Layer ``l``: ``h = x + Attn_l(rms(x; g_a))``, ``y = h
+ FFN_l(rms(h; g_b))``; after the last layer ``z = rms(.; g_f)``, then the
head. No bias anywhere.

* ``Attn`` is multi-head latent attention on H = ``num_attention_heads``
  heads. ``c_q = rms(x^ W_qa; g_q)`` (``q_lora_rank`` lanes), ``q = c_q
  W_qb``: a head has ``qk_nope_head_dim`` lanes that do not rotate, then
  ``qk_rope_head_dim`` that do. ``[c_kv ; k_r] = x^ W_kva`` (``kv_lora_rank``
  + ``qk_rope_head_dim`` lanes), ``c_kv <- rms(c_kv; g_kv)``, ``[k_nope,h ;
  v_h] = c_kv W_kvb`` a head (``qk_nope_head_dim`` + ``v_head_dim``). Rotary
  (base ``rope_theta``, ``rope_scaling`` null: plain frequencies, no scale of
  the scores) on q's rotating lanes of every head and on ``k_r``, which is ONE
  key part shared by all heads: ``k_h = [k_nope,h ; k_r]``. ``softmax(q_h
  k_h^T / sqrt(qk_nope_head_dim + qk_rope_head_dim) + causal) v_h``: q and k
  are 192 lanes wide and v 128; ``W_o`` from ``H v_head_dim`` lanes.
* ``FFN_l``, ``l < first_k_dense_replace``: ``(silu(h^ W_g) * h^ W_u) W_d`` at
  ``intermediate_size``. Else routed experts (``scoring_func`` sigmoid,
  ``topk_method`` noaux_tc, ``n_group`` = ``topk_group`` = 1: no group limit):
  ``s = sigmoid(h^ W_r)`` over ALL the experts routed over, float32; T = the
  ``num_experts_per_tok`` largest of ``s + b`` (the bias ``b`` chooses, weighs
  nothing and takes no gradient); ``w_e = routed_scaling_factor s_e / sum_T
  s`` (``norm_topk_prob``); ``sum_{e in T, held} w_e expert_e(h^) +
  shared(h^)``, the ``n_shared_experts`` = 1 shared expert
  ``moe_intermediate_size`` wide with no gate; experts and the shared one SiLU-gated as the dense FFN.
  Only the experts HELD here (``expert_offset`` .. ``+ n_routed_experts``) add
  to the sum: what the absent chips' experts would add is left out, as in the
  program (the share is cut as ``nemotron_h`` and ``laguna`` cut it: a slice
  of the routing weights' columns); the denominator runs over all chosen.
* Multi-token prediction (``num_nextn_predict_layers`` 1). With ``z_i`` the
  state the main head reads at position i and E the table: ``u_i = W_eh
  [rms(E[t_{i+1}]; g_e) ; rms(z_i; g_h)]`` (2 E -> E lanes), one full decoder
  layer of the sparse kind (its own latent attention and its own experts)
  over the positions, ``rms(.; g_m)``, the SAME head, scored against
  ``t_{i+2}``: ``L = mean_{i < S-1} nll_main(t_{i+1}) + mtp_loss_weight
  mean_{i < S-2} nll_mtp(t_{i+2})``. ``loss_sums`` returns the two numerators
  (the second already times the weight) and ``counts`` the two denominators.

Departures from the published description (also under ``assumed`` in the
configuration's file): ``z_i`` is the main stack's state AFTER its final norm
and the concatenation is ``[embedding ; state]``; rotary pairs lane i with
lane i + 32 of the 64 rotating lanes (half-split; ``rope_interleave`` is a
statement about a checkpoint's lane order, and the weights here are seeded);
``mtp_loss_weight`` is assumed; ``router_force_level`` (a fixed pseudo-random
table over (position, expert) joins the SELECTION: ``nemotron_h.level_scores``;
the weights are the router's own sigmoid scores either way; the routing is
``nemotron_h``'s own function and one expert ``qwen3_next``'s: the same
equations); no auxiliary loss and no update of ``b``, which is zeros; weights
are random: N(0, ``initializer_range``) matrices, gains 1 + N. Same numbers,
less memory: each sublayer under ``jax.checkpoint``, attention over blocks of
``BLOCK`` query rows with the mask of a block made inside it, the head's
log-likelihood over blocks of ``HEAD_BLOCK`` positions, the held experts one
at a time; and the module runs over all S positions of a row with id 0 after
the row's end, which under causal attention and position-wise experts gives
positions 0 .. S-3 the numbers that a run over S-1 positions gives them (the
two last are not scored).

Every matrix product goes through the ``dot`` it is handed. Imports nothing
of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from .nemotron_h import routing_weights
from .qwen3_next import gated_ffn

BLOCK = 128         # query positions per block of attention
HEAD_BLOCK = 1024   # positions per block of the head


def layer_kinds(cfg):
    """The FFN kind of each layer of the main stack: 'ffn' | 'moe'."""
    dense = int(cfg["first_k_dense_replace"])
    return ["ffn" if i < dense else "moe"
            for i in range(int(cfg["num_hidden_layers"]))]


def _mla(cfg, n):
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {
        "norm.g": (n, e), "wqa": (n, e, rq), "q_norm.g": (n, rq),
        "wqb": (n, rq, h * (nope + rope)), "wkva": (n, e, rkv + rope),
        "kv_norm.g": (n, rkv), "wkvb": (n, rkv, h * (nope + dv)),
        "wo": (n, h * dv, e)}


def _moe(cfg, n):
    e, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    fs = f * int(cfg.get("n_shared_experts", 1))
    held, over = cfg["n_routed_experts"], cfg["experts_routed_over"]
    return {
        "norm.g": (n, e), "router": (n, e, over), "router_bias": (n, over),
        "wg": (n, held, e, f), "wu": (n, held, e, f), "wd": (n, held, f, e),
        "shared_wg": (n, e, fs), "shared_wu": (n, e, fs),
        "shared_wd": (n, fs, e)}


def shapes(cfg):
    e, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    kinds, depth = layer_kinds(cfg), int(cfg["num_nextn_predict_layers"])
    out = {"embed": (v, e), "head": (v, e), "norm_f.g": (e,)}
    out.update({f"mla.{k}": s for k, s in _mla(cfg, len(kinds)).items()})
    n = kinds.count("ffn")
    out.update({"ffn.norm.g": (n, e), "ffn.wg": (n, e, f),
                "ffn.wu": (n, e, f), "ffn.wd": (n, f, e)})
    out.update({f"moe.{k}": s
                for k, s in _moe(cfg, kinds.count("moe")).items()})
    out.update({
        "mtp.embed_norm.g": (depth, e), "mtp.state_norm.g": (depth, e),
        "mtp.proj": (depth, 2 * e, e), "mtp.norm.g": (depth, e)})
    out.update({f"mtp.mla.{k}": s for k, s in _mla(cfg, depth).items()})
    out.update({f"mtp.moe.{k}": s for k, s in _moe(cfg, depth).items()})
    return {k: s for k, s in out.items() if 0 not in s[:1]}


def stacked(name):
    """Leaves that hold one slice per layer OF THEIR KIND on the first axis
    (the module's: one per module)."""
    return name.split(".")[0] in ("mla", "ffn", "moe", "mtp")


def init_params(key, cfg):
    sh = shapes(cfg)
    out = ops.seeded_normals(
        key, sh, cfg["initializer_range"],
        {k: 1.0 for k in sh if k.endswith(".g")})
    return {k: jnp.zeros_like(v) if k.endswith("router_bias") else v
            for k, v in out.items()}


def layer_params(params, prefix, i):
    """Slice ``i`` of the leaves under ``prefix.``, by the rest of the name."""
    return {k[len(prefix) + 1:]: v[i] for k, v in params.items()
            if k.startswith(prefix + ".")}


def rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary(x, theta):
    """Half-split rotary on every lane of x [B, H, S, R]: lane i pairs with
    lane i + R / 2, frequencies ``theta^(-2i / R)`` made in float64."""
    r = x.shape[-1]
    inv = np.asarray(float(theta) ** (-np.arange(0, r, 2) / r), np.float32)
    angle = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, cfg, dot):
    """x [B, S, E], normed -> [B, S, E]."""
    b, s, _ = x.shape
    h, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = rms(dot(x, p["wqa"], ops.X_W), p["q_norm.g"], eps)
    q = ops.split_heads(dot(c_q, p["wqb"], ops.X_W), h)     # [B, H, S, 192]
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    latent = dot(x, p["wkva"], ops.X_W)
    k_r = rotary(latent[:, None, :, rkv:], theta)           # [B, 1, S, 64]
    c_kv = rms(latent[..., :rkv], p["kv_norm.g"], eps)
    kv = ops.split_heads(dot(c_kv, p["wkvb"], ops.X_W), h)  # [B, H, S, 256]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, h) + k_r.shape[2:])], -1)
    v = kv[..., nope:]
    cols = jnp.arange(s)[None, :]

    def rows(args):
        q_rows, first = args
        at = first + jnp.arange(q_rows.shape[2])[:, None]
        bias = jnp.where(cols <= at, 0.0, -1e30).astype(jnp.float32)
        return ops.attention(dot, q_rows, k, v, bias[None, None])

    if s % BLOCK == 0 and s > BLOCK:
        blocks = q.reshape(b, h, s // BLOCK, BLOCK, -1)
        blocks = blocks.transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(
            jax.checkpoint(rows), (blocks, jnp.arange(0, s, BLOCK)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(b, h, s, dv)
    else:
        ctx = rows((q, 0))
    return dot(ops.merge_heads(ctx), p["wo"], ops.X_W)


def dense_ffn(p, x, cfg, dot):
    return gated_ffn(x, p["wg"], p["wu"], p["wd"], dot)


def experts(p, x, cfg, dot):
    lo = cfg.get("expert_offset", 0)
    weights = routing_weights(p, x, cfg, dot)[..., lo:lo + p["wg"].shape[0]]

    def expert(acc, inp):
        wg, wu, wd, w = inp
        return acc + w[..., None] * gated_ffn(x, wg, wu, wd, dot), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(x),
        (p["wg"], p["wu"], p["wd"], jnp.moveaxis(weights, -1, 0)))
    return routed + gated_ffn(
        x, p["shared_wg"], p["shared_wu"], p["shared_wd"], dot)


MIXERS = {"mla": mla, "ffn": dense_ffn, "moe": experts}


def sublayer(kind, x, p, cfg, dot):
    """``x + mixer(rms(x))`` under a checkpoint."""
    def body(x, p):
        return x + MIXERS[kind](p, rms(x, p["norm.g"], cfg["rms_norm_eps"]),
                                cfg, dot)

    return jax.checkpoint(body)(x, p)


def hidden(params, tokens, cfg, dot):
    """[B, S] token ids -> [B, S, E] after the final norm."""
    x = params["embed"][tokens]
    seen = dict.fromkeys(MIXERS, 0)
    for ffn in layer_kinds(cfg):
        for kind in ("mla", ffn):
            x = sublayer(
                kind, x, layer_params(params, kind, seen[kind]), cfg, dot)
            seen[kind] += 1
    return rms(x, params["norm_f.g"], cfg["rms_norm_eps"])


def module(params, k, z, ahead, cfg, dot):
    """Module ``k`` (from 0): the state ``z`` [B, S, E] the head reads and the
    ids ``ahead`` [B, S] of each position's token ``k + 1`` on -> the module's
    normed state [B, S, E]."""
    eps = cfg["rms_norm_eps"]
    p = layer_params(params, "mtp", k)
    u = dot(jnp.concatenate(
        [rms(params["embed"][ahead], p["embed_norm.g"], eps),
         rms(z, p["state_norm.g"], eps)], -1), p["proj"], ops.X_W)
    for kind in ("mla", "moe"):
        u = sublayer(kind, u, layer_params(params, f"mtp.{kind}", k), cfg, dot)
    return rms(u, p["norm.g"], eps)


def logits(params, tokens, cfg, dot):
    return dot(hidden(params, tokens, cfg, dot), params["head"].T, ops.X_W)


def counts(batch):
    """Denominators of the loss's terms over a whole micro-batch (host): the
    main head's positions and, one each, the modules'."""
    ids = batch["input_ids"]
    b, s = ids.shape
    return (b * (s - 1), b * (s - 2))


def _nll_sum(params, x, ids, shift, dot):
    """Sum over rows and positions i < S - shift of the negative
    log-likelihood of ``ids[i + shift]`` under the head at state ``x[i]``."""
    b, s = ids.shape
    labels = jnp.concatenate(
        [ids[:, shift:], jnp.zeros((b, shift), ids.dtype)], 1)
    counted = (jnp.arange(s) < s - shift).astype(jnp.float32)

    def block(args):
        x_rows, label_rows, weight = args
        lg = dot(x_rows, params["head"].T, ops.X_W)
        return jnp.sum(ops.nll(lg, label_rows) * weight)

    if s % HEAD_BLOCK == 0 and s > HEAD_BLOCK:
        n = s // HEAD_BLOCK
        return jnp.sum(jax.lax.map(jax.checkpoint(block), (
            x.reshape(b, n, HEAD_BLOCK, -1).swapaxes(0, 1),
            labels.reshape(b, n, HEAD_BLOCK).swapaxes(0, 1),
            jnp.broadcast_to(
                counted.reshape(n, 1, HEAD_BLOCK), (n, b, HEAD_BLOCK)))))
    return block((x, labels, counted))


def loss_sums(params, batch, cfg, dot):
    """Numerators of the loss's terms over some rows of a micro-batch: the
    next-token negative log-likelihood summed over rows and positions, and
    ``mtp_loss_weight`` times the module's, of the token after the next."""
    ids = batch["input_ids"]
    z = hidden(params, ids, cfg, dot)
    main = _nll_sum(params, z, ids, 1, dot)
    if not int(cfg["num_nextn_predict_layers"]):
        return (main,)
    ahead = jnp.concatenate(
        [ids[:, 1:], jnp.zeros((ids.shape[0], 1), ids.dtype)], 1)
    state = module(params, 0, z, ahead, cfg, dot)
    return (
        main, cfg["mtp_loss_weight"] * _nll_sum(params, state, ids, 2, dot))
