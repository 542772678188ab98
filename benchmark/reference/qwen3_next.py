"""The ``qwen3_next`` stack (Qwen3-Next-80B-A3B-Instruct, ``config.json`` on
Hugging Face) in plain float32 ``jax.numpy``: a token table, layers that are
each TWO residual sublayers (a token mixer, then gated experts, each behind
its own norm), a final norm and an untied head.

The equations, with ``norm(x; w) = x / rms(x) (1 + w)`` (zero-centred gain,
eps ``rms_norm_eps``). Layer ``i``: ``h = x + mixer_i(norm(x; w_a))``, ``out =
h + experts(norm(h; w_b))``; ``mixer_i`` is gated attention where ``(i + 1) %
full_attention_interval == 0``, else Gated DeltaNet.

* Gated DeltaNet: ``[q | k | v | z] = x^ W_qkvz`` (16 x 128, 16 x 128, 32 x
  128, 32 x 128 lanes, head by head in each), ``[b | a] = x^ W_ba`` (32 each);
  ``(q, k, v) <- silu(conv4(q | k | v))`` (causal, depthwise, no bias);
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k
  L2-normalised over their 128 lanes (``x / sqrt(sum x^2 + 1e-6)``), q times
  ``128^-1/2``; key head ``j`` serves value heads ``2j`` and ``2j + 1``. Per
  value head, state S [d_k, d_v] from zero: ``S <- exp(g_t) S``; ``u_t =
  beta_t (v_t - S^T k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``. Then
  ``o <- o / rms(o) w_o silu(z)`` per head (plain gain) and ``o W_out``. The
  recurrence is written position by position under ``lax.scan``, NOT in the
  chunked form the program runs.
* Gated attention: ``x^ W_q`` gives each of the 16 heads 512 lanes, the
  first 256 the query and the second 256 the gate; 2 kv heads of 256;
  ``norm`` (zero-centred, over 256) on q and on k; rotary on the first 64
  lanes of each (half-split: lanes 0-31 pair with 32-63; ``rope_theta`` 1e7);
  causal softmax(q k^T / 16) v, 8 query heads a kv head; ``(ctx
  sigmoid(gate)) W_o``.
* Experts: ``p = softmax(x^ W_r)`` over ALL the experts routed over; the
  chosen set T = top-k of p; ``w_e = p_e / sum_T p``; expert e is
  ``(silu(x W_g,e) (x W_u,e)) W_d,e``; ``out = sum_{e in T, held} w_e
  expert_e(x^) + sigmoid(x^ w_s) shared(x^)``. Only the experts HELD here
  (``expert_offset`` .. ``+ num_experts``) add to the sum: what the absent
  chips' experts would add is left out, as in the program; the weights'
  denominator runs over all k chosen, held here or not.

Departures from the published description (also under ``assumed`` in the
configuration's file):

* the fused projection's lanes are ordered q | k | v | z (the published
  checkpoint groups them by key head; with seeded weights any fixed order is
  the same model);
* ``router_force_level`` (the benchmark's configuration sets it): a fixed
  pseudo-random table over (position, expert) joins the SELECTION, with gaps
  so wide that it decides the top-k alone (``level_scores``, the same table
  as ``nemotron_h``'s); the weights are the router's own softmax either way;
* no auxiliary loss, no multi-token-prediction head;
* weights are random: N(0, ``initializer_range``) matrices, zero-centred
  gains N(0, range), the plain gain 1 + N, ``A_log``/``dt_bias`` around
  log(1..16 over the heads) and the inverse softplus of a time step
  log-spaced from 1e-3 to 1e-1;
* same numbers, less memory: each sublayer under ``jax.checkpoint``; the
  recurrence as a scan of scans (128 positions inside a checkpoint, the
  state kept at segment ends only) where the length divides; attention and
  the head's log-likelihood over blocks of 1,024 query positions; the held
  experts one at a time.

Every matrix product goes through the ``dot`` it is handed; the recurrence's
own per-position multiply-adds are elementwise float32. Imports nothing of
the program.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from .nemotron_h import level_scores

# the per-head vectors of the DeltaNet layers, taken together when norms are
# compared (``train.leaf_norms``): 32 numbers a layer each, as nemotron_h's
GROUPS = {"gdn.vectors": ("gdn.A_log", "gdn.dt_bias")}
BLOCK = 1024   # query positions per block of attention and of the head
INNER = 128    # recurrence positions per checkpoint
L2_EPS = 1e-6
DT_MIN, DT_MAX = 1e-3, 1e-1


def kinds(cfg):
    """The mixer of each layer: 'gdn' or 'gattn'."""
    every = int(cfg["full_attention_interval"])
    return ["gattn" if (i + 1) % every == 0 else "gdn"
            for i in range(int(cfg["num_hidden_layers"]))]


def dims(cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return hk, hv, dk, dv


def shapes(cfg):
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = kinds(cfg)
    nl, nd, na = len(layers), layers.count("gdn"), layers.count("gattn")
    hk, hv, dk, dv = dims(cfg)
    qk, vz = hk * dk, hv * dv
    taps = cfg["linear_conv_kernel_dim"]
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    held, over = cfg["num_experts"], cfg["experts_routed_over"]
    out = {
        "embed": (v, e), "head": (v, e), "norm_f.w": (e,),
        "gdn.norm.w": (nd, e), "gdn.in_qkvz": (nd, e, 2 * qk + 2 * vz),
        "gdn.in_ba": (nd, e, 2 * hv), "gdn.conv_w": (nd, taps, 2 * qk + vz),
        "gdn.A_log": (nd, hv), "gdn.dt_bias": (nd, hv),
        "gdn.out_norm.g": (nd, dv), "gdn.out_proj": (nd, vz, e),
        "gattn.norm.w": (na, e), "gattn.wq": (na, e, hq * 2 * d),
        "gattn.wk": (na, e, hkv * d), "gattn.wv": (na, e, hkv * d),
        "gattn.q_norm.w": (na, d), "gattn.k_norm.w": (na, d),
        "gattn.wo": (na, hq * d, e),
        "gmoe.norm.w": (nl, e), "gmoe.router": (nl, e, over),
        "gmoe.wg": (nl, held, e, f), "gmoe.wu": (nl, held, e, f),
        "gmoe.wd": (nl, held, f, e), "gmoe.shared_wg": (nl, e, fs),
        "gmoe.shared_wu": (nl, e, fs), "gmoe.shared_wd": (nl, fs, e),
        "gmoe.shared_gate": (nl, e, 1),
    }
    return {k: s for k, s in out.items() if 0 not in s[:1]}


def stacked(name):
    """Leaves that hold one slice per layer OF THEIR KIND on the first axis."""
    return name.split(".")[0] in ("gdn", "gattn", "gmoe")


def init_params(key, cfg):
    sh = shapes(cfg)
    mean = {k: 1.0 for k in sh if k.endswith(".g")}
    if "gdn.A_log" in sh:
        nd, hv = sh["gdn.A_log"]
        step = jnp.exp(jnp.linspace(jnp.log(DT_MIN), jnp.log(DT_MAX), hv))
        mean["gdn.A_log"] = jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, hv)), (nd, hv))
        # the inverse of softplus: softplus(dt_bias) = step
        mean["gdn.dt_bias"] = jnp.broadcast_to(
            step + jnp.log(-jnp.expm1(-step)), (nd, hv))
    return ops.seeded_normals(key, sh, cfg["initializer_range"], mean)


def norm(x, w, eps):
    """x / rms(x) (1 + w): the family's zero-centred gain."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + w)


def l2_normalise(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, position by position over axis 1. q and k
    [B,S,H,dk], v [B,S,H,dv], g and beta [B,S,H] -> o [B,S,H,dv]."""
    b, s, h, dk = q.shape

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (
            v_t - jnp.sum(state * k_t[..., :, None], axis=-2))
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.sum(state * q_t[..., :, None], axis=-2)

    def steps(state, inp):
        return jax.lax.scan(step, state, inp)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    state = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    if s % INNER == 0 and s > INNER:
        seq = tuple(t.reshape((s // INNER, INNER) + t.shape[1:]) for t in seq)
        _, o = jax.lax.scan(jax.checkpoint(steps), state, seq)
        o = o.reshape((s,) + o.shape[2:])
    else:
        _, o = steps(state, seq)
    return jnp.moveaxis(o, 0, 1)


def gdn_mixer(p, x, cfg, dot):
    hk, hv, dk, dv = dims(cfg)
    b, s, _ = x.shape
    qk, vz = hk * dk, hv * dv
    q, k, v, z = jnp.split(
        dot(x, p["in_qkvz"], ops.X_W), [qk, 2 * qk, 2 * qk + vz], axis=-1)
    bb, a = jnp.split(dot(x, p["in_ba"], ops.X_W), 2, axis=-1)
    taps = cfg["linear_conv_kernel_dim"]
    padded = jnp.pad(
        jnp.concatenate([q, k, v], -1), ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(
        sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(taps)))
    q, k, v = jnp.split(mixed, [qk, 2 * qk], axis=-1)
    q = l2_normalise(q.reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2_normalise(k.reshape(b, s, hk, dk))
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = delta_rule(q, k, v.reshape(b, s, hv, dv), g, jax.nn.sigmoid(bb))
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                     + cfg["rms_norm_eps"]) * p["out_norm.g"]
    o = o * jax.nn.silu(z.reshape(b, s, hv, dv))
    return dot(o.reshape(b, s, vz), p["out_proj"], ops.X_W)


def rotary_frequencies(cfg):
    """[lanes / 2] float32, made on the host in float64."""
    lanes = int(cfg["partial_rotary_factor"] * cfg["head_dim"])
    return np.asarray(
        float(cfg["rope_theta"]) ** (-np.arange(0, lanes, 2) / lanes),
        np.float32)


def rotary(x, inv):
    """Half-split rotary on the first 2 len(inv) lanes of x [B,H,S,D]."""
    half = inv.shape[0]
    angle = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gattn_mixer(p, x, cfg, dot):
    b, s, _ = x.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    qg = dot(x, p["wq"], ops.X_W).reshape(b, s, hq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, hq * d)
    k = dot(x, p["wk"], ops.X_W).reshape(b, s, hkv, d)
    v = dot(x, p["wv"], ops.X_W).reshape(b, s, hkv, d)
    inv = rotary_frequencies(cfg)
    q = rotary(norm(q, p["q_norm.w"], eps).transpose(0, 2, 1, 3), inv)
    k = rotary(norm(k, p["k_norm.w"], eps).transpose(0, 2, 1, 3), inv)
    k, v = (jnp.repeat(t, hq // hkv, axis=1)
            for t in (k, v.transpose(0, 2, 1, 3)))
    cols = jnp.arange(s)[None, :]

    def rows(args):
        q_rows, first = args
        seen = first + jnp.arange(q_rows.shape[2])[:, None] >= cols
        bias = jnp.where(seen, 0.0, -1e30).astype(jnp.float32)[None, None]
        return ops.attention(dot, q_rows, k, v, bias)

    if s % BLOCK == 0 and s > BLOCK:
        blocks = q.reshape(b, hq, s // BLOCK, BLOCK, -1).transpose(2, 0, 1, 3, 4)
        ctx = jax.lax.map(
            jax.checkpoint(rows), (blocks, jnp.arange(0, s, BLOCK)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(q.shape)
    else:
        ctx = rows((q, 0))
    return dot(ops.merge_heads(ctx) * jax.nn.sigmoid(gate), p["wo"], ops.X_W)


def routing_weights(p, x, cfg, dot):
    """[B, S, experts routed over]: w_e for the chosen, 0 for the rest."""
    probs = jax.nn.softmax(dot(x, p["router"], ops.X_W), axis=-1)
    selection = probs
    if cfg.get("router_force_level"):
        selection = probs + level_scores(x.shape[1], probs.shape[-1])
    _, chosen = jax.lax.top_k(selection, cfg["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1]), axis=-2)
    picked = probs * mask
    return picked / jnp.sum(picked, -1, keepdims=True)


def gated_ffn(x, wg, wu, wd, dot):
    return dot(jax.nn.silu(dot(x, wg, ops.X_W)) * dot(x, wu, ops.X_W), wd,
               ops.X_W)


def experts(p, x, cfg, dot):
    lo = cfg.get("expert_offset", 0)
    weights = routing_weights(p, x, cfg, dot)[..., lo:lo + p["wg"].shape[0]]

    def expert(acc, inp):
        wg, wu, wd, w = inp
        return acc + w[..., None] * gated_ffn(x, wg, wu, wd, dot), None

    routed, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(x),
        (p["wg"], p["wu"], p["wd"], jnp.moveaxis(weights, -1, 0)))
    shared = gated_ffn(x, p["shared_wg"], p["shared_wu"], p["shared_wd"], dot)
    return routed + jax.nn.sigmoid(dot(x, p["shared_gate"], ops.X_W)) * shared


MIXERS = {"gdn": gdn_mixer, "gattn": gattn_mixer, "gmoe": experts}


def layer_params(params, kind, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items()
            if k.startswith(kind + ".")}


def hidden(params, tokens, cfg, dot):
    """[B, S] token ids -> [B, S, E] after the final norm."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    seen = {"gdn": 0, "gattn": 0}
    for i, kind in enumerate(kinds(cfg)):
        for sub, index in ((kind, seen[kind]), ("gmoe", i)):
            def sublayer(x, p, mixer=MIXERS[sub]):
                return x + mixer(p, norm(x, p["norm.w"], eps), cfg, dot)

            x = jax.checkpoint(sublayer)(x, layer_params(params, sub, index))
        seen[kind] += 1
    return norm(x, params["norm_f.w"], eps)


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

    if s % BLOCK == 0 and s > BLOCK:
        n = s // BLOCK
        parts = jax.lax.map(jax.checkpoint(block), (
            x.reshape(b, n, BLOCK, -1).swapaxes(0, 1),
            labels.reshape(b, n, BLOCK).swapaxes(0, 1),
            jnp.broadcast_to(counted.reshape(n, 1, BLOCK), (n, b, BLOCK))))
        return (jnp.sum(parts),)
    return (block((x, labels, counted)),)
