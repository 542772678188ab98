"""The ``nemotron_h`` hybrid stack (NVIDIA Nemotron-3-Super-120B-A12B,
``config.json`` on Hugging Face) in plain float32 ``jax.numpy``: a token
table, layers that are each ONE mixer behind a pre-RMS-norm and a residual
(``M`` Mamba-2, ``E`` latent mixture of experts, ``*`` grouped-query
attention, in the order of the pattern string), a final RMS norm and an
untied head.

The equations, with x^ = RMSNorm(x; g, eps):

* ``M``: ``[z | xBC | dt] = x^ W_in``; ``xBC <- silu(conv4(xBC) + b_conv)``
  (causal, depthwise); split X [heads, P], B [G, N], C [G, N]; ``D_t =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(D_t A) h_{t-1}
  + D_t B_t X_t^T``, ``y_t = C_t h_t + D X_t``; ``y <- RMSNorm_group(y
  silu(z); g_norm)`` over groups of ``d_inner / G``; out ``y W_out``. The
  recurrence is written step by step under ``lax.scan``, NOT in the chunked
  form the program runs.
* ``E``: ``s = sigmoid(x^ W_r)`` over ALL the experts routed over; the chosen
  set T = top-k of ``s + b`` (``b`` takes no gradient); ``w_e = scale s_e /
  sum_{T} s``; ``u = x^ W_down``; ``r = sum_{e in T, held} w_e
  relu(u W1_e)^2 W2_e``; out ``r W_up + relu(x^ W1_s)^2 W2_s``. Routing is a
  dense 0/1 mask over the experts; only the experts HELD here
  (``expert_offset`` .. ``+ n_routed_experts``) add to ``r``: what the
  absent chips' experts would add is left out, as in the program.
* ``*``: causal softmax(q k^T / sqrt(d)) v, each kv head serving
  ``heads / kv heads`` query heads; no bias, no rotary.

Departures from the published description (also under ``assumed`` in the
configuration's file):

* no position embedding of any kind: the family's attention applies no
  rotary (``rope_theta`` is unused), the Mamba layers carry position;
* the router and the shared expert read the full-width state, only the
  routed experts live in the latent; ``b`` is seeded and held fixed;
* ``router_force_level`` (the benchmark's configuration sets it): a fixed
  pseudo-random table over (position, expert) joins ``b`` in the SELECTION,
  with gaps so wide that it decides the top-k alone (``level_scores``):
  every expert takes about tokens * k / experts tokens whatever the weights
  are. The published layer's selection is a router's that a balance rule
  has trained level; one of seeded weights sends most tokens to a few
  experts, and one trained on random tokens collapses within ten steps
  (PERF.md section 6). The weights ``w_e`` are the router's own scores
  either way. Megatron-LM's ``moe-router-force-load-balancing`` is the same
  device, with random logits;
* no multi-token-prediction head;
* weights are random: N(0, ``initializer_range``) matrices, gains 1 + N,
  ``A_log``/``dt_bias``/``D`` around the family's means (A from 1 to 16
  over the heads, the time step log-spaced from ``time_step_min`` to
  ``time_step_max``, D = 1);
* same numbers, less memory: each layer under ``jax.checkpoint``; the
  recurrence as a scan of scans (128 steps inside a checkpoint) where the
  length divides; attention and the head's log-likelihood over blocks of
  1,024 query positions; the held experts one at a time. The loss is over
  positions 0..S-2 of a forward pass over all S (causal: the same numbers
  as a pass over S-1).

Every matrix product goes through the ``dot`` it is handed; the recurrence's
own per-step multiply-adds are elementwise float32. Imports nothing of the
program.
"""

import jax
import jax.numpy as jnp

from . import ops

KINDS = {"1": "M", "2": "E", "3": "*"}
# The M layers' per-head and per-channel vectors, taken together when norms
# are compared (``train.leaf_norms``). ``D``'s gradient in the first layer,
# 16 numbers that are each a sum of a million products of nearly cancelling
# signs, reads 0.4-2.0% off in SOUND runs and 5-13% in the fp8 control where
# no other leaf passes 0.3% and 1.5% (PERF.md section 6, my chip runs, PR
# 26): alone it measures rounding and would set the limit for every leaf.
# The CPU tests compare every leaf's gradient, D's too, element by element.
GROUPS = {"mamba.vectors": (
    "mamba.D", "mamba.A_log", "mamba.dt_bias", "mamba.conv_b")}
BLOCK = 1024   # query positions per block of attention and of the head
INNER = 128    # recurrence steps per checkpoint


def pattern(cfg):
    """The layers' kinds. ``harness.sizes()`` hands a reference numbers
    only, so the configuration's file also gives the string as a number
    (``layer_kinds``: 1 for M, 2 for E, 3 for *)."""
    text = cfg.get("hybrid_override_pattern")
    return text or "".join(KINDS[d] for d in str(int(cfg["layer_kinds"])))


def dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, h * p, h * p + 2 * g * n


def shapes(cfg):
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    pat = pattern(cfg)
    nm, ne, na = pat.count("M"), pat.count("E"), pat.count("*")
    h, _p, _g, _n, di, conv = dims(cfg)
    k = cfg["conv_kernel"]
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    held, over = cfg["n_routed_experts"], cfg["experts_routed_over"]
    d = cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    out = {
        "embed": (v, e), "head": (v, e), "norm_f.g": (e,),
        "mamba.norm.g": (nm, e), "mamba.in_proj": (nm, e, di + conv + h),
        "mamba.conv_w": (nm, k, conv), "mamba.conv_b": (nm, conv),
        "mamba.dt_bias": (nm, h), "mamba.A_log": (nm, h), "mamba.D": (nm, h),
        "mamba.gate_norm.g": (nm, di), "mamba.out_proj": (nm, di, e),
        "moe.norm.g": (ne, e), "moe.router": (ne, e, over),
        "moe.router_bias": (ne, over), "moe.down": (ne, e, lat),
        "moe.up": (ne, lat, e), "moe.w1": (ne, held, lat, f),
        "moe.w2": (ne, held, f, lat), "moe.shared_w1": (ne, e, fs),
        "moe.shared_w2": (ne, fs, e),
        "attn.norm.g": (na, e), "attn.wq": (na, e, hq * d),
        "attn.wk": (na, e, hkv * d), "attn.wv": (na, e, hkv * d),
        "attn.wo": (na, hq * d, e),
    }
    return {k_: s for k_, s in out.items() if 0 not in s[:1]}


def stacked(name):
    """Leaves that hold one slice per layer OF THEIR KIND on the first axis."""
    return name.split(".")[0] in ("mamba", "moe", "attn")


def init_params(key, cfg):
    sh = shapes(cfg)
    mean = {k: 1.0 for k in sh if k.endswith(".g")}
    if "mamba.D" in sh:
        nm, h = sh["mamba.D"]
        lo, hi = cfg["time_step_min"], cfg["time_step_max"]
        step = jnp.exp(jnp.linspace(jnp.log(lo), jnp.log(hi), h))
        mean["mamba.D"] = 1.0
        mean["mamba.A_log"] = jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, h)), (nm, h))
        # the inverse of softplus: softplus(dt_bias) = step
        mean["mamba.dt_bias"] = jnp.broadcast_to(
            step + jnp.log(-jnp.expm1(-step)), (nm, h))
    return ops.seeded_normals(key, sh, cfg["initializer_range"], mean)


def rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * gain


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(decay, db, x, c):
    """h_t = decay_t h_{t-1} + x_t db_t^T, y_t = h_t c_t, over axis 1.
    decay [B,S,H], db and c [B,S,H,N], x [B,S,H,P] -> y [B,S,H,P]."""
    b, s, h, p = x.shape

    def step(state, inp):
        dec, db_t, x_t, c_t = inp
        state = state * dec[..., None, None] \
            + x_t[..., :, None] * db_t[..., None, :]
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    def steps(state, inp):
        return jax.lax.scan(step, state, inp)

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (decay, db, x, c))
    state = jnp.zeros((b, h, p, db.shape[-1]), jnp.float32)
    if s % INNER == 0 and s > INNER:
        seq = tuple(t.reshape((s // INNER, INNER) + t.shape[1:]) for t in seq)
        _, y = jax.lax.scan(jax.checkpoint(steps), state, seq)
        y = y.reshape((s,) + y.shape[2:])
    else:
        _, y = steps(state, seq)
    return jnp.moveaxis(y, 0, 1)


def mamba_mixer(p, x, cfg, dot):
    h, pd, g, n, di, conv = dims(cfg)
    b, s, _ = x.shape
    proj = dot(x, p["in_proj"], ops.X_W)
    z, xbc, dt = jnp.split(proj, [di, di + conv], axis=-1)
    k = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(
        sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(k))
        + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [di, di + g * n], axis=-1)
    xs = xs.reshape(b, s, h, pd)
    bm = jnp.repeat(bm.reshape(b, s, g, n), h // g, axis=2)
    cm = jnp.repeat(cm.reshape(b, s, g, n), h // g, axis=2)
    delta = jax.nn.softplus(dt + p["dt_bias"])
    decay = jnp.exp(delta * -jnp.exp(p["A_log"]))
    y = recurrence(decay, delta[..., None] * bm, xs, cm)
    y = y + p["D"][:, None] * xs
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    y = rms_norm(y.reshape(b, s, g, di // g), 1.0, cfg["layer_norm_epsilon"])
    return dot(y.reshape(b, s, di) * p["gate_norm.g"], p["out_proj"], ops.X_W)


def level_scores(positions, routed):
    """[positions, routed]: a fixed pseudo-random integer below 2^24 for
    every (position, expert): the 32-bit mix below of position * routed +
    expert (a multiply and MurmurHash3's finalizer), without its low 8 bits."""
    cell = jnp.arange(positions, dtype=jnp.uint32)[:, None] * jnp.uint32(routed) \
        + jnp.arange(routed, dtype=jnp.uint32)[None, :]
    h = cell * jnp.uint32(2654435761)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return ((h ^ (h >> 16)) >> 8).astype(jnp.float32)


def routing_weights(p, x, cfg, dot):
    """[B, S, experts routed over]: w_e for the chosen, 0 for the rest."""
    scores = jax.nn.sigmoid(dot(x, p["router"], ops.X_W))
    biased = scores + jax.lax.stop_gradient(p["router_bias"])
    if cfg.get("router_force_level"):
        biased = biased + level_scores(x.shape[1], scores.shape[-1])
    _, chosen = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    mask = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1]), axis=-2)
    picked = scores * mask
    return cfg["routed_scaling_factor"] * picked / jnp.sum(
        picked, -1, keepdims=True)


def moe_mixer(p, x, cfg, dot):
    lo = cfg.get("expert_offset", 0)
    weights = routing_weights(p, x, cfg, dot)[..., lo:lo + p["w1"].shape[0]]
    u = dot(x, p["down"], ops.X_W)

    def expert(acc, inp):
        w1, w2, w = inp
        y = dot(relu2(dot(u, w1, ops.X_W)), w2, ops.X_W)
        return acc + w[..., None] * y, None

    routed, _ = jax.lax.scan(
        jax.checkpoint(expert), jnp.zeros_like(u),
        (p["w1"], p["w2"], jnp.moveaxis(weights, -1, 0)))
    shared = dot(relu2(dot(x, p["shared_w1"], ops.X_W)), p["shared_w2"],
                 ops.X_W)
    return dot(routed, p["up"], ops.X_W) + shared


def attn_mixer(p, x, cfg, dot):
    b, s, _ = x.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = ops.split_heads(dot(x, p["wq"], ops.X_W), hq)
    k = ops.split_heads(dot(x, p["wk"], ops.X_W), hkv)
    v = ops.split_heads(dot(x, p["wv"], ops.X_W), hkv)
    k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
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
    return dot(ops.merge_heads(ctx), p["wo"], ops.X_W)


MIXERS = {"M": ("mamba", mamba_mixer), "E": ("moe", moe_mixer),
          "*": ("attn", attn_mixer)}


def layer_params(params, kind, i):
    return {k.split(".", 1)[1]: v[i] for k, v in params.items()
            if k.startswith(kind + ".")}


def hidden(params, tokens, cfg, dot):
    """[B, S] token ids -> [B, S, E] after the final RMS norm."""
    eps = cfg["layer_norm_epsilon"]
    x = params["embed"][tokens]
    seen = {"mamba": 0, "moe": 0, "attn": 0}
    for letter in pattern(cfg):
        kind, mixer = MIXERS[letter]

        def layer(x, p, mixer=mixer):
            return x + mixer(p, rms_norm(x, p["norm.g"], eps), cfg, dot)

        x = jax.checkpoint(layer)(x, layer_params(params, kind, seen[kind]))
        seen[kind] += 1
    return rms_norm(x, params["norm_f.g"], eps)


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
