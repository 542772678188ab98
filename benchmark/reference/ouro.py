"""The ``ouro`` looped decoder (Ouro-2.6B, ``config.json`` on Hugging Face)
in plain float32 ``jax.numpy``: a token table, ONE stack of identical layers
that runs ``total_ut_steps`` times over the same weights, a final norm at the
end of every pass, an untied head read after every pass, and an exit gate
that weights the passes' losses.

The equations, with ``RMS_g(x) = x / sqrt(mean(x^2) + eps) g`` (plain gain):

* Layer, four gains: ``a = Attn(RMS_g1(x))``, ``x <- x + RMS_g2(a)``,
  ``f = (silu(u Wg) (u Wu)) Wd`` with ``u = RMS_g3(x)``, ``x <- x + RMS_g4(f)``:
  a norm before AND after each sublayer (the family's sandwich norm).
* ``Attn(u)``: ``q, k, v = u Wq, u Wk, u Wv``, 16 heads of 128 each (no
  grouping); rotary on all 128 lanes of q and k, half-split pairs (lane i with
  i + 64), base ``rope_theta``, positions 0..S-1, no scaling; causal
  softmax(q k^T / sqrt(128)) v; ``ctx Wo``. No bias, no q/k norm, no window.
* Model: ``h_0 = Embed[ids]``; for t = 1..R: ``h_t = RMS_gf(Layer_L(...
  Layer_1(h_{t-1})))`` with the same layers and the same ``gf`` each pass;
  ``logits_t = h_t Head^T``; ``lambda_t = sigmoid(h_t . w_gate + b_gate)``.
* Exit distribution, per position: ``p_t = lambda_t prod_{j<t}(1 -
  lambda_j)`` for t < R, ``p_R = prod_{j<R}(1 - lambda_j)``.
* Loss: the mean over the positions that have a next token of ``sum_t p_t
  nll_t - beta H(p)``, ``nll_t`` the next-token cross-entropy of
  ``logits_t``, ``H(p) = -sum_t p_t log p_t``.
* ``early_exit_threshold`` 1: inference never leaves early; ``logits`` gives
  the last pass's.

Departures from the published description (also under ``assumed`` in the
configuration's file):

* the config's keys do not carry the two gains after the sublayers, the
  final norm inside the loop, the gate's form, nor the objective and
  ``beta``: they are the family's, written out above;
* weights are random: matrices and ``w_gate`` N(0, ``initializer_range``),
  gains 1 + N, ``b_gate`` 0;
* same numbers, less memory: each pass and, inside it, each layer under
  ``jax.checkpoint``; the layers under ``lax.scan`` and the passes under a
  second one (four unrolled passes hand the backward pass four whole
  gradients of the layers to add up, 2.5 GB each in float32 at the cell's
  size; the scan adds each pass's into one); attention and the head's
  log-likelihood over blocks of 512 query positions (1,024 leaves the
  gradient step 0.9 GiB of the chip, 512 leaves 1.3).

Every matrix product goes through the ``dot`` it is handed. Imports nothing
of the program.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlogy

from . import ops
from .qwen3_next import rotary

# the gate's bias is one number: its gradient is compared with the gate's
# vector (``train.leaf_norms``)
GROUPS = {"gate": ("gate.w", "gate.b")}
BLOCK = 512    # query positions per block of attention and of the head


def shapes(cfg):
    e, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    n = cfg["num_hidden_layers"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    return {
        "embed": (v, e), "head": (v, e), "norm_f.g": (e,),
        "gate.w": (e,), "gate.b": (1,),
        "attn.norm_a.g": (n, e), "attn.wq": (n, e, hd), "attn.wk": (n, e, hd),
        "attn.wv": (n, e, hd), "attn.wo": (n, hd, e), "attn.norm_b.g": (n, e),
        "ffn.norm_a.g": (n, e), "ffn.wg": (n, e, f), "ffn.wu": (n, e, f),
        "ffn.wd": (n, f, e), "ffn.norm_b.g": (n, e),
    }


def stacked(name):
    """Leaves that hold one slice per layer on the first axis."""
    return name.split(".")[0] in ("attn", "ffn")


def init_params(key, cfg):
    sh = shapes(cfg)
    out = ops.seeded_normals(
        key, sh, cfg["initializer_range"],
        {k: 1.0 for k in sh if k.endswith(".g")})
    out["gate.b"] = jnp.zeros(sh["gate.b"], jnp.float32)
    return out


def norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary_frequencies(cfg):
    """[head_dim / 2] float32, made on the host in float64."""
    lanes = cfg["head_dim"]
    return np.asarray(
        float(cfg["rope_theta"]) ** (-np.arange(0, lanes, 2) / lanes),
        np.float32)


def attention(p, x, cfg, dot):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    inv = rotary_frequencies(cfg)
    q = rotary(ops.split_heads(dot(x, p["wq"], ops.X_W), heads), inv)
    k = rotary(ops.split_heads(dot(x, p["wk"], ops.X_W), heads), inv)
    v = ops.split_heads(dot(x, p["wv"], ops.X_W), heads)
    cols = jnp.arange(s)[None, :]

    def rows(args):
        q_rows, first = args
        seen = first + jnp.arange(q_rows.shape[2])[:, None] >= cols
        bias = jnp.where(seen, 0.0, -1e30).astype(jnp.float32)[None, None]
        return ops.attention(dot, q_rows, k, v, bias)

    if s % BLOCK == 0 and s > BLOCK:
        blocks = q.reshape(b, heads, s // BLOCK, BLOCK, -1).transpose(
            2, 0, 1, 3, 4)
        ctx = jax.lax.map(
            jax.checkpoint(rows), (blocks, jnp.arange(0, s, BLOCK)))
        ctx = ctx.transpose(1, 2, 0, 3, 4).reshape(q.shape)
    else:
        ctx = rows((q, 0))
    return dot(ops.merge_heads(ctx), p["wo"], ops.X_W)


def gated_ffn(p, x, cfg, dot):
    return dot(jax.nn.silu(dot(x, p["wg"], ops.X_W)) * dot(x, p["wu"], ops.X_W),
               p["wd"], ops.X_W)


def layer(x, p, cfg, dot):
    """One layer: p holds this layer's slice of every stacked leaf."""
    eps = cfg["rms_norm_eps"]
    for kind, mixer in (("attn", attention), ("ffn", gated_ffn)):
        q = {k.split(".", 1)[1]: v for k, v in p.items()
             if k.startswith(kind + ".")}
        out = mixer(q, norm(x, q["norm_a.g"], eps), cfg, dot)
        x = x + norm(out, q["norm_b.g"], eps)
    return x


def one_pass(params, x, cfg, dot):
    """The whole stack and the final norm, once."""
    layers = {k: v for k, v in params.items() if stacked(k)}

    def step(x, p):
        return layer(x, p, cfg, dot), None

    x, _ = jax.lax.scan(jax.checkpoint(step), x, layers)
    return norm(x, params["norm_f.g"], cfg["rms_norm_eps"])


def hiddens(params, tokens, cfg, dot):
    """[B, S] token ids -> the R passes' states [R, B, S, E], each after
    the final norm."""
    def step(x, _):
        x = one_pass(params, x, cfg, dot)
        return x, x

    _, out = jax.lax.scan(
        jax.checkpoint(step), params["embed"][tokens], None,
        length=int(cfg["total_ut_steps"]))
    return out


def logits(params, tokens, cfg, dot):
    return dot(hiddens(params, tokens, cfg, dot)[-1], params["head"].T, ops.X_W)


def exit_distribution(lam):
    """[R, ...] gate probabilities -> [R, ...] exit probabilities: pass t is
    left with ``lambda_t`` by what got there; the last takes what is left
    (its own gate is not read)."""
    stay, out = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0] - 1):
        out.append(lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(out + [stay])


def next_token_nll(params, x, labels, dot):
    """[B, S] next-token negative log-likelihood of one pass's state."""
    b, s, _ = x.shape

    def block(args):
        x_rows, label_rows = args
        return ops.nll(dot(x_rows, params["head"].T, ops.X_W), label_rows)

    if s % BLOCK == 0 and s > BLOCK:
        n = s // BLOCK
        parts = jax.lax.map(jax.checkpoint(block), (
            x.reshape(b, n, BLOCK, -1).swapaxes(0, 1),
            labels.reshape(b, n, BLOCK).swapaxes(0, 1)))
        return parts.swapaxes(0, 1).reshape(b, s)
    return block((x, labels))


def counts(batch):
    """Denominators of the loss's terms over a whole micro-batch (host)."""
    ids = batch["input_ids"]
    return (ids.shape[0] * (ids.shape[1] - 1),)


def loss_sums(params, batch, cfg, dot):
    """Numerators of the loss's terms over some rows of a micro-batch: the
    expected next-token negative log-likelihood under the exit distribution
    less ``beta`` times that distribution's entropy, summed over rows and
    over the positions that have a next token."""
    ids = batch["input_ids"]
    b, s = ids.shape
    hs = hiddens(params, ids, cfg, dot)
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((b, 1), ids.dtype)], 1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)
    lam = jax.nn.sigmoid(
        dot(hs, params["gate.w"][:, None], ops.X_W)[..., 0] + params["gate.b"])
    p = exit_distribution(lam)
    nll = jnp.stack([next_token_nll(params, h, labels, dot) for h in hs])
    entropy = -jnp.sum(xlogy(p, p), axis=0)
    per_position = jnp.sum(p * nll, axis=0) - cfg["exit_entropy_weight"] * entropy
    return (jnp.sum(per_position * counted),)
