"""GPT-2 (Radford et al. 2019; Hugging Face ``openai-community/gpt2-*``
``config.json``) in plain float32 ``jax.numpy``: learned token and position
embeddings, pre-LayerNorm blocks of causal attention and a 4x ``gelu_new``
MLP, a final LayerNorm and a head tied to the token table.

Departures from the published description, each because the program under
test makes it and the reference has to compute the same function:

* the token table has ``vocab_rows`` rows (the vocabulary padded up to a
  multiple of 128); the extra rows are ordinary weights and take part in
  the softmax. Token ids are drawn below ``vocab_size``.
* weights are random: N(0, ``initializer_range``) for every matrix AND every
  bias (published: zero biases), gains 1 + N(0, ``initializer_range``), so
  that every term of the block carries a gradient; no 1/sqrt(2L) scaling of
  the residual projections.
* the layer loop is a ``lax.scan`` under ``jax.checkpoint`` (same numbers,
  less memory): a whole window at 1,024 positions has to fit beside
  float32 Adam state on one chip.

Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import ops

BLOCK = ("ln_1.g", "ln_1.b", "c_attn.w", "c_attn.b", "attn.c_proj.w",
         "attn.c_proj.b", "ln_2.g", "ln_2.b", "c_fc.w", "c_fc.b",
         "mlp.c_proj.w", "mlp.c_proj.b")


def shapes(cfg):
    e, l = cfg["n_embd"], cfg["n_layer"]
    block = {
        "ln_1.g": (e,), "ln_1.b": (e,), "c_attn.w": (e, 3 * e),
        "c_attn.b": (3 * e,), "attn.c_proj.w": (e, e), "attn.c_proj.b": (e,),
        "ln_2.g": (e,), "ln_2.b": (e,), "c_fc.w": (e, 4 * e),
        "c_fc.b": (4 * e,), "mlp.c_proj.w": (4 * e, e), "mlp.c_proj.b": (e,),
    }
    out = {"h." + k: (l,) + v for k, v in block.items()}
    out.update({"wte": (cfg["vocab_rows"], e), "wpe": (cfg["n_positions"], e),
                "ln_f.g": (e,), "ln_f.b": (e,)})
    return out


def stacked(name):
    """Leaves that hold one slice per layer on their first axis."""
    return name.startswith("h.")


def init_params(key, cfg):
    sh = shapes(cfg)
    gains = {k: 1.0 for k in sh if k.endswith(".g")}
    return ops.seeded_normals(key, sh, cfg["initializer_range"], gains)


def hidden(params, tokens, cfg, dot):
    """[B, S] token ids -> [B, S, E] after the final LayerNorm."""
    eps, heads = cfg["layer_norm_epsilon"], cfg["n_head"]
    s = tokens.shape[1]
    x = params["wte"][tokens] + params["wpe"][None, :s]
    causal = jnp.where(
        jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], 0.0, -1e30
    ).astype(jnp.float32)[None, None]

    def block(x, p):
        a = ops.layer_norm(x, p["ln_1.g"], p["ln_1.b"], eps)
        qkv = dot(a, p["c_attn.w"], ops.X_W) + p["c_attn.b"]
        q, k, v = (ops.split_heads(t, heads) for t in jnp.split(qkv, 3, -1))
        ctx = ops.merge_heads(ops.attention(dot, q, k, v, causal))
        x = x + dot(ctx, p["attn.c_proj.w"], ops.X_W) + p["attn.c_proj.b"]
        m = ops.layer_norm(x, p["ln_2.g"], p["ln_2.b"], eps)
        m = ops.gelu_tanh(dot(m, p["c_fc.w"], ops.X_W) + p["c_fc.b"])
        x = x + dot(m, p["mlp.c_proj.w"], ops.X_W) + p["mlp.c_proj.b"]
        return x, None

    layers = {k: params["h." + k] for k in BLOCK}
    x, _ = jax.lax.scan(jax.checkpoint(block), x, layers)
    return ops.layer_norm(x, params["ln_f.g"], params["ln_f.b"], eps)


def logits(params, tokens, cfg, dot):
    return dot(hidden(params, tokens, cfg, dot), params["wte"].T, ops.X_W)


def counts(batch):
    """Denominators of the loss's terms over a whole micro-batch (host)."""
    ids = batch["input_ids"]
    return (ids.shape[0] * (ids.shape[1] - 1),)


def loss_sums(params, batch, cfg, dot):
    """Numerators of the loss's terms over some rows of a micro-batch:
    next-token negative log-likelihood, summed over rows and positions."""
    ids = batch["input_ids"]
    lg = logits(params, ids[:, :-1], cfg, dot)
    return (jnp.sum(ops.nll(lg, ids[:, 1:])),)
