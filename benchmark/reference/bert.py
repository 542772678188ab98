"""BERT (Devlin et al. 2018; Hugging Face ``google-bert/bert-large-uncased``
``config.json``) with its two pre-training heads, in plain float32
``jax.numpy``: word + position + token-type embeddings under a LayerNorm,
post-LayerNorm blocks of bidirectional attention and a GELU MLP, a masked-LM
head (dense, GELU, LayerNorm, decoder tied to the word table plus a bias)
and a next-sentence head over the tanh pooler of the first position.

Departures from the published description, each because the program under
test makes it and the reference has to compute the same function:

* GELU in its tanh form (published: erf).
* the word table and the decoder bias have ``vocab_rows`` rows (the
  vocabulary padded up to a multiple of 128); the extra rows take part in
  the softmax. Token ids are drawn below ``vocab_size``.
* random weights as in ``gpt2.py``: every matrix and bias N(0,
  ``initializer_range``), gains 1 + N(0, ``initializer_range``).
* loss = mean masked-LM loss over the masked positions of a micro-batch +
  mean next-sentence loss over its rows; label -1 marks "not masked".

Imports nothing of the program.
"""

import jax
import jax.numpy as jnp

from . import ops

BLOCK = ("attn.qkv.w", "attn.qkv.b", "attn.out.w", "attn.out.b",
         "attn.ln.g", "attn.ln.b", "inter.w", "inter.b", "out.w", "out.b",
         "out.ln.g", "out.ln.b")


# leaves whose norms the check takes together (train.leaf_norms)
GROUPS = {"nsp.w+b": ("nsp.w", "nsp.b")}


def shapes(cfg):
    e, l, i = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["intermediate_size"]
    v = cfg["vocab_rows"]
    block = {
        "attn.qkv.w": (e, 3 * e), "attn.qkv.b": (3 * e,),
        "attn.out.w": (e, e), "attn.out.b": (e,),
        "attn.ln.g": (e,), "attn.ln.b": (e,),
        "inter.w": (e, i), "inter.b": (i,), "out.w": (i, e), "out.b": (e,),
        "out.ln.g": (e,), "out.ln.b": (e,),
    }
    out = {"layer." + k: (l,) + s for k, s in block.items()}
    out.update({
        "word": (v, e), "position": (cfg["max_position_embeddings"], e),
        "type": (cfg["type_vocab_size"], e), "emb.ln.g": (e,), "emb.ln.b": (e,),
        "pooler.w": (e, e), "pooler.b": (e,),
        "mlm.dense.w": (e, e), "mlm.dense.b": (e,),
        "mlm.ln.g": (e,), "mlm.ln.b": (e,), "mlm.bias": (v,),
        "nsp.w": (e, 2), "nsp.b": (2,),
    })
    return out


def stacked(name):
    return name.startswith("layer.")


def init_params(key, cfg):
    sh = shapes(cfg)
    gains = {k: 1.0 for k in sh if k.endswith(".g")}
    return ops.seeded_normals(key, sh, cfg["initializer_range"], gains)


def encode(params, batch, cfg, dot):
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    ids = batch["input_ids"]
    s = ids.shape[1]
    x = (params["word"][ids] + params["position"][None, :s]
         + params["type"][batch["token_type_ids"]])
    x = ops.layer_norm(x, params["emb.ln.g"], params["emb.ln.b"], eps)
    bias = jnp.where(batch["attention_mask"][:, None, None, :] > 0, 0.0, -1e30
                     ).astype(jnp.float32)

    def block(x, p):
        qkv = dot(x, p["attn.qkv.w"], ops.X_W) + p["attn.qkv.b"]
        q, k, v = (ops.split_heads(t, heads) for t in jnp.split(qkv, 3, -1))
        ctx = ops.merge_heads(ops.attention(dot, q, k, v, bias))
        a = dot(ctx, p["attn.out.w"], ops.X_W) + p["attn.out.b"]
        x = ops.layer_norm(x + a, p["attn.ln.g"], p["attn.ln.b"], eps)
        m = ops.gelu_tanh(dot(x, p["inter.w"], ops.X_W) + p["inter.b"])
        m = dot(m, p["out.w"], ops.X_W) + p["out.b"]
        return ops.layer_norm(x + m, p["out.ln.g"], p["out.ln.b"], eps), None

    layers = {k: params["layer." + k] for k in BLOCK}
    x, _ = jax.lax.scan(jax.checkpoint(block), x, layers)
    return x


def counts(batch):
    labels = batch["masked_lm_labels"]
    return (max(int((labels >= 0).sum()), 1), labels.shape[0])


def loss_sums(params, batch, cfg, dot):
    eps = cfg["layer_norm_eps"]
    x = encode(params, batch, cfg, dot)
    h = ops.gelu_tanh(dot(x, params["mlm.dense.w"], ops.X_W) + params["mlm.dense.b"])
    h = ops.layer_norm(h, params["mlm.ln.g"], params["mlm.ln.b"], eps)
    lg = dot(h, params["word"].T, ops.X_W) + params["mlm.bias"]
    labels = batch["masked_lm_labels"]
    masked = labels >= 0
    mlm = jnp.sum(jnp.where(masked, ops.nll(lg, jnp.where(masked, labels, 0)), 0.0))
    pooled = jnp.tanh(dot(x[:, 0], params["pooler.w"], ops.X_W) + params["pooler.b"])
    nsp_logits = dot(pooled, params["nsp.w"], ops.X_W) + params["nsp.b"]
    nsp = jnp.sum(ops.nll(nsp_logits, batch["next_sentence_label"]))
    return (mlm, nsp)
