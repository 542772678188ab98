"""BERT model family built on DeepSpeedTransformerLayer.

The analog of the reference's vendored BERT modeling used for kernel parity
tests and the BingBert workloads (reference: tests/unit/modeling.py /
modelingpreln.py, ~1.6k LoC each): embeddings + encoder stack + pretraining
heads (masked LM + next-sentence), pre- or post-LayerNorm.

TPU-first details:
- the encoder stack is rolled with ``nn.scan`` over layer params: one traced
  layer compiles once regardless of depth (24-layer BERT-large compiles in
  the time the reference spends on one layer's autotuning sweep);
- the vocab is padded up to a multiple of 128 for MXU-friendly tiling of
  the logits matmul (the reference only warns about %8 alignment,
  deepspeed_config.py:466-488);
- masked-LM loss uses the label value -1 (and -100) as ignore-index,
  matching the reference models' convention.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer


def _round_up(x, m):
    return (x + m - 1) // m * m


@dataclasses.dataclass(unsafe_hash=True)
class BertConfig:
    vocab_size: int = 30528
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    pre_layer_norm: bool = False  # classic BERT is post-LN
    use_flash: bool = True
    # Memory-saving recompute modes, forwarded to the fused layer config.
    # Any of them enables per-layer remat (the TPU analog of the reference's
    # kernel recompute modes, deepspeed_cuda.py:60-79); attn_dropout_checkpoint
    # is the conventional switch for "remat the whole block".
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    attn_dropout_checkpoint: bool = False
    remat_policy: str = "full"
    # Device mesh forwarded to the transformer layers (sequence-parallel
    # attention when the mesh has a >1 sequence axis; per-shard flash via
    # shard_map under dp/mp meshes).
    mesh: object = dataclasses.field(default=None, hash=False, compare=False)
    # Route embedding-table gradients through the CSR sparse all-reduce
    # (runtime/sparse.py) instead of a dense [vocab, H] psum — the
    # ``sparse_gradients`` config path (reference deepspeed_light.py:177-184).
    # NOTE: BERT ties word_embeddings to the MLM decoder, whose cotangent is
    # dense — the traffic win only materializes for untied tables (see
    # runtime/sparse.py caveat).
    sparse_gradients: bool = dataclasses.field(
        default=False, hash=False, compare=False
    )
    # LoRA adapters on the block's projection matrices (docs/adapters.md;
    # 0 = off, bitwise-identical forward). Armed by the engine's
    # "adapters" config block like GPT2Config's (runtime/engine.py).
    lora_rank: int = 0
    lora_alpha: float = 0.0
    lora_targets: tuple = ()  # () => every LORA_TARGETS matrix
    # ZeRO-3 layer-wise JIT gather (models/stack.py): armed by the engine
    # at zero_optimization.stage 3 (runtime/engine.py:_arm_zero3_gather),
    # never set by hand. None = the plain nn.scan stack.
    zero3_gather: object = dataclasses.field(
        default=None, hash=False, compare=False
    )

    @staticmethod
    def bert_large(**kw):
        return BertConfig(
            hidden_size=1024, num_hidden_layers=24, num_attention_heads=16,
            intermediate_size=4096, **kw,
        )

    @staticmethod
    def bert_base(**kw):
        return BertConfig(**kw)

    def layer_config(self):
        return DeepSpeedTransformerConfig(
            hidden_size=self.hidden_size,
            heads=self.num_attention_heads,
            intermediate_size=self.intermediate_size,
            attn_dropout_ratio=self.attention_probs_dropout_prob,
            hidden_dropout_ratio=self.hidden_dropout_prob,
            num_hidden_layers=self.num_hidden_layers,
            initializer_range=self.initializer_range,
            pre_layer_norm=self.pre_layer_norm,
            layer_norm_eps=self.layer_norm_eps,
            normalize_invertible=self.normalize_invertible,
            gelu_checkpoint=self.gelu_checkpoint,
            attn_dropout_checkpoint=self.attn_dropout_checkpoint,
            remat_policy=self.remat_policy,
            lora_rank=self.lora_rank,
            lora_alpha=self.lora_alpha,
            lora_targets=tuple(self.lora_targets),
        )


class BertEmbeddings(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, train=True):
        cfg = self.config
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        vocab_padded = _round_up(cfg.vocab_size, 128)
        word = self.param("word_embeddings", init, (vocab_padded, cfg.hidden_size))
        pos = self.param(
            "position_embeddings", init,
            (cfg.max_position_embeddings, cfg.hidden_size),
        )
        tok = self.param("token_type_embeddings", init, (cfg.type_vocab_size, cfg.hidden_size))

        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            if cfg.sparse_gradients:
                from ..runtime.sparse import sparse_embedding_lookup

                x = sparse_embedding_lookup(word, input_ids, cfg.mesh)
            else:
                x = word[input_ids]
            x = x + pos[None, :s, :]
            if token_type_ids is not None:
                x = x + tok[token_type_ids]
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="LayerNorm")(x)
        if train and cfg.hidden_dropout_prob > 0:
            x = nn.Dropout(cfg.hidden_dropout_prob, deterministic=False)(
                x, rng=self.make_rng("dropout")
            )
        return x, word  # word table returned for the tied MLM decoder


class BertEncoder(nn.Module):
    """Scanned stack of DeepSpeedTransformerLayers: one traced layer,
    stacked params with a leading ``layers`` axis."""

    config: BertConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, train=True):
        cfg = self.config
        if cfg.zero3_gather is not None:
            # ZeRO-3 layer-wise JIT gather (models/stack.py): same param
            # names/shapes as the nn.scan stack below, so checkpoints and
            # stage changes interchange
            from .stack import _StackedBlockParams, zero3_scan_stack

            layer_cfg = cfg.layer_config()
            p = _StackedBlockParams(
                layer_cfg, cfg.num_hidden_layers, name="layer"
            )()
            need_rng = train and (
                cfg.hidden_dropout_prob > 0
                or cfg.attention_probs_dropout_prob > 0
            )
            dropout_key = self.make_rng("dropout") if need_rng else None
            with jax.named_scope("stack_scan"):
                return zero3_scan_stack(
                    layer_cfg, p, hidden_states, cfg.zero3_gather, cfg.mesh,
                    causal=False, use_flash=cfg.use_flash, train=train,
                    dropout_key=dropout_key, attention_mask=attention_mask,
                )
        with jax.named_scope("stack_scan"):
            hidden_states, _ = nn.scan(
                lambda mdl, c, _: (mdl(c, attention_mask, train=train), None),
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                length=cfg.num_hidden_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(
                DeepSpeedTransformerLayer(
                    config=cfg.layer_config(), causal=False,
                    use_flash=cfg.use_flash, mesh=cfg.mesh, name="layer",
                ),
                hidden_states,
                None,
            )
        return hidden_states


class BertModel(nn.Module):
    config: BertConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None, train=True):
        cfg = self.config
        x, word_table = BertEmbeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, train=train
        )
        additive_mask = None
        if attention_mask is not None:
            additive_mask = jnp.where(
                attention_mask[:, None, None, :] > 0, 0.0, -1e30
            ).astype(jnp.float32)
        x = BertEncoder(cfg, name="encoder")(x, additive_mask, train=train)
        # pooler: tanh(dense(first token)), used by the NSP head
        with jax.named_scope("head_loss"):
            pooled = nn.tanh(
                nn.Dense(cfg.hidden_size, name="pooler")(x[:, 0])
            )
        return x, pooled, word_table


def cross_entropy_ignore_index(logits, labels, ignore_values=(-1, -100)):
    """Mean CE over positions whose label is not an ignore value.

    Memory note: logits stay in their compute dtype; the logsumexp runs in
    f32 but fuses into the reduction, so no [B, S, vocab] f32 buffer (or
    log-softmax copy) is ever materialized — at BERT-large bench shapes
    that's ~6 GB of HBM the naive ``log_softmax`` formulation allocates.
    """
    valid = jnp.ones(labels.shape, bool)
    for iv in ignore_values:
        valid &= labels != iv
    safe_labels = jnp.where(valid, labels, 0)
    picked = jnp.take_along_axis(
        logits, safe_labels[..., None], axis=-1
    )[..., 0].astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    z = jnp.sum(
        jnp.exp(logits.astype(jnp.float32) - m.astype(jnp.float32)[..., None]),
        axis=-1,
    )
    log_z = jnp.log(z) + m.astype(jnp.float32)
    nll = log_z - picked
    num = jnp.sum(jnp.where(valid, nll, 0.0))
    den = jnp.maximum(jnp.sum(valid), 1)
    return num / den


class BertForQuestionAnswering(nn.Module):
    """Extractive-QA head: start/end span logits over the sequence
    (reference: the vendored modeling.py BertForQuestionAnswering consumed
    by the BingBertSquad harness, tests/model/BingBertSquad/*).

    ``__call__(ids, mask, token_type_ids, start_positions, end_positions)``
    returns the scalar loss (engine contract) when positions are given,
    else ``(start_logits, end_logits)`` for inference.
    """

    config: BertConfig

    @nn.compact
    def __call__(
        self, input_ids, attention_mask=None, token_type_ids=None,
        start_positions=None, end_positions=None, train=True,
    ):
        cfg = self.config
        seq_out, _, _ = BertModel(cfg, name="bert")(
            input_ids, attention_mask, token_type_ids, train=train
        )
        logits = nn.Dense(2, name="qa_outputs")(seq_out)  # [B, S, 2]
        start_logits = logits[..., 0]
        end_logits = logits[..., 1]
        if start_positions is None or end_positions is None:
            return start_logits, end_logits
        # positions index into the sequence: CE over S "classes"
        loss = 0.5 * (
            cross_entropy_ignore_index(start_logits, start_positions)
            + cross_entropy_ignore_index(end_logits, end_positions)
        )
        return loss


class BertForPreTraining(nn.Module):
    """MLM + NSP pretraining objective; __call__ returns the scalar loss
    (the engine's model contract)."""

    config: BertConfig

    @nn.compact
    def __call__(
        self, input_ids, attention_mask=None, token_type_ids=None,
        masked_lm_labels=None, next_sentence_label=None, train=True,
    ):
        cfg = self.config
        seq_out, pooled, word_emb = BertModel(cfg, name="bert")(
            input_ids, attention_mask, token_type_ids, train=train
        )
        # MLM head: transform + decoder tied to word embeddings
        with jax.named_scope("head_loss"):
            h = nn.Dense(cfg.hidden_size, name="transform")(seq_out)
            h = nn.gelu(h, approximate=True)
            h = nn.LayerNorm(
                epsilon=cfg.layer_norm_eps, name="transform_ln")(h)
            vocab_padded = word_emb.shape[0]
            mlm_bias = self.param(
                "mlm_bias", nn.initializers.zeros, (vocab_padded,))
            logits = h @ word_emb.T + mlm_bias

            loss = jnp.float32(0.0)
            if masked_lm_labels is not None:
                loss = loss + cross_entropy_ignore_index(
                    logits, masked_lm_labels)
            if next_sentence_label is not None:
                nsp_logits = nn.Dense(2, name="nsp")(pooled)
                loss = loss + cross_entropy_ignore_index(
                    nsp_logits, next_sentence_label
                )
        return loss
