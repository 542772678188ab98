"""GPT-2 model family (the Megatron-GPT2 workload analog).

The reference drove GPT-2 through the external Megatron-LM example with an
``mpu`` hook for tensor parallelism (reference: tests/model/Megatron_GPT2/*,
docs/_tutorials/megatron.md). Here the model is in-tree, built on the same
DeepSpeedTransformerLayer (causal mode), with Megatron-style tensor-parallel
partition specs published per-parameter (``partition_specs``) so the engine
shards the qkv/mlp projections over the mesh's ``model`` axis — the
column-/row-parallel split of Megatron expressed as PartitionSpecs instead
of hand-written all-reduces.

Sizes follow the reference's perf-test configs
(tests/model/Megatron_GPT2/run_perf_test.py:18-60): gpt2_1_5b = 48L/1600h/
25 heads/seq1024, gpt2_4b = 64L/2304h, gpt2_8b = 72L/3072h.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config.constants import DATA_AXIS, MODEL_AXIS, SEQUENCE_AXIS
from ..ops.transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer
from .bert import cross_entropy_ignore_index, _round_up
from .stack import _StackedBlockParams, zero3_scan_stack


@dataclasses.dataclass(unsafe_hash=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    use_flash: bool = True
    remat: bool = False
    remat_policy: str = "full"
    # Pipeline parallelism (beyond the reference, which has no pipeline
    # engine): split the layer stack into this many stages over the mesh's
    # ``pipe`` axis and run the SPMD GPipe schedule
    # (parallel/pipeline.py). n_layer must divide evenly.
    pipeline_stages: int = 1
    # microbatches per forward through the pipeline (bubble fraction is
    # (P-1)/(M+P-1)); 0 = default of 4*stages when the batch divides, else
    # 2*stages (4*stages keeps the bubble under ~20% — parallel/pipeline.py).
    pipeline_microbatches: int = 0
    # Mixture-of-Experts (beyond the reference): >0 replaces every layer's
    # FFN with an expert-parallel MoE of this many experts (ops/moe.py);
    # experts shard over the mesh's data axis, router aux losses join the
    # objective and surface via the multi-output contract.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 1e-2
    # Blocked LM-head cross-entropy (ops/cross_entropy.py): stream the
    # [B*S, vocab] logits through the tied head in ce_block_rows chunks so
    # neither the bf16 logits plane nor its fp32 softmax copy is ever
    # materialized (the biggest GPT-2 transient). 0 disables (naive path).
    ce_block_rows: int = 512
    # Device mesh forwarded to the transformer layers: enables the
    # sequence-parallel (ring/Ulysses) path when the mesh has a >1
    # ``sequence`` axis, and per-shard flash via shard_map under dp/mp.
    mesh: object = dataclasses.field(default=None, hash=False, compare=False)
    # Route wte gradients through the CSR sparse all-reduce
    # (runtime/sparse.py; reference deepspeed_light.py:177-184). NOTE: the
    # tied lm head's cotangent is dense, so the traffic win only
    # materializes for untied tables (see runtime/sparse.py caveat).
    sparse_gradients: bool = dataclasses.field(
        default=False, hash=False, compare=False
    )
    # LoRA adapters (deepspeed_tpu/adapters/, docs/adapters.md): rank-r
    # A/B pairs beside the block's projection matrices. 0 = off — the
    # forward is then bitwise-identical to the adapter-free model.
    # Usually armed by the engine's "adapters" config block rather than
    # set by hand (runtime/engine.py injects these like it injects mesh).
    lora_rank: int = 0
    lora_alpha: float = 0.0  # 0 => rank (scaling 1.0)
    lora_targets: tuple = ()  # () => every LORA_TARGETS matrix
    # ZeRO-3 layer-wise JIT gather (models/stack.py, docs/performance.md
    # "ZeRO-3 & collective overlap"): armed by the engine at
    # zero_optimization.stage 3 (runtime/engine.py:_arm_zero3_gather),
    # never set by hand — a dict {"specs", "stacked_specs", "block"}
    # describing the gather seam. None = the plain nn.scan stack.
    zero3_gather: object = dataclasses.field(
        default=None, hash=False, compare=False
    )

    @property
    def vocab_padded(self):
        return _round_up(self.vocab_size, 128)

    @staticmethod
    def small(**kw):
        return GPT2Config(**kw)

    @staticmethod
    def medium(**kw):
        return GPT2Config(n_embd=1024, n_layer=24, n_head=16, **kw)

    @staticmethod
    def large(**kw):
        return GPT2Config(n_embd=1280, n_layer=36, n_head=20, **kw)

    @staticmethod
    def xl_1_5b(**kw):
        # the reference perf harness's 1.5B: 48L/1600h (run_perf_test.py:18-35)
        return GPT2Config(n_embd=1600, n_layer=48, n_head=25, **kw)

    @staticmethod
    def gpt2_4b(**kw):
        return GPT2Config(n_embd=2304, n_layer=64, n_head=24, **kw)

    @staticmethod
    def gpt2_8b(**kw):
        return GPT2Config(n_embd=3072, n_layer=72, n_head=24, **kw)

    def layer_config(self):
        return DeepSpeedTransformerConfig(
            hidden_size=self.n_embd,
            heads=self.n_head,
            intermediate_size=4 * self.n_embd,
            attn_dropout_ratio=self.dropout,
            hidden_dropout_ratio=self.dropout,
            num_hidden_layers=self.n_layer,
            initializer_range=self.initializer_range,
            pre_layer_norm=True,  # GPT-2 is pre-LN
            layer_norm_eps=self.layer_norm_eps,
            normalize_invertible=self.remat,  # remat flag reuse
            remat_policy=self.remat_policy,
            lora_rank=self.lora_rank,
            lora_alpha=self.lora_alpha,
            lora_targets=tuple(self.lora_targets),
        )


class GPT2Model(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, train: bool = True):
        cfg = self.config
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        wte = self.param("wte", init, (cfg.vocab_padded, cfg.n_embd))
        wpe = self.param("wpe", init, (cfg.n_positions, cfg.n_embd))

        s = input_ids.shape[1]
        with jax.named_scope("embed"):
            if cfg.sparse_gradients:
                from ..runtime.sparse import sparse_embedding_lookup

                x = sparse_embedding_lookup(
                    wte, input_ids, cfg.mesh) + wpe[None, :s, :]
            else:
                x = wte[input_ids] + wpe[None, :s, :]
        if train and cfg.dropout > 0:
            x = nn.Dropout(cfg.dropout, deterministic=False)(
                x, rng=self.make_rng("dropout")
            )

        moe_aux = None
        with jax.named_scope("stack_scan"):
            if cfg.pipeline_stages > 1:
                if cfg.moe_experts > 0:
                    raise ValueError(
                        "pipeline_stages > 1 with moe_experts > 0 is not "
                        "supported yet; pick one of pp or ep for the stack"
                    )
                x = self._pipelined_stack(x, train)
            elif cfg.moe_experts > 0:
                from ..ops.moe import DeepSpeedMoETransformerLayer, MoEConfig

                x, aux_per_layer = nn.scan(
                    lambda mdl, c, _: mdl(c, None, train=train),
                    variable_axes={"params": 0},
                    split_rngs={"params": True, "dropout": True},
                    length=cfg.n_layer,
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(
                    DeepSpeedMoETransformerLayer(
                        config=cfg.layer_config(),
                        moe=MoEConfig(
                            n_experts=cfg.moe_experts,
                            top_k=cfg.moe_top_k,
                            capacity_factor=cfg.moe_capacity_factor,
                            aux_loss_weight=cfg.moe_aux_loss_weight,
                        ),
                        causal=True, use_flash=cfg.use_flash, mesh=cfg.mesh,
                        name="h",
                    ),
                    x,
                    None,
                )
                moe_aux = jnp.sum(aux_per_layer)
            elif cfg.zero3_gather is not None:
                x = self._zero3_stack(x, train)
            else:
                x, _ = nn.scan(
                    lambda mdl, c, _: (mdl(c, None, train=train), None),
                    variable_axes={"params": 0},
                    split_rngs={"params": True, "dropout": True},
                    length=cfg.n_layer,
                    metadata_params={nn.PARTITION_NAME: "layers"},
                )(
                    DeepSpeedTransformerLayer(
                        config=cfg.layer_config(), causal=True,
                        use_flash=cfg.use_flash, mesh=cfg.mesh, name="h",
                    ),
                    x,
                    None,
                )
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, name="ln_f")(x)
        return (x, wte) if moe_aux is None else (x, wte, moe_aux)

    def _zero3_stack(self, x, train):
        """Run the layer stack with ZeRO-3 layer-wise JIT gather
        (models/stack.py): stacked params stay dp-sharded persistently;
        each scan iteration all-gathers one gather-block of layers just
        in time and frees them after use (backward re-gathers under the
        remat policy). Same param names/shapes as the nn.scan stack, so
        checkpoints and stage changes interchange."""
        cfg = self.config
        layer_cfg = cfg.layer_config()
        p = _StackedBlockParams(layer_cfg, cfg.n_layer, name="h")()
        need_rng = train and cfg.dropout > 0
        dropout_key = self.make_rng("dropout") if need_rng else None
        return zero3_scan_stack(
            layer_cfg, p, x, cfg.zero3_gather, cfg.mesh,
            causal=True, use_flash=cfg.use_flash, train=train,
            dropout_key=dropout_key,
        )

    def _pipelined_stack(self, x, train):
        """Run the layer stack as an SPMD GPipe pipeline over the mesh's
        ``pipe`` axis (parallel/pipeline.py). Embeddings and the LM head
        stay outside (pipe-replicated under GSPMD)."""
        from ..config import constants as C
        from ..ops.transformer import transformer_block_apply
        from ..parallel.pipeline import gpipe_spmd

        cfg = self.config
        n_stages = cfg.pipeline_stages
        layer_cfg = cfg.layer_config()
        if cfg.mesh is None or dict(cfg.mesh.shape).get(C.PIPELINE_AXIS, 1) != n_stages:
            raise ValueError(
                f"pipeline_stages={n_stages} needs a mesh whose "
                f"'{C.PIPELINE_AXIS}' axis has that size (got "
                f"{None if cfg.mesh is None else dict(cfg.mesh.shape)})"
            )
        if dict(cfg.mesh.shape).get(C.SEQUENCE_AXIS, 1) > 1:
            # attention inside the pipeline runs with mesh=None — a >1
            # sequence axis would be silently ignored (replicated work),
            # so reject the combination instead
            raise ValueError(
                "pipeline_stages > 1 does not compose with a >1 sequence "
                "axis yet; use sp or pp for the stack, not both"
            )
        if cfg.n_layer % n_stages:
            raise ValueError(
                f"n_layer={cfg.n_layer} must divide into "
                f"pipeline_stages={n_stages}"
            )
        layers_per_stage = cfg.n_layer // n_stages
        b, s, H = x.shape
        n_micro = cfg.pipeline_microbatches
        if not n_micro:
            # prefer 4*stages (bubble < ~20%, per parallel/pipeline.py);
            # fall back to 2*stages when the batch doesn't divide
            n_micro = 4 * n_stages if b % (4 * n_stages) == 0 else 2 * n_stages
        if b % n_micro:
            raise ValueError(
                f"batch {b} must divide into pipeline microbatches {n_micro}"
            )

        p = _StackedBlockParams(layer_cfg, cfg.n_layer, name="h")()
        stacked = jax.tree_util.tree_map(
            lambda a: a.reshape(n_stages, layers_per_stage, *a.shape[1:]), p
        )
        need_rng = train and cfg.dropout > 0
        if need_rng:
            seed = jax.random.randint(
                self.make_rng("dropout"), (), 0, jnp.iinfo(jnp.int32).max
            )
        else:
            seed = jnp.int32(0)

        x_mb = x.reshape(n_micro, b // n_micro, s, H)
        dp = dict(cfg.mesh.shape).get(C.DATA_AXIS, 1)
        if (b // n_micro) % dp == 0:
            # keep each microbatch data-sharded (auto axis inside the
            # pipeline's shard_map); smaller microbatches are left to GSPMD
            x_mb = jax.lax.with_sharding_constraint(
                x_mb,
                jax.sharding.NamedSharding(
                    cfg.mesh, P(None, C.DATA_AXIS, None, None)
                ),
            )

        def stage_fn(local_p, h, t, extras):
            stage = jax.lax.axis_index(C.PIPELINE_AXIS)
            mb_idx = t - stage  # which microbatch this stage sees this tick

            def one_layer(h, sl):
                layer_p, li = sl
                if need_rng:
                    key = jax.random.PRNGKey(extras["seed"])
                    key = jax.random.fold_in(key, mb_idx)
                    key = jax.random.fold_in(key, stage * layers_per_stage + li)
                else:
                    key = None
                y = transformer_block_apply(
                    layer_cfg, layer_p, h, None,
                    causal=True, use_flash=cfg.use_flash, mesh=None,
                    train=train, dropout_rng=key,
                )
                return y, None

            h, _ = jax.lax.scan(
                one_layer, h, (local_p, jnp.arange(layers_per_stage))
            )
            return h

        out = gpipe_spmd(
            stage_fn, stacked, x_mb, cfg.mesh,
            extras={"seed": seed},
        )
        return out.reshape(b, s, H)


class GPT2LMHeadModel(nn.Module):
    """__call__(input_ids, labels) -> scalar next-token LM loss
    (labels typically input_ids; the shift happens inside).

    With ``moe_experts > 0`` the return is the multi-output tuple
    ``(lm_loss + aux, lm_loss, aux)`` — the engine trains on element 0 and
    the router load-balancing loss stays observable via ``last_aux``."""

    config: GPT2Config

    @nn.compact
    def __call__(self, input_ids, labels=None, train: bool = True):
        out = GPT2Model(self.config, name="transformer")(input_ids, train=train)
        x, wte = out[0], out[1]
        moe_aux = out[2] if len(out) == 3 else None
        if labels is None:
            return x @ wte.T  # tied lm head
        # next-token prediction: logits[:, :-1] vs labels[:, 1:]
        with jax.named_scope("head_loss"):
            if self.config.ce_block_rows > 0:
                from ..ops.cross_entropy import blocked_lm_head_loss

                lm_loss = blocked_lm_head_loss(
                    x[:, :-1], wte, labels[:, 1:],
                    block_rows=self.config.ce_block_rows,
                )
            else:
                lm_loss = cross_entropy_ignore_index(
                    x[:, :-1] @ wte.T, labels[:, 1:]
                )
        if moe_aux is None:
            return lm_loss
        return lm_loss + moe_aux, lm_loss, moe_aux


def kv_cache_partition_specs(mp_axis=MODEL_AXIS):
    """PartitionSpec for a decode KV cache laid out
    ``[layers, slots, heads, max_len, head_dim]`` (inference/decode.py):
    heads shard over the mesh's ``model`` axis — the same Megatron head
    split ``partition_specs`` applies to the qkv projections that produce
    them, so prefill/decode write each head's cache rows on the chip that
    owns that head's weights. Layers/slots/positions stay unsharded
    (slots join and leave every step; resharding them would thrash)."""
    return P(None, None, mp_axis, None, None)


def kv_pool_partition_specs(mp_axis=MODEL_AXIS):
    """PartitionSpec for the block-paged decode pool laid out ``[layers,
    num_blocks, block_size, heads, head_dim]`` (inference/decode.py:
    KVPool): same Megatron head split as :func:`kv_cache_partition_specs`
    — each chip holds its own heads' rows of EVERY page, so block-table
    gathers and the single-token scatters stay chip-local along the
    sharded axis. Pages/offsets stay unsharded: the block table reassigns
    them every admission and eviction, and resharding pages would thrash
    exactly the way resharding slots would."""
    return P(None, None, None, mp_axis, None)


def adapter_pool_partition_specs(targets=None, mp_axis=MODEL_AXIS):
    """PartitionSpecs for the serving-side in-HBM adapter pool
    (inference/engine.py): ``{target: (A, B)}`` with A laid out
    ``[layers, n_adapters, in, rank]`` and B ``[layers, n_adapters,
    rank, out]``. The factor carrying the base matrix's Megatron-sharded
    dim shards on the same ``model`` axis the base weights use
    (column-parallel => B's output dim; row-parallel => A's input dim) —
    each chip holds its own shard of EVERY adapter, so the per-slot
    gathers along the adapter axis stay chip-local along the sharded
    dim. Layers/adapters/rank replicate (adapters load and evict at
    runtime; resharding them would thrash exactly like resharding KV
    slots would)."""
    from ..ops.transformer import (
        LORA_TARGET_PARALLEL,
        resolve_lora_targets,
    )

    out = {}
    for t in resolve_lora_targets(targets):
        if LORA_TARGET_PARALLEL[t] == "row":
            out[t] = (P(None, None, mp_axis, None), P())
        else:  # column-parallel: B carries the sharded output dim
            out[t] = (P(), P(None, None, None, mp_axis))
    return out


def partition_specs(params, mp_axis=MODEL_AXIS, pipeline=False):
    """Megatron-style tensor-parallel PartitionSpecs for a GPT2LMHeadModel
    param tree (same structure, PartitionSpec leaves).

    Column-parallel (shard output dim): attn qkv, mlp up (inter_w).
    Row-parallel (shard input dim): attn out (attn_ow), mlp down (output_w).
    Embeddings: shard the vocab dim. Scanned layer params carry a leading
    ``layers`` axis, so dims below shift by one.

    With ``pipeline=True`` the leading ``layers`` axis of the stacked layer
    params shards over the mesh's ``pipe`` axis: layer L = stages * L/stage
    splits into contiguous per-stage blocks, exactly the [P, L/P, ...]
    reshape the pipelined stack performs (models/gpt2.py:_pipelined_stack),
    so each pipe rank stores only its own stage's weights.
    """
    from ..config.constants import PIPELINE_AXIS

    lead = PIPELINE_AXIS if pipeline else None

    def spec_for(path, leaf):
        names = [getattr(k, "key", None) for k in path]
        nd = leaf.ndim
        if any(n and n.startswith(("expert_", "gate_")) for n in names):
            # MoE subtree: experts shard over the data axis (ops/moe.py)
            from ..ops.moe import moe_leaf_spec

            return moe_leaf_spec(names, leaf)
        lora_name = next(
            (n for n in names if n and "_lora_" in n), None
        )
        if lora_name is not None:
            # LoRA A/B ride the SAME model axis as their base matrix
            # (docs/adapters.md): column-parallel bases (qkv, inter_w)
            # shard their output dim — carried by B [r, out]; row-parallel
            # bases (attn_ow, output_w) shard their input dim — carried by
            # A [in, r]. The rank dim never shards (tiny, rarely divides
            # the axis); the other factor replicates.
            from ..ops.transformer import LORA_TARGET_PARALLEL

            target, ab = lora_name.rsplit("_lora_", 1)
            parallel = LORA_TARGET_PARALLEL.get(target)
            head = (lead,) if nd == 3 else ()  # stacked layers axis
            if parallel == "column" and ab == "b":
                return P(*head, None, mp_axis)
            if parallel == "row" and ab == "a":
                return P(*head, mp_axis, None)
            return P(*head, None, None)
        if "wte" in names:
            return P(mp_axis, None)
        if "wpe" in names:
            return P()
        # scanned transformer params: leading 'layers' dim
        if "attn_qkvw" in names or "inter_w" in names:
            return P(lead, None, mp_axis) if nd == 3 else P(None, mp_axis)
        if "attn_qkvb" in names or "inter_b" in names:
            return P(lead, mp_axis) if nd == 2 else P(mp_axis)
        if "attn_ow" in names or "output_w" in names:
            return P(lead, mp_axis, None) if nd == 3 else P(mp_axis, None)
        if nd >= 1 and any(
            n in names
            for n in ("attn_ob", "attn_nw", "attn_nb", "output_b",
                      "norm_w", "norm_b")
        ):
            # stacked per-layer vectors: shard the layers dim over pipe too
            return P(lead, None) if nd == 2 else P(lead)
        return P()  # ln_f etc.: replicated

    return jax.tree_util.tree_map_with_path(spec_for, params)
