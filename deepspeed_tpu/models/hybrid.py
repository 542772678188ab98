"""A causal LM whose layers differ in kind: each layer is ONE mixer behind
a pre-RMS-norm and a residual, and a pattern string says which — ``M`` a
Mamba-2 state-space mixer (ops/ssm.py), ``E`` a latent mixture of experts
that drops no token (ops/moe.py:latent_moe_mixer), ``*`` causal
grouped-query attention (ops/transformer.py:gqa_attention_mixer); ``D`` a
Gated DeltaNet linear-attention mixer (ops/linear_attention.py), ``G`` gated
softmax attention with per-head q/k norms and partial rotary
(ops/transformer.py:gated_attention_mixer), ``X`` a mixture of SiLU-gated
experts at the model's own width behind softmax routing, with a gated shared
expert (ops/moe.py:gated_moe_mixer). A published layer that is a token mixer
THEN experts is two letters (``DXDXDXGX`` is one period of three DeltaNet
layers and one attention layer, each with its experts). No learned position
embedding (the recurrent layers carry position; ``G`` rotates), a final RMS
norm, an untied head, bias-free projections; ``norm_zero_centered`` stores
every norm's gain around 0 and applies ``1 + gain``.

``models/stack.py`` and ``nn.scan`` assume identical layers, so this stack
is a Python loop over the pattern. The parameters of each KIND are stacked
on a leading axis (``mamba_in_proj`` is [n M-layers, E, ...], ``moe_w1`` is
[n E-layers, held, L, F]): a checkpoint, a ZeRO partition spec
(runtime/zero.py shards any leaf over the data axis) and the optimizer see
a dozen-odd leaves, not a dozen-odd per layer.

Counts HELD and counts ROUTED OVER are separate fields. A chip of an
expert-parallel group holds ``n_experts_held`` experts starting at
``expert_offset`` and routes over ``n_experts_routed``; it computes its own
experts' part of the result and adds nothing for the absent chips (no
exchange is built). Head counts are the heads held here.

``HybridCausalLM(input_ids, labels)`` returns ``(loss, counters)``: the
engine trains on the loss, and the routing counters (``moe/...``, summed or
maximised over the E layers) leave the compiled window beside it through
the multi-output contract and reach the telemetry registry in
``train.finish_step``.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.cross_entropy import blocked_lm_head_loss
from ..ops.linear_attention import gated_deltanet_mixer
from ..ops.moe import gated_moe_mixer, latent_moe_mixer
from ..ops.ssm import mamba2_mixer
from ..ops.transformer import (
    gated_attention_mixer,
    gqa_attention_mixer,
    resolve_remat_policy,
    rms_norm,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attn",
         "D": "gdn", "G": "gattn", "X": "gmoe"}


@dataclasses.dataclass(unsafe_hash=True)
class HybridLMConfig:
    vocab_size: int = 512
    hidden_size: int = 64
    pattern: str = "MEM*E"
    norm_eps: float = 1e-5
    # gains stored around 0 and applied as 1 + gain (the final norm too)
    norm_zero_centered: bool = False
    initializer_range: float = 0.02
    # M: Mamba-2. heads and groups HELD here (a whole group at a time)
    mamba_heads: int = 4
    mamba_head_dim: int = 16
    mamba_groups: int = 1
    ssm_state: int = 16
    conv_kernel: int = 4
    chunk_size: int = 16
    # E: latent mixture of experts. held of routed, from expert_offset
    n_experts_held: int = 2
    n_experts_routed: int = 8
    expert_offset: int = 0
    top_k: int = 3
    routed_scaling: float = 5.0
    # force a level selection (ops/moe.py:level_selection_scores): a router
    # that no balance rule has trained sends most tokens to a few experts,
    # and one trained on noise collapses within ten steps. For measurements
    # on seeded weights; the published layer has it off
    router_force_level: bool = False
    moe_latent: int = 32
    moe_intermediate: int = 48
    moe_shared_intermediate: int = 96
    # rows per tile of the grouped expert products
    moe_tile: int = 512
    # X: gated experts at the model's width. Shares the held/routed counts,
    # top_k, router_force_level, moe_intermediate, moe_shared_intermediate
    # and moe_tile with E; has no latent, bias or scaling
    # * and G: grouped-query attention. heads HELD here
    attn_heads: int = 2
    kv_heads: int = 1
    head_dim: int = 16
    # G: lanes of each head that rotate (0: none), and the base
    rotary_lanes: int = 0
    rope_theta: float = 10000.0
    # D: Gated DeltaNet. key heads each serve value_heads / key_heads value
    # heads; the chunk of the recurrence is a power of two; conv_kernel taps
    gdn_key_heads: int = 2
    gdn_value_heads: int = 4
    gdn_key_dim: int = 16
    gdn_value_dim: int = 16
    gdn_chunk: int = 64
    # per-layer remat (jax.checkpoint around each layer, whatever its kind)
    remat: bool = False
    remat_policy: str = "nothing_saveable"
    # sequence positions per block of the head loss (ops/cross_entropy.py)
    ce_block_rows: int = 512
    mesh: object = dataclasses.field(default=None, hash=False, compare=False)

    def __post_init__(self):
        unknown = set(self.pattern) - set(KINDS)
        if unknown:
            raise ValueError(
                f"pattern {self.pattern!r}: unknown layer kinds "
                f"{sorted(unknown)}; {', '.join(KINDS)} are known")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError("mamba_heads must be a multiple of mamba_groups")
        if self.attn_heads % self.kv_heads:
            raise ValueError("attn_heads must be a multiple of kv_heads")
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                "gdn_value_heads must be a multiple of gdn_key_heads")
        if self.rotary_lanes % 2 or self.rotary_lanes > self.head_dim:
            raise ValueError("rotary_lanes must be even and within head_dim")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.n_experts_held
                <= self.n_experts_routed):
            raise ValueError("held experts must lie inside those routed over")

    def leaf_shapes(self):
        """{kind: {leaf: shape of ONE layer's slice}}."""
        e = self.hidden_size
        di = self.mamba_heads * self.mamba_head_dim
        conv = di + 2 * self.mamba_groups * self.ssm_state
        lat, f = self.moe_latent, self.moe_intermediate
        qd, kvd = self.attn_heads * self.head_dim, self.kv_heads * self.head_dim
        gqk = self.gdn_key_heads * self.gdn_key_dim
        gvz = self.gdn_value_heads * self.gdn_value_dim
        fs = self.moe_shared_intermediate
        return {
            "mamba": {
                "norm": (e,), "in_proj": (e, di + conv + self.mamba_heads),
                "conv_w": (self.conv_kernel, conv), "conv_b": (conv,),
                "dt_bias": (self.mamba_heads,), "A_log": (self.mamba_heads,),
                "D": (self.mamba_heads,), "gate_norm": (di,),
                "out_proj": (di, e),
            },
            "moe": {
                "norm": (e,), "router": (e, self.n_experts_routed),
                "router_bias": (self.n_experts_routed,),
                "down": (e, lat), "up": (lat, e),
                "w1": (self.n_experts_held, lat, f),
                "w2": (self.n_experts_held, f, lat),
                "shared_w1": (e, self.moe_shared_intermediate),
                "shared_w2": (self.moe_shared_intermediate, e),
            },
            "attn": {
                "norm": (e,), "wq": (e, qd), "wk": (e, kvd), "wv": (e, kvd),
                "wo": (qd, e),
            },
            "gdn": {
                "norm": (e,), "in_qkvz": (e, 2 * gqk + 2 * gvz),
                "in_ba": (e, 2 * self.gdn_value_heads),
                "conv_w": (self.conv_kernel, 2 * gqk + gvz),
                "dt_bias": (self.gdn_value_heads,),
                "A_log": (self.gdn_value_heads,),
                "out_norm": (self.gdn_value_dim,), "out_proj": (gvz, e),
            },
            "gattn": {
                "norm": (e,), "wq": (e, 2 * qd), "wk": (e, kvd),
                "wv": (e, kvd), "q_norm": (self.head_dim,),
                "k_norm": (self.head_dim,), "wo": (qd, e),
            },
            "gmoe": {
                "norm": (e,), "router": (e, self.n_experts_routed),
                "wg": (self.n_experts_held, e, f),
                "wu": (self.n_experts_held, e, f),
                "wd": (self.n_experts_held, f, e),
                "shared_wg": (e, fs), "shared_wu": (e, fs),
                "shared_wd": (fs, e), "shared_gate": (e, 1),
            },
        }


# leaves that start at 1 (gains, D) and at 0 (biases; the gains that
# ``norm_zero_centered`` stores around 0); A_log and dt_bias start at the
# family's usual spread, every other leaf at N(0, range)
_ONES = ("gate_norm", "out_norm", "D")
_GAINS = ("norm", "q_norm", "k_norm")
_ZEROS = ("conv_b", "router_bias")


def _leaf_init(cfg, leaf):
    if leaf in _ONES or (leaf in _GAINS and not cfg.norm_zero_centered):
        return nn.initializers.ones
    if leaf in _ZEROS or leaf in _GAINS:
        return nn.initializers.zeros
    if leaf == "A_log":
        return lambda key, shape, dtype=jnp.float32: jnp.broadcast_to(
            jnp.log(jnp.linspace(1.0, 16.0, shape[-1])), shape).astype(dtype)
    if leaf == "dt_bias":
        def init(key, shape, dtype=jnp.float32):
            step = jnp.exp(jnp.linspace(
                jnp.log(1e-3), jnp.log(1e-1), shape[-1]))
            return jnp.broadcast_to(
                step + jnp.log(-jnp.expm1(-step)), shape).astype(dtype)
        return init
    return nn.initializers.normal(stddev=cfg.initializer_range)


class HybridModel(nn.Module):
    """input_ids [B, S] -> (hidden [B, S, E] after the final norm, the
    head's table, counters)."""

    config: HybridLMConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = self.param("head", init, (cfg.vocab_size, cfg.hidden_size))
        norm_f = self.param(
            "norm_f", _leaf_init(cfg, "norm"), (cfg.hidden_size,))
        params = {}
        for kind, leaves in cfg.leaf_shapes().items():
            n = sum(KINDS[c] == kind for c in cfg.pattern)
            if n:
                params[kind] = {
                    leaf: self.param(
                        f"{kind}_{leaf}", _leaf_init(cfg, leaf),
                        (n,) + shape)
                    for leaf, shape in leaves.items()}

        mixers = {
            "mamba": lambda p, x: (mamba2_mixer(
                p, x, heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
                groups=cfg.mamba_groups, state=cfg.ssm_state,
                chunk=cfg.chunk_size, eps=cfg.norm_eps), {}),
            "moe": lambda p, x: latent_moe_mixer(
                p, x, top_k=cfg.top_k, scale=cfg.routed_scaling,
                held=cfg.n_experts_held, offset=cfg.expert_offset,
                tile=cfg.moe_tile, force_level=cfg.router_force_level),
            "attn": lambda p, x: (gqa_attention_mixer(
                p, x, heads=cfg.attn_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, mesh=cfg.mesh), {}),
            "gdn": lambda p, x: (gated_deltanet_mixer(
                p, x, key_heads=cfg.gdn_key_heads,
                value_heads=cfg.gdn_value_heads, key_dim=cfg.gdn_key_dim,
                value_dim=cfg.gdn_value_dim, chunk=cfg.gdn_chunk,
                eps=cfg.norm_eps, mesh=cfg.mesh), {}),
            "gattn": lambda p, x: (gated_attention_mixer(
                p, x, heads=cfg.attn_heads, kv_heads=cfg.kv_heads,
                head_dim=cfg.head_dim, rotary_lanes=cfg.rotary_lanes,
                rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
                mesh=cfg.mesh), {}),
            "gmoe": lambda p, x: gated_moe_mixer(
                p, x, top_k=cfg.top_k, held=cfg.n_experts_held,
                offset=cfg.expert_offset, tile=cfg.moe_tile,
                force_level=cfg.router_force_level),
        }

        def layer(kind):
            def apply(p, x):
                out, counters = mixers[kind](p, rms_norm(
                    x, p["norm"], cfg.norm_eps, cfg.norm_zero_centered))
                return x + out.astype(x.dtype), counters

            if cfg.remat:
                return jax.checkpoint(
                    apply, policy=resolve_remat_policy(cfg.remat_policy))
            return apply

        x = embed[input_ids]
        seen = dict.fromkeys(params, 0)
        per_layer = []
        for c in cfg.pattern:
            kind = KINDS[c]
            p = {k: v[seen[kind]] for k, v in params[kind].items()}
            seen[kind] += 1
            x, counters = layer(kind)(p, x)
            if counters:
                per_layer.append(counters)
        counters = {
            # the registry's rule (telemetry/manager.py): max_* keep the maximum
            name: (jnp.max if name.rsplit("/", 1)[-1].startswith("max_")
                   else jnp.sum)(
                jnp.stack([c[name] for c in per_layer]))
            for name in (per_layer[0] if per_layer else {})}
        return rms_norm(
            x, norm_f, cfg.norm_eps, cfg.norm_zero_centered), head, counters


class HybridCausalLM(nn.Module):
    """``__call__(input_ids, labels) -> (loss, counters)``: next-token loss
    (the shift happens inside) through the blocked head loss, and the
    routing counters of this micro-step. ``labels=None`` gives logits."""

    config: HybridLMConfig

    @nn.compact
    def __call__(self, input_ids, labels=None):
        x, head, counters = HybridModel(self.config, name="model")(input_ids)
        if labels is None:
            return x @ head.T
        loss = blocked_lm_head_loss(
            x[:, :-1], head, labels[:, 1:],
            block_rows=self.config.ce_block_rows)
        return (loss, counters) if counters else loss
