"""A causal LM whose layers differ in kind: each layer is ONE mixer behind
a pre-RMS-norm and a residual, and a pattern string says which — ``M`` a
Mamba-2 state-space mixer (ops/ssm.py), ``E`` a latent mixture of experts
that drops no token (ops/moe.py:latent_moe_mixer), ``D`` a Gated DeltaNet
linear-attention mixer (ops/linear_attention.py), ``X`` a mixture of
SiLU-gated experts at the model's own width behind softmax routing, with a
gated shared expert (ops/moe.py:gated_moe_mixer), ``S`` the experts of ``X``
with no shared expert beside them, ``U`` the experts of ``X`` with an UNGATED
shared expert and the routed sum times ``routed_scaling``, ``B`` the experts
of ``U`` chosen by SIGMOID scores plus a selection bias (a ``router_bias``
leaf that chooses, weighs nothing and takes no gradient), ``F`` a dense
SiLU-gated FFN (ops/transformer.py:swiglu_ffn_mixer); and seven letters of
attention, all ops/transformer.py:attention_mixer, each under the spec that
``attention_spec`` makes of the configuration: ``*`` causal grouped-query
attention; ``G`` gated softmax attention with zero-centred per-head q/k norms
and partial rotary; ``R`` causal multi-head attention with rotary on every
lane; ``A`` grouped-query attention with per-head q/k norms and rotary on
every lane; ``H`` and ``W`` grouped-query attention gated per head with no
q/k norm, ``H`` seeing the whole causal row with YaRN rotary on
``rotary_lanes`` lanes, ``W`` a sliding window of ``window`` keys (the band
inside the flash kernels) with plain rotary on every lane, each with its own
head count on the same kv heads; ``L`` multi-head LATENT attention: q and k
out of two low-rank chains with a norm in the middle of each, ``mla_nope_dim
+ mla_rope_dim`` lanes a head of which the last ``mla_rope_dim`` rotate (the
key's rotated part is ONE vector shared by the heads), v of ``mla_v_dim``
lanes, the flash kernels at those unequal widths. A published layer that is
a token mixer THEN experts or an FFN is two letters (``DXDXDXGX`` is one
period of three DeltaNet layers and one attention layer, each with its
experts; ``RF`` and ``AS`` are one decoder layer each; ``HF`` then ``WUWUWUHU`` repeated is a
leading dense layer and periods of three windowed layers and a full one;
``LF`` then ``LB`` repeated is a leading dense layer and sparse ones). No
learned position embedding (the recurrent layers carry position; ``G``,
``R``, ``A``, ``H``, ``W`` and ``L`` rotate), a
final RMS norm, an untied head, bias-free projections; ``norm_zero_centered``
stores every norm's gain around 0 and applies ``1 + gain``; ``post_norm``
gives ``R`` and ``F`` a second gain AFTER the mixer (``x + norm_b(mixer(
norm_a(x)))``, a sandwich norm).

``models/stack.py`` and ``nn.scan`` assume identical layers, so this stack
is a Python loop over the pattern's PERIOD (the shortest string whose
repetition the pattern is: all of ``DXDXDXGX``, the ``RF`` of ``RFRFRF``),
and ``lax.scan`` walks the repetitions where there are several: the compiled
program holds one period's bodies however deep the stack. A pattern that is
no pure repetition is a PREFIX, the longest repeated run and a TAIL
(``stack_plan``: ``HF`` + 11 x ``WUWUWUHU`` + ``WUWUWU``): the scan walks the
run and the layers before and after it unroll, one period's bodies plus
theirs. The parameters of
each KIND are stacked on a leading axis (``mamba_in_proj`` is [n M-layers, E,
...], ``moe_w1`` is [n E-layers, held, L, F]): a checkpoint, a ZeRO partition
spec (runtime/zero.py shards any leaf over the data axis) and the optimizer
see a dozen-odd leaves, not a dozen-odd per layer.

``passes`` > 1 is a LOOPED stack: the whole pattern and the final norm run
``passes`` times over the same parameters (a second ``lax.scan``, over the
passes; the parameters are constants of its body, so the backward pass sums
the passes' gradients of each leaf in the leaf's own type), each pass's
normed state feeding the next. Every pass's state is read by the head, and
an exit gate ``lambda_t = sigmoid(h_t . gate_w + gate_b)`` turns the passes
into a distribution over where a position leaves (ops/cross_entropy.py:
exit_log_probs); the loss is the expected next-token loss under it less
``exit_entropy_weight`` times its entropy. ``labels=None`` gives the last
pass's logits (inference here never leaves early).

Counts HELD and counts ROUTED OVER are separate fields. A chip of an
expert-parallel group holds ``n_experts_held`` experts starting at
``expert_offset`` and routes over ``n_experts_routed``; it computes its own
experts' part of the result and adds nothing for the absent chips (no
exchange is built). Head counts are the heads held here.

``objective="block_diffusion"`` trains a stack of ``A`` mixers (and any
position-wise kinds) by block diffusion with an absorbing mask:
``HybridCausalLM(noisy_ids, clean_ids, loss_weights)``, each ``[B, L]``. The
stack runs on the row ``[noisy ; clean]`` of ``2 L`` positions, both halves
with position ids ``0..L-1``, under the block-diffusion mask of
``diffusion_block`` positions a block inside the flash kernels
(ops/attention.py); the head reads the noisy half only and position ``i``'s
logits are scored against ``clean_ids[i]`` (no shift): the loss is ``sum_i
loss_weights[i] nll_i / (B L)``. The caller draws the noise: ``loss_weights``
is ``1 / t`` of a position's block where the position was replaced by the
mask id, else 0. It adds the ``diffusion/...`` counters.

``mtp_depth`` > 0 adds MULTI-TOKEN PREDICTION: module k (1 .. depth) reads
the state ``z`` that the head reads (the main stack's after its final norm,
then module k - 1's) and the TABLE a second time, at ids shifted by k: ``u_i
= mtp_proj [rms(embed[t_{i+k}]; mtp_embed_norm) ; rms(z_i; mtp_state_norm)]``
([2 E] -> E), one layer of the stack's repeated unit on leaves of its own
(``mtp_<kind>_<leaf>``, stacked over the modules as the stack's are over its
layers: a checkpoint, a ZeRO spec and the optimizer see more leaves of the
same form), ``rms(.; mtp_norm)`` and the SHARED head, scored against
``t_{i+k+1}``. The loss is the main one plus ``mtp_loss_weight`` times the
mean over the modules of each module's mean nll; the table's and the head's
gradients are the sums of both uses. The module runs over all S positions of
a row (the ids past the row's end are the row's first: those positions'
targets do not exist and are not scored, and under causal mixers nothing
they hold reaches a scored position), so the kernels see the stack's own
shapes. It adds ``mtp/depth`` and ``mtp/loss`` (the modules' own mean nll,
unweighted). ``labels=None`` gives the main logits only.

``HybridCausalLM(input_ids, labels)`` returns ``(loss, counters)``: the
engine trains on the loss, and the routing counters (``moe/...``, summed or
maximised over the E layers) leave the compiled window beside it through
the multi-output contract and reach the telemetry registry in
``train.finish_step``; a looped stack adds ``loop/...`` (docs/hybrid.md).
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.cross_entropy import (
    blocked_lm_head_loss,
    exit_log_probs,
    weighted_lm_head_loss,
)
from ..ops.attention import window_visited_share
from ..ops.linear_attention import gated_deltanet_mixer
from ..ops.moe import gated_moe_mixer, latent_moe_mixer
from ..ops.ssm import mamba2_mixer
from ..ops.transformer import (
    AttentionSpec,
    attention_mixer,
    resolve_remat_policy,
    rms_norm,
    rotary_frequencies,
    swiglu_ffn_mixer,
    yarn_frequencies,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attn",
         "D": "gdn", "G": "gattn", "X": "gmoe",
         "R": "rattn", "F": "ffn", "A": "qattn", "S": "smoe",
         "H": "hattn", "W": "wattn", "U": "umoe",
         "L": "lattn", "B": "bmoe"}
# the token mixers that are causal by construction: not for block diffusion
CAUSAL_ONLY = "M*DGRHWL"
OBJECTIVES = ("next_token", "block_diffusion")


def period(pattern):
    """``(unit, repetitions)``: the shortest string whose repetition
    ``pattern`` is."""
    n = len(pattern)
    size = next(k for k in range(1, n + 1)
                if n % k == 0 and pattern[:k] * (n // k) == pattern)
    return pattern[:size], n // size


# A scan stacks its bodies' residuals and slices them back (4% of the SDAR
# window, 5% of the Ouro one: ``stack_scan_ms.train``, PERF.md). Inside a
# longer pattern a run is worth one only where it takes at least this share
# of the layers' bodies out of the program (the published window/full stack
# loses 80 of 96; ``MEMEMEMEM*E`` would lose 6 of 11 and ``HFWUWUWUHU`` 4 of
# 10: both unroll). A pattern that is nothing but a repetition scans from
# two repetitions, as it always has.
SCAN_MIN_SAVING = 2 / 3


def stack_plan(pattern):
    """``(prefix, unit, repetitions, tail)`` with ``pattern == prefix + unit
    * repetitions + tail``: how the stack walks the pattern. A pure
    repetition is ``period``'s, with no prefix and no tail. Else the repeated
    run whose scan takes the most bodies out of the program (``len(unit) *
    (repetitions - 1)``, at least ``SCAN_MIN_SAVING`` of the pattern), of the
    shortest unit and then the earliest start where several take as many,
    between the layers before and after it; a pattern with no such run is
    its own unit, once."""
    unit, repetitions = period(pattern)
    best, n = ("", unit, repetitions, ""), len(pattern)
    saved = n * SCAN_MIN_SAVING - 1e-9
    for size in range(1, n // 2 + 1) if repetitions == 1 else ():
        for start in range(n - 2 * size + 1):
            unit = pattern[start:start + size]
            reps = 1
            while pattern.startswith(unit, start + reps * size):
                reps += 1
            if size * (reps - 1) > saved:
                saved = size * (reps - 1)
                best = (pattern[:start], unit, reps,
                        pattern[start + size * reps:])
    return best


def merge_counters(found, axis=None):
    """One value a name out of several layers' (or passes') counters by the
    registry's rule (telemetry/manager.py): names whose last part starts
    with ``max_`` keep the maximum, the others add up. ``found`` is a list
    of dicts (a name need not be in each: layers of different kinds count
    different things), or with ``axis`` one dict of stacked values."""
    if axis is None:
        found = {name: jnp.stack([c[name] for c in found if name in c])
                 for name in dict.fromkeys(n for c in found for n in c)}
    return {
        name: (jnp.max if name.rsplit("/", 1)[-1].startswith("max_")
               else jnp.sum)(value, axis=axis)
        for name, value in found.items()}


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
    # and moe_tile with E; has no latent, bias or scaling. S shares X's
    # fields and has no shared expert; U shares them too, has no gate on its
    # shared expert, and scales the routed sum by routed_scaling; B is U
    # under E's routing: sigmoid scores, a selection bias, routed_scaling
    # in the weights
    # *, G, A, H and L: heads HELD here; *, G, A, H and W: grouped-query
    # attention on kv_heads. R has attn_heads kv heads too; R and A rotate
    # all head_dim lanes
    attn_heads: int = 2
    kv_heads: int = 1
    head_dim: int = 16
    # G and H: lanes of each head that rotate (0: none); G, R, A, H: the base
    rotary_lanes: int = 0
    rope_theta: float = 10000.0
    # H: YaRN over rotary_lanes (yarn_factor 1: plain frequencies); cosine
    # and sine times rotary_attention_factor
    yarn_factor: float = 1.0
    yarn_original_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    rotary_attention_factor: float = 1.0
    # W: keys a query sees, itself among them; its own head count on the
    # same kv heads (0: attn_heads) and its own base, on every lane
    window: int = 0
    window_attn_heads: int = 0
    window_rope_theta: float = 10000.0
    # L: latent attention on attn_heads heads. The ranks of q's and of k and
    # v's low-rank chains; a head's unrotated and rotated q/k lanes (the
    # kernels' q/k width is their sum) and its v lanes; rope_theta is the base
    mla_q_rank: int = 24
    mla_kv_rank: int = 16
    mla_nope_dim: int = 16
    mla_rope_dim: int = 8
    mla_v_dim: int = 16
    # F: the dense gated FFN's width
    ffn_intermediate: int = 96
    # R and F: a second gain, after the mixer
    post_norm: bool = False
    # the whole stack and the final norm run this many times over the same
    # parameters; above 1 an exit gate weights the passes' losses
    passes: int = 1
    exit_entropy_weight: float = 0.1
    # multi-token-prediction modules after the final norm (0: none), each one
    # layer of the stack's repeated unit (the ``LB`` of ``LF`` + 5 x ``LB``) on
    # leaves of its own; their mean nll joins the loss times mtp_loss_weight
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.3
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
    # "block_diffusion": rows are [noisy ; clean] under the block-diffusion
    # mask of diffusion_block positions a block (a power of two)
    objective: str = "next_token"
    diffusion_block: int = 4
    mesh: object = dataclasses.field(default=None, hash=False, compare=False)

    def __post_init__(self):
        unknown = set(self.pattern) - set(KINDS)
        if unknown:
            raise ValueError(
                f"pattern {self.pattern!r}: unknown layer kinds "
                f"{sorted(unknown)}; {', '.join(KINDS)} are known")
        if self.mamba_heads % self.mamba_groups:
            raise ValueError("mamba_heads must be a multiple of mamba_groups")
        if self.attn_heads % self.kv_heads \
                or self.window_attn_heads % self.kv_heads:
            raise ValueError(
                "attn_heads and window_attn_heads must be multiples of "
                "kv_heads")
        if "W" in self.pattern and self.window < 1:
            raise ValueError("W layers need a window of at least one key")
        if "H" in self.pattern and self.yarn_factor != 1.0 \
                and self.yarn_original_positions < 1:
            raise ValueError("YaRN needs yarn_original_positions")
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError(
                "gdn_value_heads must be a multiple of gdn_key_heads")
        if self.rotary_lanes % 2 or self.rotary_lanes > self.head_dim:
            raise ValueError("rotary_lanes must be even and within head_dim")
        if "R" in self.pattern and self.head_dim % 2:
            raise ValueError("R rotates lane pairs: head_dim must be even")
        if self.passes < 1:
            raise ValueError("passes must be at least 1")
        if "L" in self.pattern and self.mla_rope_dim % 2:
            raise ValueError("L rotates lane pairs: mla_rope_dim must be even")
        if self.mtp_depth:
            if self.mtp_depth < 0:
                raise ValueError(f"mtp_depth {self.mtp_depth}")
            if self.passes > 1 or self.objective != "next_token":
                raise ValueError(
                    "multi-token prediction is built for one pass of the "
                    "next-token objective")
        if self.objective not in OBJECTIVES:
            raise ValueError(
                f"objective {self.objective!r}: {', '.join(OBJECTIVES)} "
                "are known")
        if self.objective == "block_diffusion":
            causal = sorted(set(self.pattern) & set(CAUSAL_ONLY))
            if causal or self.passes > 1:
                raise ValueError(
                    "block diffusion needs token mixers that take its mask "
                    f"(A) and one pass; the pattern has {causal}, passes "
                    f"{self.passes}")
        if not (0 <= self.expert_offset
                and self.expert_offset + self.n_experts_held
                <= self.n_experts_routed):
            raise ValueError("held experts must lie inside those routed over")

    @property
    def mtp_letters(self):
        """The kinds of one multi-token-prediction module's layer: the
        stack's repeated unit (the whole pattern where nothing repeats)."""
        return stack_plan(self.pattern)[1]

    def heads(self, kind):
        """Query heads held here of an attention kind: W's own count where
        it is set, else ``attn_heads``."""
        return kind == "wattn" and self.window_attn_heads or self.attn_heads

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
        post = {"post_norm": (e,)} if self.post_norm else {}
        gated_experts = {
            "norm": (e,), "router": (e, self.n_experts_routed),
            "wg": (self.n_experts_held, e, f),
            "wu": (self.n_experts_held, e, f),
            "wd": (self.n_experts_held, f, e),
        }
        shared_expert = {
            "shared_wg": (e, fs), "shared_wu": (e, fs), "shared_wd": (fs, e)}
        qk = self.mla_nope_dim + self.mla_rope_dim

        def head_gated(heads):
            return {
                "norm": (e,), "wq": (e, heads * self.head_dim),
                "wk": (e, kvd), "wv": (e, kvd), "wg": (e, heads),
                "wo": (heads * self.head_dim, e)}

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
                **gated_experts, **shared_expert, "shared_gate": (e, 1)},
            "rattn": {
                "norm": (e,), "wq": (e, qd), "wk": (e, qd), "wv": (e, qd),
                "wo": (qd, e), **post,
            },
            "ffn": {
                "norm": (e,), "wg": (e, self.ffn_intermediate),
                "wu": (e, self.ffn_intermediate),
                "wd": (self.ffn_intermediate, e), **post,
            },
            "qattn": {
                "norm": (e,), "wq": (e, qd), "wk": (e, kvd), "wv": (e, kvd),
                "q_norm": (self.head_dim,), "k_norm": (self.head_dim,),
                "wo": (qd, e),
            },
            "smoe": gated_experts,
            "hattn": head_gated(self.heads("hattn")),
            "wattn": head_gated(self.heads("wattn")),
            "umoe": {**gated_experts, **shared_expert},
            "lattn": {
                "norm": (e,), "wqa": (e, self.mla_q_rank),
                "q_norm": (self.mla_q_rank,),
                "wqb": (self.mla_q_rank, self.attn_heads * qk),
                "wkva": (e, self.mla_kv_rank + self.mla_rope_dim),
                "kv_norm": (self.mla_kv_rank,),
                "wkvb": (self.mla_kv_rank, self.attn_heads * (
                    self.mla_nope_dim + self.mla_v_dim)),
                "wo": (self.attn_heads * self.mla_v_dim, e),
            },
            "bmoe": {
                **gated_experts, "router_bias": (self.n_experts_routed,),
                **shared_expert},
        }


# leaves that start at 1 (gains, D) and at 0 (biases; the gains that
# ``norm_zero_centered`` stores around 0); A_log and dt_bias start at the
# family's usual spread, every other leaf at N(0, range)
_ONES = ("gate_norm", "out_norm", "D")
_GAINS = ("norm", "q_norm", "k_norm", "kv_norm", "post_norm",
          "embed_norm", "state_norm")
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


def attention_spec(cfg, kind):
    """What attention letter ``kind`` is under ``cfg``: the one table between
    the configuration's fields and ``attention_mixer``."""
    d = cfg.head_dim

    def rotary(lanes, theta):
        return dict(lanes=lanes, frequencies=rotary_frequencies(lanes, theta))

    spec = dict(
        heads=cfg.heads(KINDS[kind]), kv_heads=cfg.kv_heads, head_dim=d)
    if kind == "G":
        spec.update(rotary(cfg.rotary_lanes, cfg.rope_theta), gate="lanes",
                    norm="zero_centered", eps=cfg.norm_eps)
    elif kind == "R":
        spec.update(rotary(d, cfg.rope_theta), kv_heads=cfg.attn_heads)
    elif kind == "A":
        spec.update(
            rotary(d, cfg.rope_theta), eps=cfg.norm_eps,
            norm="zero_centered" if cfg.norm_zero_centered else "rms",
            block_diffusion=cfg.diffusion_block
            if cfg.objective == "block_diffusion" else 0)
    elif kind == "H":
        lanes = cfg.rotary_lanes or d
        spec.update(rotary(lanes, cfg.rope_theta), gate="head",
                    scope="attn_full",
                    rotary_factor=cfg.rotary_attention_factor)
        if cfg.yarn_factor != 1.0:
            spec.update(frequencies=yarn_frequencies(
                lanes, cfg.rope_theta, cfg.yarn_factor,
                cfg.yarn_original_positions, cfg.yarn_beta_fast,
                cfg.yarn_beta_slow))
    elif kind == "W":
        spec.update(rotary(d, cfg.window_rope_theta), gate="head",
                    scope="attn_window", window=cfg.window)
    elif kind == "L":
        spec.update(
            rotary(cfg.mla_rope_dim, cfg.rope_theta), kv_heads=cfg.attn_heads,
            head_dim=cfg.mla_nope_dim + cfg.mla_rope_dim, eps=cfg.norm_eps,
            norm="zero_centered" if cfg.norm_zero_centered else None,
            q_rank=cfg.mla_q_rank, kv_rank=cfg.mla_kv_rank,
            v_dim=cfg.mla_v_dim, scope="attn_mla")
    return AttentionSpec(**spec)


class HybridModel(nn.Module):
    """input_ids [B, S] -> (hidden [B, S, E] after the final norm, the
    head's table, counters, None); a looped stack gives every pass's hidden
    [passes, B, S, E] and, last, its exit gate ``(gate_w, gate_b)``. Under
    ``objective="block_diffusion"`` a row is ``[noisy ; clean]``: S = 2 L.
    ``mtp`` (``mtp_depth`` > 0): the multi-token-prediction modules run too
    and their normed states [depth, B, S, E] come last."""

    config: HybridLMConfig

    @nn.compact
    def __call__(self, input_ids, mtp=False):
        cfg = self.config
        positions = None
        if cfg.objective == "block_diffusion":
            half = input_ids.shape[1] // 2
            positions = jnp.arange(2 * half) % half
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        embed = self.param("embed", init, (cfg.vocab_size, cfg.hidden_size))
        head = self.param("head", init, (cfg.vocab_size, cfg.hidden_size))
        norm_f = self.param(
            "norm_f", _leaf_init(cfg, "norm"), (cfg.hidden_size,))
        def stacked(letters, times=1, prefix=""):
            """{kind: {leaf: [its layers among ``letters`` x ``times``, ...]}},
            the leaves named ``<prefix><kind>_<leaf>``."""
            found = {}
            for kind, leaves in cfg.leaf_shapes().items():
                n = sum(KINDS[c] == kind for c in letters) * times
                if n:
                    found[kind] = {
                        leaf: self.param(
                            f"{prefix}{kind}_{leaf}", _leaf_init(cfg, leaf),
                            (n,) + shape)
                        for leaf, shape in leaves.items()}
            return found

        params = stacked(cfg.pattern)

        mixers = {
            "mamba": lambda p, x: (mamba2_mixer(
                p, x, heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
                groups=cfg.mamba_groups, state=cfg.ssm_state,
                chunk=cfg.chunk_size, eps=cfg.norm_eps), {}),
            "moe": lambda p, x: latent_moe_mixer(
                p, x, top_k=cfg.top_k, scale=cfg.routed_scaling,
                held=cfg.n_experts_held, offset=cfg.expert_offset,
                tile=cfg.moe_tile, force_level=cfg.router_force_level,
                mesh=cfg.mesh),
            "gdn": lambda p, x: (gated_deltanet_mixer(
                p, x, key_heads=cfg.gdn_key_heads,
                value_heads=cfg.gdn_value_heads, key_dim=cfg.gdn_key_dim,
                value_dim=cfg.gdn_value_dim, chunk=cfg.gdn_chunk,
                eps=cfg.norm_eps, mesh=cfg.mesh), {}),
            "ffn": lambda p, x: (swiglu_ffn_mixer(p, x), {}),
        }

        def gated_experts(scale=1.0, route="softmax"):
            return lambda p, x: gated_moe_mixer(
                p, x, top_k=cfg.top_k, held=cfg.n_experts_held,
                offset=cfg.expert_offset, tile=cfg.moe_tile,
                force_level=cfg.router_force_level, scale=scale, route=route,
                mesh=cfg.mesh)

        # one layer, by its leaves: with a gated shared expert, with none,
        # with an ungated one beside a scaled routed sum; that under sigmoid
        # scores and a selection bias
        mixers["gmoe"] = mixers["smoe"] = gated_experts()
        mixers["umoe"] = gated_experts(cfg.routed_scaling)
        mixers["bmoe"] = gated_experts(cfg.routed_scaling, "sigmoid")

        def attention(kind):
            """The one mixer under ``kind``'s spec. A head-gated or latent
            layer counts the query heads it runs (added up over layers and
            micro-steps) and, windowed, the band and the share of the score
            square that the banded kernels' walks visit."""
            def mixer(p, x):
                spec = attention_spec(cfg, kind)
                out = attention_mixer(
                    p, x, spec, positions=positions, mesh=cfg.mesh)
                if kind == "L":
                    return out, {"attn/mla_heads": jnp.int32(spec.heads)}
                if kind not in "HW":
                    return out, {}
                if not spec.window:
                    return out, {"attn/full_heads": jnp.int32(spec.heads)}
                return out, {
                    "attn/window_heads": jnp.int32(spec.heads),
                    "attn/max_window": jnp.int32(spec.window),
                    "attn/max_window_visited_share": jnp.float32(
                        window_visited_share(x.shape[1], spec.window))}

            return mixer

        mixers.update({KINDS[kind]: attention(kind) for kind in "*GRAHWL"})

        prefix, unit, repetitions, tail = stack_plan(cfg.pattern)
        scanned = repetitions > 1

        def remat(body):
            """A checkpoint is the body of the loop that walks the stack: a
            layer of the Python loop, a period of the scan (a layer's input
            saved for each period, not for each of its sublayers)."""
            if cfg.remat:
                return jax.checkpoint(
                    body, policy=resolve_remat_policy(cfg.remat_policy))
            return body

        def layer(kind):
            def apply(p, x):
                with jax.named_scope("stack_norms"):
                    normed = rms_norm(
                        x, p["norm"], cfg.norm_eps, cfg.norm_zero_centered)
                out, counters = mixers[kind](p, normed)
                with jax.named_scope("stack_norms"):
                    if "post_norm" in p:
                        out = rms_norm(out, p["post_norm"], cfg.norm_eps,
                                       cfg.norm_zero_centered)
                    return x + out.astype(x.dtype), counters

            return apply

        def walk(letters, each=lambda body: body):
            """``(x, params) -> (x, counters)`` over ``letters``' layers, each
            on the next slice of its kind."""
            def body(x, params):
                seen = dict.fromkeys(params, 0)
                per_layer = []
                for c in letters:
                    kind = KINDS[c]
                    p = {k: v[seen[kind]] for k, v in params[kind].items()}
                    seen[kind] += 1
                    x, counters = each(layer(kind))(p, x)
                    if counters:
                        per_layer.append(counters)
                return x, merge_counters(per_layer)

            return body

        def slices(letters, before, times=1):
            """The stacked leaves' slices for ``times`` runs of ``letters``
            that stand after ``before`` in the pattern."""
            out = {}
            for kind in {KINDS[c] for c in letters}:
                lo = sum(KINDS[c] == kind for c in before)
                n = sum(KINDS[c] == kind for c in letters) * times
                out[kind] = {k: v if (lo, n) == (0, v.shape[0])
                             else v[lo:lo + n]
                             for k, v in params[kind].items()}
            return out

        def one_pass(x):
            """The whole pattern and the final norm: the layers before the
            scanned run unrolled, the run, the layers after it; a pattern
            that scans nothing is ``unit`` alone, unrolled."""
            if not scanned:
                x, counters = walk(unit, remat)(x, params)
            else:
                found = []
                x, counters = walk(prefix, remat)(x, slices(prefix, ""))
                found += [counters] if counters else []
                with jax.named_scope("stack_scan"):
                    x, counters = jax.lax.scan(
                        remat(walk(unit)), x, jax.tree_util.tree_map(
                            lambda v: v.reshape(
                                (repetitions, v.shape[0] // repetitions)
                                + v.shape[1:]),
                            slices(unit, prefix, repetitions)))
                found += [merge_counters(counters, axis=0)] if counters else []
                x, counters = walk(tail, remat)(
                    x, slices(tail, prefix + unit * repetitions))
                found += [counters] if counters else []
                counters = found[0] if len(found) == 1 \
                    else merge_counters(found)
            with jax.named_scope("stack_norms"):
                return rms_norm(
                    x, norm_f, cfg.norm_eps, cfg.norm_zero_centered), counters

        if mtp and cfg.mtp_depth:
            e, depth, letters = cfg.hidden_size, cfg.mtp_depth, cfg.mtp_letters
            modules = stacked(letters, depth, "mtp_")
            joint = {
                leaf: self.param(
                    f"mtp_{leaf}", _leaf_init(cfg, leaf), (depth,) + shape)
                for leaf, shape in {
                    "embed_norm": (e,), "state_norm": (e,),
                    "proj": (2 * e, e), "norm": (e,)}.items()}

        def mtp_module(k, z):
            """Module ``k + 1`` over the state ``z`` [B, S, E] that the head
            reads: position i's token ``k + 1`` ahead out of the table beside
            its state, one layer of ``letters`` on the module's own slices,
            its own final norm. The ids past a row's end are the row's
            first: what those positions hold is not scored."""
            def normed(t, leaf):
                return rms_norm(t, joint[leaf][k], cfg.norm_eps,
                                cfg.norm_zero_centered)

            with jax.named_scope("mtp"):
                with jax.named_scope("mtp_proj"):
                    ahead = embed[jnp.roll(input_ids, -(k + 1), axis=1)]
                with jax.named_scope("stack_norms"):
                    both = jnp.concatenate(
                        [normed(ahead, "embed_norm"),
                         normed(z, "state_norm")], axis=-1)
                with jax.named_scope("mtp_proj"):
                    u = both @ joint["proj"][k]
                u, counters = walk(letters, remat)(u, {
                    kind: {leaf: v if depth == 1 else
                           v[k * v.shape[0] // depth:
                             (k + 1) * v.shape[0] // depth]
                           for leaf, v in leaves.items()}
                    for kind, leaves in modules.items()})
                with jax.named_scope("stack_norms"):
                    return normed(u, "norm"), counters

        with jax.named_scope("embed"):
            x = embed[input_ids]
        if cfg.passes == 1:
            x, counters = one_pass(x)
            if not (mtp and cfg.mtp_depth):
                return x, head, counters, None
            states, found, z = [], [counters] if counters else [], x
            for k in range(cfg.mtp_depth):
                z, counters = mtp_module(k, z)
                states.append(z)
                found += [counters] if counters else []
            return x, head, merge_counters(found), jnp.stack(states)
        gate = (self.param("gate_w", init, (cfg.hidden_size,)),
                self.param("gate_b", nn.initializers.zeros, (1,)))

        def loop_pass(x, _):
            with jax.named_scope("loop_pass"):
                x, counters = one_pass(x)
            return x, (x, counters)

        with jax.named_scope("stack_scan"):
            _, (states, counters) = jax.lax.scan(
                loop_pass, x, None, length=cfg.passes)
        return states, head, merge_counters(counters, axis=0), gate


class HybridCausalLM(nn.Module):
    """``__call__(input_ids, labels) -> (loss, counters)``: next-token loss
    (the shift happens inside) through the blocked head loss, and the
    routing counters of this micro-step. ``labels=None`` gives logits. A
    looped stack's loss is ``looped_loss``. Under
    ``objective="block_diffusion"`` the call is ``(noisy_ids, clean_ids,
    loss_weights)`` and the loss ``block_diffusion_loss``."""

    config: HybridLMConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, loss_weights=None):
        cfg = self.config
        if cfg.objective == "block_diffusion":
            if labels is None or loss_weights is None:
                raise ValueError(
                    "block diffusion trains on (noisy_ids, clean_ids, "
                    "loss_weights); generation by blocks is not built")
            x, head, counters, _ = HybridModel(cfg, name="model")(
                jnp.concatenate([input_ids, labels], axis=1))
            loss, diffusion = block_diffusion_loss(
                x[:, :labels.shape[1]], head, labels, loss_weights,
                block_rows=cfg.ce_block_rows)
            return loss, {**counters, **diffusion}
        # last: a looped stack's exit gate, or the modules' states
        x, head, counters, last = HybridModel(cfg, name="model")(
            input_ids, mtp=labels is not None)
        if cfg.mtp_depth and labels is not None:
            loss, modules = mtp_loss(
                x, last, head, labels, weight=cfg.mtp_loss_weight,
                block_rows=cfg.ce_block_rows)
            return loss, {**counters, **modules}
        gate = last
        if labels is None:
            return (x if gate is None else x[-1]) @ head.T
        if gate is not None:
            loss, loop = looped_loss(
                x[:, :, :-1], head, labels[:, 1:], *gate,
                entropy_weight=cfg.exit_entropy_weight,
                block_rows=cfg.ce_block_rows)
            return loss, {**counters, **loop}
        with jax.named_scope("head_loss"):
            loss = blocked_lm_head_loss(
                x[:, :-1], head, labels[:, 1:], block_rows=cfg.ce_block_rows)
        return (loss, counters) if counters else loss


def mtp_loss(state, module_states, head, labels, *, weight, block_rows):
    """The objective with multi-token prediction: the next-token loss of
    ``state`` [B, S, E] plus ``weight`` times the mean over the modules of
    each one's mean nll, ``module_states[k]`` [B, S, E] at position i scored
    against ``labels[i + k + 2]`` through the SAME ``head`` (its gradient is
    the sum over the passes); and the ``mtp/...`` counters."""
    with jax.named_scope("head_loss"):
        loss = blocked_lm_head_loss(
            state[:, :-1], head, labels[:, 1:], block_rows=block_rows)
    depth = module_states.shape[0]
    with jax.named_scope("mtp_head_loss"):
        ahead = sum(
            blocked_lm_head_loss(
                module_states[k, :, :-(k + 2)], head, labels[:, k + 2:],
                block_rows=block_rows)
            for k in range(depth)) / depth
    counters = {"mtp/depth": jnp.int32(depth), "mtp/loss": ahead}
    return loss + weight * ahead, jax.lax.stop_gradient(counters)


def block_diffusion_loss(noisy_states, head, clean_ids, loss_weights, *,
                         block_rows):
    """``sum_i loss_weights[i] nll_i / (B L)`` over the noisy half's normed
    states [B, L, E], position i scored against ``clean_ids[i]`` (no shift),
    every position in the denominator: ``weighted_lm_head_loss`` with one
    pass of per-position weights. And the ``diffusion/...`` counters of this
    micro-step."""
    weights = loss_weights.astype(jnp.float32)
    with jax.named_scope("head_loss"):
        loss = weighted_lm_head_loss(
            noisy_states[None], head, clean_ids, weights[None],
            block_rows=block_rows, ignore_values=())
    counters = {
        "diffusion/positions": jnp.int32(clean_ids.size),
        "diffusion/masked_positions": jnp.sum(weights > 0).astype(jnp.int32),
        "diffusion/loss_weight_sum": jnp.sum(weights),
    }
    return loss, jax.lax.stop_gradient(counters)


def looped_loss(states, head, labels, gate_w, gate_b, *, entropy_weight,
                block_rows, ignore_values=(-1, -100)):
    """The objective of a looped stack over ``states`` [R, B, T, E] (each
    pass's normed state at the positions that have a next token) and their
    ``labels`` [B, T]: the mean over the counted positions of ``sum_t p_t
    nll_t - entropy_weight * H(p)``, ``p`` the exit distribution of the gate
    ``sigmoid(states . gate_w + gate_b)`` and ``nll_t`` pass t's next-token
    loss through the head; and the ``loop/...`` counters of this micro-step:
    the passes run, each pass's mean exit probability and the mean entropy
    (added up over micro-steps by the registry: a pass's share of the exits
    is its counter over the four's sum)."""
    passes = states.shape[0]
    with jax.named_scope("loop_head_loss"):
        with jax.named_scope("exit_gate"):
            log_p = exit_log_probs(jnp.einsum(
                "rbte,e->rbt", states, gate_w,
                preferred_element_type=jnp.float32)
                + gate_b.astype(jnp.float32))
            p = jnp.exp(log_p)
            counted = jnp.ones(labels.shape, bool)
            for value in ignore_values:
                counted &= labels != value
            share = counted / jnp.maximum(jnp.sum(counted), 1)
            exit_share = jnp.sum(p * share, axis=(1, 2))
            entropy = -jnp.sum(p * log_p * share)
        expected = weighted_lm_head_loss(
            states, head, labels, p, block_rows=block_rows,
            ignore_values=ignore_values)
        loss = expected - entropy_weight * entropy
    counters = {"loop/passes": jnp.int32(passes),
                "loop/exit_entropy": entropy}
    counters.update({f"loop/exit_share_{t + 1}": exit_share[t]
                     for t in range(passes)})
    return loss, jax.lax.stop_gradient(counters)
