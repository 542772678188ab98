"""DeepSpeedTransformerLayer: the fused transformer block, TPU-native.

Capability parity with the reference's hand-fused CUDA BERT layer
(reference: csrc/transformer/ds_transformer_cuda.cpp:153-295 forward,
deepspeed/pt/deepspeed_cuda.py:31-520 Python binding): same computation —
qkv projection -> multi-head attention (scale+mask+softmax+dropout) ->
output projection -> dropout+residual -> LayerNorm -> FF1 -> GeLU -> FF2 ->
dropout+residual -> LayerNorm, with both pre- and post-LayerNorm orders —
and the same config surface (DeepSpeedTransformerConfig incl. the memory-
mode flags).

TPU-first mapping of the reference's 8 CUDA kernel families:
  softmax/dropout/transform/gelu/norm/general kernels -> the Pallas flash
  attention kernel (ops/attention.py) + XLA fusion for the elementwise
  chains (bias+gelu, bias+dropout+residual, layernorm all fuse into their
  surrounding matmuls under XLA — hand-scheduling them would fight the
  compiler);
  memory-saving recompute modes (normalize_invertible, gelu_checkpoint,
  attn_dropout_checkpoint, ds_transformer_cuda.cpp:189-191) ->
  ``jax.checkpoint`` (remat) over the layer body;
  seq<=1024 cap (ds_transformer_cuda.cpp:133) -> none (blockwise flash).

Parameter names mirror the reference's 12-tensor layout
(deepspeed_cuda.py:393-520: attn_qkvw/qkvb, attn_ow/ob, attn_nw/nb,
inter_w/b, output_w/b, norm_w/b) so state_dicts translate mechanically.
"""

import contextlib
import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .attention import (
    NEG_INF, additive_mask_to_kv_valid, attention, attention_packed,
    flash_attention_latent, latent_layout,
)
from .qk_prep import qk_prep, qk_prep_in_place, qk_prep_path


@dataclasses.dataclass
class DeepSpeedTransformerConfig:
    """Config parity with reference deepspeed_cuda.py:31-132."""

    batch_size: int = -1
    max_seq_length: int = -1
    hidden_size: int = -1
    heads: int = -1
    intermediate_size: int = -1  # -1 => 4*hidden
    attn_dropout_ratio: float = 0.1
    hidden_dropout_ratio: float = 0.1
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    local_rank: int = -1
    seed: int = -1
    fp16: bool = False
    pre_layer_norm: bool = True
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False
    # Relaxed-precision fast path (the reference builds a second kernel
    # variant with -D__STOCHASTIC_MODE__, setup.py:44-118, surfaced at
    # deepspeed_cuda.py:60-79: slightly faster, run-to-run nondeterministic,
    # "acceptable for pretraining"). TPU analog: LayerNorm statistics stay
    # in the compute dtype (bf16/fp16) instead of upcasting to fp32 —
    # trims the widest HBM-bound elementwise chain in the block. No-op
    # under fp32 compute.
    stochastic_mode: bool = False
    huggingface: bool = False
    layer_norm_eps: float = 1e-12
    # Remat granularity when a memory mode is on: "full" recomputes the
    # whole block in backward (max memory saving, ~1 extra forward of
    # FLOPs); any other value names a jax.checkpoint_policies entry, e.g.
    # "dots_saveable" keeps matmul outputs and recomputes only the cheap
    # elementwise chains (LN/GeLU/dropout) — the sweet spot the reference
    # reaches with its per-buffer recompute flags
    # (ds_transformer_cuda.cpp:189-191).
    remat_policy: str = "full"
    # LoRA adapters (Hu et al. — PAPERS.md "Adapters";
    # deepspeed_tpu/adapters/, docs/adapters.md): rank-r A/B pairs on the
    # projection matrices named in ``lora_targets``. 0 = no adapters —
    # the block then runs the EXACT pre-adapter code path (no extra ops),
    # so an adapter-free config stays bitwise-identical to today.
    lora_rank: int = 0
    # LoRA scaling numerator: delta = (alpha / rank) * x @ A @ B.
    # 0 => alpha = rank (scaling 1.0), the convention bench/tests use.
    lora_alpha: float = 0.0
    lora_targets: tuple = ()  # () => LORA_TARGETS when lora_rank > 0

    @property
    def intermediate(self):
        return (
            self.intermediate_size
            if self.intermediate_size > 0
            else 4 * self.hidden_size
        )

    @property
    def use_remat(self):
        """Any reference memory-mode flag maps onto remat of the layer."""
        return (
            self.normalize_invertible
            or self.gelu_checkpoint
            or self.attn_dropout_checkpoint
        )


def resolve_remat_policy(spec: str):
    """Resolve a remat-policy spec: '+'-separated parts, each either a
    ``jax.checkpoint_policies`` attribute or a ``checkpoint_name`` tag to
    save (e.g. "dots_with_no_batch_dims_saveable+flash_out+flash_lse" keeps
    weight-matmul outputs AND the flash kernel's residuals, so backward
    recomputes only cheap elementwise chains)."""
    import functools as _ft

    from .attention import CHECKPOINT_NAMES

    parts = spec.split("+")
    policies, names = [], []
    for p in parts:
        if hasattr(jax.checkpoint_policies, p):
            policies.append(getattr(jax.checkpoint_policies, p))
        elif p in CHECKPOINT_NAMES:
            names.append(p)
        else:
            # a typo'd policy name must fail loudly, not silently become a
            # never-matching name-saver that recomputes everything
            raise ValueError(
                f"unknown remat policy part {p!r}: neither a "
                f"jax.checkpoint_policies attribute nor a known checkpoint "
                f"name {CHECKPOINT_NAMES}"
            )
    if names:
        policies.append(jax.checkpoint_policies.save_only_these_names(*names))
    if not policies:
        raise ValueError(f"unresolvable remat policy spec: {spec!r}")
    return _ft.reduce(jax.checkpoint_policies.save_from_both_policies, policies)


#: checkpoint_name tag on the ZeRO-3 stack's just-in-time all-gathered
#: layer weights (models/stack.py:zero3_scan_stack). Every default remat
#: policy leaves it unsaved, so backward RE-GATHERS each layer's weights
#: instead of holding n_layers x full copies as residuals; a policy spec
#: naming it explicitly (resolve_remat_policy) opts into saving them.
ZERO3_GATHER_CHECKPOINT_NAME = "zero3_gathered"


def zero3_remat_policy(cfg: "DeepSpeedTransformerConfig"):
    """The ``jax.checkpoint`` policy for one ZeRO-3 stack layer
    (models/stack.py wraps each layer body — gather INCLUDED — in
    ``jax.checkpoint`` with this policy, so gathered weights are never
    scan residuals):

    - remat configured (any reference memory-mode flag): the layer's own
      policy applies unchanged — "full" saves nothing, and the named/dots
      policies never match the gathered weights (an all-gather is neither
      a dot nor one of their saved names) unless the spec names
      ``zero3_gathered`` explicitly.
    - remat NOT configured: everything except the gathered weights is
      saved (``save_anything_except_these_names``) — the memory contract
      stage 3 needs (backward re-gathers, 1/dp param residency) with the
      minimum recompute: only the gathers re-run in backward.
    """
    if cfg.use_remat:
        if cfg.remat_policy == "full":
            return None  # plain jax.checkpoint: nothing saved
        return resolve_remat_policy(cfg.remat_policy)
    return jax.checkpoint_policies.save_anything_except_these_names(
        ZERO3_GATHER_CHECKPOINT_NAME
    )


_STOCHASTIC_NOTICED = [False, False]  # [active-path notice, no-op notice]


def _notice_stochastic_once(active: bool, dtype=None):
    idx = 0 if active else 1
    if _STOCHASTIC_NOTICED[idx]:
        return
    _STOCHASTIC_NOTICED[idx] = True
    from ..utils.logging import log_dist

    if active:
        log_dist(
            "stochastic_mode: relaxed-precision transformer path active — "
            "LayerNorm statistics in bf16 (fp32 upcast skipped). Matches "
            "the reference's __STOCHASTIC_MODE__ kernel contract: faster, "
            "pretraining-safe, not bit-deterministic vs the default path.",
            ranks=[0],
        )
    else:
        log_dist(
            f"stochastic_mode requested but compute dtype is {dtype}; the "
            "relaxed LayerNorm path applies only under bf16 (fp16's range "
            "would overflow the statistics) — running the default "
            "fp32-statistics path.",
            ranks=[0],
        )


#: The reference's 12-tensor parameter layout (deepspeed_cuda.py:393-520).
#: shapes as functions of (H, intermediate I); norms are always fp32.
TRANSFORMER_PARAM_LAYOUT = (
    ("attn_qkvw", ("H", "3H"), "init"),
    ("attn_qkvb", ("3H",), "zeros"),
    ("attn_ow", ("H", "H"), "init"),
    ("attn_ob", ("H",), "zeros"),
    ("attn_nw", ("H",), "ones32"),
    ("attn_nb", ("H",), "zeros32"),
    ("inter_w", ("H", "I"), "init"),
    ("inter_b", ("I",), "zeros"),
    ("output_w", ("I", "H"), "init"),
    ("output_b", ("H",), "zeros"),
    ("norm_w", ("H",), "ones32"),
    ("norm_b", ("H",), "zeros32"),
)


#: Projection matrices LoRA can target, with their (in, out) dims in the
#: shape vocabulary of TRANSFORMER_PARAM_LAYOUT — every weight MATRIX of
#: the block (biases/norms gain nothing from low-rank deltas).
LORA_TARGETS = ("attn_qkvw", "attn_ow", "inter_w", "output_w")
LORA_TARGET_DIMS = {
    "attn_qkvw": ("H", "3H"),
    "attn_ow": ("H", "H"),
    "inter_w": ("H", "I"),
    "output_w": ("I", "H"),
}
#: Megatron split of each target's base matrix (models/gpt2.py:
#: partition_specs): "column" shards the OUTPUT dim over the model axis —
#: LoRA B ([r, out]) carries that dim, so B shards with it and A
#: replicates; "row" shards the INPUT dim — A ([in, r]) carries it. The
#: rank dim never shards (r is tiny and rarely divides the mesh axis).
LORA_TARGET_PARALLEL = {
    "attn_qkvw": "column", "inter_w": "column",
    "attn_ow": "row", "output_w": "row",
}


def resolve_lora_targets(targets):
    """Normalize + validate a lora_targets value: () / None => every
    target; anything naming an unknown matrix fails loudly (a typo'd
    target would otherwise silently train/serve a partial adapter)."""
    targets = tuple(targets) if targets else LORA_TARGETS
    unknown = [t for t in targets if t not in LORA_TARGETS]
    if unknown:
        raise ValueError(
            f"unknown LoRA target(s) {unknown}; valid: {list(LORA_TARGETS)}"
        )
    if len(set(targets)) != len(targets):
        raise ValueError(f"duplicate LoRA targets in {targets}")
    return targets


def lora_scaling(rank, alpha=0.0):
    """delta multiplier: alpha / rank (alpha 0/None => rank => 1.0)."""
    return (float(alpha) if alpha else float(rank)) / float(rank)


def apply_lora(cfg, p, lora, name, x, y):
    """``y`` (the base projection ``x @ W + b``) plus projection
    ``name``'s LoRA delta, from one of two adapter sources:

    - ``lora = (pools, ids, scale)`` — the BATCHED multi-adapter serving
      path (S-LoRA / Punica — PAPERS.md "Adapters"): ``pools`` maps
      target -> (A [n_adapters, in, r], B [n_adapters, r, out]),
      ``ids`` [B] int32 picks each slot's adapter (id 0 = the all-zeros
      identity rows — no adapter). Ids are ARRAYS, not shapes, so a
      batch mixing any adapters runs ONE compiled program; the gather +
      einsum is row-independent along the slot dim, which is what makes
      a mixed batch bitwise-equal to per-adapter single-slot runs.
      A 4th element ``fused=True`` routes single-token (decode-shaped)
      calls through the Pallas SGMV kernel
      (ops/decode_attention.py:lora_sgmv): the per-slot A/B rows are
      read straight from the pool by scalar-prefetched ids instead of
      materializing gathered ``[B, in, r]`` weight stacks — the
      adapter-heavy-batch half of the fused decode path
      (``inference.fused_decode``). Multi-token calls (prefill, suffix,
      speculative verify) keep the XLA gather path.
    - per-layer ``{name}_lora_a`` / ``{name}_lora_b`` entries riding in
      the param dict ``p`` (the fine-tune path, ``cfg.lora_rank > 0``):
      one shared adapter, differentiated with the rest of ``p``.

    Returns ``y`` untouched when neither source names this projection —
    the adapter-disabled path adds zero ops.
    """
    if lora is not None:
        pools, ids, scale = lora[0], lora[1], lora[2]
        fused = lora[3] if len(lora) > 3 else False
        ab = pools.get(name)
        if ab is None:
            return y
        a, b = ab
        if fused and x.shape[1] == 1:
            from .decode_attention import lora_sgmv

            delta = lora_sgmv(x[:, 0, :], a, b, ids)  # [B, out] f32
            return y + (scale * delta[:, None, :]).astype(y.dtype)
        t = jnp.einsum("bsi,bir->bsr", x, a[ids])
        return y + (scale * jnp.einsum("bsr,bro->bso", t, b[ids])).astype(
            y.dtype
        )
    if getattr(cfg, "lora_rank", 0) > 0 and isinstance(p, dict):
        a = p.get(f"{name}_lora_a")
        if a is None:
            return y
        b = p[f"{name}_lora_b"]
        scale = lora_scaling(cfg.lora_rank, cfg.lora_alpha)
        return y + (scale * ((x @ a) @ b)).astype(y.dtype)
    return y


def layer_norm_apply(cfg: DeepSpeedTransformerConfig, x, scale, bias):
    """The block's LayerNorm (module-level so the KV-cache decode path
    shares the exact arithmetic). stochastic_mode keeps LN statistics in
    the compute dtype (the reference's __STOCHASTIC_MODE__ relaxed
    kernel); default is fp32. bf16 only: it shares fp32's exponent range,
    so x^2 cannot overflow the statistics — fp16 (range to 65504, eps
    underflow) always takes the fp32 path."""
    relaxed = cfg.stochastic_mode and x.dtype == jnp.bfloat16
    xs = x if relaxed else x.astype(jnp.float32)
    mean = jnp.mean(xs, axis=-1, keepdims=True)
    var = jnp.var(xs, axis=-1, keepdims=True)
    # eps joins in fp32 regardless: 1e-12 underflows in bf16/fp16
    inv = jax.lax.rsqrt(
        var.astype(jnp.float32) + cfg.layer_norm_eps
    ).astype(xs.dtype)
    y = (xs - mean) * inv
    return (y * scale.astype(xs.dtype) + bias.astype(xs.dtype)).astype(
        x.dtype
    )


def rms_norm(x, gain, eps, zero_centered=False):
    """RMS norm (no mean, no bias), statistics in float32. ``zero_centered``:
    the gain is stored around 0 and applied as ``1 + gain``."""
    xs = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xs), axis=-1, keepdims=True) + eps)
    gain = gain.astype(jnp.float32)
    return (xs * inv * (1.0 + gain if zero_centered else gain)).astype(x.dtype)


def rotary_frequencies(lanes, theta):
    """[lanes / 2] float32 inverse frequencies ``theta^(-2i / lanes)``, made
    on the host in float64."""
    return np.asarray(
        float(theta) ** (-np.arange(0, lanes, 2) / lanes), np.float32)


def yarn_frequencies(lanes, theta, factor, original_positions,
                     beta_fast=32.0, beta_slow=1.0):
    """[lanes / 2] float32 inverse frequencies under YaRN (Peng et al. 2023,
    arXiv:2309.00071), made on the host in float64: frequency i is a blend of
    ``theta^(-2i / lanes)`` (kept where a lane turns more than ``beta_fast``
    times over ``original_positions``) and that over ``factor`` (where it
    turns fewer than ``beta_slow`` times), by a linear ramp over the lane
    pairs between the two correction dimensions (floor and ceiling, kept
    inside the lanes)."""
    plain = float(theta) ** (-np.arange(0, lanes, 2) / lanes)

    def correction(turns):
        return lanes * np.log(original_positions / (turns * 2 * np.pi)) / (
            2 * np.log(float(theta)))

    low = max(np.floor(correction(beta_fast)), 0)
    high = min(np.ceil(correction(beta_slow)), lanes - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(lanes // 2) - low) / (high - low), 0, 1)
    return np.asarray(plain / factor * ramp + plain * (1 - ramp), np.float32)


def rotary_angles(seq, lanes, theta, positions=None, frequencies=None):
    """[S, lanes / 2] float32 angles ``position * frequency``. ``positions``
    [S]: each row's position id (the two halves of a block-diffusion row
    carry the same ids); None is 0..S-1. ``frequencies`` [lanes / 2]: a
    table that takes ``rotary_frequencies``' place (``yarn_frequencies``)."""
    if positions is None:
        positions = jnp.arange(seq, dtype=jnp.float32)
    if frequencies is None:
        frequencies = rotary_frequencies(lanes, theta)
    return positions.astype(jnp.float32)[:, None] * frequencies[None, :]


def apply_rotary(x, lanes, theta, seq_axis=2, positions=None,
                 frequencies=None, factor=1.0):
    """Rotary position embedding in the half-split convention on the first
    ``lanes`` lanes of each head of ``x`` [B, H, S, D] (lane i pairs with
    lane i + lanes / 2; the lanes after stay as they are), angles and
    rotation in float32, at ``rotary_angles``' positions and frequencies;
    ``factor`` multiplies cosine and sine, so the rotated lanes and not the
    others (YaRN's attention factor). ``seq_axis=1``: ``x`` is [B, S, H, D],
    as a projection leaves it. The reference of the kernels of
    ops/qk_prep.py, and the path of every shape ``qk_prep_path`` refuses."""
    half = lanes // 2
    angle = rotary_angles(
        x.shape[seq_axis], lanes, theta, positions, frequencies)
    if seq_axis == 1:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xs = x.astype(jnp.float32)
    x1, x2 = xs[..., :half], xs[..., half:lanes]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xs[..., lanes:]],
        axis=-1).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """What a layer kind's attention IS; how it runs is ``attention_mixer``'s.
    ``heads`` query heads on ``kv_heads`` kv heads of ``head_dim`` lanes (kv
    head j serves query heads ``j * heads / kv_heads`` onward). ``norm``: an
    RMS norm over each head of q (gain q_norm [D]) and of k (k_norm [D])
    before rotary: None, ``"rms"`` or ``"zero_centered"`` (the gain applied
    as ``1 + gain``), with ``eps``. Rotary on the first ``lanes`` lanes (0:
    none) at ``frequencies`` [lanes / 2] (``rotary_frequencies``,
    ``yarn_frequencies``; kept as a tuple), cosine and sine times
    ``rotary_factor``. ``gate``: None; ``"lanes"``: wq [E, heads * 2 D] gives
    each head D query lanes then D gate lanes; ``"head"``: one value a head
    out of wg [E, heads], read from the sublayer's own input; ``out =
    (context * sigmoid(gate)) wo``. ``window=W``: a query sees its last W
    keys only; ``block_diffusion=B``: that mask over a ``[noisy ; clean]``
    row (both inside the flash kernels, ops/attention.py); neither: causal.
    ``scope``: the device scope the kind opens inside ``attn_mixer``.

    ``kv_rank`` > 0 is a LATENT mixer (multi-head latent attention): q and k
    of ``head_dim`` lanes, the LAST ``lanes`` of them rotated, and v of
    ``v_dim``. ``c_q = rms(x wqa; q_norm)`` [``q_rank``], ``q = c_q wqb``
    (each head its ``head_dim - lanes`` unrotated lanes, then its rotated
    ones); ``[c_kv ; k_r] = x wkva`` [``kv_rank`` + ``lanes``], ``c_kv`` under
    ``rms(.; kv_norm)``, ``[k_nope ; v] = c_kv wkvb`` a head, and the ONE
    rotated ``k_r`` is every head's last ``lanes`` key lanes; ``softmax(q k^T
    / sqrt(head_dim)) v`` with ``wo`` from ``heads * v_dim``. It has as many
    kv heads as heads and takes no gate or mask form; ``norm`` says only
    whether the two latent gains are ``"zero_centered"``."""

    heads: int
    kv_heads: int
    head_dim: int
    norm: Optional[str] = None
    eps: float = 0.0
    lanes: int = 0
    frequencies: tuple = ()
    rotary_factor: float = 1.0
    gate: Optional[str] = None
    window: int = 0
    block_diffusion: int = 0
    scope: Optional[str] = None
    q_rank: int = 0
    kv_rank: int = 0
    v_dim: int = 0

    def __post_init__(self):
        object.__setattr__(
            self, "frequencies", tuple(float(f) for f in self.frequencies))
        if self.kv_rank and (
                self.gate or self.window or self.block_diffusion
                or self.kv_heads != self.heads):
            raise ValueError(
                "a latent mixer has one key and one value a head and no "
                "gate or mask form")


def attention_mixer(p, x, spec, *, positions=None, mesh=None):
    """``spec``'s attention over normalized ``x`` [B, S, E] with bias-free
    projections wq [E, heads * D], wk/wv [E, kv_heads * D], wo [heads * D,
    E]; rotary at ``positions`` [S] (None: 0..S-1; the halves of a
    block-diffusion row carry the same ids). The q/k norm and rotary are the
    kernels of ops/qk_prep.py where ``qk_prep_path`` says ``fused``, else
    ``apply_rotary(rms_norm(..))``. The layout follows from the spec: ONE
    product q | k | v, rotated in place and taken whole by
    ``attention_packed`` ([B, S, 3 * heads * D]: no head transpose on either
    side of the kernels), where there are as many kv heads as query heads and
    nothing but a rotation of every lane before the kernels; else three
    products and ``attention`` over [B, heads, S, D]. A latent spec
    (``kv_rank``) is ``_latent_attention``'s."""
    b, s, _ = x.shape
    heads, kv_heads, d = spec.heads, spec.kv_heads, spec.head_dim
    fused = qk_prep_path(b, s, heads, d, spec.lanes, mesh)[0] == "fused"
    packed = kv_heads == heads and spec.lanes == d \
        and not (spec.norm or spec.gate or spec.window or spec.block_diffusion)
    zero_centered = spec.norm == "zero_centered"
    rotary = dict(positions=positions,
                  frequencies=np.asarray(spec.frequencies, np.float32))

    def by_xla(t, gain=None):
        """The heads ``t`` [B, S, n, D] normed and rotated."""
        if gain is not None:
            t = rms_norm(t, gain, spec.eps, zero_centered)
        if spec.lanes:
            t = apply_rotary(t, spec.lanes, None, seq_axis=1,
                             factor=spec.rotary_factor, **rotary)
        return t

    kind = jax.named_scope(spec.scope) if spec.scope \
        else contextlib.nullcontext()
    with jax.named_scope("attn_mixer"), kind:
        if spec.kv_rank:
            return _latent_attention(p, x, spec, rotary, mesh)
        if packed:
            qkv = x @ jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1)
            if fused:
                qkv = qk_prep_in_place(
                    qkv, rotary_angles(s, d, None, **rotary),
                    heads=2 * heads, head_dim=d)
            else:
                qkv = qkv.reshape(b, s, 3 * heads, d)
                qkv = jnp.concatenate(
                    [by_xla(qkv[:, :, :2 * heads]), qkv[:, :, 2 * heads:]],
                    axis=2)
            return attention_packed(
                qkv.reshape(b, s, 3 * heads * d), heads, causal=True,
                mesh=mesh) @ p["wo"]
        gains = (p["q_norm"], p["k_norm"]) if spec.norm else (None, None)
        if fused:
            if spec.gate == "lanes":
                # q and the gate as two products of wq's two halves of each
                # head: both lane-dense [B, S, heads * D], no interleave to
                # take apart
                wq = p["wq"].reshape(-1, heads, 2, d)
                q, gate = (x @ wq[:, :, i].reshape(-1, heads * d)
                           for i in (0, 1))
                gate = gate.reshape(b, s, heads, d)
            else:
                q = x @ p["wq"]
            k = x @ p["wk"]
            angle = rotary_angles(s, spec.lanes, None, **rotary)
            q, k = (
                qk_prep(t, gain, angle, head_dim=d, eps=spec.eps,
                        zero_centered=zero_centered,
                        factor=spec.rotary_factor)
                for t, gain in zip((q, k), gains))
        else:
            q = (x @ p["wq"]).reshape(b, s, heads, -1)
            if spec.gate == "lanes":
                q, gate = q[..., :d], q[..., d:]
            q = by_xla(q, gains[0]).transpose(0, 2, 1, 3)
            k = by_xla((x @ p["wk"]).reshape(b, s, kv_heads, d),
                       gains[1]).transpose(0, 2, 1, 3)
        v = (x @ p["wv"]).reshape(b, s, kv_heads, d).transpose(0, 2, 1, 3)
        ctx = attention(q, k, v, causal=not spec.block_diffusion, mesh=mesh,
                        block_diffusion=spec.block_diffusion,
                        window=spec.window).transpose(0, 2, 1, 3)
        if spec.gate == "head":
            gate = jax.nn.sigmoid(jnp.dot(
                x, p["wg"], preferred_element_type=jnp.float32))[..., None]
        elif spec.gate:
            gate = jax.nn.sigmoid(gate.astype(jnp.float32))
        if spec.gate:
            ctx = ctx * gate.astype(ctx.dtype)
        return ctx.reshape(b, s, heads * d) @ p["wo"]


def _latent_attention(p, x, spec, rotary, mesh):
    """``AttentionSpec``'s latent mixer over normalized ``x`` [B, S, E], at
    ``rotary``'s positions and frequencies (``apply_rotary``'s): wqa [E,
    q_rank], q_norm [q_rank], wqb [q_rank, heads * head_dim], wkva [E,
    kv_rank + lanes], kv_norm [kv_rank], wkvb [kv_rank, heads * (head_dim -
    lanes + v_dim)], wo [heads * v_dim, E]. Nothing is padded to another
    width. The norms and the rotations are ``rms_norm`` and ``apply_rotary``
    (``qk_prep_path`` refuses a head that does not fill 128-lane blocks: the
    passes of ops/qk_prep.py rotate whole heads on their FIRST lanes, and
    here the rotated lanes are the last 64 of 192 and a lone 64-lane key;
    the latent norms come with no rotation).

    Where ``latent_layout`` says ``latent`` (the published widths on one
    device) the flash kernels read the projections' own results and nothing
    is laid out anew on either side of them: ``q_nope`` [B, S, heads * nope]
    and ``q_r`` [B, S, heads * lanes] are two products of wqb's two column
    ranges of each head (``q_r`` then rotated), ``kv = rms(c_kv) wkvb`` [B,
    S, heads * (nope + v_dim)] goes in as the product wrote it (a head's
    unrotated key lanes, then its value: the buffer a dots-saveable policy
    keeps is the one the backward kernel reads), the ONE rotated key part
    ``k_r`` [B, S, lanes] is read once a key block by every head, and the
    context comes back [B, S, heads * v_dim], what ``wo`` reads. Else (toy
    widths, several devices; ``latent_refusal`` has the reason) q and k are
    built [B, heads, S, head_dim], the rotated key part repeated over the
    heads, with v [B, heads, S, v_dim], for ``attention``."""
    b, s, _ = x.shape
    heads, rope = spec.heads, spec.lanes
    nope = spec.head_dim - rope

    def rotated(t):
        return apply_rotary(t, rope, None, seq_axis=1,
                            factor=spec.rotary_factor, **rotary)

    centered = spec.norm == "zero_centered"
    layout, _, why_split = latent_layout(
        b, s, heads, nope, rope, spec.v_dim, mesh)
    c_q = rms_norm(x @ p["wqa"], p["q_norm"], spec.eps, centered)
    if layout == "latent":
        wqb = p["wqb"].reshape(-1, heads, spec.head_dim)
        q_nope = c_q @ wqb[:, :, :nope].reshape(-1, heads * nope)
        q_r = c_q @ wqb[:, :, nope:].reshape(-1, heads * rope)
        q_r = rotated(q_r.reshape(b, s, heads, rope)).reshape(q_r.shape)
        latent = x @ p["wkva"]
        k_r = rotated(latent[:, :, None, spec.kv_rank:]).reshape(b, s, rope)
        kv = rms_norm(latent[..., :spec.kv_rank], p["kv_norm"], spec.eps,
                      centered) @ p["wkvb"]
        return flash_attention_latent(
            q_nope, q_r, kv, k_r, heads,
            sm_scale=spec.head_dim ** -0.5) @ p["wo"]
    q = (c_q @ p["wqb"]).reshape(b, s, heads, spec.head_dim)
    q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])], axis=-1)
    latent = x @ p["wkva"]
    k_rope = rotated(latent[:, :, None, spec.kv_rank:])
    kv = rms_norm(latent[..., :spec.kv_rank], p["kv_norm"], spec.eps,
                  centered) @ p["wkvb"]
    kv = kv.reshape(b, s, heads, nope + spec.v_dim)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))],
        axis=-1)
    ctx = attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        kv[..., nope:].transpose(0, 2, 1, 3), causal=True, mesh=mesh,
        why_split=why_split)
    return ctx.transpose(0, 2, 1, 3).reshape(b, s, heads * spec.v_dim) \
        @ p["wo"]


def swiglu_ffn_mixer(p, x):
    """A dense SiLU-gated FFN over normalized ``x`` [B, S, E]: ``(silu(x wg)
    * (x wu)) wd`` with wg, wu [E, F] and wd [F, E]; no bias."""
    with jax.named_scope("swiglu_ffn"):
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def transformer_block_apply(
    cfg: DeepSpeedTransformerConfig,
    p: dict,
    hidden_states,
    attention_mask=None,
    *,
    causal=False,
    use_flash=True,
    mesh=None,
    seq_parallel_impl="auto",
    train=True,
    dropout_rng=None,
    ffn_fn=None,
    return_kv=False,
    lora=None,
):
    """Pure-function transformer block over the 12-tensor param dict ``p``
    (keys per TRANSFORMER_PARAM_LAYOUT). Shared by the flax layer module
    (which creates the params) and the pipeline-parallel stack (which
    slices them from a pipe-sharded stack). Applies the config's remat
    policy itself.

    ``ffn_fn``: optional replacement for the dense FFN sublayer —
    ``ffn_fn(ff_in) -> h`` or ``-> (h, aux)`` (pre-residual, pre-dropout).
    Used by the MoE layer (ops/moe.py) to swap in an expert-parallel FFN
    while keeping the attention sublayer and LN/dropout/residual
    structure; when it returns an aux value (the router's load-balancing
    loss) this function returns ``(out, aux)``.

    ``return_kv``: additionally return this block's split-head key/value
    projections ``(k, v)`` each [B, heads, S, hd] — the KV-cache PREFILL
    mode (inference/decode.py): the values attention consumed are exactly
    the values the cache must hold, so no second projection pass runs.
    Result becomes ``(out, (k, v))``; remat is skipped (no backward
    exists to recompute for) and MoE aux / sequence parallelism do not
    compose with it.

    ``lora``: optional batched adapter source for :func:`apply_lora`
    (the serving prefill path); per-layer A/B pairs in ``p`` cover the
    fine-tune path. An ``ffn_fn`` (MoE) replaces the dense FFN, so the
    inter_w/output_w targets do not apply under it."""
    H = cfg.hidden_size
    heads = cfg.heads
    head_dim = H // heads
    assert head_dim * heads == H, "hidden_size must divide heads"

    # All RNG keys are drawn BEFORE the (optionally remat'd) block so the
    # closure is a pure array function — safe under jax.checkpoint, and
    # recompute regenerates identical dropout masks (the semantics the
    # reference gets from its saved byte masks / RNG tracker).
    need_rng = train and dropout_rng is not None and (
        cfg.attn_dropout_ratio > 0 or cfg.hidden_dropout_ratio > 0
    )
    if need_rng:
        attn_rng, h1_rng, h2_rng = jax.random.split(dropout_rng, 3)
    else:
        attn_rng = h1_rng = h2_rng = None

    def hid_dropout(x, drop_rng):
        rate = cfg.hidden_dropout_ratio
        if not train or rate <= 0 or drop_rng is None:
            return x
        keep = jax.random.bernoulli(drop_rng, 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)

    if cfg.stochastic_mode:
        _notice_stochastic_once(
            active=hidden_states.dtype == jnp.bfloat16,
            dtype=hidden_states.dtype,
        )

    def layer_norm(x, scale, bias):
        return layer_norm_apply(cfg, x, scale, bias)

    def block(x):
        b, s, _ = x.shape
        # ---- attention sublayer -----------------------------------
        with jax.named_scope("dense_attn"):
            residual = x
            attn_in = (
                layer_norm(x, p["attn_nw"], p["attn_nb"])
                if cfg.pre_layer_norm else x
            )
            product = attn_in @ p["attn_qkvw"]
            biased = product + p["attn_qkvb"]
            qkv = apply_lora(cfg, p, lora, "attn_qkvw", attn_in, biased)
            # [B,S,3H] -> 3 x [B,heads,S,hd]  (the reference's
            # bias_add_transform_0213, transform_kernels.cu:149): only for the
            # callers that need heads apart (sequence parallelism, the KV
            # cache's prefill); the dispatcher below takes ``qkv`` whole
            def split_heads():
                return tuple(
                    t.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)
                    for t in jnp.split(qkv, 3, axis=-1)
                )

            from ..config import constants as C

            seq_parallel = (
                mesh is not None
                and dict(mesh.shape).get(C.SEQUENCE_AXIS, 1) > 1
            )
            if seq_parallel:
                from ..parallel.sequence import sequence_parallel_attention

                if return_kv:
                    raise ValueError(
                        "return_kv (KV-cache prefill) does not compose "
                        "with sequence-parallel attention; decode with a "
                        "mesh whose sequence axis is 1"
                    )
                kv_valid = additive_mask_to_kv_valid(attention_mask)
                if attention_mask is not None and kv_valid is None:
                    raise ValueError(
                        "sequence-parallel attention supports padding-style "
                        "masks only (broadcast over the query dim)"
                    )
                ctx = sequence_parallel_attention(
                    *split_heads(),
                    mesh, kv_valid, impl=seq_parallel_impl,
                    use_flash=use_flash, causal=causal,
                    dropout_rate=cfg.attn_dropout_ratio if train else 0.0,
                    dropout_rng=attn_rng,
                )
                # transform4d_0213
                ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, H)
            else:
                # the projection's result goes in whole and the context comes
                # back [B,S,H]: where the flash kernels run (one device, or per
                # shard of a dp mesh via shard_map) they read the heads out of
                # ``qkv`` themselves; else the dispatcher splits as above
                # Without an adapter on this projection the BARE product goes
                # in with the bias beside it: kernels that read it as it lies
                # add the bias as they load, so what the projection writes is
                # what a remat policy saves and what backward hands them.
                bare = qkv is biased
                ctx = attention_packed(
                    product if bare else qkv, heads,
                    bias=p["attn_qkvb"] if bare else None,
                    mask=attention_mask, causal=causal,
                    dropout_rate=cfg.attn_dropout_ratio if train else 0.0,
                    dropout_rng=attn_rng, use_flash=use_flash,
                    mesh=mesh,
                )
            attn_out = apply_lora(
                cfg, p, lora, "attn_ow", ctx, ctx @ p["attn_ow"] + p["attn_ob"]
            )
            attn_out = hid_dropout(attn_out, h1_rng)
            x = residual + attn_out
            if not cfg.pre_layer_norm:
                x = layer_norm(x, p["attn_nw"], p["attn_nb"])

        # ---- feed-forward sublayer --------------------------------
        with jax.named_scope("dense_ffn"):
            residual = x
            ff_in = (
                layer_norm(x, p["norm_w"], p["norm_b"])
                if cfg.pre_layer_norm else x
            )
            ffn_aux = None
            if ffn_fn is not None:
                h = ffn_fn(ff_in)
                if isinstance(h, tuple):
                    h, ffn_aux = h
            else:
                h = apply_lora(
                    cfg, p, lora, "inter_w", ff_in,
                    ff_in @ p["inter_w"] + p["inter_b"],
                )
                # tanh-approx gelu, gelu_kernels.cu:38
                h = nn.gelu(h, approximate=True)
                h = apply_lora(
                    cfg, p, lora, "output_w", h,
                    h @ p["output_w"] + p["output_b"],
                )
            h = hid_dropout(h, h2_rng)
            x = residual + h
            if not cfg.pre_layer_norm:
                x = layer_norm(x, p["norm_w"], p["norm_b"])
        if return_kv:
            if ffn_aux is not None:
                raise ValueError(
                    "return_kv does not compose with an aux-returning "
                    "ffn_fn (MoE decode is not supported)"
                )
            return x, split_heads()[1:]
        return x if ffn_aux is None else (x, ffn_aux)

    if cfg.use_remat and not return_kv:
        if cfg.remat_policy == "full":
            block = jax.checkpoint(block)
        else:
            block = jax.checkpoint(
                block, policy=resolve_remat_policy(cfg.remat_policy)
            )
    return block(hidden_states)


def _attend_gathered(q, k_full, v_full, positions, live=None):
    """The XLA reference single-query decode attention over a gathered
    contiguous view: ``q`` [B, heads, hd], ``k_full``/``v_full`` [B,
    heads, K, hd], masked to key indices ``<= positions``. This is the
    bitwise-parity anchor — the contiguous and paged XLA paths run this
    EXACT arithmetic over identical views, so their greedy decode is
    bitwise-identical (pinned in tests/unit/test_paged_kv.py), and the
    fused Pallas kernel (ops/decode_attention.py) is validated against
    it.

    ``live`` [B] bool (paged path): slots whose block table is empty
    attend only the NULL page's garbage — their context is forced to
    exact zeros instead (``jnp.where`` keeps live rows bitwise-
    untouched), matching the fused kernel's dead-slot early-out."""
    b, heads, hd = q.shape
    max_len = k_full.shape[2]
    # [B, heads, max_len] scores in f32 (MXU-accumulate dtype discipline
    # of ops/attention.py); future positions masked by validity, so the
    # garbage beyond each row's length never contributes
    sm_scale = 1.0 / (hd ** 0.5)
    s = jnp.einsum(
        "bhd,bhkd->bhk", q, k_full, preferred_element_type=jnp.float32
    ) * sm_scale
    valid = (
        jax.lax.broadcasted_iota(jnp.int32, (b, 1, max_len), 2)
        <= positions[:, None, None]
    )
    s = jnp.where(valid, s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum(
        "bhk,bhkd->bhd", probs.astype(v_full.dtype), v_full
    )
    if live is not None:
        ctx = jnp.where(
            live[:, None, None], ctx, jnp.zeros((), ctx.dtype)
        )
    return ctx


def _decode_block_core(cfg, p, hidden_states, attend, lora=None):
    """The shared single-token decode block: LN/qkv/attention/FFN, with
    the attention CONTEXT computation abstracted behind ``attend(q,
    k_new, v_new) -> (ctx, carry)`` — ``q``/``k_new``/``v_new`` are this
    token's split-head projections [B, heads, hd], ``ctx`` the attention
    context [B, heads, hd] over every cached position, ``carry`` the
    updated cache container threaded back to the caller. Every cache
    layout (contiguous, paged-XLA, paged-fused-Pallas) shares the
    LN/qkv/FFN arithmetic through this function; the XLA layouts
    additionally share :func:`_attend_gathered`, which is what makes
    their greedy decode bitwise-identical (pinned in
    tests/unit/test_paged_kv.py).

    ``lora``: optional ``(pools, ids, scale[, fused])`` batched-adapter
    source (:func:`apply_lora`) — per-slot gathered A/B matmuls on every
    targeted projection, so one fixed-shape decode program serves slots
    running DIFFERENT adapters concurrently (id 0 = identity)."""
    H = cfg.hidden_size
    heads = cfg.heads
    head_dim = H // heads
    b = hidden_states.shape[0]

    def ln(x, scale, bias):
        return layer_norm_apply(cfg, x, scale, bias)

    # ---- attention sublayer, incremental ------------------------------
    residual = hidden_states
    attn_in = (
        ln(hidden_states, p["attn_nw"], p["attn_nb"])
        if cfg.pre_layer_norm else hidden_states
    )
    qkv = apply_lora(
        cfg, p, lora, "attn_qkvw", attn_in,
        attn_in @ p["attn_qkvw"] + p["attn_qkvb"],
    )  # [B, 1, 3H]
    q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, heads, head_dim)
    k_new = k_new.reshape(b, heads, head_dim)
    v_new = v_new.reshape(b, heads, head_dim)

    ctx, carry = attend(q, k_new, v_new)
    ctx = ctx.reshape(b, 1, H)
    attn_out = apply_lora(
        cfg, p, lora, "attn_ow", ctx, ctx @ p["attn_ow"] + p["attn_ob"]
    )
    x = residual + attn_out
    if not cfg.pre_layer_norm:
        x = ln(x, p["attn_nw"], p["attn_nb"])

    # ---- feed-forward sublayer (identical to the training block) ------
    residual = x
    ff_in = ln(x, p["norm_w"], p["norm_b"]) if cfg.pre_layer_norm else x
    h = apply_lora(
        cfg, p, lora, "inter_w", ff_in, ff_in @ p["inter_w"] + p["inter_b"]
    )
    h = nn.gelu(h, approximate=True)
    h = apply_lora(
        cfg, p, lora, "output_w", h, h @ p["output_w"] + p["output_b"]
    )
    x = residual + h
    if not cfg.pre_layer_norm:
        x = ln(x, p["norm_w"], p["norm_b"])
    return x, carry


def transformer_block_decode(
    cfg: DeepSpeedTransformerConfig,
    p: dict,
    hidden_states,
    k_cache,
    v_cache,
    positions,
    lora=None,
):
    """One KV-cache incremental-decode step through the block.

    ``hidden_states`` [B, 1, H] is the current token's hidden state per
    sequence (B = decode slots), ``k_cache``/``v_cache`` [B, heads,
    max_len, hd] hold every earlier position's projections, ``positions``
    [B] int32 is this token's position (== tokens already in the cache for
    that row). The block projects qkv for the single token, WRITES its k/v
    at ``positions``, and attends the query over cache positions
    ``<= positions`` — O(max_len) work instead of the O(S^2) full-sequence
    recompute (the reason models/gpt2.py's training ``__call__`` cannot
    serve decode traffic).

    Inference-only: eval-mode arithmetic (no dropout), shares
    ``layer_norm_apply`` and the reference 12-tensor layout with
    :func:`transformer_block_apply` so a greedy decode rollout reproduces
    the full-forward argmax trajectory (pinned by
    tests/unit/test_inference.py). Returns ``(out [B,1,H], k_cache,
    v_cache)`` with the updated caches.
    """
    b = hidden_states.shape[0]

    def attend(q, k_new, v_new):
        # scatter this token's k/v into the cache at its position
        # (advanced indexing pairs the two [B] index arrays, so row i
        # writes cache[i, :, positions[i]]); positions are clamped by the
        # caller's length accounting, and jit scatter drops OOB writes
        rows = jnp.arange(b)
        kc = k_cache.at[rows, :, positions, :].set(
            k_new.astype(k_cache.dtype)
        )
        vc = v_cache.at[rows, :, positions, :].set(
            v_new.astype(v_cache.dtype)
        )
        return _attend_gathered(q, kc, vc, positions), (kc, vc)

    x, (kc, vc) = _decode_block_core(
        cfg, p, hidden_states, attend, lora=lora
    )
    return x, kc, vc


def transformer_block_decode_paged(
    cfg: DeepSpeedTransformerConfig,
    p: dict,
    hidden_states,
    k_pool,
    v_pool,
    block_tables,
    positions,
    lora=None,
    fused=False,
):
    """One incremental-decode step over a BLOCK-PAGED KV cache.

    Same computation as :func:`transformer_block_decode` (it runs the
    identical ``_decode_block_core``), but the cache container is a
    global page pool ``k_pool``/``v_pool`` [num_blocks, block_size,
    heads, hd] indirected through ``block_tables`` [B, max_blocks] int32
    (PagedAttention, vLLM — PAPERS.md): slot i's logical position ``pos``
    lives at physical page ``block_tables[i, pos // block_size]``, offset
    ``pos % block_size``. Physical block 0 is the NULL page — unallocated
    table entries point at it, so dead slots' ride-along writes and
    gathers of never-written positions land in a sacrificial page whose
    garbage the validity mask zeroes out of every softmax.

    The write is a 2-element scatter per row; with ``fused=False``
    attention gathers the slot's pages back into a [B, heads,
    max_blocks*block_size, hd] view and runs the exact contiguous einsum
    over it — index arrays, not shapes, so slots joining/leaving/evicting
    never recompile. ``fused=True`` (``inference.fused_decode``) skips
    the gather entirely: the Pallas single-query flash-decode kernel
    (ops/decode_attention.py:paged_flash_decode) streams the slot's LIVE
    pages through VMEM via the block table with an online softmax — no
    gathered temporary, no compute on null pages or beyond each slot's
    position. Greedy-parity (not bitwise-logit) equivalent to the XLA
    path. Empty slots (zero-length block tables — the table's first
    entry is the null page) contribute exact-zero attention context on
    BOTH paths instead of attending the null page's garbage. Returns
    ``(out [B,1,H], k_pool, v_pool)``.
    """
    block_size = k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    b = hidden_states.shape[0]

    rows = jnp.arange(b)
    block_idx = jnp.minimum(positions // block_size, max_blocks - 1)
    phys = block_tables[rows, block_idx]  # [B]
    offs = positions % block_size  # [B]
    # a slot whose table starts at the null page holds no pages at all —
    # the dead-slot ride-along (scheduler keeps shapes fixed); its
    # attention context is forced to exact zeros rather than a softmax
    # over the null page's garbage
    live = block_tables[:, 0] != 0

    def attend(q, k_new, v_new):
        kp = k_pool.at[phys, offs, :, :].set(k_new.astype(k_pool.dtype))
        vp = v_pool.at[phys, offs, :, :].set(v_new.astype(v_pool.dtype))
        if fused:
            from .decode_attention import paged_flash_decode

            return paged_flash_decode(
                q, kp, vp, block_tables, positions
            ), (kp, vp)
        # gather each slot's pages into the contiguous logical view the
        # shared core attends over: [B, MB, bs, heads, hd] -> [B, heads,
        # MB*bs, hd] (transposed to the contiguous cache's layout so the
        # einsum contraction is the same HLO, hence bitwise)
        k_full = kp[block_tables].reshape(
            b, max_blocks * block_size, kp.shape[2], kp.shape[3]
        ).transpose(0, 2, 1, 3)
        v_full = vp[block_tables].reshape(
            b, max_blocks * block_size, vp.shape[2], vp.shape[3]
        ).transpose(0, 2, 1, 3)
        return _attend_gathered(
            q, k_full, v_full, positions, live=live
        ), (kp, vp)

    x, (kp, vp) = _decode_block_core(
        cfg, p, hidden_states, attend, lora=lora
    )
    return x, kp, vp


def transformer_block_prefill_paged(
    cfg: DeepSpeedTransformerConfig,
    p: dict,
    hidden_states,
    k_pool,
    v_pool,
    block_tables,
    start_pos,
    lora=None,
):
    """Suffix prefill through one block against cached prefix pages: the
    CROSS-REQUEST PREFIX CACHE's compute-skip path (docs/inference.md).

    ``hidden_states`` [B, S, H] holds the prompt's UNIQUE SUFFIX (padded
    to a fixed bucket), whose first token sits at absolute position
    ``start_pos`` [B] — the length of the shared, already-cached prefix
    (always a whole number of pages). The block projects qkv for the
    suffix tokens, writes their k/v into the slot's own pages, and runs
    causal attention over the ENTIRE gathered page view — cached prefix
    pages (computed once by whichever request was cold first) plus the
    suffix's just-written pages — so a templated prompt pays compute for
    its unique tail only. Eval-mode arithmetic mirroring
    :func:`transformer_block_apply`; padding rows write beyond the prompt
    into positions later overwritten by decode (and masked until then).
    Returns ``(out [B,S,H], k_pool, v_pool)``.
    """
    H = cfg.hidden_size
    heads = cfg.heads
    head_dim = H // heads
    b, s, _ = hidden_states.shape
    block_size = k_pool.shape[1]
    max_blocks = block_tables.shape[1]
    kv_len = max_blocks * block_size

    def ln(x, scale, bias):
        return layer_norm_apply(cfg, x, scale, bias)

    # ---- attention sublayer ------------------------------------------
    residual = hidden_states
    attn_in = (
        ln(hidden_states, p["attn_nw"], p["attn_nb"])
        if cfg.pre_layer_norm else hidden_states
    )
    qkv = apply_lora(
        cfg, p, lora, "attn_qkvw", attn_in,
        attn_in @ p["attn_qkvw"] + p["attn_qkvb"],
    )  # [B, S, 3H]
    q, k_new, v_new = jnp.split(qkv, 3, axis=-1)

    def split_heads(t):
        return t.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)

    qh = split_heads(q)  # [B, heads, S, hd]

    # absolute position of each suffix row, its page, and its offset
    positions = start_pos[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (b, s), 1
    )  # [B, S]
    block_idx = jnp.minimum(positions // block_size, max_blocks - 1)
    phys = jnp.take_along_axis(block_tables, block_idx, axis=1)  # [B, S]
    # rows past the slot's logical extent write to the NULL page instead
    # of clamping into the slot's REAL last page (which may be a SHARED
    # prefix page another request still attends). The prefix-hit path
    # never pads past kv_len (engine._suffix_bucket guarantees it — the
    # redirect is then an identity select), but the speculative VERIFY
    # step reuses this block with per-slot start positions that can run
    # within k tokens of the cap.
    phys = jnp.where(positions < kv_len, phys, 0)
    offs = positions % block_size
    k_rows = k_new.reshape(b, s, heads, head_dim)  # [B, S, heads, hd]
    v_rows = v_new.reshape(b, s, heads, head_dim)
    k_pool = k_pool.at[phys, offs, :, :].set(k_rows.astype(k_pool.dtype))
    v_pool = v_pool.at[phys, offs, :, :].set(v_rows.astype(v_pool.dtype))

    # gather prefix + suffix pages into the logical view and attend
    # causally: suffix row j (absolute position start+j) sees key
    # positions <= start+j — the cached prefix in full, the suffix up to
    # and including itself
    k_full = k_pool[block_tables].reshape(
        b, kv_len, heads, head_dim
    ).transpose(0, 2, 1, 3)  # [B, heads, K, hd]
    v_full = v_pool[block_tables].reshape(
        b, kv_len, heads, head_dim
    ).transpose(0, 2, 1, 3)
    sm_scale = 1.0 / (head_dim ** 0.5)
    scores = jnp.einsum(
        "bhsd,bhkd->bhsk", qh, k_full, preferred_element_type=jnp.float32
    ) * sm_scale
    kpos = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, kv_len), 3)
    valid = kpos <= positions[:, None, :, None]  # [B, 1, S, K]
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum(
        "bhsk,bhkd->bhsd", probs.astype(v_full.dtype), v_full
    )
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, H)
    attn_out = apply_lora(
        cfg, p, lora, "attn_ow", ctx, ctx @ p["attn_ow"] + p["attn_ob"]
    )
    x = residual + attn_out
    if not cfg.pre_layer_norm:
        x = ln(x, p["attn_nw"], p["attn_nb"])

    # ---- feed-forward sublayer ---------------------------------------
    residual = x
    ff_in = ln(x, p["norm_w"], p["norm_b"]) if cfg.pre_layer_norm else x
    h = apply_lora(
        cfg, p, lora, "inter_w", ff_in, ff_in @ p["inter_w"] + p["inter_b"]
    )
    h = nn.gelu(h, approximate=True)
    h = apply_lora(
        cfg, p, lora, "output_w", h, h @ p["output_w"] + p["output_b"]
    )
    x = residual + h
    if not cfg.pre_layer_norm:
        x = ln(x, p["norm_w"], p["norm_b"])
    return x, k_pool, v_pool


class DeepSpeedTransformerLayer(nn.Module):
    """One transformer block. __call__(hidden [B,S,H], attention_mask
    additive [B,1,1,S] or None) -> [B,S,H]."""

    config: DeepSpeedTransformerConfig
    causal: bool = False
    use_flash: bool = True
    # When a mesh with a >1 ``sequence`` axis is supplied, attention runs
    # sequence-parallel (ring / Ulysses all-to-all, parallel/sequence.py) —
    # the long-context path the reference cannot express (its kernel caps
    # seq at 1024, ds_transformer_cuda.cpp:133).
    mesh: Optional[object] = None
    seq_parallel_impl: str = "auto"

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None, train: bool = True):
        cfg = self.config
        H = cfg.hidden_size
        dtype = hidden_states.dtype
        init = nn.initializers.normal(stddev=cfg.initializer_range)
        shapes = {"H": H, "3H": 3 * H, "I": cfg.intermediate}
        makers = {
            "init": (init, dtype),
            "zeros": (nn.initializers.zeros, dtype),
            "ones32": (nn.initializers.ones, jnp.float32),
            "zeros32": (nn.initializers.zeros, jnp.float32),
        }
        p = {
            name: self.param(
                name, makers[kind][0],
                tuple(shapes[d] for d in dims), makers[kind][1],
            )
            for name, dims, kind in TRANSFORMER_PARAM_LAYOUT
        }
        if cfg.lora_rank > 0:
            # rank-r A/B pairs beside their base matrices: A ~ N(0, std)
            # and B = 0, so the initial delta is EXACTLY zero and a fresh
            # adapter starts from the base model's behavior (Hu et al.).
            # NOTE: a from-scratch init of a rank-r module draws DIFFERENT
            # base values than a rank-0 init (nn.scan's rng splitting is
            # call-count based) — to adapt an existing base bitwise, init
            # the base rank-0 and grow adapters with
            # adapters.init_lora_params (the engine's "adapters" path).
            r = int(cfg.lora_rank)
            for t in resolve_lora_targets(cfg.lora_targets):
                din, dout = (shapes[d] for d in LORA_TARGET_DIMS[t])
                p[f"{t}_lora_a"] = self.param(
                    f"{t}_lora_a", init, (din, r), dtype
                )
                p[f"{t}_lora_b"] = self.param(
                    f"{t}_lora_b", nn.initializers.zeros, (r, dout), dtype
                )

        need_rng = train and (
            cfg.attn_dropout_ratio > 0 or cfg.hidden_dropout_ratio > 0
        )
        rng = self.make_rng("dropout") if need_rng else None
        return transformer_block_apply(
            cfg, p, hidden_states, attention_mask,
            causal=self.causal, use_flash=self.use_flash, mesh=self.mesh,
            seq_parallel_impl=self.seq_parallel_impl, train=train,
            dropout_rng=rng,
        )
