"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has NO sequence parallelism — its only long-sequence tools are
activation checkpointing and a hard ``seq_length <= 1024`` kernel cap
(reference: csrc/transformer/ds_transformer_cuda.cpp:133, SURVEY.md §2.4).
This module is the TPU-first upgrade: shard the token dimension over the
mesh ``sequence`` axis and keep attention exact via either

  * **ring attention** (`ring_attention`): K/V chunks rotate around the
    sequence axis with ``lax.ppermute`` while each device accumulates an
    online softmax over its local queries. Peak memory per device is
    O(S/sp * S/sp) for one score block; ICI traffic per step is one K/V
    chunk, fully overlappable with the block matmul. Works for any head
    count, supports causal masking (ring steps that lie entirely in the
    masked future are skipped via masking) and per-key padding masks that
    travel with the K/V chunks.

  * **Ulysses-style all-to-all** (`ulysses_attention`): two
    ``lax.all_to_all`` collectives re-shard [B, H, S/sp, D] into
    [B, H/sp, S, D], run ordinary (flash) attention on the full sequence
    with a head subset, and shard back. Cheaper collectives than the ring
    (2 all-to-alls vs sp-1 permutes) but requires heads % sp == 0.

Both are written as *local* functions (operands are per-device shards,
callable inside an enclosing ``shard_map``) plus global convenience
wrappers that apply the ``shard_map`` themselves. The wrappers are jit-
compatible and differentiable: backward is JAX autodiff through the scan /
collectives (ppermute transposes to the inverted permutation, all_to_all to
its inverse), so there is no hand-maintained VJP to drift out of sync.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..config import constants as C
from ..ops.attention import NEG_INF, flash_attention, mha_reference

DATA_AXIS = C.DATA_AXIS
SEQ_AXIS = C.SEQUENCE_AXIS
MODEL_AXIS = C.MODEL_AXIS


def _axis_size(axis_name):
    return jax.lax.psum(1, axis_name)


# ---------------------------------------------------------------------------
# Ring attention (local form: call inside shard_map over the sequence axis)
# ---------------------------------------------------------------------------
def ring_attention_local(
    q,
    k,
    v,
    kv_valid=None,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    remat_steps: bool = True,
):
    """Exact attention over a sequence-sharded [B, H, S/sp, D] layout.

    Device with index ``i`` on ``axis_name`` holds global token positions
    ``[i*Sl, (i+1)*Sl)`` for q, k, v (and ``kv_valid`` [B, Sl], nonzero =
    attend). K/V (and the validity vector) rotate one hop per ring step;
    each step folds one score block into an online-softmax accumulator
    (same math as the flash kernel's inter-block combine,
    ops/attention.py:_fwd_kernel, lifted to the mesh level).
    """
    B, H, Sl, D = q.shape
    sp = _axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if sm_scale is None:
        sm_scale = 1.0 / (D**0.5)
    # kv moves j -> j+1 each step, so at step t device i holds chunk (i-t)%sp
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    # matmul operands stay in the storage dtype (bf16 MXU pairs with f32
    # accumulation via preferred_element_type — an explicit f32 upcast
    # forces the slow f32 MXU path, the round-4 flash-kernel finding);
    # only the online-softmax bookkeeping (m, l, acc) runs f32
    iota_q = jax.lax.iota(jnp.int32, Sl)
    gq = idx * Sl + iota_q  # global query positions [Sl]
    have_valid = kv_valid is not None
    use_dropout = dropout_rate > 0.0 and dropout_rng is not None

    def step_body(carry, t):
        k_c, v_c, kvv, m, l, acc = carry
        chunk = (idx - t) % sp
        s = (
            jnp.einsum(
                "bhqd,bhkd->bhqk",
                q,
                k_c,
                preferred_element_type=jnp.float32,
            )
            * sm_scale
        )  # [B,H,Sl,Sl] f32
        gk = chunk * Sl + jax.lax.iota(jnp.int32, Sl)  # global key positions
        if causal:
            s = jnp.where(gk[None, None, None, :] <= gq[None, None, :, None], s, NEG_INF)
        if have_valid:
            s = jnp.where(kvv[:, None, None, :] > 0, s, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        # zero masked entries: for an all-masked row m_new == NEG_INF and
        # exp(s - m_new) would be exp(0) = 1 everywhere
        p = jnp.where(s > NEG_INF / 2, p, 0.0)
        if use_dropout:
            # per (device, step) fold keeps masks independent across ring hops
            step_key = jax.random.fold_in(jax.random.fold_in(dropout_rng, t), idx)
            keep = jax.random.bernoulli(step_key, 1.0 - dropout_rate, p.shape)
            p_use = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        else:
            p_use = p
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd",
            p_use.astype(v_c.dtype),
            v_c,
            preferred_element_type=jnp.float32,
        )

        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        if have_valid:
            kvv = jax.lax.ppermute(kvv, axis_name, perm)
        return (k_c, v_c, kvv, m_new, l_new, acc_new), None

    if remat_steps:
        step_body = jax.checkpoint(step_body)

    m0 = jnp.full((B, H, Sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sl), jnp.float32)
    acc0 = jnp.zeros((B, H, Sl, D), jnp.float32)
    kvv0 = kv_valid if have_valid else jnp.zeros((B, 1), jnp.int32)
    (_, _, _, m, l, acc), _ = jax.lax.scan(
        step_body, (k, v, kvv0, m0, l0, acc0), jnp.arange(sp)
    )
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
    # NOTE: dropout uses the *undropped* normalizer l (matching the flash
    # kernel and the reference, which drop softmax probs post-normalization).
    return (acc / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Ulysses all-to-all attention (local form)
# ---------------------------------------------------------------------------
def ulysses_attention_local(
    q,
    k,
    v,
    kv_valid=None,
    *,
    axis_name: str = SEQ_AXIS,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng=None,
    use_flash: bool = True,
):
    """All-to-all sequence parallelism: [B, H, S/sp, D] -> attention over the
    full sequence with H/sp heads per device -> shard back.

    Requires H % sp == 0. The head dimension is re-sharded so each device
    sees every token for a subset of heads; attention itself is then the
    ordinary single-device kernel (Pallas flash on TPU).
    """
    B, H, Sl, D = q.shape
    sp = _axis_size(axis_name)
    if H % sp != 0:
        raise ValueError(f"ulysses needs heads % sp == 0, got H={H}, sp={sp}")

    def seq_to_heads(x):  # [B,H,Sl,D] -> [B,H/sp,S,D]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    kvv_full = None
    if kv_valid is not None:
        kvv_full = jax.lax.all_gather(kv_valid, axis_name, axis=1, tiled=True)

    use_dropout = dropout_rate > 0.0 and dropout_rng is not None
    if use_dropout:
        # each device owns distinct heads -> distinct masks per device
        dropout_rng = jax.random.fold_in(dropout_rng, jax.lax.axis_index(axis_name))

    S = Sl * sp
    from ..ops.attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, pick_block
    from ..utils import device
    from ..utils.logging import warn_once

    bq, bk = pick_block(S, DEFAULT_BLOCK_Q), pick_block(S, DEFAULT_BLOCK_K)
    # off-TPU the XLA path is the design (the interpreter is no faster);
    # on the chip a refused shape is named once
    on_tpu = device.on_tpu()
    can_flash = use_flash and on_tpu and bq > 0 and bk > 0
    if use_flash and on_tpu and not can_flash:
        warn_once(
            f"ulysses-flash-gave-way:{S}",
            "ulysses attention: no flash block divides the gathered "
            "sequence %d; running the O(S^2) XLA path", S,
        )
    if can_flash:
        seed = jnp.asarray(0, jnp.int32)
        if use_dropout:
            seed = jax.random.randint(dropout_rng, (), 0, 2**31 - 1)
        ctx = flash_attention(
            qg, kg, vg, kv_mask=kvv_full, causal=causal, sm_scale=sm_scale,
            dropout_rate=dropout_rate if use_dropout else 0.0, dropout_seed=seed,
            block_q=bq, block_k=bk,
        )
    else:
        mask = None
        if kvv_full is not None:
            mask = jnp.where(kvv_full > 0, 0.0, NEG_INF)[:, None, None, :]
        ctx = mha_reference(
            qg, kg, vg, mask=mask, causal=causal, sm_scale=sm_scale,
            dropout_rate=dropout_rate if use_dropout else 0.0,
            dropout_rng=dropout_rng if use_dropout else None,
        )
    # [B,H/sp,S,D] -> [B,H,Sl,D]
    return jax.lax.all_to_all(ctx, axis_name, split_axis=2, concat_axis=1, tiled=True)


# ---------------------------------------------------------------------------
# Global wrappers: shard_map applied for you
# ---------------------------------------------------------------------------
def _mesh_axes(mesh, seq_axis, batch_axis, head_axis):
    """Tolerate user meshes without data/model axes (a plain
    ('data','sequence') or even ('sequence',) mesh is legal); the sequence
    axis itself is mandatory."""
    axes = dict(mesh.shape)
    if seq_axis not in axes:
        raise ValueError(
            f"sequence-parallel attention needs a {seq_axis!r} axis on the "
            f"mesh; got axes {tuple(axes)}"
        )
    return (
        batch_axis if batch_axis in axes else None,
        head_axis if head_axis in axes else None,
    )


def _shard_mapped(local_fn, mesh, have_valid, have_rng, seq_axis, batch_axis, head_axis):
    qkv_spec = P(batch_axis, head_axis, seq_axis, None)
    kvv_spec = P(batch_axis, seq_axis)
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if have_valid:
        in_specs.append(kvv_spec)
    if have_rng:
        in_specs.append(P())
    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        check_vma=False,
    )


def _global_form(local_kernel):
    @functools.wraps(local_kernel)
    def wrapper(
        q, k, v, mesh: Mesh, kv_valid=None, *, causal=False, sm_scale=None,
        dropout_rate=0.0, dropout_rng=None, seq_axis=SEQ_AXIS,
        batch_axis=DATA_AXIS, head_axis=MODEL_AXIS, **kw,
    ):
        have_valid = kv_valid is not None
        have_rng = dropout_rng is not None and dropout_rate > 0.0
        batch_axis, head_axis = _mesh_axes(mesh, seq_axis, batch_axis, head_axis)

        def local_fn(*args):
            args = list(args)
            q_, k_, v_ = args[:3]
            kvv = args[3] if have_valid else None
            rng = args[3 + int(have_valid)] if have_rng else None
            return local_kernel(
                q_, k_, v_, kvv, axis_name=seq_axis, causal=causal,
                sm_scale=sm_scale,
                dropout_rate=dropout_rate if have_rng else 0.0,
                dropout_rng=rng, **kw,
            )

        fn = _shard_mapped(
            local_fn, mesh, have_valid, have_rng, seq_axis, batch_axis, head_axis
        )
        args = [q, k, v]
        if have_valid:
            args.append(kv_valid)
        if have_rng:
            args.append(dropout_rng)
        return fn(*args)

    return wrapper


ring_attention = _global_form(ring_attention_local)
ring_attention.__name__ = "ring_attention"
ulysses_attention = _global_form(ulysses_attention_local)
ulysses_attention.__name__ = "ulysses_attention"


def sequence_parallel_attention(
    q, k, v, mesh: Mesh, kv_valid=None, *, impl="auto", use_flash=True, **kw,
):
    """Dispatcher: 'ring' | 'ulysses' | 'auto' (ulysses when the *per-device*
    head count — global heads / model-axis size — divides evenly by the
    sequence-axis size: fewer collectives — else ring). ``use_flash`` only
    affects the ulysses path (ring is an exact mesh-level decomposition with
    no kernel choice)."""
    axes = dict(mesh.shape)
    seq_axis = kw.get("seq_axis", SEQ_AXIS)
    if seq_axis not in axes:
        raise ValueError(
            f"sequence-parallel attention needs a {seq_axis!r} axis on the "
            f"mesh; got axes {tuple(axes)}"
        )
    sp = axes[seq_axis]
    mp = axes.get(kw.get("head_axis", MODEL_AXIS), 1)
    local_heads, rem = divmod(q.shape[1], mp)
    if impl == "auto":
        impl = "ulysses" if rem == 0 and local_heads % sp == 0 else "ring"
    if impl == "ulysses":
        return ulysses_attention(q, k, v, mesh, kv_valid, use_flash=use_flash, **kw)
    if impl == "ring":
        return ring_attention(q, k, v, mesh, kv_valid, **kw)
    raise ValueError(f"unknown sequence-parallel impl {impl!r}")
