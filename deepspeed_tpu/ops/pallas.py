"""Pallas fused optimizer kernels.

TPU analog of the reference's fused LAMB CUDA kernel
(reference: csrc/lamb/fused_lamb_cuda_kernel.cu — part1 computes the Adam
update and per-block L2 partials of the weight and the update, part2
reduces the partials across blocks, part3 applies the clamped trust ratio
``clamp(||w||/||u||, min_coeff, max_coeff)``; host driver
csrc/lamb/fused_lamb_cuda.cpp:32-104, python frontend
deepspeed/pt/deepspeed_fused_lamb.py:13-201).

TPU mapping:
  * **phase 1 is the Pallas kernel** (`_lamb_phase1_kernel`): one pass over
    HBM reading (p, g, m, v) and writing (m', v', u) while accumulating the
    ``sum(p*p)`` / ``sum(u*u)`` partials per grid block — the fusion the
    CUDA kernel exists for (XLA tends to split the norm reductions from the
    moment updates into separate passes over the same buffers).
  * **phases 2+3 stay in XLA**: the cross-block reduction is a tiny
    [nblk, 128] sum and the trust-ratio apply is one fused elementwise pass
    — exactly the work XLA schedules optimally, so hand-writing it would
    only fight the compiler.

`FusedLamb` wraps this per-leaf (the reference kernel is likewise invoked
per-parameter, deepspeed_fused_lamb.py:167-181) behind the same
``Optimizer`` interface as the pure-JAX `Lamb`, with identical numerics and
the same ``lamb_coeffs`` introspection.

Measured verdict (v5e, BERT-large 336M-param bench, full train step):
358 samples/s with the XLA-fused `Lamb` vs 344 with this kernel — XLA's
own fusion of the update math is already optimal on TPU and the kernel's
explicit ``u`` output costs one extra HBM write per step. `FusedLamb` is
therefore opt-in (config optimizer type "FusedLamb"), kept as the faithful
analog of the reference's kernel and as the base for multi-tensor variants
on very fragmented pytrees, where per-leaf XLA dispatch overhead dominates;
"Lamb" stays the XLA-fused default. This is the hand-scheduling-vs-compiler
tradeoff called out in ops/transformer.py:12-21, measured rather than
assumed.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import device
from .optimizers import Lamb, _f32


def _smem():
    return pltpu.SMEM

# One grid block processes BLOCK_ROWS x 128 f32 elements of the flattened
# leaf. 8 KiB/operand keeps 7 operands well inside VMEM.
BLOCK_ROWS = 256
LANES = 128
BLOCK = BLOCK_ROWS * LANES



def _lamb_phase1_kernel(
    scal_ref, p_ref, g_ref, m_ref, v_ref,
    m_out, v_out, u_out, wsq_out, usq_out,
    *, b1, b2, eps, weight_decay, eps_inside_sqrt,
):
    c1 = scal_ref[0]
    c2 = scal_ref[1]
    p = p_ref[...]
    g = g_ref[...]
    m_new = b1 * m_ref[...] + (1.0 - b1) * g
    v_new = b2 * v_ref[...] + (1.0 - b2) * g * g
    if eps_inside_sqrt:
        denom = jnp.sqrt(v_new / c2 + eps)
    else:
        denom = jnp.sqrt(v_new / c2) + eps
    u = (m_new / c1) / denom
    if weight_decay:
        u = u + weight_decay * p
    m_out[...] = m_new
    v_out[...] = v_new
    u_out[...] = u
    # per-block L2 partials folded to an (8, 128) tile — TPU blocks need
    # (8, 128)-divisible trailing dims (part1's s_a/s_b shared-memory
    # reductions, fused_lamb_cuda_kernel.cu:186-231)
    grp = p.shape[0] // 8
    wsq_out[0] = jnp.sum((p * p).reshape(8, grp, p.shape[1]), axis=1)
    usq_out[0] = jnp.sum((u * u).reshape(8, grp, p.shape[1]), axis=1)


def lamb_leaf_update(
    p, g, m, v, c1, c2, lr,
    *, b1, b2, eps, weight_decay, min_coeff, max_coeff, eps_inside_sqrt,
    interpret=None,
):
    """Fused LAMB update of ONE flattened leaf. Returns
    (p_new, m_new, v_new, trust_ratio)."""
    if interpret is None:
        interpret = not device.on_tpu()
    n = p.size
    nblk = max(1, -(-n // BLOCK))
    padded = nblk * BLOCK

    def prep(x):
        flat = _f32(x).reshape(-1)
        if padded != n:
            flat = jnp.pad(flat, (0, padded - n))
        return flat.reshape(nblk * BLOCK_ROWS, LANES)

    p2, g2, m2, v2 = prep(p), prep(g), prep(m), prep(v)
    scal = jnp.stack([_f32(c1), _f32(c2)])

    kernel = functools.partial(
        _lamb_phase1_kernel,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        eps_inside_sqrt=eps_inside_sqrt,
    )
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    partial_blk = pl.BlockSpec((1, 8, LANES), lambda i: (i, 0, 0))
    m_new, v_new, u, wsq, usq = pl.pallas_call(
        kernel,
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=_smem()),
            blk, blk, blk, blk,
        ],
        out_specs=[blk, blk, blk, partial_blk, partial_blk],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct((nblk, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblk, 8, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="lamb_phase1",
    )(scal, p2, g2, m2, v2)

    # phase 2: cross-block reduction (fused_lamb_cuda_kernel.cu:233-250)
    w_norm = jnp.sqrt(jnp.sum(wsq))
    u_norm = jnp.sqrt(jnp.sum(usq))
    ratio = jnp.where(
        (w_norm > 0) & (u_norm > 0),
        jnp.clip(w_norm / u_norm, min_coeff, max_coeff),
        jnp.float32(1.0),
    )
    # phase 3: apply trust ratio (one fused elementwise pass; :252-283)
    p_new2 = p2 - lr * ratio * u

    def unprep(x2):
        return x2.reshape(-1)[:n].reshape(p.shape)

    return (
        unprep(p_new2).astype(p.dtype),
        unprep(m_new),
        unprep(v_new),
        ratio,
    )


def lamb_multi_tensor_update(
    ps, gs, ms, vs, c1, c2, lr,
    *, b1, b2, eps, weight_decay, min_coeff, max_coeff, eps_inside_sqrt,
    interpret=None,
):
    """Fused LAMB update of MANY small leaves in ONE kernel launch — the
    TPU analog of the reference's multi-tensor-apply batching
    (csrc/lamb/fused_lamb_cuda.cpp drives one kernel per tensor; apex's
    multi_tensor_apply batches chunks of many tensors per launch, which is
    the regime where per-tensor dispatch overhead dominates).

    Each leaf pads to a whole number of kernel blocks and the leaves
    concatenate into one flat buffer, so one ``pallas_call`` computes
    every moment update plus per-BLOCK L2 partials; a static
    block->segment map then reduces the partials per LEAF (phase 2) and
    broadcasts each leaf's clamped trust ratio back over its blocks
    (phase 3) — still exactly one elementwise pass over HBM per phase.

    Returns (new_ps, new_ms, new_vs, ratios) with lists parallel to the
    inputs.
    """
    import numpy as np

    if interpret is None:
        interpret = not device.on_tpu()
    nblks = [max(1, -(-p.size // BLOCK)) for p in ps]
    offsets = np.cumsum([0] + nblks)
    nblk_total = int(offsets[-1])
    seg_ids = np.repeat(np.arange(len(ps)), nblks)

    def prep(x, n_pad_blocks):
        flat = _f32(x).reshape(-1)
        padded = n_pad_blocks * BLOCK
        if padded != flat.size:
            flat = jnp.pad(flat, (0, padded - flat.size))
        return flat

    p2 = jnp.concatenate([prep(p, nb) for p, nb in zip(ps, nblks)])
    g2 = jnp.concatenate([prep(g, nb) for g, nb in zip(gs, nblks)])
    m2 = jnp.concatenate([prep(m, nb) for m, nb in zip(ms, nblks)])
    v2 = jnp.concatenate([prep(v, nb) for v, nb in zip(vs, nblks)])
    shape2 = (nblk_total * BLOCK_ROWS, LANES)
    p2, g2, m2, v2 = (x.reshape(shape2) for x in (p2, g2, m2, v2))
    scal = jnp.stack([_f32(c1), _f32(c2)])

    kernel = functools.partial(
        _lamb_phase1_kernel,
        b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
        eps_inside_sqrt=eps_inside_sqrt,
    )
    blk = pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))
    partial_blk = pl.BlockSpec((1, 8, LANES), lambda i: (i, 0, 0))
    m_new, v_new, u, wsq, usq = pl.pallas_call(
        kernel,
        grid=(nblk_total,),
        in_specs=[pl.BlockSpec(memory_space=_smem()), blk, blk, blk, blk],
        out_specs=[blk, blk, blk, partial_blk, partial_blk],
        out_shape=[
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct(shape2, jnp.float32),
            jax.ShapeDtypeStruct((nblk_total, 8, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblk_total, 8, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="lamb_phase1",
    )(scal, p2, g2, m2, v2)

    # phase 2: per-SEGMENT (= per-leaf) reduction of the block partials
    blk_w = jnp.sum(wsq, axis=(1, 2))
    blk_u = jnp.sum(usq, axis=(1, 2))
    seg = jnp.asarray(seg_ids)
    w_norm = jnp.sqrt(jax.ops.segment_sum(blk_w, seg, len(ps)))
    u_norm = jnp.sqrt(jax.ops.segment_sum(blk_u, seg, len(ps)))
    ratios = jnp.where(
        (w_norm > 0) & (u_norm > 0),
        jnp.clip(w_norm / u_norm, min_coeff, max_coeff),
        jnp.float32(1.0),
    )
    # phase 3: broadcast each leaf's ratio over its blocks; one fused pass
    ratio_per_block = ratios[seg]  # static gather
    p_new2 = (
        p2.reshape(nblk_total, BLOCK_ROWS, LANES)
        - lr * ratio_per_block[:, None, None]
        * u.reshape(nblk_total, BLOCK_ROWS, LANES)
    ).reshape(-1)
    m_new, v_new = m_new.reshape(-1), v_new.reshape(-1)

    new_ps, new_ms, new_vs = [], [], []
    for i, p in enumerate(ps):
        lo = int(offsets[i]) * BLOCK
        n = p.size

        def cut(flat2):
            return jax.lax.slice(flat2, (lo,), (lo + n,)).reshape(p.shape)

        new_ps.append(cut(p_new2).astype(p.dtype))
        new_ms.append(cut(m_new))
        new_vs.append(cut(v_new))
    return new_ps, new_ms, new_vs, [ratios[i] for i in range(len(ps))]


@dataclasses.dataclass
class FusedLamb(Lamb):
    """LAMB backed by the Pallas phase-1 kernel; numerics identical to the
    pure-JAX `Lamb` (same trust-ratio clamp, same ``lamb_coeffs`` aux).

    Leaves smaller than ``multi_tensor_max`` elements batch into ONE
    packed kernel launch (``lamb_multi_tensor_update``); larger leaves run
    the per-leaf kernel. ``multi_tensor_max=0`` disables batching."""

    # the opaque pallas_call cannot fold a skip-gate select into its
    # update pass — overflow skips go through the engine's lax.cond path
    supports_gate = False
    # b1 is a compile-time kernel constant; a traced OneCycle momentum
    # would recompile the kernel every step — use 'Lamb' for mom cycling
    supports_mom = False
    multi_tensor_max: int = 1 << 21  # 2M elements (64 kernel blocks)

    def apply(self, params, grads, state, lr, grad_scale=None):
        if self.state_dtype != "fp32":
            raise ValueError(
                "FusedLamb's Pallas kernel reads fp32 moments; use "
                "optimizer type 'Lamb' for reduced state_dtype storage"
            )
        step = state["step"] + 1
        if self.bias_correction:
            c1 = 1.0 - self.b1 ** step.astype(jnp.float32)
            c2 = 1.0 - self.b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = jnp.float32(1.0)
        if grad_scale is not None:
            # pre-scale per-leaf (the kernel takes raw grads); FusedLamb
            # targets BERT-sized models where a scaled copy is cheap
            grads = jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) * grad_scale).astype(g.dtype),
                grads,
            )

        kw = dict(
            b1=self.b1, b2=self.b2, eps=self.eps,
            weight_decay=self.weight_decay,
            min_coeff=self.min_coeff, max_coeff=self.max_coeff,
            eps_inside_sqrt=self.eps_inside_sqrt,
        )
        flat_p, treedef = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        flat_m = jax.tree_util.tree_leaves(state["mu"])
        flat_v = jax.tree_util.tree_leaves(state["nu"])
        n_leaves = len(flat_p)
        small = [
            i for i, p in enumerate(flat_p)
            if self.multi_tensor_max and p.size <= self.multi_tensor_max
        ]
        out_p = [None] * n_leaves
        out_m = [None] * n_leaves
        out_v = [None] * n_leaves
        coeffs = [None] * n_leaves
        if len(small) >= 2:
            new_ps, new_ms, new_vs, ratios = lamb_multi_tensor_update(
                [flat_p[i] for i in small], [flat_g[i] for i in small],
                [flat_m[i] for i in small], [flat_v[i] for i in small],
                c1, c2, lr, **kw,
            )
            for j, i in enumerate(small):
                out_p[i], out_m[i], out_v[i] = new_ps[j], new_ms[j], new_vs[j]
                coeffs[i] = ratios[j]
        else:
            small = []
        for i in range(n_leaves):
            if out_p[i] is not None:
                continue
            out_p[i], out_m[i], out_v[i], coeffs[i] = lamb_leaf_update(
                flat_p[i], flat_g[i], flat_m[i], flat_v[i], c1, c2, lr, **kw,
            )
        new_params = jax.tree_util.tree_unflatten(treedef, out_p)
        new_mu = jax.tree_util.tree_unflatten(treedef, out_m)
        new_nu = jax.tree_util.tree_unflatten(treedef, out_v)
        aux = {"lamb_coeffs": coeffs}
        return new_params, {"step": step, "mu": new_mu, "nu": new_nu}, aux
