"""Core optimizers with a uniform functional interface.

Replaces the reference's optimizer zoo — apex FusedAdam (consumed at
deepspeed/pt/deepspeed_light.py:536), FusedLamb
(deepspeed/pt/deepspeed_fused_lamb.py:13-201 + csrc/lamb CUDA kernels) — with
pure-JAX updates. With float32 or bf16 state "fusion" needs no hand-written
kernel: each leaf's update is a handful of elementwise ops that XLA fuses
into one or two HBM passes. The int8 first moment is the exception: its
per-run absmax and the codes' decode and encode do not fuse into one pass
(PERF.md, PR 27), so Adam hands every leaf stored that way to the
one-pass kernel ``ops/pallas.py:adam_leaf_update`` where its shape allows,
and keeps the plain XLA update, over the same ``adam_core``, for the rest.
``deepspeed_tpu.ops.pallas.FusedLamb`` (config name "FusedLamb")
is the hand-fused variant mirroring the reference's 3-phase CUDA kernel:
the Adam update and both L2-norm partial reductions happen in a single
Pallas pass over HBM.

LAMB reproduces the reference's trust-ratio semantics (csrc/lamb/
fused_lamb_cuda_kernel.cu part1-3: Adam update, L2 norms of weight & update,
``clamp(||w||/||u||, min_coeff, max_coeff)``) including the ``lamb_coeffs``
introspection surface (deepspeed_fused_lamb.py:183-201).

Interface: ``opt.init(params) -> state``;
``opt.apply(params, grads, state, lr) -> (new_params, new_state, aux)``.
``lr`` is a traced scalar so LR schedules don't retrigger compilation.
State is fp32 ("master" precision) regardless of param dtype unless
``state_dtype`` says otherwise, matching the fp32-master-weights design of
the reference's FP16 optimizers; the arithmetic is float32 either way.
"""

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..utils.logging import logger


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _tree_f32(tree):
    return jax.tree_util.tree_map(_f32, tree)


class Optimizer:
    """Base class; subclasses implement leaf-wise update math.

    ``grad_scale``: optional scalar folded into each leaf's fp32 grad cast
    (g32 = f32(g) * grad_scale). The engine passes its combined
    loss-unscale x clip factor here so gradients stay in the accumulation
    dtype end-to-end — materializing a pre-scaled fp32 copy of a
    billion-param grad tree (~6 GB) is what OOMed GPT-2 1.5B on one chip.

    ``mom`` (optimizers with ``supports_mom = True``): optional traced
    scalar overriding the first-moment coefficient (``b1`` / SGD
    ``momentum``) for THIS step — the OneCycle momentum-cycling hook
    (reference deepspeed_lr_schedules.py:477-520 mutates optimizer groups;
    here the engine threads the scheduler's ``get_mom()`` value through the
    jit like ``lr``, so cycling never recompiles).

    ``gate`` (optimizers with ``supports_gate = True``): scalar bool; False
    makes the whole update a bit-exact no-op by selecting the OLD stored
    bytes just before every write. This replaces a ``lax.cond`` skip around
    the update: with a cond, XLA must keep the untouched state alive for
    the skip branch, which defeats in-place buffer reuse and copies every
    state array per chunk iteration (measured 132 ms of a 614 ms GPT-2
    774M window — ~21% — in the round-4 profile). The gated select fuses
    into the update chain and writes identical bytes on a skip.
    """

    supports_gate = False
    supports_mom = False

    def init(self, params) -> Dict[str, Any]:
        raise NotImplementedError

    def apply(
        self, params, grads, state, lr, grad_scale=None, gate=None
    ) -> Tuple[Any, Dict[str, Any], Dict]:
        raise NotImplementedError


def _gate_stored(gate, new, old):
    """Select between NEW and OLD *stored* representations (bit-exact skip:
    the old bytes are re-written unchanged). Handles quantized dicts."""
    if gate is None:
        return new
    if isinstance(new, dict):
        return {k: _gate_stored(gate, new[k], old[k]) for k in new}
    return jnp.where(gate, new, old)


def adam_core(p32, g32, m, v, *, lr, b1, b2, c1, c2, eps, weight_decay,
              adam_w_mode):
    """The fp32 update math: ONE implementation, traced by the plain XLA
    leaf update and inside the Pallas kernel alike."""
    if weight_decay and not adam_w_mode:
        g32 = g32 + weight_decay * p32
    m_new = b1 * m + (1.0 - b1) * g32
    v_new = b2 * v + (1.0 - b2) * g32 * g32
    update = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps)
    if weight_decay and adam_w_mode:
        update = update + weight_decay * p32
    return p32 - lr * update, m_new, v_new


@functools.lru_cache(maxsize=None)
def _log_update_path(kernel, plain, runs):
    """``adam_update_path``: how much of a parameter tree takes the
    one-pass kernel, logged once per distinct tree."""
    logger.debug(
        "adam_update_path kernel=%d leaves %d elements plain=%d leaves "
        "%d elements kernel_share=%.6f run_by_width=%s",
        *kernel, *plain, kernel[1] / max(kernel[1] + plain[1], 1),
        ",".join(f"{w}:{r}" for w, r in runs) or "-",
    )


@dataclasses.dataclass
class Adam(Optimizer):
    """Adam / AdamW. ``adam_w_mode=True`` decouples weight decay (AdamW);
    False applies L2-style decay added to the gradient (classic Adam+wd),
    matching apex FusedAdam's two modes.

    ``state_dtype`` selects the moment STORAGE format ("fp32" default,
    "bf16", or "int8" per run of the minor axis — ops/quant.py): the
    update math always runs in fp32 transiently; reduced formats shrink
    persistent HBM so models like GPT-2 1.5B fit a single 16 GB chip (the
    memory relief the reference family later shipped as ZeRO-Offload).

    ``apply(..., shard=(mesh, specs), kernel=True)``: the engine's word on
    where the update runs. ``shard`` is the mesh and the tree of
    PartitionSpecs the state is stored under, for a mesh of several
    devices: the kernel then runs per shard under ``shard_map`` (GSPMD
    cannot partition a ``pallas_call``). ``kernel=False`` keeps every leaf
    on the plain XLA update (the host-side offload step; the tests'
    reference)."""

    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    bias_correction: bool = True
    adam_w_mode: bool = True
    state_dtype: str = "fp32"
    # Kahan-style compensated masters (ops/quant.py): params stay in the
    # compute dtype (bf16) and an int8 per-element error code carries the
    # rounding residue, replacing fp32 master storage AND the bf16 cast
    # copies that fp32 storage forces through backward. Enabled by the
    # engine for single-chip billion-param runs (data_types.master_dtype
    # = "compensated").
    master_compensation: bool = False
    supports_gate = True
    supports_mom = True
    supports_placement = True  # apply() takes ``shard`` and ``kernel``

    def init(self, params):
        from .quant import comp_zeros_like, moments_zeros_like

        state = {
            "step": jnp.zeros((), jnp.int32),
            "mu": moments_zeros_like(params, self.state_dtype, "mu"),
            "nu": moments_zeros_like(params, self.state_dtype, "nu"),
        }
        if self.master_compensation:
            state["comp"] = comp_zeros_like(params)
        return state

    def apply(self, params, grads, state, lr, grad_scale=None, gate=None,
              mom=None, shard=None, kernel=True):
        from . import pallas as kernels
        from .quant import (
            decode_master,
            decode_moment,
            encode_master,
            encode_moment,
            is_quantized,
        )

        if gate is None:
            step = state["step"] + 1
        else:
            step = state["step"] + gate.astype(jnp.int32)
        b1 = self.b1 if mom is None else mom
        if self.bias_correction:
            c1 = 1.0 - b1 ** step.astype(jnp.float32)
            c2 = 1.0 - self.b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = jnp.float32(1.0)
        comped = self.master_compensation
        scalars = dict(lr=lr, b1=b1, c1=c1, c2=c2)
        static = dict(
            b2=self.b2, eps=self.eps, weight_decay=self.weight_decay,
            adam_w_mode=self.adam_w_mode,
        )

        def leaf(p, g, m_st, v_st, comp):
            g32 = _f32(g)
            if grad_scale is not None:
                g32 = g32 * grad_scale
            p32 = decode_master(p, comp) if comped else _f32(p)
            master_new, m_new, v_new = adam_core(
                p32, g32, decode_moment(m_st), decode_moment(v_st),
                **scalars, **static,
            )
            if comped:
                p_new, comp_new = encode_master(master_new, p.dtype)
            else:
                p_new, comp_new = master_new.astype(p.dtype), None
            # gate at the STORED level: a skipped step re-writes the old
            # bytes unchanged (bit-exact no-op, in-place friendly — see
            # Optimizer.supports_gate)
            return (
                _gate_stored(gate, p_new, p),
                _gate_stored(gate, encode_moment(m_new, m_st), m_st),
                _gate_stored(gate, encode_moment(v_new, v_st), v_st),
                _gate_stored(gate, comp_new, comp) if comped else None,
            )

        p_leaves, treedef = jax.tree_util.tree_flatten(params)
        n = len(p_leaves)
        g_leaves = treedef.flatten_up_to(grads)
        m_leaves = treedef.flatten_up_to(state["mu"])
        v_leaves = treedef.flatten_up_to(state["nu"])
        c_leaves = treedef.flatten_up_to(state["comp"]) if comped else [None] * n
        mesh, specs = shard if shard is not None else (None, None)
        spec_leaves = (
            [None] * n if specs is None
            else jax.tree_util.tree_leaves(
                specs,
                is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
            )
        )
        out, on_kernel, plain, runs = [], [0, 0], [0, 0], set()
        for p, g, m_st, v_st, comp, spec in zip(
            p_leaves, g_leaves, m_leaves, v_leaves, c_leaves, spec_leaves
        ):
            run = (
                kernels.adam_kernel_run(p, m_st, v_st, mesh, spec)
                if kernel else None
            )
            if run is None:
                out.append(leaf(p, g, m_st, v_st, comp))
                tally = plain
            else:
                p_new, *stored = kernels.adam_leaf_update(
                    p, g, m_st, v_st, comp, run=run, grad_scale=grad_scale,
                    gate=gate, mesh=mesh, spec=spec, **scalars, **static,
                )
                if mesh is not None:
                    # ZeRO-1/2 keeps the parameter whole on every chip and
                    # the kernel returns a shard of it. Gated against the
                    # old parameter once more out here (the kernel's own
                    # gate covers the state), the gathered value reaches
                    # the donated buffer through an elementwise select, as
                    # on the plain path, and XLA updates that buffer in
                    # place. Handed over bare, XLA kept a copy of every
                    # such parameter from the start of the window to its
                    # update (1.44 GiB a chip for GPT-2 large on dp 4:
                    # described-chip compile, PR 27).
                    p_new = _gate_stored(gate, p_new, p)
                out.append((p_new, *stored))
                tally = on_kernel
                runs.add((p.shape[-1], run))
            tally[0] += 1
            tally[1] += p.size
        if kernel and any(map(is_quantized, m_leaves)):
            _log_update_path(
                tuple(on_kernel), tuple(plain), tuple(sorted(runs))
            )

        def tree(i):
            return jax.tree_util.tree_unflatten(treedef, [t[i] for t in out])

        new_state = {"step": step, "mu": tree(1), "nu": tree(2)}
        if comped:
            new_state["comp"] = tree(3)
        return tree(0), new_state, {}


@dataclasses.dataclass
class Lamb(Optimizer):
    """LAMB with the reference's clamped trust ratio.

    Per-leaf (≙ per-layer, LAMB's granularity in the reference's unfused
    fp32-master path, fp16_unfused_optimizer.py:17):
      u = adam_update(g) (+ wd * p)
      ratio = clamp(||p|| / ||u||, min_coeff, max_coeff)   if both norms > 0
      p <- p - lr * ratio * u
    ``aux['lamb_coeffs']`` carries the ratios (deepspeed_fused_lamb.py:183-201).
    """

    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    bias_correction: bool = True
    max_coeff: float = 10.0
    min_coeff: float = 0.01
    eps_inside_sqrt: bool = False
    state_dtype: str = "fp32"  # moment storage; see Adam.state_dtype
    supports_gate = True
    supports_mom = True

    def init(self, params):
        from .quant import moments_zeros_like

        return {
            "step": jnp.zeros((), jnp.int32),
            "mu": moments_zeros_like(params, self.state_dtype, "mu"),
            "nu": moments_zeros_like(params, self.state_dtype, "nu"),
        }

    def apply(self, params, grads, state, lr, grad_scale=None, gate=None,
              mom=None):
        from .quant import decode_moment, encode_moment

        if gate is None:
            step = state["step"] + 1
        else:
            step = state["step"] + gate.astype(jnp.int32)
        b1 = self.b1 if mom is None else mom
        b2 = self.b2
        if self.bias_correction:
            c1 = 1.0 - b1 ** step.astype(jnp.float32)
            c2 = 1.0 - b2 ** step.astype(jnp.float32)
        else:
            c1 = c2 = jnp.float32(1.0)

        coeffs = []

        def leaf(p, g, m_st, v_st):
            g32, p32 = _f32(g), _f32(p)
            if grad_scale is not None:
                g32 = g32 * grad_scale
            m = decode_moment(m_st, p.shape)
            v = decode_moment(v_st, p.shape)
            m_new = b1 * m + (1.0 - b1) * g32
            v_new = b2 * v + (1.0 - b2) * g32 * g32
            if self.eps_inside_sqrt:
                denom = jnp.sqrt(v_new / c2 + self.eps)
            else:
                denom = jnp.sqrt(v_new / c2) + self.eps
            update = (m_new / c1) / denom
            if self.weight_decay:
                update = update + self.weight_decay * p32
            w_norm = jnp.sqrt(jnp.sum(p32 * p32))
            u_norm = jnp.sqrt(jnp.sum(update * update))
            ratio = jnp.where(
                (w_norm > 0) & (u_norm > 0),
                jnp.clip(w_norm / u_norm, self.min_coeff, self.max_coeff),
                jnp.float32(1.0),
            )
            coeffs.append(ratio)
            p_new = p32 - lr * ratio * update
            return (
                _gate_stored(gate, p_new.astype(p.dtype), p),
                _gate_stored(gate, encode_moment(m_new, m_st), m_st),
                _gate_stored(gate, encode_moment(v_new, v_st), v_st),
            )

        out = jax.tree_util.tree_map(leaf, params, grads, state["mu"], state["nu"])
        is_tup = lambda x: isinstance(x, tuple)
        new_params = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_tup)
        new_mu = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_tup)
        new_nu = jax.tree_util.tree_map(lambda t: t[2], out, is_leaf=is_tup)
        aux = {"lamb_coeffs": coeffs}
        return new_params, {"step": step, "mu": new_mu, "nu": new_nu}, aux


@dataclasses.dataclass
class SGD(Optimizer):
    momentum: float = 0.0
    weight_decay: float = 0.0
    nesterov: bool = False

    @property
    def supports_mom(self):
        # momentum cycling needs the momentum BUFFER, whose existence is
        # fixed at init time by self.momentum != 0 (torch SGD creates it
        # lazily; a traced pytree cannot). momentum=0.0 therefore reports
        # unsupported and the engine warns instead of silently ignoring a
        # configured OneCycle momentum cycle.
        return bool(self.momentum)

    def init(self, params):
        if self.momentum:
            return {
                "step": jnp.zeros((), jnp.int32),
                "mom": jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                ),
            }
        return {"step": jnp.zeros((), jnp.int32), "mom": None}

    def apply(self, params, grads, state, lr, grad_scale=None, mom=None):
        step = state["step"] + 1
        mu_coeff = self.momentum if mom is None else mom

        if self.momentum:

            def leaf(p, g, m):
                g32, p32 = _f32(g), _f32(p)
                if grad_scale is not None:
                    g32 = g32 * grad_scale
                if self.weight_decay:
                    g32 = g32 + self.weight_decay * p32
                m_new = mu_coeff * m + g32
                d = g32 + mu_coeff * m_new if self.nesterov else m_new
                return (p32 - lr * d).astype(p.dtype), m_new

            out = jax.tree_util.tree_map(leaf, params, grads, state["mom"])
            is_tup = lambda x: isinstance(x, tuple)
            new_params = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_tup)
            new_mom = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_tup)
            return new_params, {"step": step, "mom": new_mom}, {}

        def leaf_plain(p, g):
            g32, p32 = _f32(g), _f32(p)
            if grad_scale is not None:
                g32 = g32 * grad_scale
            if self.weight_decay:
                g32 = g32 + self.weight_decay * p32
            return (p32 - lr * g32).astype(p.dtype)

        new_params = jax.tree_util.tree_map(leaf_plain, params, grads)
        return new_params, {"step": step, "mom": None}, {}


@dataclasses.dataclass
class Lion(Optimizer):
    """Lion (sign-momentum) — cheap state (one moment), a good fit for
    ZeRO-1 memory budgets on TPU. Not in the reference; additive."""

    b1: float = 0.9
    b2: float = 0.99
    weight_decay: float = 0.0

    def init(self, params):
        return {
            "step": jnp.zeros((), jnp.int32),
            "mu": jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            ),
        }

    def apply(self, params, grads, state, lr, grad_scale=None):
        step = state["step"] + 1

        def leaf(p, g, m):
            g32, p32 = _f32(g), _f32(p)
            if grad_scale is not None:
                g32 = g32 * grad_scale
            update = jnp.sign(self.b1 * m + (1.0 - self.b1) * g32)
            if self.weight_decay:
                update = update + self.weight_decay * p32
            m_new = self.b2 * m + (1.0 - self.b2) * g32
            return (p32 - lr * update).astype(p.dtype), m_new

        out = jax.tree_util.tree_map(leaf, params, grads, state["mu"])
        is_tup = lambda x: isinstance(x, tuple)
        new_params = jax.tree_util.tree_map(lambda t: t[0], out, is_leaf=is_tup)
        new_mu = jax.tree_util.tree_map(lambda t: t[1], out, is_leaf=is_tup)
        return new_params, {"step": step, "mu": new_mu}, {}


def build_optimizer(name: str, params_dict: dict) -> Optimizer:
    """Instantiate by config name (engine path, mirroring
    deepspeed_light.py:529-543's named-optimizer selection)."""
    name = name.lower()
    kw = dict(params_dict)
    kw.pop("lr", None)  # lr is supplied per-step by the scheduler
    betas = kw.pop("betas", None)
    if betas is not None:
        kw["b1"], kw["b2"] = betas
    kw.pop("torch_adam", None)
    kw.pop("amsgrad", None)
    if name == "adam":
        kw.pop("max_grad_norm", None)
        return Adam(adam_w_mode=kw.pop("adam_w_mode", True), **kw)
    if name == "adamw":
        kw.pop("max_grad_norm", None)
        return Adam(adam_w_mode=True, **kw)
    if name == "lamb":
        kw.pop("max_grad_norm", None)
        return Lamb(**kw)
    if name in ("fusedlamb", "fused_lamb"):
        # Pallas phase-1 kernel variant (ops/pallas.py), numerics-identical
        from .pallas import FusedLamb

        kw.pop("max_grad_norm", None)
        return FusedLamb(**kw)
    if name == "sgd":
        return SGD(**kw)
    if name == "lion":
        return Lion(**kw)
    raise ValueError(f"Unknown optimizer '{name}'")
