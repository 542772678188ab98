"""Activation checkpointing: the ``deepspeed.checkpointing`` API, TPU-native.

Reference: deepspeed/pt/deepspeed_checkpointing.py — a reimplementation of
torch.utils.checkpoint with (1) CUDA+model-parallel RNG state tracking so
recompute regenerates identical dropout masks (:146-261), (2) activation
*partitioning*: each saved input sliced 1/mp_size per model-parallel rank
and all-gathered back in backward (:264-310,369-412), (3) CPU offload of
saved activations (:409,519-520), (4) contiguous preallocated checkpoint
buffers (:381-407), and (5) profiling timers (:330-334,477-479).

TPU-first mapping — most of the reference's machinery is structural in JAX:

  * recompute               -> ``jax.checkpoint`` (remat). Saved-tensor
    bookkeeping, detach/requires-grad plumbing: gone (functional autodiff).
  * RNG reproducibility     -> JAX PRNG keys are values, so recompute is
    bit-identical *by construction*; ``RNGStatesTracker`` exists for the
    reference's API shape (named seeds, model-parallel fork) and produces
    per-rank dropout keys the way ``model_parallel_cuda_manual_seed`` does.
  * partition_activations   -> a sharding constraint over the model axis on
    the checkpointed function's inputs: XLA stores the residual sharded
    (1/mp per rank) and re-gathers it for the backward pass — the same
    memory/comm trade as the reference's scatter/all_gather, minus the
    hand-rolled collectives.
  * cpu_checkpointing       -> remat policy that saves nothing on-device
    (``nothing_saveable``): inputs of each segment are recomputed from the
    previous segment. (True host offload is an XLA memories feature;
    ``offload_to_host`` selects it when the backend supports it.)
  * contiguous_memory_optimization / synchronize_checkpoint_boundary ->
    accepted no-ops: XLA's allocator already packs buffers.
  * PROFILE_TIME            -> ``jax.named_scope`` so segments show up in
    the jax.profiler trace.
"""

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .config import constants as C
from .utils.logging import logger

_MODEL_PARALLEL_RNG_TRACKER_NAME = "model-parallel-rng"

# module state mirroring the reference's globals (deepspeed_checkpointing.py:34-53)
_CONFIGURED = False
_MPU = None
PARTITION_ACTIVATIONS = False
CPU_CHECKPOINT = False
CONTIGUOUS_CHECKPOINTING = False
SYNCHRONIZE = False
PROFILE_TIME = False
_NUM_LAYERS = -1
_OFFLOAD_SUPPORTED = None  # lazily probed


class RNGStatesTracker:
    """Named JAX PRNG states (reference CudaRNGStatesTracker,
    deepspeed_checkpointing.py:146-215).

    JAX keys are pure values, so "restoring" a state is just reusing a key;
    ``fork`` yields a fresh subkey per call while advancing the named
    stream, which is what the reference's RNG fork achieves with device
    state swaps.
    """

    def __init__(self):
        self.states_ = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def get_states(self):
        return dict(self.states_)

    def set_states(self, states):
        self.states_ = dict(states)

    def add(self, name, seed):
        if seed in self.seeds_:
            raise ValueError(f"seed {seed} already present")
        self.seeds_.add(seed)
        if name in self.states_:
            raise ValueError(f"rng state {name} already present")
        self.states_[name] = jax.random.PRNGKey(seed)

    @contextlib.contextmanager
    def fork(self, name=_MODEL_PARALLEL_RNG_TRACKER_NAME):
        """Yield a fresh key from the named stream (advances the stream)."""
        if name not in self.states_:
            raise KeyError(f"rng state {name} is not added")
        self.states_[name], sub = jax.random.split(self.states_[name])
        yield sub


_RNG_TRACKER = RNGStatesTracker()


def get_rng_tracker():
    return _RNG_TRACKER


# reference-compatible alias (deepspeed_checkpointing.py:217)
get_cuda_rng_tracker = get_rng_tracker


def model_parallel_seed(seed, mpu=None):
    """Seed the default + model-parallel RNG streams per rank (reference
    ``model_parallel_cuda_manual_seed``, deepspeed_checkpointing.py:222-261):
    replicated regions share ``seed``; model-parallel regions (e.g. split
    dropout inside a Megatron layer) get ``seed + 2718 + mp_rank``."""
    mpu = mpu if mpu is not None else _MPU
    mp_rank = mpu.get_model_parallel_rank() if mpu is not None else 0
    offset = seed + 2718
    model_parallel_seed_ = offset + mp_rank
    _RNG_TRACKER.reset()
    _RNG_TRACKER.states_["default"] = jax.random.PRNGKey(seed)
    _RNG_TRACKER.seeds_.add(seed)
    _RNG_TRACKER.add(_MODEL_PARALLEL_RNG_TRACKER_NAME, model_parallel_seed_)
    return _RNG_TRACKER


model_parallel_cuda_manual_seed = model_parallel_seed


def _offload_supported():
    global _OFFLOAD_SUPPORTED
    if _OFFLOAD_SUPPORTED is None:
        _OFFLOAD_SUPPORTED = any(
            m.kind == "pinned_host"
            for m in jax.devices()[0].addressable_memories()
        )
    return _OFFLOAD_SUPPORTED


def _policy():
    """Remat policy from the configured flags."""
    if CPU_CHECKPOINT:
        if _offload_supported():
            return jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["checkpointed"],
                offload_src="device",
                offload_dst="pinned_host",
            )
        # no host memory space on this backend: closest memory behavior is
        # saving nothing and recomputing each segment from its inputs
        return jax.checkpoint_policies.nothing_saveable
    return None  # jax.checkpoint default: save inputs, recompute the rest


def _partition_constraint(x):
    """Shard a saved input over the model axis (largest divisible dim),
    mirroring the reference's 1/mp_size activation slices
    (deepspeed_checkpointing.py:264-277,369-412)."""
    mesh = _MPU.mesh if _MPU is not None and hasattr(_MPU, "mesh") else None
    if mesh is None or not hasattr(x, "ndim") or x.ndim == 0:
        return x
    mp = dict(mesh.shape).get(C.MODEL_AXIS, 1)
    if mp <= 1:
        return x
    from jax.sharding import NamedSharding

    for dim in range(x.ndim):
        if x.shape[dim] % mp == 0 and x.shape[dim] >= mp:
            spec = [None] * x.ndim
            spec[dim] = C.MODEL_AXIS
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, PartitionSpec(*spec))
            )
    return x


def checkpoint(function, *args):
    """Checkpoint (remat) ``function(*args)`` — reference
    deepspeed_checkpointing.py:560-563. The forward result is returned;
    under ``jax.grad`` the activations inside ``function`` are recomputed
    during backward rather than stored."""
    fn = function
    if PARTITION_ACTIVATIONS:
        inner = fn

        def fn(*xs):
            xs = tuple(_partition_constraint(x) for x in xs)
            return inner(*xs)

    if PROFILE_TIME:
        timed = fn

        def fn(*xs):
            with jax.named_scope("ds_checkpoint_segment"):
                return timed(*xs)

    ckpt = jax.checkpoint(fn, policy=_policy())
    if CPU_CHECKPOINT and _offload_supported():
        inner_ckpt = ckpt

        from jax.ad_checkpoint import checkpoint_name

        def ckpt(*xs):
            xs = tuple(
                checkpoint_name(x, "checkpointed") if hasattr(x, "dtype") else x
                for x in xs
            )
            return inner_ckpt(*xs)

    return ckpt(*args)


def partition_activations_in_checkpoint(partition_activation):
    global PARTITION_ACTIVATIONS
    PARTITION_ACTIVATIONS = partition_activation
    logger.info("**************Partition Activations %s************",
                PARTITION_ACTIVATIONS)


def set_num_layers(nlayers):
    global _NUM_LAYERS
    _NUM_LAYERS = nlayers


def reset():
    """Per-iteration reset (reference deepspeed_checkpointing.py:579): the
    reference frees its contiguous activation buffers here. This rebuild
    keeps no per-iteration buffer state, so there is nothing to clear —
    notably the RNG tracker survives, matching the reference (it is seeded
    once and reused across iterations). Tests wanting RNG isolation use
    get_cuda_rng_tracker().reset() directly."""


def configure(
    mpu_=None,
    deepspeed_config=None,
    partition_activations=None,
    contiguous_checkpointing=None,
    num_checkpoints=None,
    checkpoint_in_cpu=None,
    synchronize=None,
    profile=None,
):
    """Configure module flags from a DeepSpeedConfig and/or explicit args
    (reference deepspeed_checkpointing.py:635-714; explicit args win)."""
    global _CONFIGURED, _MPU, PARTITION_ACTIVATIONS, CPU_CHECKPOINT
    global CONTIGUOUS_CHECKPOINTING, SYNCHRONIZE, PROFILE_TIME, _NUM_LAYERS

    _MPU = mpu_
    acfg = None
    if deepspeed_config is not None:
        acfg = getattr(
            deepspeed_config, "activation_checkpointing_config", None
        )
    if acfg is not None:
        PARTITION_ACTIVATIONS = acfg.partition_activations
        CONTIGUOUS_CHECKPOINTING = acfg.contiguous_memory_optimization
        CPU_CHECKPOINT = acfg.cpu_checkpointing
        SYNCHRONIZE = acfg.synchronize_checkpoint_boundary
        PROFILE_TIME = acfg.profile
        if acfg.number_checkpoints is not None:
            _NUM_LAYERS = acfg.number_checkpoints
    if partition_activations is not None:
        PARTITION_ACTIVATIONS = partition_activations
    if contiguous_checkpointing is not None:
        CONTIGUOUS_CHECKPOINTING = contiguous_checkpointing
    if num_checkpoints is not None:
        _NUM_LAYERS = num_checkpoints
    if checkpoint_in_cpu is not None:
        CPU_CHECKPOINT = checkpoint_in_cpu
    if synchronize is not None:
        SYNCHRONIZE = synchronize
    if profile is not None:
        PROFILE_TIME = profile

    if CONTIGUOUS_CHECKPOINTING:
        assert _NUM_LAYERS is not None and _NUM_LAYERS > 0, (
            "must specify the number of checkpoints with contiguous memory "
            "optimization"
        )
    _CONFIGURED = True


def is_configured():
    return _CONFIGURED


def see_memory_usage(message, force=False):
    """Device-memory snapshot (reference deepspeed_checkpointing.py:56-85,
    CUDA allocator stats -> jax memory_stats)."""
    if not force:
        return
    for i, dev in enumerate(jax.local_devices()):
        stats = dev.memory_stats() or {}
        in_use = stats.get("bytes_in_use", 0)
        peak = stats.get("peak_bytes_in_use", 0)
        limit = stats.get("bytes_limit", 0)
        logger.info(
            "%s | device %d: in_use=%.2fGB peak=%.2fGB limit=%.2fGB",
            message, i, in_use / 2**30, peak / 2**30, limit / 2**30,
        )
