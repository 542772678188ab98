"""deepspeed_tpu: a TPU-native training-acceleration framework.

A from-scratch JAX/XLA/Pallas rebuild of the capability surface of early
DeepSpeed (reference: deepspeed/__init__.py:33-110): one ``initialize()``
call wraps a model into a training engine providing data parallelism over a
device mesh, bf16/fp16 mixed precision with dynamic loss scaling, ZeRO
stages 1-3 as sharding layouts, fused Adam/LAMB optimizers, a fused
transformer layer (Pallas flash attention), activation checkpointing,
Megatron-style model parallelism over mesh axes, JSON config, a multi-host
launcher, and elastic checkpoint save/resume.
"""

import argparse

from .runtime.dist import init_distributed, maybe_auto_init as _maybe_auto_init

# Under bin/deepspeed the coordinator env is present at process start; the
# jax.distributed bootstrap must happen before any JAX computation, so it
# rides package import (see runtime/dist.py).
_maybe_auto_init()

from .config import DeepSpeedConfig
from .config import constants as _constants
from .ops.optimizers import Adam, Lamb, Lion, Optimizer, SGD
from .ops.transformer import DeepSpeedTransformerConfig, DeepSpeedTransformerLayer
from .runtime.engine import DeepSpeedEngine
from .telemetry.registry import install_recompile_hook
from .version import __version__
from . import adapters, checkpointing


def initialize(
    args=None,
    model=None,
    optimizer=None,
    model_parameters=None,
    training_data=None,
    lr_scheduler=None,
    mpu=None,
    dist_init_required=None,
    collate_fn=None,
    config_params=None,
    mesh=None,
    rng_seed=0,
    param_specs=None,
):
    """Build a training engine; returns the reference's 4-tuple
    ``(engine, optimizer, training_dataloader, lr_scheduler)``
    (reference deepspeed/__init__.py:33-110).

    ``model`` is a flax Module whose ``__call__(*batch)`` returns the scalar
    loss (or a bare ``loss_fn(params, batch, rng)``); ``model_parameters`` is
    the initialized parameter pytree.
    """
    from .runtime.engine import EngineOptimizerFacade

    install_recompile_hook()  # compile seconds by phase, telemetry or not
    engine = DeepSpeedEngine(
        args=args,
        model=model,
        optimizer=optimizer,
        model_parameters=model_parameters,
        training_data=training_data,
        lr_scheduler=lr_scheduler,
        mpu=mpu,
        dist_init_required=dist_init_required,
        collate_fn=collate_fn,
        config_params=config_params,
        mesh=mesh,
        rng_seed=rng_seed,
        param_specs=param_specs,
    )
    return (
        engine,
        EngineOptimizerFacade(engine),
        engine.training_dataloader,
        engine.lr_scheduler,
    )


def init_inference(
    model=None,
    config=None,
    model_parameters=None,
    mesh=None,
    param_specs=None,
    rng_seed=0,
    draft_model=None,
    draft_parameters=None,
):
    """Build a continuous-batching serving engine around ``model``
    (deepspeed_tpu/inference/, docs/inference.md): KV-cache decode,
    bounded-queue admission, slot-managed batching. Returns an
    ``InferenceEngine`` with ``generate(prompts, max_new_tokens=...)``
    and the ``submit``/``serve_forever`` server mode. The reference
    stopped at training; this is the serving act on top of the same
    sharded params, mesh, telemetry, and verified-checkpoint layers.
    ``draft_model``/``draft_parameters`` supply the draft for
    speculative decoding (the ``inference.speculative`` block,
    docs/inference.md "Speculative decoding").
    """
    from .inference.engine import init_inference as _init_inference

    install_recompile_hook()  # compile seconds by phase, telemetry or not
    return _init_inference(
        model=model,
        config=config,
        model_parameters=model_parameters,
        mesh=mesh,
        param_specs=param_specs,
        rng_seed=rng_seed,
        draft_model=draft_model,
        draft_parameters=draft_parameters,
    )


def init_fleet(
    engine_factory=None,
    worker_spec=None,
    nodes=None,
    config=None,
    registry=None,
    start=True,
):
    """Build a multi-replica serving fleet (deepspeed_tpu/serving/,
    docs/serving.md): a ``FleetRouter`` spreading requests over N
    inference-engine replicas with per-tenant rate limits, pluggable
    placement (least-loaded / prefix-affinity), and rolling-restart
    lifecycle. Pass ``engine_factory`` (in-process replicas),
    ``worker_spec`` (one engine per worker subprocess), or ``nodes``
    (the socket backend's fleet map — one ``SocketReplica`` per
    (node, replica) pair against already-running node agents,
    docs/serving.md "Networked fleet"); the ``"serving"`` config block
    sizes the fleet."""
    from .serving import init_fleet as _init_fleet

    return _init_fleet(
        engine_factory=engine_factory,
        worker_spec=worker_spec,
        nodes=nodes,
        config=config,
        registry=registry,
        start=start,
    )


def _add_core_arguments(parser):
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument(
        "--deepspeed",
        default=False,
        action="store_true",
        help="Enable DeepSpeed (helper flag for user scripts)",
    )
    group.add_argument(
        "--deepspeed_config", default=None, type=str, help="DeepSpeed json config file"
    )
    group.add_argument(
        "--deepscale",
        default=False,
        action="store_true",
        help="Deprecated alias for --deepspeed",
    )
    group.add_argument(
        "--deepscale_config",
        default=None,
        type=str,
        help="Deprecated alias for --deepspeed_config",
    )
    group.add_argument(
        "--deepspeed_mpi",
        default=False,
        action="store_true",
        help="Run via MPI-style multi-host discovery",
    )
    return parser


def add_config_arguments(parser):
    """Inject DeepSpeed CLI args into an argparse parser
    (reference deepspeed/__init__.py:164-177)."""
    return _add_core_arguments(parser)


__all__ = [
    "initialize",
    "init_inference",
    "init_distributed",
    "add_config_arguments",
    "adapters",
    "checkpointing",
    "DeepSpeedConfig",
    "DeepSpeedEngine",
    "DeepSpeedTransformerConfig",
    "DeepSpeedTransformerLayer",
    "Optimizer",
    "Adam",
    "Lamb",
    "Lion",
    "SGD",
    "__version__",
]
