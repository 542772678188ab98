"""Distributed bootstrap: jax.distributed from the launcher's environment.

Replaces the reference's NCCL ``init_process_group`` + MPI env discovery
(reference: deepspeed/pt/deepspeed_light.py:132-137,195-232). The per-node
launcher (launcher/launch.py) exports DS_TPU_COORDINATOR_ADDRESS /
DS_TPU_NUM_PROCESSES / DS_TPU_PROCESS_ID; this module turns them into a
``jax.distributed.initialize`` call, after which ``jax.devices()`` spans
every host and the mesh is the communication backend.

Timing constraint: ``jax.distributed.initialize`` must run BEFORE any JAX
computation touches a backend — i.e. before the user builds their
parameter pytree. ``import deepspeed_tpu`` therefore auto-initializes when
the launcher environment is present (``maybe_auto_init``); the engine's
later ``init_distributed`` call is an idempotent check, not the bootstrap.
"""

import os

from ..utils.logging import logger

from ..telemetry.registry import count_suppressed

_INITIALIZED = False


COORD_ENV = "DS_TPU_COORDINATOR_ADDRESS"
NPROC_ENV = "DS_TPU_NUM_PROCESSES"
PID_ENV = "DS_TPU_PROCESS_ID"


def _jax_client_initialized():
    """True when jax.distributed was already initialized (by us or the user)."""
    try:
        from jax._src import distributed

        return distributed.global_state.client is not None
    except Exception as e:  # jax internals moved: treat as uninitialized
        count_suppressed("dist.jax_client_probe", e)
        return False


def _backends_initialized():
    try:
        from jax._src import xla_bridge

        return xla_bridge.backends_are_initialized()
    except Exception as e:  # jax internals moved: treat as uninitialized
        count_suppressed("dist.backend_probe", e)
        return False


def is_initialized():
    return _INITIALIZED or _jax_client_initialized()


def maybe_auto_init():
    """Called at ``import deepspeed_tpu``: bootstrap jax.distributed when the
    launcher environment asks for a multi-process run and the JAX backend is
    still untouched (the only window in which initialization is legal)."""
    num_processes = int(os.environ.get(NPROC_ENV, "1"))
    if num_processes <= 1 or is_initialized():
        return
    if _backends_initialized():
        logger.warning(
            "%s=%d but the JAX backend is already initialized; skipping "
            "jax.distributed bootstrap. Import deepspeed_tpu (or call "
            "deepspeed_tpu.init_distributed()) before running any JAX "
            "computation, or initialize jax.distributed yourself.",
            NPROC_ENV, num_processes,
        )
        return
    init_distributed(dist_init_required=True)


def init_distributed(dist_init_required=None):
    """Idempotently initialize jax.distributed for multi-host runs.

    Returns True when a multi-process runtime is active, False for
    single-process. ``dist_init_required=False`` skips entirely (caller
    manages jax.distributed themselves); ``True`` raises if a multi-process
    environment was requested but cannot be set up.
    """
    global _INITIALIZED
    if dist_init_required is False:
        return is_initialized()
    if is_initialized():
        return True
    coordinator = os.environ.get(COORD_ENV)
    num_processes = int(os.environ.get(NPROC_ENV, "1"))
    process_id = int(os.environ.get(PID_ENV, "0"))
    if num_processes <= 1:
        # world size 1: nothing to rendezvous (even under the launcher)
        return False
    if coordinator is None:
        if dist_init_required:
            raise RuntimeError(
                f"dist_init_required=True with {NPROC_ENV}={num_processes} "
                f"but {COORD_ENV} is unset; start via bin/deepspeed or "
                "export the DS_TPU_* variables"
            )
        return False
    import jax

    if _backends_initialized():
        raise RuntimeError(
            "jax.distributed must be initialized before any JAX computation, "
            "but the backend is already live. Import deepspeed_tpu (which "
            "auto-initializes under the launcher) or call "
            "deepspeed_tpu.init_distributed() at the very top of the script."
        )
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _INITIALIZED = True
    logger.info(
        "jax.distributed initialized: process %d/%d via %s",
        process_id, num_processes, coordinator,
    )
    return True
