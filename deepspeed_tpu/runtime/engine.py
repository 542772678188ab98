"""DeepSpeedEngine: the training engine façade.

Capability parity with the reference's DeepSpeedLight engine (reference:
deepspeed/pt/deepspeed_light.py:95-1360): same user contract —

    engine, optimizer, dataloader, scheduler = deepspeed_tpu.initialize(...)
    for batch in dataloader:
        loss = engine(batch)        # forward
        engine.backward(loss)       # accumulate gradients
        engine.step()               # optimizer step at accumulation boundary

— same config-driven optimizer selection (deepspeed_light.py:494-543), LR
scheduling, gradient-accumulation boundary semantics (:809), loss-scale
overflow skipping, and checkpoint save/load.

TPU-native internals (the reference's imperative machinery has no analog
here, by design):

- One ``jax.jit``-compiled ``value_and_grad`` micro-step and one compiled
  update step replace autograd hooks + bucketed NCCL calls. ``forward``
  computes loss AND gradients in a single fused pass (on TPU the backward
  pass re-runs forward anyway, so this costs exactly the torch
  forward+backward total, not more); ``backward`` accumulates the stashed
  gradients; ``step`` applies the update. The cleaner all-in-one
  ``train_batch()`` fuses the whole microbatch loop into one jit for peak
  throughput.
- Data parallelism: the batch is sharded over the mesh's ``data`` axis; the
  mean-loss gradient automatically all-reduces via GSPMD (replaces
  buffered_allreduce_fallback, deepspeed_light.py:962-1035).
- ZeRO stages are sharding layouts (see runtime/zero.py): stage 1 shards
  optimizer state, stage 2 shards the gradient-accumulation buffer, stage 3
  shards parameters. XLA inserts reduce-scatter/all-gather on ICI.
- Master parameters are fp32; fp16/bf16 compute casts happen inside the
  jitted loss (the fp32-master-weights design of fp16_optimizer.py:48-66).
- The data-dependent overflow branch runs inside jit via ``lax.cond``
  (SURVEY.md §7 hard part (b)).
"""

import inspect
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..config import constants as C
from ..config.config import DeepSpeedConfig, DeepSpeedConfigError
from ..ops.optimizers import Optimizer, build_optimizer
from ..resilience.supervisor import SupervisorEscalation
from ..telemetry.tracing import phase
from ..parallel import mesh as mesh_lib
from ..parallel.mpu import TPUMpu
from ..utils.logging import log_dist, logger, warn_once
from ..utils.numerics import global_norm, has_overflow
from ..utils.timers import SynchronizedWallClockTimer, ThroughputTimer
from . import zero as zero_lib
from .dataloader import DeepSpeedDataLoader
from .lr_schedules import build_lr_scheduler
from .precision import (
    LossScaleState,
    loss_scale_state_from_config,
    update_scale,
)

FORWARD_TIMER = "forward"
BACKWARD_TIMER = "backward"
STEP_TIMER = "step"
# fused train_batch() path: the window is ONE compiled program, so host
# timers cannot split fwd/bwd/step — the whole-window wall clock is timed
# instead (named_scope sections inside the jit label profiler traces for
# the per-phase view)
TRAIN_BATCH_TIMER = "train_batch_window"

# sentinel: forward() already folded this micro-step's grads into the
# donated accumulation buffer (fwd_bwd_into); backward() only bookkeeps
_GRADS_ACCUMULATED = object()


def _split_window_keys(rng, accum):
    """One window's RNG advance: ``(new_rng, [accum] keys)``. The single
    authority for BOTH the unstaged dispatch and the window stager's
    pre-split (runtime/staging.py) — staged and unstaged runs must
    produce bit-identical key streams."""
    rng, sub = jax.random.split(rng)
    return rng, jax.random.split(sub, accum)


def _split_model_output(out):
    """Multi-output contract (reference multi_output_model.py): a tuple
    return trains on element 0; the rest ride along as observable aux."""
    if isinstance(out, (tuple, list)):
        return out[0], tuple(out[1:])
    return out, ()


def _aux_counters(aux):
    """The named counters among a model's extra outputs: a dict keyed by
    registry names (``"moe/overflow"``) anywhere in the aux tuple. Device
    values, [accum]-stacked; nothing here reads them."""
    out = {}
    for item in aux if isinstance(aux, (tuple, list)) else ():
        if isinstance(item, dict):
            out.update({k: v for k, v in item.items() if isinstance(k, str)})
    return out


def _poison_first_float_leaf(tree):
    """Fault site ``grads.nan`` (resilience/faults.py): NaN-multiply the
    window's first floating batch leaf so its loss AND gradients go
    non-finite through the production dispatch — the on-device skip guard
    and the run supervisor see exactly what a real numeric blowup
    produces. Integer-only batches have nothing poisonable; the fault
    then fires as a no-op (warned once)."""
    done = []

    def poison(x):
        if not done and hasattr(x, "dtype") and np.issubdtype(
            np.dtype(x.dtype), np.floating
        ):
            done.append(True)
            return x * np.float32("nan")
        return x

    out = jax.tree_util.tree_map(poison, tree)
    if not done:
        warn_once(
            "grads-nan-no-float-leaf",
            "fault site 'grads.nan' fired but the batch has no floating "
            "leaf to poison — the injected fault had no effect",
        )
    return out


class EngineOptimizerFacade:
    """What ``initialize()`` returns as ``optimizer``: exposes the
    reference's optimizer duck-type (loss_scale, overflow, lamb_coeffs)
    backed by engine state."""

    def __init__(self, engine):
        self._engine = engine

    @property
    def loss_scale(self):
        return float(self._engine.loss_scale_state.loss_scale)

    @property
    def cur_scale(self):
        return self.loss_scale

    @property
    def overflow(self):
        return self._engine.last_overflow

    def get_lamb_coeffs(self):
        return self._engine.lamb_coeffs

    @property
    def state(self):
        return self._engine.optimizer_state

    def state_dict(self):
        return self._engine._optimizer_state_dict()

    def zero_grad(self):
        self._engine._zero_grad_buffer()


class DeepSpeedEngine:
    def __init__(
        self,
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
        from .dist import init_distributed

        init_distributed(dist_init_required)
        # param_specs: optional pytree of PartitionSpecs (same structure as
        # the params) carrying model-parallel shardings, e.g.
        # models.gpt2.partition_specs — the TPU-native replacement for the
        # reference's external Megatron mpu hook.
        self._model_specs = param_specs
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.collate_fn = collate_fn

        # ---- config ---------------------------------------------------
        config_path = None
        if args is not None:
            config_path = getattr(args, C.DEEPSPEED_CONFIG_ARG, None) or getattr(
                args, C.DEEPSCALE_CONFIG_ARG, None
            )
        # mesh first (its data-axis size feeds the batch triangle), reading
        # only the raw mesh block — full config validation needs the mesh.
        self._mesh = mesh
        if self._mesh is None:
            raw = {}
            if config_params is not None:
                raw = config_params
            elif config_path is not None:
                from ..config.config_utils import load_config_json

                raw = load_config_json(config_path)
            mesh_block = raw.get(C.MESH, {}) if isinstance(raw, dict) else {}
            self._mesh = mesh_lib.build_mesh(
                data_parallel_size=mesh_block.get(C.MESH_DATA_PARALLEL_SIZE),
                model_parallel_size=mesh_block.get(C.MESH_MODEL_PARALLEL_SIZE, 1),
                sequence_parallel_size=mesh_block.get(
                    C.MESH_SEQUENCE_PARALLEL_SIZE, 1
                ),
                pipeline_parallel_size=mesh_block.get(
                    C.MESH_PIPELINE_PARALLEL_SIZE, 1
                ),
            )
        self.mpu = TPUMpu(self._mesh) if mpu is None else mpu
        dp_size = dict(self._mesh.shape).get(mesh_lib.DATA_AXIS, 1)
        self.config = DeepSpeedConfig(
            config_path, param_dict=config_params, world_size=dp_size
        )

        self.dp_world_size = dp_size
        self.mp_world_size = dict(self._mesh.shape).get(mesh_lib.MODEL_AXIS, 1)

        # ---- persistent compile cache ---------------------------------
        # Armed BEFORE any engine compile so restarts (incl. preemption
        # restarts) reuse compiled programs (runtime/compile_cache.py,
        # docs/performance.md). No-op unless the config block enables it.
        from .compile_cache import configure_compile_cache

        configure_compile_cache(self.config)

        # ---- model ----------------------------------------------------
        self.module = model
        if model_parameters is None:
            raise ValueError(
                "model_parameters (the initialized parameter pytree) is required"
            )
        # The engine configures the module it wraps (the reference casts and
        # moves it, deepspeed_light.py:463-491; here we inject the device
        # mesh — so layers can pick sequence-parallel / shard_map attention
        # paths — and the sparse-gradient routing for embedding tables,
        # deepspeed_light.py:177-184). Mutation happens before first trace.
        mcfg = getattr(model, "config", None)
        if mcfg is not None:
            if hasattr(mcfg, "mesh") and getattr(mcfg, "mesh", None) is None:
                mcfg.mesh = self._mesh
            if self.config.sparse_gradients_enabled and hasattr(
                mcfg, "sparse_gradients"
            ):
                mcfg.sparse_gradients = True
        self._loss_fn = self._build_loss_fn(model)

        # ---- precision ------------------------------------------------
        # fp16 mode keeps the reference's loss-scaler semantics, but on TPU
        # backends the compute dtype is bfloat16: the MXU has no native
        # float16 path (it upcasts), so bf16 is strictly better there. On
        # CPU (tests) float16 is honored so overflow semantics are real.
        if self.config.fp16_enabled:
            platform = jax.devices()[0].platform
            self.compute_dtype = (
                jnp.float16 if platform == "cpu" else jnp.bfloat16
            )
        elif self.config.bf16_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        # gradient-accumulation dtype (config data_types.grad_accum_dtype):
        # reduced precision halves grad-buffer HBM (the reference keeps
        # fp16 grads until the master step); fp32 accumulates exactly
        if self.config.grad_accum_dtype == "fp32":
            self.grad_accum_dtype = jnp.float32
        elif self.compute_dtype == jnp.float32:
            log_dist(
                "grad_accum_dtype ignored for fp32 compute (grads are fp32)",
                ranks=[0],
            )
            self.grad_accum_dtype = jnp.float32
        else:
            # fp16 request follows the compute dtype rule (bf16 on TPU)
            self.grad_accum_dtype = self.compute_dtype
        self.loss_scale_state: LossScaleState = self._place_scaler(
            loss_scale_state_from_config(self.config)
        )

        # ---- multi-tenant LoRA adapters (docs/adapters.md) ------------
        # With the "adapters" block enabled the TRAINABLE tree is the
        # rank-r A/B pairs ALONE: the base params freeze into a pinned
        # compute-dtype tree the loss closure merges back in, and every
        # downstream stage (ZeRO specs, optimizer state, grad buffer,
        # checkpoints) sees only the adapter leaves — which is exactly
        # what makes adapter checkpoints tiny per-tenant artifacts and
        # the base bitwise-frozen across any number of fine-tune steps.
        self.adapters_enabled = bool(self.config.adapters_enabled)
        self.frozen_base_params = None
        self._frozen_n_params = 0
        if self.adapters_enabled:
            model_parameters = self._configure_adapters(
                model, model_parameters, rng_seed
            )

        # ---- ZeRO shardings -------------------------------------------
        stage = self.config.zero_optimization_stage
        self.zero_stage = stage
        # Deep-copy the caller's parameters: the jitted update step donates
        # its param buffers, and aliasing the user's pytree would delete
        # their arrays out from under them.
        with phase("init.place_params"):  # the float32 host-side copy
            params_f32 = jax.tree_util.tree_map(
                lambda p: jnp.array(p, dtype=jnp.float32, copy=True),
                model_parameters,
            )
        # parameter count feeds telemetry's model-TFLOPS gauge (the
        # 6*N-per-token accounting); a LoRA fine-tune still pushes every
        # token through the frozen base, so those params count too
        self._n_params = self._frozen_n_params + sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(params_f32)
        )
        # int8 moments are stored per run of the MINOR axis and updated by
        # a kernel that works on whole rows of a shard: leading-dim specs
        # keep runs and rows whole (zero.py module docstring); fp32/bf16
        # state keeps the largest-dim layout of the measured AOT memory
        # proofs
        prefer_leading = self.config.optimizer_state_dtype == "int8"
        self._param_specs = zero_lib.zero_param_specs(
            params_f32, dp_size, stage, model_specs=self._model_specs,
            prefer_leading=prefer_leading,
        )
        self._grad_specs = zero_lib.zero_grad_specs(
            params_f32, dp_size, stage, model_specs=self._model_specs,
            prefer_leading=prefer_leading,
        )
        optstate_param_specs = zero_lib.zero_optstate_specs(
            params_f32, dp_size, stage, model_specs=self._model_specs,
            prefer_leading=prefer_leading,
        )
        self._optstate_param_specs = optstate_param_specs
        self._param_shardings = zero_lib.specs_to_shardings(
            self._param_specs, self._mesh
        )
        self._grad_shardings = zero_lib.specs_to_shardings(
            self._grad_specs, self._mesh
        )
        # ---- ZeRO-3: layer-wise JIT gather + collective overlap -------
        # (docs/performance.md "ZeRO-3 & collective overlap"). The
        # persistent param tree above is already dp-sharded by the
        # stage-3 specs; arming the model's gather seam makes the forward
        # all-gather each scanned layer's weights JUST IN TIME and free
        # them after use (backward re-gathers under the remat policy), so
        # steady-state param HBM is 1/dp instead of "sharded at rest,
        # fully gathered for the whole step".
        self.zero3_gather_enabled = False
        self._zero3_shard_bytes = 0
        self._zero3_gather_bytes = 0
        if stage >= C.ZERO_OPTIMIZATION_WEIGHTS and dp_size > 1:
            self._arm_zero3_gather(model)
            if getattr(self.config.zero_config, "stage3_latency_hiding", True):
                from .overlap import arm_latency_hiding

                arm_latency_hiding()
        else:
            # a model reused from a previous stage-3 engine still carries
            # that engine's arming — running its specs/mesh under this
            # engine's layout would be silently wrong, so disarm
            self._disarm_zero3_gather(model)
        # Reference ZeRO layout (deepspeed_zero_optimizer.py:256-263):
        # model params live in the compute dtype (replicated over dp like
        # the reference's fp16 params) while the fp32 MASTER copy rides
        # the stage>=1-sharded optimizer state. Numerically identical to
        # storing fp32 params and casting each step; halves the
        # replicated param bytes under bf16/fp16.
        # Compensated masters (data_types.master_dtype = "compensated"):
        # params stored IN the compute dtype with an int8 Kahan error code
        # in the optimizer state (ops/quant.py) — no fp32 master bytes and
        # no bf16 cast copies through backward. Mutually exclusive with the
        # fp32-master-in-opt layout below.
        self.compensated_master = (
            self.config.master_dtype == "compensated"
            and self.compute_dtype != jnp.float32
        )
        # ZeRO-Offload analog (zero_optimization.offload_optimizer): fp32
        # master + moments live on the HOST cpu device; the accelerator
        # keeps compute-dtype params and grads. The update runs as a
        # cpu-jitted program fed by an explicit d2h grad transfer.
        self.host_offload = (
            getattr(
                self.config.zero_config, "offload_optimizer_device", "none"
            ) == "cpu"
        )
        if self.host_offload and self.compensated_master:
            raise DeepSpeedConfigError(
                "offload_optimizer and master_dtype='compensated' are "
                "alternative memory strategies — pick one (docs/memory.md)"
            )
        if self.host_offload and jax.process_count() > 1:
            # mesh-sharded grads are not fully addressable from one
            # process, so the per-step d2h/h2d transfers would crash
            # mid-training; fail at init with the actionable message
            raise DeepSpeedConfigError(
                "offload_optimizer requires a single-process mesh; on "
                "multi-host pods use ZeRO sharding (stage>=1 divides "
                "optimizer state by dp) or "
                "data_types.master_dtype='compensated' instead"
            )
        self.master_in_opt = (
            self.host_offload
            or (
                not self.compensated_master
                and self.compute_dtype != jnp.float32
                and stage >= 1
                and dp_size > 1  # dp=1: a master copy would only add bytes
                and getattr(self.config.zero_config, "master_weights", True)
            )
        )
        with phase("init.place_params"):
            if self.master_in_opt or self.compensated_master:
                self.params = jax.device_put(
                    jax.tree_util.tree_map(
                        lambda p: p.astype(self.compute_dtype), params_f32
                    ),
                    self._param_shardings,
                )
            else:
                self.params = jax.device_put(
                    params_f32, self._param_shardings
                )
        if stage >= C.ZERO_OPTIMIZATION_WEIGHTS and dp_size > 1:
            self._zero3_account_bytes()

        # ---- optimizer ------------------------------------------------
        with phase("init.optimizer_state"):
            self.optimizer_obj = self._configure_optimizer()
            if stage >= 1 and type(self.optimizer_obj).__name__ == "FusedLamb":
                # the opaque pallas_call is not partitionable by GSPMD: sharded
                # optimizer-state leaves would be gathered at the kernel
                # boundary, silently undoing the ZeRO memory saving
                log_dist(
                    "WARNING: FusedLamb's Pallas kernel is not GSPMD-"
                    "partitionable; with zero_optimization.stage >= 1 the "
                    "sharded optimizer state is gathered at the kernel "
                    "boundary. Use optimizer type 'Lamb' (XLA-fused, shards "
                    "cleanly) with ZeRO.",
                    ranks=[0],
                )
            inner_state = self.optimizer_obj.init(params_f32)
            inner_shardings = zero_lib.specs_to_shardings(
                zero_lib.optstate_specs_like(
                    inner_state, optstate_param_specs, params_f32,
                    axis_sizes=dict(self._mesh.shape),
                ),
                self._mesh,
            )
            if self.host_offload:
                from ..utils.device import host_cpu_device

                cpu = host_cpu_device()
                self._cpu_device = cpu
                from jax.sharding import SingleDeviceSharding

                cpu_sh = SingleDeviceSharding(cpu)
                self._opt_shardings = {
                    "master": jax.tree_util.tree_map(lambda _: cpu_sh, params_f32),
                    "inner": jax.tree_util.tree_map(
                        lambda _: cpu_sh, inner_state
                    ),
                }
                self.optimizer_state = {
                    "master": jax.device_put(params_f32, cpu),
                    "inner": jax.device_put(inner_state, cpu),
                }
                log_dist(
                    "ZeRO-Offload: fp32 master + optimizer moments on host "
                    "cpu; accelerator holds compute-dtype params/grads "
                    "(per-step d2h grads + h2d params)",
                    ranks=[0],
                )
            elif self.master_in_opt:
                master_shardings = zero_lib.specs_to_shardings(
                    optstate_param_specs, self._mesh
                )
                self._opt_shardings = {
                    "master": master_shardings, "inner": inner_shardings,
                }
                self.optimizer_state = {
                    "master": jax.device_put(params_f32, master_shardings),
                    "inner": jax.device_put(inner_state, inner_shardings),
                }
            else:
                self._opt_shardings = inner_shardings
                self.optimizer_state = jax.device_put(inner_state, inner_shardings)
        del params_f32  # don't pin the unsharded fp32 copy beyond init

        # ---- grad accumulation buffer ---------------------------------
        self._grad_buffer = None  # lazily allocated on first backward
        self._pending_grads = None
        self._pending_loss = None
        self._pending_aux = ()
        self._window_losses = []  # device arrays; one per micro-step
        self._window_aux = []  # per-micro-step aux tuples (stacked at step())

        # ---- lr scheduler ---------------------------------------------
        self.lr_scheduler = self._configure_lr_scheduler()

        # activation checkpointing module flags from the json config
        # (reference _configure_checkpointing, deepspeed_light.py:374)
        from .. import checkpointing as _act_ckpt

        _act_ckpt.configure(self.mpu, deepspeed_config=self.config)

        # rank-0 scalar event stream (reference tensorboard wiring,
        # deepspeed_light.py:749-762,876-931)
        from ..utils.monitor import Monitor

        self.monitor = Monitor(
            enabled=self.config.tensorboard_enabled and jax.process_index() == 0,
            output_path=self.config.tensorboard_output_path,
            job_name=self.config.tensorboard_job_name,
        )
        base_lr = self.config.optimizer_params.get("lr", 1e-3)
        self._base_lr = float(base_lr)

        # ---- counters / bookkeeping -----------------------------------
        self.micro_steps = 0
        self.global_steps = 0
        self.skipped_steps = 0
        self.last_overflow = False
        # bf16/fp32 device-side skips reconcile lazily (one window late) —
        # queued (overflow flag, monitor entry) pairs still on device; see
        # _finish_step / _reconcile_deferred. _settled_steps counts settled
        # non-skipped windows (= the truthful step index monitor scalars
        # are written at).
        self._deferred_overflows = []
        self._settled_steps = 0
        self._warned_unrollable_scheduler = False
        self.last_aux = ()  # extra model outputs (multi-output contract)
        self.lamb_coeffs = []
        self._training = True
        # rbg keys generate random bits ~an order of magnitude faster than
        # threefry on TPU (hardware RNG path); dropout masks stay
        # deterministic per key. Non-TPU backends keep the default impl.
        if jax.devices()[0].platform == "tpu":
            self._rng = jax.random.key(rng_seed, impl="rbg")
        else:
            self._rng = jax.random.PRNGKey(rng_seed)

        # ---- timers ---------------------------------------------------
        self.wall_clock_breakdown = self.config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu()
            * self.gradient_accumulation_steps(),
            num_workers=self.dp_world_size,
            steps_per_output=self.steps_per_print(),
            # drain via a REAL output of the newest update program: it
            # waits for exactly that work on every device holding a shard,
            # with no extra dispatch (utils/timers._device_sync is the
            # generic fence for callers that hold no output)
            fence_fn=lambda: jax.block_until_ready(
                jax.tree_util.tree_leaves(self.optimizer_state)[0]
            ),
        )

        # ---- telemetry (docs/observability.md) ------------------------
        # Registry + exporters + config-armed profiler window + heartbeat
        # watchdog. A no-op facade when the "telemetry" block is absent, so
        # the async fast path never touches a device value for it.
        from ..telemetry import build_telemetry

        self.telemetry = build_telemetry(
            self.config,
            rank=jax.process_index(),
            n_params=self._n_params,
            timers=self.timers,
            # trace/stall fences block on a REAL output of the newest
            # update program, like the throughput timer's fence above
            fence_fn=lambda: jax.block_until_ready(
                jax.tree_util.tree_leaves(self.optimizer_state)[0]
            ),
        )
        if self.telemetry.enabled and (
            self._zero3_shard_bytes or self._zero3_gather_bytes
        ):
            # static stage-3 layout gauges (docs/observability.md): what
            # the dp sharding buys per chip and what each window pays in
            # gather traffic for it
            self.telemetry.set_zero3_layout(
                self._zero3_shard_bytes, self._zero3_gather_bytes
            )

        # ---- resilience (docs/resilience.md) --------------------------
        # Atomic-commit checkpoint protocol, retryable I/O, corruption
        # fallback, retention GC, preemption drain — policy object handed
        # to the checkpoint paths; metrics share the telemetry registry.
        from ..resilience import build_resilience

        self.resilience = build_resilience(self.config, telemetry=self.telemetry)
        # SIGTERM/SIGINT arm a save-at-next-step-boundary flag checked in
        # _finish_step (no-op unless the config enables preemption drain)
        self.resilience.install_preemption()
        # the drain's default save target when the config names none: the
        # last directory this engine saved to or resumed from
        self._last_checkpoint_dir = None
        # fault-injection registry (resilience/faults.py): NULL unless the
        # config armed sites; consulted at the step boundary, the window
        # placement path, and (via the manager) the checkpoint I/O seams
        self.faults = self.resilience.faults
        # self-healing run supervision (resilience/supervisor.py): anomaly
        # detectors at the step boundary + bounded rollback to the last
        # committed checkpoint. None unless the config enables it — the
        # async fast path never pays the per-window host sync otherwise.
        from ..resilience.supervisor import build_supervisor

        self.supervisor = build_supervisor(
            self.config,
            registry=(
                self.telemetry.registry
                if self.telemetry.enabled
                else self.resilience.registry
            ),
            # rollback spans + escalation flight dumps ride the
            # telemetry tracer (NOOP unless telemetry.tracing armed it);
            # ctx fn parents them under the run's train trace
            tracer=self.telemetry.tracer,
            trace_ctx_fn=self.telemetry.train_trace_ctx,
        )
        # rolled-back flag for the supervised train_batch retry loop: set
        # by _finish_step when the supervisor discarded this window's
        # timeline
        self._window_rolled_back = False
        if (
            self.supervisor is not None
            and getattr(self.telemetry, "watchdog", None) is not None
        ):
            # watchdog stall reports arm a rollback at the next completed
            # step boundary (the "wedged stager / transient hang" healer)
            self.telemetry.watchdog.add_stall_listener(
                self.supervisor.notify_stall
            )

        # ---- input staging pipeline (runtime/staging.py) --------------
        # Double-buffered async window staging: while window N computes,
        # window N+1 is pulled/stacked/device_put on a background worker.
        # The stager is created lazily at the first iterator-fed
        # train_batch() and torn down on source change, exhaustion, or
        # preemption drain.
        self._staging_enabled = self.config.data_pipeline_enabled
        self._staging_buffers = self.config.data_pipeline_staging_buffers
        self._stage_to_device = self.config.data_pipeline_stage_to_device
        self._stager = None
        self._stager_source = None
        self._stager_finalizer = None
        # consecutive source replacements whose stager served <= 1 window:
        # the fingerprint of fresh per-call iterators (iter(list) each
        # step), where staging is pure thread churn — see _ensure_stager
        self._stager_churn = 0
        self._last_unstaged_source = None
        # loaders built by deepspeed_io, weakly held: close_data_pipeline
        # must reach LOADER-owned staging workers (the accum==1
        # stage_to_device path) too, not only the engine-owned stager
        self._data_loaders = []

        # ---- dataloader -----------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # ---- jitted functions -----------------------------------------
        with phase("init.build_steps"):
            self._build_jitted_steps()

        log_dist(
            f"DeepSpeedEngine initialized: mesh={dict(self._mesh.shape)} "
            f"zero_stage={stage} dtype={self.compute_dtype.__name__} "
            f"optimizer={type(self.optimizer_obj).__name__}",
            ranks=[0],
        )

    # ------------------------------------------------------------------
    # configuration accessors (reference API surface)
    # ------------------------------------------------------------------
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def steps_per_print(self):
        return self.config.steps_per_print

    def zero_optimization(self):
        return self.config.zero_enabled

    def fp16_enabled(self):
        return self.config.fp16_enabled

    def bfloat16_enabled(self):
        return self.config.bf16_enabled

    def gradient_clipping(self):
        return self.config.gradient_clipping

    def sparse_gradients_enabled(self):
        return self.config.sparse_gradients_enabled

    @property
    def mesh(self):
        return self._mesh

    def is_gradient_accumulation_boundary(self):
        """True when the NEXT step() will apply an optimizer update
        (reference deepspeed_light.py:809-817)."""
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def train(self, mode=True):
        self._training = mode

    def eval(self):
        self._training = False

    # ------------------------------------------------------------------
    # wiring
    # ------------------------------------------------------------------
    def _build_loss_fn(self, model):
        """Normalize the model into loss_fn(params, batch_tuple, rng)->loss.

        Accepts a flax Module whose __call__ returns the scalar loss (the
        reference's nn.Module contract), or a bare callable with the
        loss_fn signature already.
        """
        if hasattr(model, "apply") and hasattr(model, "init"):
            sig_params = ()
            try:
                sig_params = tuple(
                    inspect.signature(model.__call__).parameters.keys()
                )
            except (TypeError, ValueError):
                pass
            takes_train = "train" in sig_params
            engine = self

            def loss_fn(params, batch, rng):
                kwargs = {}
                if takes_train:
                    kwargs["train"] = engine._training
                return model.apply(
                    {"params": params}, *batch, rngs={"dropout": rng}, **kwargs
                )

            return loss_fn
        if callable(model):
            return model
        raise TypeError(
            "model must be a flax Module or a callable loss_fn(params, batch, rng)"
        )

    def _configure_adapters(self, model, model_parameters, rng_seed):
        """LoRA fine-tune wiring (docs/adapters.md): split/grow the
        adapter tree, freeze the base, and return the adapter tree as
        the engine's trainable parameters.

        The module's config is armed with the block's rank/alpha/targets
        (the same pre-trace mutation pattern as the mesh injection) so
        ``model.apply`` consumes the merged tree's ``*_lora_*`` leaves.
        ``model_parameters`` may already carry adapter leaves (a module
        initialized with ``lora_rank > 0``, or a resumed fine-tune) —
        they are split out; otherwise a fresh adapter tree grows beside
        the base (A ~ N(0, 0.02), B = 0: the first forward is the base
        model bitwise). The frozen base pins to its model-parallel
        shardings in the compute dtype and is only ever READ — no
        optimizer state, no gradients, no donation — so it stays
        bitwise-identical across every fine-tune step.
        """
        from ..adapters import lora as lora_lib

        cfg = self.config
        rank = int(cfg.adapters_rank)
        alpha = float(cfg.adapters_alpha or 0.0)
        targets = lora_lib.resolve_lora_targets(cfg.adapters_targets)
        mcfg = getattr(model, "config", None)
        if mcfg is not None and hasattr(mcfg, "lora_rank"):
            if getattr(mcfg, "lora_rank", 0) == 0:
                mcfg.lora_rank = rank
                mcfg.lora_alpha = alpha
                mcfg.lora_targets = targets
            elif (
                int(mcfg.lora_rank) != rank
                or lora_lib.resolve_lora_targets(mcfg.lora_targets)
                != targets
            ):
                raise DeepSpeedConfigError(
                    f"model config carries lora_rank="
                    f"{mcfg.lora_rank}/targets="
                    f"{tuple(mcfg.lora_targets)} but the adapters block "
                    f"asks for rank={rank}/targets={targets}; make them "
                    "agree (or leave the model at lora_rank=0 and let "
                    "the engine arm it)"
                )
        base, adapters = lora_lib.split_lora_params(model_parameters)
        if not adapters:
            adapters = lora_lib.init_lora_params(
                base, rank, targets=targets,
                rng=jax.random.PRNGKey(rng_seed),
            )
        # model-parallel specs split the same way the params do: the
        # engine's spec machinery sees adapter specs only, the frozen
        # base keeps its own
        base_specs = None
        if self._model_specs is not None:
            base_specs, adapter_specs = lora_lib.split_lora_params(
                self._model_specs
            )
            self._model_specs = adapter_specs or None
        from jax.sharding import NamedSharding, PartitionSpec

        if base_specs:
            base_shardings = zero_lib.specs_to_shardings(
                base_specs, self._mesh
            )
        else:
            base_shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(self._mesh, PartitionSpec()), base
            )
        self.frozen_base_params = jax.device_put(
            jax.tree_util.tree_map(
                lambda p: jnp.asarray(p, self.compute_dtype), base
            ),
            base_shardings,
        )
        self._frozen_n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(base)
        )
        # the loss closes over the frozen tree and differentiates ONLY
        # the adapter tree — base cotangents are never formed, and the
        # merge is pure dict surgery inside the jitted program
        inner_loss = self._loss_fn
        frozen = self.frozen_base_params
        merge = lora_lib.merge_lora_params

        def lora_loss(adapter_params, batch, rng):
            return inner_loss(merge(frozen, adapter_params), batch, rng)

        self._loss_fn = lora_loss
        self._adapters_meta = {
            "rank": rank, "alpha": alpha, "targets": list(targets),
        }
        n_adapter = lora_lib.adapter_num_params(adapters)
        log_dist(
            f"adapters: LoRA fine-tune — rank {rank} on "
            f"{list(targets)}; {n_adapter} trainable adapter params, "
            f"{self._frozen_n_params} base params frozen "
            f"({100.0 * n_adapter / max(self._frozen_n_params, 1):.2f}%)",
            ranks=[0],
        )
        return adapters

    def _arm_zero3_gather(self, model):
        """Arm the model's ZeRO-3 layer-wise JIT gather seam
        (models/stack.py; docs/performance.md "ZeRO-3 & collective
        overlap"). The descriptor carries, per 12-tensor block param:

        - the GATHERED per-layer spec — this leaf's persistent stage-3
          spec with the ``data`` axis stripped and the leading layers
          dim dropped. It is derived from ``self._param_specs``, so the
          gather composes with whatever model-parallel layout the caller
          passed (TP axes stay sharded; an axis is never double-used);
        - the persistent STACKED spec, anchoring the scan operand so
          sharding propagation cannot hoist one whole-stack gather out
          of the loop;
        - the gather block size (``zero_optimization.stage3_gather_block``):
          layers gathered together per scan iteration, the "gather layer
          i+1 while computing layer i" overlap structure.

        Models without the seam (bare loss_fn callables, custom modules)
        still train correctly at stage 3 — params stay dp-sharded and
        XLA places the gathers — they just don't get the layer-wise
        residency guarantee; logged so the gap is visible.
        """
        from jax.sharding import PartitionSpec
        from ..ops.transformer import TRANSFORMER_PARAM_LAYOUT

        mcfg = getattr(model, "config", None)
        if mcfg is None or not hasattr(mcfg, "zero3_gather"):
            log_dist(
                "ZeRO-3: model exposes no layer-gather seam "
                "(zero3_gather); persistent params stay dp-sharded and "
                "XLA chooses gather placement",
                ranks=[0],
            )
            return
        blockers = []
        if getattr(mcfg, "pipeline_stages", 1) > 1:
            blockers.append("pipeline_stages > 1")
        if getattr(mcfg, "moe_experts", 0) > 0:
            blockers.append("moe_experts > 0")
        if getattr(mcfg, "lora_rank", 0) > 0 or self.adapters_enabled:
            blockers.append("LoRA adapters")
        if blockers:
            log_dist(
                "ZeRO-3: layer-wise gather seam not armed ("
                + ", ".join(blockers)
                + " do not compose with the zero3 stack yet); params "
                "stay dp-sharded, XLA chooses gather placement",
                ranks=[0],
            )
            self._disarm_zero3_gather(model)
            return
        block_names = {n for n, _, _ in TRANSFORMER_PARAM_LAYOUT}
        specs, stacked_specs, conflicts = {}, {}, set()
        flat = jax.tree_util.tree_flatten_with_path(
            self._param_specs,
            is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec),
        )[0]
        for path, spec in flat:
            name = zero_lib._key_token(path[-1])
            if name not in block_names:
                continue
            per_layer = PartitionSpec(
                *zero_lib.gathered_spec(spec)[1:]
            )
            if name in specs and (
                specs[name] != per_layer or stacked_specs[name] != spec
            ):
                # two stacks sharing tensor names with different layouts:
                # replicate conservatively (correct either way) and drop
                # the anchor rather than pin one stack's layout onto the
                # other's operand
                conflicts.add(name)
            specs[name] = per_layer
            stacked_specs[name] = spec
        for name in conflicts:
            specs[name] = PartitionSpec()
            stacked_specs.pop(name, None)
        if not specs:
            self._disarm_zero3_gather(model)
            return
        gb = int(
            getattr(self.config.zero_config, "stage3_gather_block", 2)
        )
        mcfg.zero3_gather = {
            "specs": specs,
            "stacked_specs": stacked_specs,
            "block": gb,
        }
        self.zero3_gather_enabled = True
        log_dist(
            f"ZeRO-3: layer-wise JIT gather armed over {len(specs)} "
            f"block tensors (gather_block={gb}; gathered weights remat "
            "as 'zero3_gathered' — backward re-gathers)",
            ranks=[0],
        )

    def _disarm_zero3_gather(self, model):
        """Clear a gather-seam arming left on the model config by a
        PREVIOUS engine (the arming is a config mutation so the flax
        module picks it up inside apply): a non-stage-3 engine — or an
        arming pass that declined — must not run the zero3 stack with a
        stale engine's specs/mesh."""
        mcfg = getattr(model, "config", None)
        if mcfg is not None and getattr(mcfg, "zero3_gather", None) is not None:
            mcfg.zero3_gather = None
            log_dist(
                "ZeRO-3: disarmed a stale layer-gather seam from a "
                "previous engine on this model config",
                ranks=[0],
            )

    def _zero3_account_bytes(self):
        """Stage-3 memory/traffic accounting for the telemetry gauges
        (train/zero3_param_shard_bytes, train/zero3_gather_bytes_per_
        window): per-chip persistent param bytes under the FULL sharding
        (every mesh axis a leaf's spec names divides its residency, not
        just ZeRO's data axis), and the per-chip all-gather volume one
        window moves for the JIT weight gathers (forward + backward
        re-gather; each gather materializes the leaf with only the data
        axis stripped — model-parallel shards stay sharded — so a ring
        all-gather delivers the other dp shards' (dp-1)/dp of the
        mp-local portion)."""
        mesh_axes = dict(self._mesh.shape) if self._mesh is not None else {}

        def spec_factor(spec, skip=()):
            f = 1
            for e in spec:
                names = e if isinstance(e, tuple) else (e,)
                for n in names:
                    if n is not None and n not in skip:
                        f *= mesh_axes.get(n, 1)
            return f

        resident = gather = 0
        flat = jax.tree_util.tree_flatten_with_path(self.params)[0]
        specs_flat = jax.tree_util.tree_leaves(
            self._param_specs,
            is_leaf=lambda v: isinstance(v, jax.sharding.PartitionSpec),
        )
        for (path, leaf), spec in zip(flat, specs_flat):
            nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            resident += nbytes // spec_factor(spec)
            if zero_lib.has_axis(spec):
                dp = mesh_axes.get(C.DATA_AXIS, 1)
                mp_local = nbytes // spec_factor(spec, skip=(C.DATA_AXIS,))
                gather += 2 * (mp_local * (dp - 1) // dp)
        self._zero3_shard_bytes = resident
        self._zero3_gather_bytes = gather

    def _check_zero_optimizer_tested(self, name):
        """ZeRO wrapping an optimizer outside the tested set requires the
        ``zero_allow_untested_optimizer`` opt-in (reference guard:
        deepspeed_light.py:506-515, deepspeed_constants.py:150-156)."""
        if self.zero_stage < 1 or name in C.ZERO_TESTED_OPTIMIZERS:
            return
        # FusedLamb shares Lamb's state layout; its own fp32-moment
        # restriction is enforced separately below
        if name in ("fusedlamb", "fused_lamb"):
            return
        if not self.config.zero_allow_untested_optimizer:
            raise DeepSpeedConfigError(
                f"optimizer {name!r} is untested with ZeRO (sharded "
                "optimizer-state specs are derived per optimizer). Add "
                f'{{"{C.ZERO_ALLOW_UNTESTED_OPTIMIZER}": true}} to the '
                "config to proceed anyway."
            )
        log_dist(
            f"WARNING: running ZeRO with untested optimizer {name!r} "
            f"({C.ZERO_ALLOW_UNTESTED_OPTIMIZER}=true) — proceed with "
            "caution",
            ranks=[0],
        )

    def _configure_optimizer(self) -> Optimizer:
        if self.client_optimizer is not None:
            if not isinstance(self.client_optimizer, Optimizer):
                raise TypeError(
                    "client optimizer must be a deepspeed_tpu.ops.Optimizer"
                )
            self._check_zero_optimizer_tested(
                type(self.client_optimizer).__name__.lower()
            )
            log_dist("Using client optimizer", ranks=[0])
            return self.client_optimizer
        name = self.config.optimizer_name
        if name is None:
            name = C.ADAM_OPTIMIZER
        self._check_zero_optimizer_tested(name)
        opt = build_optimizer(name, self.config.optimizer_params)
        sd = self.config.optimizer_state_dtype
        if sd != "fp32":
            if not hasattr(opt, "state_dtype"):
                raise DeepSpeedConfigError(
                    f"optimizer {name!r} does not support "
                    f"{C.OPTIMIZER_STATE_DTYPE}={sd!r} (Adam/AdamW/Lamb do)"
                )
            if type(opt).__name__ == "FusedLamb":
                # surface at init, not at the first step's jit trace
                raise DeepSpeedConfigError(
                    "FusedLamb's Pallas kernel reads fp32 moments; use "
                    "optimizer type 'Lamb' with reduced "
                    f"{C.OPTIMIZER_STATE_DTYPE}"
                )
            opt.state_dtype = sd
            log_dist(
                f"optimizer moments stored as {sd} "
                "(fp32 update math; ops/quant.py)",
                ranks=[0],
            )
        if getattr(self, "compensated_master", False):
            if not hasattr(opt, "master_compensation"):
                raise DeepSpeedConfigError(
                    f"optimizer {name!r} does not support "
                    f"{C.MASTER_DTYPE}='compensated' (Adam/AdamW do)"
                )
            opt.master_compensation = True
            log_dist(
                "compensated master weights: params stored in the compute "
                "dtype + int8 Kahan error codes in the optimizer state "
                "(ops/quant.py)",
                ranks=[0],
            )
        return opt

    def _place_scaler(self, state):
        """The loss-scale state, replicated over the mesh. Every step
        program returns it that way; a fresh (or restored) state left
        uncommitted on the default device would make the SECOND window
        recompile each program it feeds."""
        return jax.device_put(
            state,
            jax.sharding.NamedSharding(
                self._mesh, jax.sharding.PartitionSpec()
            ),
        )

    def _configure_lr_scheduler(self):
        if self.client_lr_scheduler is not None:
            return self.client_lr_scheduler
        if self.config.scheduler_name is not None:
            return build_lr_scheduler(
                self.config.scheduler_name, self.config.scheduler_params
            )
        return None

    def _current_lr(self):
        if self.lr_scheduler is not None:
            lr = self.lr_scheduler.get_lr()
            if isinstance(lr, (list, tuple)):
                lr = lr[0]
            return float(lr)
        return self._base_lr

    def get_lr(self):
        return [self._current_lr()]

    def _current_mom(self):
        """First-moment coefficient for THIS step: the scheduler's cycled
        momentum (OneCycle ``get_mom()``, reference
        deepspeed_lr_schedules.py:477-520) when available, else the
        optimizer's configured coefficient. Threaded into the jitted
        update as a traced scalar alongside lr — cycling never
        recompiles."""
        if self.lr_scheduler is not None and hasattr(
            self.lr_scheduler, "get_mom"
        ):
            mom = self.lr_scheduler.get_mom()
            if mom is not None:
                if isinstance(mom, (list, tuple)):
                    mom = mom[0]
                return float(mom)
        opt = self.optimizer_obj
        if hasattr(opt, "b1"):
            return float(opt.b1)
        return float(getattr(opt, "momentum", 0.0))

    def get_mom(self):
        return [self._current_mom()]

    # ------------------------------------------------------------------
    # jitted step construction
    # ------------------------------------------------------------------
    def _build_jitted_steps(self):
        compute_dtype = self.compute_dtype
        loss_fn = self._loss_fn
        grad_shardings = self._grad_shardings
        accum = self.gradient_accumulation_steps()
        clip = float(self.config.gradient_clipping or 0.0)
        optimizer = self.optimizer_obj
        param_shardings = self._param_shardings
        master_in_opt = self.master_in_opt
        opt_shardings = self._opt_shardings

        def cast_params(params):
            if compute_dtype == jnp.float32:
                return params
            return jax.tree_util.tree_map(
                lambda p: p.astype(compute_dtype), params
            )

        def cast_batch(batch):
            # float inputs follow the compute dtype (the analog of the
            # reference casting the model AND batch to half,
            # deepspeed_light.py:463-491); integer ids/labels untouched.
            if compute_dtype == jnp.float32:
                return batch
            return jax.tree_util.tree_map(
                lambda x: x.astype(compute_dtype)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                batch,
            )

        accum_dtype = self.grad_accum_dtype

        def fwd_bwd(params, batch, rng, loss_scale):
            # Differentiate w.r.t. the COMPUTE-dtype params (cast applied
            # OUTSIDE jax.grad): the cast's derivative is 1, so grads are
            # identical, but cotangents stay bf16 end-to-end instead of
            # being up-converted to match fp32 param storage — at GPT-2
            # 1.5B those fp32 cotangent temps are several GB of HLO temp
            # that decide whether one 16 GB chip fits the model.
            params_c = cast_params(params)

            def scaled_loss_fn(pc):
                out = loss_fn(pc, cast_batch(batch), rng)
                loss, aux = _split_model_output(out)
                return (
                    loss.astype(jnp.float32) * loss_scale / accum,
                    (loss, aux),
                )

            grads, (loss, aux) = jax.grad(scaled_loss_fn, has_aux=True)(
                params_c
            )
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g.astype(accum_dtype), s
                ),
                grads,
                grad_shardings,
            )
            return loss, aux, grads

        self._jit_fwd_bwd = jax.jit(fwd_bwd)

        def fwd_only(params, batch, rng):
            out = loss_fn(cast_params(params), cast_batch(batch), rng)
            return _split_model_output(out)

        self._jit_fwd_only = jax.jit(fwd_only)

        def accumulate(buffer, grads):
            return jax.tree_util.tree_map(
                lambda b, g, s: jax.lax.with_sharding_constraint(b + g, s),
                buffer,
                grads,
                grad_shardings,
            )

        self._jit_accumulate = jax.jit(accumulate, donate_argnums=(0,))

        def fwd_bwd_into(params, batch, rng, loss_scale, gbuf):
            """fwd+bwd with the grad-accumulate FOLDED IN: the fresh grad
            tree never exists next to the buffer (the buffer is donated and
            each leaf's add fuses into backward), so accumulation costs one
            leaf of transient liveness instead of a whole extra grad tree —
            at GPT-2 1.5B that is +0.6 GB vs +3.1 GB, the difference
            between accum>1 fitting the chip and OOM (measured r05)."""
            loss, aux, grads = fwd_bwd(params, batch, rng, loss_scale)
            return loss, aux, accumulate(gbuf, grads)

        self._jit_fwd_bwd_into = jax.jit(fwd_bwd_into, donate_argnums=(4,))

        # Full inf/nan-scan overflow detection exists for fp16 loss-scaling
        # semantics (reference fp16_optimizer.py); the reference likewise
        # only wraps the optimizer in FP16_Optimizer when fp16 is on
        # (deepspeed_light.py:506-525). bf16/fp32 runs keep a cheaper guard:
        # a non-finite global grad norm skips the update on-device, so a
        # loss spike can't NaN the params — without the per-step host sync
        # that fp16's skipped-step accounting needs.
        check_overflow = self.config.fp16_enabled

        def detect_overflow(grad_buffer):
            # ONE fp32 reduction over the accumulation-dtype buffer; the
            # scalar unscale factors out of the norm (||g/s|| = ||g||/s) so
            # no fp32 copy of the grad tree is ever materialized — at
            # GPT-2 1.5B that copy is ~6 GB, the difference between fitting
            # one 16 GB chip and OOM.
            raw_norm = global_norm(grad_buffer)  # -1.0 sentinel if inf/nan
            if check_overflow:
                overflow = has_overflow(grad_buffer)
            else:
                # global_norm returns the reference's -1.0 SENTINEL for an
                # inf/nan norm (deepspeed_utils.py:140-147) — never a
                # non-finite value, so test the sentinel, not isfinite
                overflow = raw_norm < 0.0
            return raw_norm, overflow

        # momentum threads through the jit like lr (a traced scalar) only
        # for optimizers whose update math accepts a per-step coefficient;
        # others (e.g. FusedLamb's compile-time kernel constants) never see
        # the argument
        use_mom = getattr(optimizer, "supports_mom", False)
        if (
            not use_mom
            and self.lr_scheduler is not None
            and getattr(self.lr_scheduler, "get_mom", lambda: None)()
            is not None
        ):
            log_dist(
                "WARNING: the LR scheduler cycles momentum but optimizer "
                f"{type(optimizer).__name__} cannot apply a per-step "
                "coefficient (SGD needs momentum != 0; FusedLamb bakes b1 "
                "into its kernel — use 'Lamb') — momentum cycling is "
                "ignored",
                ranks=[0],
            )

        # where the update runs, for optimizers that hand leaves to a Pallas
        # kernel: per shard of the state's own layout on a mesh of several
        # devices (GSPMD cannot partition a pallas_call), and not at all in
        # the host-side offload step
        placed = getattr(optimizer, "supports_placement", False)
        update_shard = (
            (self._mesh, self._optstate_param_specs)
            if self._mesh.size > 1 else None
        )

        def cond_update(params, opt_state, grads, raw_norm, overflow,
                        inv_scale, lr, mom, layout, on_host=False):
            """Shared overflow-gated update core: unscale+clip as one
            scalar grad_scale into the optimizer; layout 'master' steps
            opt_state['master'] and publishes compute-dtype params,
            'plain' steps params directly.

            Optimizers with ``supports_gate`` take the skip as a scalar
            gate INSIDE the update (old stored bytes re-written on a
            skipped step) instead of a ``lax.cond`` branch: the cond keeps
            the untouched state alive for its skip arm, which blocks
            XLA's in-place buffer reuse and copied every state array per
            chunk iteration — measured 132 ms of a 614 ms GPT-2 774M
            window (round-4 profile) before this change."""
            def do_update(operands, gate=None):
                params, opt_state, grads = operands
                grad_norm = raw_norm * inv_scale  # post-unscale norm
                gscale = inv_scale
                if clip > 0:
                    gscale = gscale * jnp.where(
                        (grad_norm > clip) & (grad_norm > 0),
                        clip / grad_norm, jnp.float32(1.0),
                    )
                opt_kw = {} if gate is None else {"gate": gate}
                if use_mom:
                    opt_kw["mom"] = mom
                if placed:
                    opt_kw["kernel"] = not on_host
                    opt_kw["shard"] = None if on_host else update_shard
                if layout == "master":
                    # step the fp32 master, then publish the compute-dtype
                    # params — the reference's fp32-partition step + fp16
                    # copy (deepspeed_zero_optimizer.py:1157-1199); under
                    # GSPMD the all-gather is XLA's
                    new_master, new_inner, aux = optimizer.apply(
                        opt_state["master"], grads, opt_state["inner"], lr,
                        grad_scale=gscale, **opt_kw,
                    )
                    new_opt = {"master": new_master, "inner": new_inner}
                    new_params = jax.tree_util.tree_map(
                        lambda m, p: m.astype(p.dtype), new_master, params
                    )
                else:
                    new_params, new_opt, aux = optimizer.apply(
                        params, grads, opt_state, lr, grad_scale=gscale,
                        **opt_kw,
                    )
                coeffs = aux.get("lamb_coeffs", [])
                coeff_vec = (
                    jnp.stack(coeffs) if coeffs else jnp.zeros((0,), jnp.float32)
                )
                return new_params, new_opt, grad_norm, coeff_vec

            if getattr(optimizer, "supports_gate", False):
                new_params, new_opt, grad_norm, coeff_vec = do_update(
                    (params, opt_state, grads),
                    gate=jnp.logical_not(overflow),
                )
                return (
                    new_params,
                    new_opt,
                    jnp.where(overflow, jnp.float32(-1.0), grad_norm),
                    jnp.where(overflow, jnp.zeros_like(coeff_vec), coeff_vec),
                )

            def skip_update(operands):
                params, opt_state, grads = operands
                n_coeffs = 0
                if hasattr(optimizer, "max_coeff"):
                    n_coeffs = len(jax.tree_util.tree_leaves(params))
                return (
                    params,
                    opt_state,
                    jnp.float32(-1.0),
                    jnp.zeros((n_coeffs,), jnp.float32),
                )

            return jax.lax.cond(
                overflow, skip_update, do_update, (params, opt_state, grads)
            )

        def update_body(params, opt_state, grad_buffer, scaler_state, lr,
                        mom):
            inv_scale = 1.0 / scaler_state.loss_scale
            # a collective carries the scope of the operation that
            # PRODUCED the resharded value, so these two scopes say which
            # of the update's collectives are the norm's and which the
            # parameters' (benchmark reader collective_scope_time)
            with jax.named_scope("update_grad_norm"):
                raw_norm, overflow = detect_overflow(grad_buffer)
            with jax.named_scope("update_apply"):
                new_params, new_opt, grad_norm, coeffs = cond_update(
                    params, opt_state, grad_buffer, raw_norm, overflow,
                    inv_scale, lr, mom,
                    "master" if master_in_opt else "plain",
                )
                new_params = jax.tree_util.tree_map(
                    lambda p, s: jax.lax.with_sharding_constraint(p, s),
                    new_params,
                    param_shardings,
                )
            new_scaler = update_scale(scaler_state, overflow)
            return new_params, new_opt, new_scaler, overflow, grad_norm, coeffs

        # No zeroed replacement buffer comes back from the update: the next
        # window's backward() lazily re-seeds the accumulator from its first
        # micro-step's grads, so a multi-GB tree of zeros would be pure HLO
        # temp (it alone pushed GPT-2 1.5B past 16 GB). The grad buffer is
        # still DONATED — with no aliasable output XLA reuses it as scratch
        # and frees it early; jax's "donated buffers were not usable"
        # warning at first compile is EXPECTED for the grad argnum and left
        # unsuppressed (a global filter would also hide genuine donation
        # regressions on params/opt state).
        self._jit_apply_update = jax.jit(
            update_body, donate_argnums=(0, 1, 2)
        )

        if self.host_offload:

            def update_body_offload(master, inner, grads, scaler_state, lr,
                                    mom):
                """Host-side (cpu-jitted) master update: all inputs live on
                the cpu device, so XLA compiles this for the host backend.
                Same cond_update core as the on-device path ('master'
                layout, params role played by the master itself since the
                fresh compute-dtype params derive from it); returns those
                params for the h2d push."""
                inv_scale = 1.0 / scaler_state.loss_scale
                with jax.named_scope("update_grad_norm"):
                    raw_norm, overflow = detect_overflow(grads)
                params_like = jax.tree_util.tree_map(
                    lambda m: m.astype(compute_dtype), master
                )
                with jax.named_scope("update_apply"):
                    new_params, new_opt, grad_norm, coeffs = cond_update(
                        params_like, {"master": master, "inner": inner},
                        grads, raw_norm, overflow, inv_scale, lr, mom,
                        "master", on_host=True,
                    )
                new_scaler = update_scale(scaler_state, overflow)
                return (
                    new_params, new_opt["master"], new_opt["inner"],
                    new_scaler, overflow, grad_norm, coeffs,
                )

            self._jit_apply_update_offload = jax.jit(
                update_body_offload, donate_argnums=(0, 1, 2)
            )

        def train_window(params, opt_state, scaler_state, batches, rng_keys,
                         lr, mom):
            """One full accumulation window in a single compiled program:
            accum x (forward+backward) -> grad sum -> optimizer update.

            ``batches`` leaves carry a leading [accum] axis; ``rng_keys`` is
            [accum, key]. Fusing the window removes per-micro-step dispatch
            (its cost on a locally attached chip: not measured) and lets
            XLA overlap the update with the last backward.
            """
            loss_scale = scaler_state.loss_scale
            # named_scope sections label the profiler trace (the fused
            # window's analog of the reference's per-phase breakdown,
            # deepspeed_light.py:886-931) — phase attribution survives the
            # single-program fusion
            with jax.named_scope("window_fwd_bwd"):
                if accum == 1:
                    first = jax.tree_util.tree_map(lambda x: x[0], batches)
                    loss, aux, grads = fwd_bwd(
                        params, first, rng_keys[0], loss_scale
                    )
                    losses = loss.astype(jnp.float32)[None]
                    # match the accum>1 scan's [accum]-stacked aux layout
                    aux = jax.tree_util.tree_map(lambda a: a[None], aux)
                else:
                    with jax.named_scope("grad_accum"):
                        zeros = jax.tree_util.tree_map(
                            lambda p, s: jax.lax.with_sharding_constraint(
                                jnp.zeros(p.shape, accum_dtype), s
                            ),
                            params,
                            grad_shardings,
                        )

                    def body(gbuf, xs):
                        b, k = xs
                        loss, aux, g = fwd_bwd(params, b, k, loss_scale)
                        with jax.named_scope("grad_accum"):
                            gbuf = jax.tree_util.tree_map(
                                lambda a, gg, s:
                                jax.lax.with_sharding_constraint(a + gg, s),
                                gbuf,
                                g,
                                grad_shardings,
                            )
                        return gbuf, (loss.astype(jnp.float32), aux)

                    grads, (losses, aux) = jax.lax.scan(
                        body, zeros, (batches, rng_keys)
                    )
            with jax.named_scope("window_optimizer_update"):
                new_params, new_opt, new_scaler, overflow, grad_norm, coeffs = (
                    update_body(params, opt_state, grads, scaler_state, lr,
                                mom)
                )
            return (
                new_params, new_opt, new_scaler, overflow, grad_norm, coeffs,
                jnp.mean(losses), aux,
            )

        self._jit_train_window = jax.jit(train_window, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    # training API
    # ------------------------------------------------------------------
    def forward(self, *inputs):
        """Run the model; in train mode also computes and stashes gradients
        for the following backward() (one fused fwd+bwd pass — see module
        docstring for why this matches torch's cost)."""
        if self._training and self._pending_grads is _GRADS_ACCUMULATED:
            # checked BEFORE any state mutates (timer start, rng split):
            # the buffer was already consumed by the previous forward, so
            # a second forward() without backward() would corrupt the
            # accumulation window
            raise RuntimeError(
                "two forward() calls without backward() inside an "
                "accumulation window (gradients already folded into the "
                "buffer)"
            )
        if self._training and self.telemetry.enabled:
            # every micro-step is liveness, not just window completion: a
            # deep accumulation window (or one slow-host micro-step) can
            # legitimately outlast the watchdog timeout end-to-end, and
            # only on_window_end beats
            self.telemetry.heartbeat()
            if self.micro_steps % self.gradient_accumulation_steps() == 0:
                # first micro-step of a new accumulation window
                self.telemetry.on_window_start()
            self.telemetry.count_batch(*self._batch_tokens(inputs))
        elif not self._training:
            # eval forwards are liveness, not windows: without this an
            # eval epoch longer than the watchdog timeout reads as a stall
            self.telemetry.heartbeat()
        if self.wall_clock_breakdown:
            self.timers(FORWARD_TIMER).start()
        batch = self._shard_batch(inputs)
        self._rng, key = jax.random.split(self._rng)
        if self._training:
            if self._grad_buffer is not None:
                # mid-window micro-step: grads fold into the DONATED buffer
                # inside the fwd+bwd program (see fwd_bwd_into)
                loss, aux, self._grad_buffer = self._jit_fwd_bwd_into(
                    self.params, batch, key,
                    self.loss_scale_state.loss_scale, self._grad_buffer,
                )
                self._pending_grads = _GRADS_ACCUMULATED
            else:
                loss, aux, grads = self._jit_fwd_bwd(
                    self.params, batch, key, self.loss_scale_state.loss_scale
                )
                self._pending_grads = grads
            self._pending_loss = loss
            self._pending_aux = aux
            # mid-window view: this micro-step's raw aux; step() replaces it
            # with the [accum]-stacked window (same layout as train_batch)
            self.last_aux = aux
        else:
            loss, aux = self._jit_fwd_only(self.params, batch, key)
            self.last_aux = aux
        if self.wall_clock_breakdown:
            # fence on the phase's REAL output: blocking on the loss waits
            # for exactly the work being timed, on every device that holds
            # a shard of it, and dispatches no extra program. Breakdown
            # mode serializes the loop by design — it is a diagnostic.
            jax.block_until_ready(loss)
            self.timers(FORWARD_TIMER).stop()
        return loss

    __call__ = forward

    @staticmethod
    def _batch_tokens(inputs):
        """(tokens, samples) of one micro-batch from its first array leaf:
        rows are samples; rows x dim-1 extent are tokens ONLY for 2-d
        INTEGER leaves (the (batch, seq) id/label layout of LM batches).
        Float feature matrices, images, and other non-id inputs count
        tokens == samples — calling the feature dim of a (B, 512) dense
        batch or dim-1 of a (B, H, W, C) image "sequence length" would
        inflate the tokens/sec and model-TFLOPS gauges by that factor."""
        for leaf in jax.tree_util.tree_leaves(inputs):
            shape = getattr(leaf, "shape", None)
            if shape:
                samples = int(shape[0])
                dtype = getattr(leaf, "dtype", None)
                is_token_ids = (
                    len(shape) == 2
                    and dtype is not None
                    and np.issubdtype(dtype, np.integer)
                )
                tokens = samples * int(shape[1]) if is_token_ids else samples
                return tokens, samples
        return 0, 0

    def backward(self, loss, allreduce_gradients=True):
        """Accumulate the gradients stashed by forward (reference contract:
        deepspeed_light.py:736-806; gradient averaging over the data axis is
        already folded into the jitted grad computation)."""
        del loss, allreduce_gradients
        if self._pending_grads is None:
            raise RuntimeError(
                "backward() called without a preceding forward() in train mode"
            )
        if self.wall_clock_breakdown:
            self.timers(BACKWARD_TIMER).start()
        if self._pending_grads is _GRADS_ACCUMULATED:
            pass  # already folded into the buffer by fwd_bwd_into
        elif self._grad_buffer is None:
            self._grad_buffer = self._pending_grads
        else:
            # reachable only for grads stashed before the buffer existed
            # (clients juggling buffers directly); the hot path folds in
            # forward()
            self._grad_buffer = self._jit_accumulate(
                self._grad_buffer, self._pending_grads
            )
        self._pending_grads = None
        self._window_losses.append(self._pending_loss)
        self._pending_loss = None
        self._window_aux.append(self._pending_aux)
        self._pending_aux = ()
        self.micro_steps += 1
        if self.wall_clock_breakdown:
            if self._grad_buffer is not None:
                jax.block_until_ready(
                    jax.tree_util.tree_leaves(self._grad_buffer)[0]
                )
            self.timers(BACKWARD_TIMER).stop()

    def step(self):
        """Apply the optimizer update at the gradient-accumulation boundary
        (reference deepspeed_light.py:824-869, incl. overflow-skip)."""
        if self.micro_steps == 0 or self.micro_steps % self.gradient_accumulation_steps() != 0:
            return
        if self._grad_buffer is None:
            return
        if self.wall_clock_breakdown:
            self.timers(STEP_TIMER).start()
        lr = jnp.float32(self._current_lr())
        mom = jnp.float32(self._current_mom())
        if self.host_offload:
            grads_host = jax.device_put(self._grad_buffer, self._cpu_device)
            (
                params_c,
                new_master,
                new_inner,
                self.loss_scale_state,
                overflow,
                grad_norm,
                coeffs,
            ) = self._jit_apply_update_offload(
                self.optimizer_state["master"],
                self.optimizer_state["inner"],
                grads_host,
                jax.device_put(self.loss_scale_state, self._cpu_device),
                jax.device_put(lr, self._cpu_device),
                jax.device_put(mom, self._cpu_device),
            )
            self.optimizer_state = {"master": new_master, "inner": new_inner}
            # the offload path is inherently synchronous (transfers bound
            # it), so checking the flag costs nothing extra — and on a
            # skipped step the master is untouched, making the full-model
            # h2d push (~3 GB at 1.5B) pure waste
            if not bool(overflow):
                self.params = jax.device_put(params_c, self._param_shardings)
            # the scaler feeds the next accelerator-side fwd_bwd: move it
            # back off the host (replicated over the mesh) so the mesh jit
            # doesn't see a committed cpu input
            self.loss_scale_state = self._place_scaler(
                self.loss_scale_state
            )
        else:
            (
                self.params,
                self.optimizer_state,
                self.loss_scale_state,
                overflow,
                grad_norm,
                coeffs,
            ) = self._jit_apply_update(
                self.params,
                self.optimizer_state,
                self._grad_buffer,
                self.loss_scale_state,
                lr,
                mom,
            )
        # donated; backward() lazily re-seeds from the next micro-step
        self._grad_buffer = None
        window_loss = None
        if self._window_losses:
            # mean UNSCALED loss over the whole accumulation window
            # (reference logs the window loss, deepspeed_light.py:876-885)
            window_loss = jnp.mean(
                jnp.stack([l.astype(jnp.float32) for l in self._window_losses])
            )
        self._window_losses = []
        if self._window_aux:
            # [accum]-stack the window's aux — the same layout train_batch()
            # produces, so multi-output logging code sees one contract on
            # both train paths
            self.last_aux = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *self._window_aux
            )
        self._window_aux = []
        if self.wall_clock_breakdown:
            # fence on the update program's real output (see forward())
            jax.block_until_ready(
                jax.tree_util.tree_leaves(self.optimizer_state)[0]
            )
            self.timers(STEP_TIMER).stop()
        with phase("train.finish_step"):
            self._finish_step(overflow, grad_norm, coeffs, window_loss)

    def _finish_step(self, overflow, grad_norm, coeffs, window_loss):
        """Post-update host bookkeeping shared by step() and train_batch():
        overflow/skipped-step accounting, LR schedule, throughput window,
        periodic step line, monitor scalars."""
        self._last_grad_norm = grad_norm
        self.lamb_coeffs = coeffs
        if self.config.fp16_enabled:
            # fp16 semantics need the overflow flag NOW (it gates the LR
            # schedule and skipped-step accounting) — one host sync.
            self.last_overflow = bool(overflow)
        else:
            # bf16/fp32: the jitted update still skips on a non-finite grad
            # norm (params stay safe on device) and the loop stays fully
            # async — counters advance OPTIMISTICALLY now and the device
            # flag is reconciled ONE WINDOW LATE (below), so skipped_steps /
            # global_steps / the LR schedule end up truthful without a
            # per-step host sync (reference accounting contract:
            # deepspeed_light.py:858-869). Monitor scalars ride the same
            # queue as DEVICE values and are written at settle time with
            # the settled step index — no host sync here, and a reconciled
            # skip can never make two windows share a step index.
            self.last_overflow = False
            entry = None
            if self.monitor.enabled:
                entry = {
                    "lr": float(self.get_lr()[0]),  # host-side, no sync
                    "scale_dev": self.loss_scale_state.loss_scale,
                    "loss_dev": window_loss,
                    "gn_dev": grad_norm,
                }
            self._deferred_overflows.append((overflow, entry))
        if self.last_overflow:
            self.skipped_steps += 1
            log_dist(
                f"OVERFLOW: skipping step; loss scale -> "
                f"{float(self.loss_scale_state.loss_scale)}",
                ranks=[0],
            )
        else:
            self.global_steps += 1
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
        # close the samples/sec window opened by the dataloader's __next__
        self.tput_timer.stop(report_speed=True)
        if (
            self.global_steps > 0
            and self.global_steps % self.steps_per_print() == 0
        ):
            log_dist(
                f"step={self.global_steps}, skipped={self.skipped_steps}, "
                f"lr={self.get_lr()}, loss_scale="
                f"{float(self.loss_scale_state.loss_scale)}",
                ranks=[0],
            )
            if self.wall_clock_breakdown:
                # per-phase means over the print interval: fwd/bwd/step on
                # the unfused path, whole-window on the fused path (the
                # reference's breakdown, deepspeed_light.py:886-931; the
                # fused program's phase split lives in profiler traces via
                # named_scope)
                interval = self.steps_per_print()
                if self.timers.has_timer(TRAIN_BATCH_TIMER):
                    # divide by windows actually RUN since the last print
                    # (incl. overflow-skipped ones), not steps counted
                    n_windows = max(1, getattr(self, "_tb_windows", 0))
                    win_s = self.timers(TRAIN_BATCH_TIMER).elapsed(
                        reset=True
                    ) / n_windows
                    self._tb_windows = 0
                    if win_s > 0:
                        sps = self.train_batch_size() / win_s
                        log_dist(
                            f"train_batch window: {win_s * 1e3:.1f} ms avg "
                            f"| {sps:.1f} samples/s",
                            ranks=[0],
                        )
                # the window timer reports via the dedicated line above
                # (per-window divisor); fwd/bwd/step normalize per printed
                # step like the reference
                names = [
                    n
                    for n in (FORWARD_TIMER, BACKWARD_TIMER, STEP_TIMER)
                    if self.timers.has_timer(n)
                ]
                if names:
                    self.timers.log(names, normalizer=interval)
        if (
            self.config.fp16_enabled
            and self.monitor.enabled
            and not self.last_overflow
        ):
            # fp16 is synchronous (the overflow sync above already waited),
            # so the write lands immediately at the exact step index; the
            # async bf16/fp32 path writes from the settle queue instead
            # (_reconcile_deferred)
            self.monitor.write_scalars(
                self._monitor_scalars(
                    float(self.get_lr()[0]),
                    float(self.loss_scale_state.loss_scale),
                    window_loss,
                    float(grad_norm) if grad_norm is not None else None,
                ),
                self.global_steps,
            )
        if self.telemetry.enabled:
            # raw device values go in; the manager materializes them (one
            # host sync) only at export boundaries (telemetry.interval)
            self.telemetry.on_window_end(
                loss=window_loss,
                grad_norm=grad_norm,
                loss_scale=self.loss_scale_state.loss_scale,
                lr=self.get_lr()[0],
                global_steps=self.global_steps,
                skipped_steps=self.skipped_steps,
                micro_steps=self.micro_steps,
                counters=_aux_counters(self.last_aux),
            )
        # settle overflow flags from windows BEFORE this one: their compute
        # has finished (or is about to — the current window is already
        # dispatched, so the device stays busy while we wait)
        if len(self._deferred_overflows) > 1:
            self._reconcile_deferred(keep_last=True)
        # fault site: artificial step stall (watchdog food) — before the
        # supervisor check so a long-enough stall can escalate same-window
        if self.faults.enabled:
            self.faults.maybe_stall("step.stall")
        # self-healing supervision at the step boundary: the detectors
        # read this window's loss/grad-norm (one host sync, supervised
        # runs only) and may roll the engine back to the last committed
        # checkpoint. The flag tells the supervised train_batch loop that
        # the window it just ran belongs to a discarded timeline.
        if self.supervisor is not None:
            self._window_rolled_back = self.supervisor.on_window(
                self, window_loss
            )
            if self._window_rolled_back:
                return  # rolled back: the drain check below would act on
                # a boundary that no longer exists
        # preemption drain: a SIGTERM/SIGINT received mid-window armed a
        # flag; this step boundary is the first safe commit point
        self._maybe_preemption_save()

    def _maybe_preemption_save(self):
        """Honor an armed preemption drain: commit one final checkpoint at
        this step boundary, then exit via the original signal disposition
        (resilience.preemption semantics, docs/resilience.md)."""
        res = getattr(self, "resilience", None)
        if res is None or res.preemption is None:
            return
        armed = res.preemption_armed
        if jax.process_count() > 1:
            # cross-host consensus on the drain decision: signal delivery
            # is per-host and can straddle a step boundary, and the save
            # path barriers — hosts entering save_checkpoint at different
            # boundaries (or only some hosts entering) would deadlock the
            # pod. A tiny 1-flag allgather per boundary (drain is opt-in,
            # so this costs nothing unless preemption is enabled) makes
            # every host see the OR of all local flags at the SAME step.
            from jax.experimental import multihost_utils

            flags = multihost_utils.process_allgather(
                np.asarray([armed], dtype=np.bool_)
            )
            armed = bool(np.any(flags))
            if armed and not res.preemption_armed:
                res.preemption.arm()  # mirror the remote host's signal
        if not armed:
            return
        save_dir = res.preemption_save_dir or self._last_checkpoint_dir
        if not save_dir:
            warn_once(
                "preemption-no-save-dir",
                "preemption drain armed but no save target is known (no "
                "resilience.preemption.save_dir configured and the engine "
                "has not saved or loaded a checkpoint yet) — no final "
                "checkpoint will be written",
            )
            return
        if res.preemption_exit_after_save:
            # the process exits after this save: stop the staging workers
            # (the engine's window stager AND loader-owned ones) so none
            # is mid-device_put at exit (bounded waits only — close()
            # cannot stall the drain). Staged-but-unconsumed windows are
            # dropped; the restart replays the data order from this
            # checkpoint. When the drain KEEPS training (exit_after_save
            # false), the pipeline stays attached — closing it would
            # silently skip the windows already pulled from the live
            # iterator.
            self.close_data_pipeline()
        tag = f"{res.preemption_tag_prefix}_global_step{self.global_steps}"
        log_dist(
            f"preemption drain: saving final checkpoint {tag} to "
            f"{save_dir}",
            ranks=[-1],
        )
        self.save_checkpoint(save_dir, tag=tag)
        # counts the save, then exits by re-raising the captured signal
        # (or just disarms when exit_after_save is off)
        res.finish_preemption_save()

    @staticmethod
    def _monitor_scalars(lr, loss_scale, loss, gn):
        """One Train/* scalar-dict builder for BOTH monitor paths (fp16
        immediate, bf16/fp32 settle queue) — incl. the -1.0 sentinel guard
        on the grad norm."""
        scalars = {"Train/lr": lr, "Train/loss_scale": loss_scale}
        if loss is not None:
            scalars["Train/loss"] = float(loss)
        if gn is not None and gn >= 0.0:
            scalars["Train/grad_norm"] = gn
        return scalars

    def flush_monitor(self):
        """Settle ALL pending windows (one host sync) and flush queued
        monitor scalars. The async bf16/fp32 path holds the newest
        window's entry until the next settle point — checkpoint saves
        flush automatically; call this before reading the event sink at
        the end of training."""
        self._reconcile_deferred(keep_last=False)
        if self.monitor.enabled and getattr(self.monitor, "writer", None):
            self.monitor.writer.flush()
        self.telemetry.flush()

    def _reconcile_deferred(self, keep_last=True):
        """Settle queued bf16/fp32 device-side overflow flags.

        A window whose global grad norm came out non-finite was skipped ON
        DEVICE by the jitted update; the host advanced its counters
        optimistically.  Fetching the flag here (a window late, or forced at
        a checkpoint/sync point with ``keep_last=False``) corrects
        ``skipped_steps``/``global_steps`` and rolls the LR scheduler back
        one tick, so a skipped window never advances the schedule — the
        reference's semantics (deepspeed_light.py:858-869) without its
        per-step host sync.

        Monitor scalars settle HERE too (queued as device values at
        ``_finish_step``): each non-skipped window writes at its settled
        step index (``_settled_steps``), so step indices in
        TensorBoard-style sinks are unique and truthful — the round-3/4
        "two windows share a step after a reconciled skip" artifact is
        gone, at the cost of scalars landing one window late. Checkpoint
        saves force ``keep_last=False`` first, so persisted counters are
        always truthful and pending scalars are flushed."""
        keep = 1 if keep_last else 0
        if len(self._deferred_overflows) <= keep:
            return
        with phase("train.settle"):  # bool(flag) WAITS for that window
            self._settle_deferred(keep)

    def _settle_deferred(self, keep):
        while len(self._deferred_overflows) > keep:
            flag, entry = self._deferred_overflows.pop(0)
            if not bool(flag):
                self._settled_steps += 1
                if entry is not None:
                    gn = (
                        float(entry["gn_dev"])
                        if entry["gn_dev"] is not None
                        else None
                    )
                    self.monitor.write_scalars(
                        self._monitor_scalars(
                            entry["lr"], float(entry["scale_dev"]),
                            entry["loss_dev"], gn,
                        ),
                        self._settled_steps,
                    )
                continue
            # NOTE: last_overflow is deliberately NOT set here — it reports
            # the CURRENT window (fp16 semantics); a past window's skip
            # surfaces through skipped_steps/global_steps and the log line.
            self.skipped_steps += 1
            self.global_steps -= 1
            rolled = False
            if self.lr_scheduler is not None:
                if hasattr(self.lr_scheduler, "last_batch_iteration"):
                    self.lr_scheduler.last_batch_iteration -= 1
                    rolled = True
                elif not self._warned_unrollable_scheduler:
                    self._warned_unrollable_scheduler = True
                    log_dist(
                        "WARNING: a device-side skipped step could not roll "
                        "back the client LR scheduler (no "
                        "last_batch_iteration attribute) — the schedule ran "
                        "one tick ahead",
                        ranks=[0],
                    )
            log_dist(
                "SKIP (reconciled): non-finite grad norm skipped the update "
                f"on device; counters corrected (skipped={self.skipped_steps},"
                f" step={self.global_steps}"
                + (", lr schedule rolled back" if rolled else "") + ")",
                ranks=[0],
            )

    def train_batch(self, batch_iter_or_batches):
        """Run one accumulation window (see :meth:`_train_batch_once` for
        the dispatch mechanics). With the run supervisor enabled
        (``resilience.supervisor``), this is the self-healing entry
        point: an anomalous window (sustained non-finite loss, loss
        spike, stall escalation) or a recoverable window failure (dead
        staging worker, device_put error, injected chaos) triggers a
        bounded in-process rollback to the last committed checkpoint and
        the window re-runs from the rewound data source — callers see a
        finite loss or, when the retry budget is exhausted, a typed
        :class:`~deepspeed_tpu.resilience.SupervisorEscalation`.
        Supervision costs one host sync per window; without the config
        block this is a zero-overhead passthrough."""
        sup = self.supervisor
        if sup is None:
            return self._train_batch_once(batch_iter_or_batches)
        sup.note_source(batch_iter_or_batches)
        while True:
            self._window_rolled_back = False
            try:
                loss = self._train_batch_once(batch_iter_or_batches)
            except (StopIteration, SupervisorEscalation):
                raise
            except Exception as exc:
                if not sup.on_failure(self, exc):
                    raise
                continue  # rolled back; re-run from the rewound source
            if self._window_rolled_back:
                # the returned loss belongs to the discarded timeline
                continue
            return loss

    def _train_batch_once(self, batch_iter_or_batches):
        """Native fast path: run a full accumulation window (forward,
        accumulate, update) as ONE compiled program and return the mean
        unscaled loss. Semantically equivalent to
        gradient_accumulation_steps x (forward()+backward()) + step().

        With the ``data_pipeline`` config block enabled and a PERSISTENT
        iterator passed (the same iterator object across calls — a
        generator, ``itertools.cycle``, a dataloader iterator), the
        window is served by the background stager (runtime/staging.py):
        window N+1 is pulled, stacked, and device_put while window N
        computes, so its host-side assembly leaves the critical path.
        Numerics (params, loss, RNG stream) are identical either way.
        """
        accum = self.gradient_accumulation_steps()
        tel = self.telemetry
        with phase(
            "train.window", tel.train_trace_ctx(), tel.tracer,
            window=self.micro_steps // accum + 1,
            global_steps=self.global_steps,
        ) as window:
            loss = self._train_window(batch_iter_or_batches, accum)
        if not self.host_offload:  # offload loops forward(): timed there
            tel.observe_window_time(window.seconds * 1e3, window.span)
        return loss

    def _train_window(self, batch_iter_or_batches, accum):
        if self._staging_enabled and not self.host_offload:
            stager = self._ensure_stager(batch_iter_or_batches)
            if stager is not None:
                return self._train_batch_staged(stager, accum)
        it = iter(batch_iter_or_batches)
        batches = []
        for _ in range(accum):
            try:
                batch = next(it)
            except StopIteration:
                if not batches:
                    # clean end-of-data AT a window boundary: the natural
                    # end-of-stream signal, propagated for callers looping
                    # "until the data runs out"
                    raise
                # mid-window dry is a data-sizing bug: a bare
                # StopIteration here would silently terminate any
                # enclosing generator instead of surfacing the raggedness
                from .staging import ragged_window_error

                raise ragged_window_error(len(batches), accum) from None
            if not isinstance(batch, (tuple, list)):
                batch = (batch,)
            batches.append(tuple(batch))
        if self.host_offload:
            # the fused window would jit the update INTO the mesh program;
            # offload runs it host-side instead — loop the micro-steps
            losses = []
            for batch in batches:
                loss = self.forward(*batch)
                self.backward(loss)
                losses.append(loss.astype(jnp.float32))
            self.step()
            return jnp.mean(jnp.stack(losses))

        if self.telemetry.enabled:
            self.telemetry.on_window_start(timed_by_caller=True)
            for batch in batches:
                self.telemetry.count_batch(*self._batch_tokens(batch))
        if self.wall_clock_breakdown:
            # whole-window wall clock (start() fences outstanding device
            # work); the async fast path is untouched when breakdown is off
            self.timers(TRAIN_BATCH_TIMER).start()
        with phase("train.stack_and_place"):
            stacked = self._stack_window(batches)
            stacked = self._shard_window_batch(stacked)
        self._rng, keys = _split_window_keys(self._rng, accum)
        return self._run_window(stacked, keys, accum)

    @staticmethod
    def _stack_window(batches):
        """Host-stack a window's micro-batches into the [accum, ...]
        layout. Stacking host leaves on host means the window goes to
        devices ONCE, directly in its target sharding; a device-side
        jnp.stack would stage the whole unsharded window through the
        default device."""
        def stack_leaf(*xs):
            if any(isinstance(x, jax.Array) for x in xs):
                return jnp.stack([jnp.asarray(x) for x in xs])
            return np.stack([np.asarray(x) for x in xs])

        return jax.tree_util.tree_map(stack_leaf, *batches)

    def _ensure_stager(self, source):
        """Return the window stager serving ``source``, creating it on
        first sight. Returns None (= run unstaged) when staging cannot
        help: non-iterator sources, batches a loader already staged, or
        a caller passing a FRESH iterator object every window (detected
        by churn) — those give the stager nothing to pull ahead from, so
        staging would only add thread churn."""
        if self._stager is not None:
            if source is self._stager_source:
                return self._stager
            # new source: the old stream's staged windows belong to a
            # dead timeline. Count it toward the churn guard, and make
            # any discarded pulled-ahead data visible — it was consumed
            # from the PREVIOUS iterator and will not be trained on.
            dropped = self._stager.unconsumed_micro_batches()
            if dropped:
                warn_once(
                    "stager-source-changed-dropped-data",
                    "window stager torn down on a source change with %d "
                    "staged-but-unconsumed micro-batches (already pulled "
                    "from the previous iterator) — alternating live "
                    "iterators across train_batch() calls loses their "
                    "prefetched items; exhaust one stream before "
                    "switching, or disable data_pipeline staging",
                    dropped,
                )
            churned = self._stager.windows_served <= 1
            self._close_stager()
            self._stager_churn = self._stager_churn + 1 if churned else 0
        if self._stager_churn >= 2:
            # two consecutive single-window stagers: the caller passes a
            # fresh iterator per call — stop paying a thread per window.
            # NOT a permanent latch: seeing the SAME source twice means
            # the caller switched to a persistent iterator (e.g. fresh-
            # iterator compile warmups followed by the real loop), so
            # staging re-engages.
            if source is not self._last_unstaged_source:
                self._last_unstaged_source = source
                warn_once(
                    "stager-fresh-iterator-churn",
                    "data_pipeline staging paused for this engine: "
                    "train_batch() keeps receiving a NEW iterator object "
                    "per window, so nothing can be staged ahead — pass "
                    "one persistent iterator (a generator / "
                    "itertools.cycle / a dataloader iterator) to overlap "
                    "input staging",
                )
                return None
            self._stager_churn = 0
            self._last_unstaged_source = None
        if getattr(source, "already_staged", False):
            # the loader's staging worker already assembled AND placed
            # these batches (accum == 1 only); a second stager here would
            # double-buffer duplicate windows on another thread. Dispatch
            # still restacks the placed batch to [1, ...] on device — a
            # cheap device-to-device op at accum == 1.
            return None
        try:
            if iter(source) is not source:
                return None
        except TypeError:
            return None
        from .staging import WindowStager

        # The stager owns the RNG chain while attached: keys are
        # pre-split at staging time and the post-split state rides each
        # window back into self._rng at consume time. telemetry/meta are
        # withheld entirely when telemetry is off — the unstaged path
        # counts tokens only under the same condition, and the worker
        # skips the bookkeeping tree walks for a no-op facade.
        # The worker must not pin this engine (params + optimizer state)
        # beyond its life: place_fn holds a WEAK engine ref, and the
        # finalizer below closes the stager when the engine is collected
        # — an abandoned engine (sweep, notebook rebuild) cannot leak its
        # staging thread or its memory.
        tel_on = self.telemetry.enabled
        eref = weakref.ref(self)

        def place_fn(stacked):
            engine = eref()
            if engine is None:  # pragma: no cover - finalizer races this
                raise RuntimeError("engine dropped while staging")
            return engine._shard_window_batch(stacked)

        # fault site: staging worker death. The hook closes over the
        # injector only (never the engine — the worker must not pin it)
        faults = self.faults
        fault_fn = (
            (lambda: faults.maybe_raise("staging.worker"))
            if faults.enabled else None
        )

        self._stager = WindowStager(
            source=source,
            accum=self.gradient_accumulation_steps(),
            stack_fn=self._stack_window,
            place_fn=place_fn,
            rng=self._rng,
            split_fn=_split_window_keys,
            meta_fn=self._batch_tokens if tel_on else None,
            buffers=self._staging_buffers,
            stage_to_device=self._stage_to_device,
            telemetry=self.telemetry if tel_on else None,
            fault_fn=fault_fn,
        )
        self._stager_source = source
        self._stager_finalizer = weakref.finalize(self, self._stager.close)
        return self._stager

    def close_data_pipeline(self):
        """Public teardown for the staged input pipeline: stop the
        background staging workers — the engine's window stager AND any
        staging worker owned by a deepspeed_io-built loader — and drop
        staged-but-unconsumed windows. Runs automatically on source
        exhaustion, source change, engine garbage collection, and
        preemption exit — call it explicitly when abandoning an engine
        mid-stream to release the workers immediately."""
        self._close_stager()
        for ref in self._data_loaders:
            loader = ref()
            if loader is not None:
                loader.close_staging()

    def _close_stager(self):
        if self._stager is not None:
            if self._stager_finalizer is not None:
                self._stager_finalizer.detach()
                self._stager_finalizer = None
            self._stager.close()
            self._stager = None
            self._stager_source = None

    def _train_batch_staged(self, stager, accum):
        """Consume one pre-staged window: inputs are already host-stacked
        (and, with stage_to_device, already on device in their target
        shardings) — dispatch is all that's left on the critical path."""
        try:
            window = stager.get_window()
        except Exception:
            # clean exhaustion (StopIteration) and staging failures alike
            # end this stream
            self._close_stager()
            raise
        if self.telemetry.enabled:
            self.telemetry.on_window_start(timed_by_caller=True)
            self.telemetry.count_batch(window.tokens, window.samples)
        if self.wall_clock_breakdown:
            self.timers(TRAIN_BATCH_TIMER).start()
        # adopt the pre-split chain (see _split_window_keys)
        self._rng = window.rng_after
        return self._run_window(window.arrays, window.keys, accum)

    def _run_window(self, stacked, keys, accum):
        """Dispatch one stacked window through the fused program and do
        the post-update bookkeeping — the shared tail of the staged and
        unstaged train_batch paths."""
        if self.faults.enabled and self.faults.fire("grads.nan") is not None:
            stacked = _poison_first_float_leaf(stacked)
        with phase("train.place_scalars"):  # two converts, two device_puts
            lr = jnp.float32(self._current_lr())
            mom = jnp.float32(self._current_mom())
        with phase("train.dispatch"):
            (
                self.params,
                self.optimizer_state,
                self.loss_scale_state,
                overflow,
                grad_norm,
                coeffs,
                mean_loss,
                aux,
            ) = self._jit_train_window(
                self.params,
                self.optimizer_state,
                self.loss_scale_state,
                stacked,
                keys,
                lr,
                mom,
            )
        self.micro_steps += accum
        if self.wall_clock_breakdown:
            jax.block_until_ready(mean_loss)
            self.timers(TRAIN_BATCH_TIMER).stop()
            # window count since the last breakdown print: overflow-skipped
            # windows accumulate TIME but not global_steps, so dividing the
            # timer by steps_per_print would overstate the per-window
            # average exactly when loss-scale backoff makes it interesting
            self._tb_windows = getattr(self, "_tb_windows", 0) + 1
        # aux outputs from a multi-output model, [accum, ...]-stacked
        self.last_aux = aux
        with phase("train.finish_step"):
            self._finish_step(overflow, grad_norm, coeffs, mean_loss)
        # Returned as a device scalar: float(loss) would block the host on
        # this window and stop it dispatching the next one ahead of the
        # device. Callers that want a python float call float() on it.
        return mean_loss

    # ------------------------------------------------------------------
    def _place_leaf(self, x, batch_axis):
        """Place one batch leaf: the batch dim shards over data, the
        following (token) dim over sequence when sizes divide; anything that
        doesn't fit the mesh is replicated.

        Single-process: plain device_put. Multi-process (a pod): ``x`` is
        this HOST'S slice of the batch (the reference's DistributedSampler
        contract — each rank loads its own rows, deepspeed_dataloader.py:
        10-78) and the global array is assembled from the per-process
        slices without any cross-host transfer."""
        from jax.sharding import NamedSharding, PartitionSpec

        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # already a global (multi-host) array: the caller chose its
            # layout — the escape hatch for host-replicated tables etc.
            return x
        if not isinstance(x, (jax.Array, np.ndarray)):
            x = np.asarray(x)  # python scalars / lists
        pcount = jax.process_count()
        if pcount > 1:
            x = np.asarray(x)
            if x.ndim <= batch_axis:
                # batch-dim-less leaf (scalar config value etc.): hosts are
                # expected to pass the same value; replicate it
                return jax.make_array_from_process_local_data(
                    mesh_lib.replicated(self._mesh), x
                )
            global_rows = x.shape[batch_axis] * pcount
            if global_rows % self.dp_world_size != 0:
                # a host-distinct slice cannot be replicated (ranks would
                # silently hold different data for the "same" array)
                raise ValueError(
                    f"per-host batch of {x.shape[batch_axis]} rows x "
                    f"{pcount} processes = {global_rows} global rows does "
                    f"not divide dp_world_size={self.dp_world_size}; size "
                    "the per-host batch so the global batch shards evenly"
                )
            spec = [None] * x.ndim
            spec[batch_axis] = mesh_lib.DATA_AXIS
            sp = dict(self._mesh.shape).get(mesh_lib.SEQ_AXIS, 1)
            if (
                sp > 1
                and x.ndim > batch_axis + 1
                and x.shape[batch_axis + 1] % sp == 0
            ):
                # mirror the single-process seq sharding when the sequence
                # shards are host-local (the local slice then matches the
                # process's shard extents); spanning hosts falls back to a
                # data-only spec and XLA reshards
                seq_spec = list(spec)
                seq_spec[batch_axis + 1] = mesh_lib.SEQ_AXIS
                try:
                    return jax.make_array_from_process_local_data(
                        NamedSharding(self._mesh, PartitionSpec(*seq_spec)), x
                    )
                except ValueError:
                    pass
            return jax.make_array_from_process_local_data(
                NamedSharding(self._mesh, PartitionSpec(*spec)), x
            )

        sp = dict(self._mesh.shape).get(mesh_lib.SEQ_AXIS, 1)
        spec = [None] * x.ndim
        if x.ndim > batch_axis and x.shape[batch_axis] % self.dp_world_size == 0:
            spec[batch_axis] = mesh_lib.DATA_AXIS
        if sp > 1 and x.ndim > batch_axis + 1 and x.shape[batch_axis + 1] % sp == 0:
            spec[batch_axis + 1] = mesh_lib.SEQ_AXIS
        try:
            return jax.device_put(
                x, NamedSharding(self._mesh, PartitionSpec(*spec))
            )
        except ValueError:
            return jax.device_put(x, mesh_lib.replicated(self._mesh))

    def _shard_batch(self, inputs):
        # raw numpy/python leaves go straight into _place_leaf (device_put /
        # make_array handle host arrays directly — a jnp.asarray here would
        # add a device round-trip on the input hot path)
        return tuple(
            jax.tree_util.tree_map(lambda x: self._place_leaf(x, 0), x)
            for x in inputs
        )

    def _shard_window_batch(self, stacked):
        """Place a stacked accumulation window: leaves are [accum, micro, ...];
        the micro-batch dim (axis 1) shards over data."""
        if self.faults.enabled:
            # fault site: the window's device placement (fires on
            # whichever thread places — the staging worker under
            # stage_to_device, the dispatch thread otherwise)
            self.faults.maybe_raise("staging.device_put")
        return jax.tree_util.tree_map(
            lambda x: self._place_leaf(x, 1), stacked
        )

    def _zero_grad_buffer(self):
        if self._grad_buffer is not None:
            self._grad_buffer = jax.tree_util.tree_map(
                jnp.zeros_like, self._grad_buffer
            )

    def _optimizer_state_dict(self):
        return jax.tree_util.tree_map(np.asarray, self.optimizer_state)

    def deepspeed_io(self, dataset, batch_size=None, route=C.ROUTE_TRAIN):
        """Build the data loader (reference deepspeed_light.py:624-665)."""
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * self.dp_world_size
        is_train = route == C.ROUTE_TRAIN
        # data_pipeline staging (runtime/staging.py): the loader runs the
        # window stager itself with accum=1 ONLY when one micro-batch IS
        # the window AND the config stages to device — then its batches
        # arrive pre-placed and train_batch skips its own stager (the
        # already_staged marker). In EVERY other staging-enabled train
        # case the engine's window stager consumes the loader, so the
        # loader must yield HOST batches: pre-placed ones would make the
        # window restack through the default device and transfer twice.
        # (The unfused loop places per micro-batch in forward(), same as
        # a mesh-less loader.)
        loader_stages = (
            is_train and self._staging_enabled and self._stage_to_device
            and self.gradient_accumulation_steps() == 1
        )
        loader = DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size,
            mesh=self._mesh,
            collate_fn=self.collate_fn,
            shuffle=is_train,  # the reference's DistributedSampler shuffles
            tput_timer=self.tput_timer if is_train else None,
            telemetry=self.telemetry if is_train else None,
            stage_to_device=loader_stages,
            staging_buffers=self._staging_buffers,
            device_place=(
                loader_stages or not (is_train and self._staging_enabled)
            ),
        )
        # weak: tracking for close_data_pipeline must not pin the
        # loader (and its dataset) to the engine's lifetime
        self._data_loaders.append(weakref.ref(loader))
        return loader

    # ------------------------------------------------------------------
    # profiling (the TPU analog of the reference's wall-clock breakdown +
    # CUDA-event timers, SURVEY §5): captures an XLA trace viewable in
    # TensorBoard/Perfetto, covering device compute, ICI collectives and
    # host dispatch.
    # ------------------------------------------------------------------
    def start_profile(self, log_dir="profile"):
        """Begin a ``jax.profiler`` trace; pair with :meth:`stop_profile`.
        Typical use: profile 3-5 steady-state steps, not the compile.

        The PRIMARY profiling path is the config-armed window — a
        ``"telemetry": {"profile": {"start_step": N, "num_steps": M}}``
        block traces automatically and wraps each window in
        ``StepTraceAnnotation`` (docs/observability.md). These manual
        methods remain for interactive sessions."""
        if getattr(self, "_profiling", False):
            return
        jax.profiler.start_trace(log_dir)
        self._profiling = True
        log_dist(f"profiler trace started -> {log_dir}", ranks=[0])

    def stop_profile(self):
        if not getattr(self, "_profiling", False):
            return
        # flush in-flight device work so the trace window is complete
        jax.effects_barrier()
        if self._pending_loss is not None:
            jax.block_until_ready(self._pending_loss)
        jax.profiler.stop_trace()
        self._profiling = False
        log_dist("profiler trace stopped", ranks=[0])

    # checkpointing implemented in runtime/checkpointing.py, bound here
    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        from .checkpointing import save_checkpoint as _save

        # persisted counters must be truthful: settle ALL in-flight
        # device-side skip flags, including the newest window's
        self._reconcile_deferred(keep_last=False)
        if getattr(self, "adapters_enabled", False):
            # an adapter-only checkpoint self-describes its geometry:
            # serving-side load_adapter validates rank/targets against
            # its own pool before writing any rows
            client_state = dict(client_state or {})
            client_state.setdefault("adapters", dict(self._adapters_meta))
        # a large-model save can outlast the watchdog timeout; suspend
        # stall detection for its whole duration, not just a beat around it
        with self.telemetry.liveness_exempt():
            # checkpoint-commit span (telemetry/tracing.py): atomic
            # commits are the training timeline's landmarks — a trace
            # shows what the run was doing around each one
            with phase(
                "train.checkpoint_commit", self.telemetry.train_trace_ctx(),
                self.telemetry.tracer, save_dir=str(save_dir), tag=tag,
            ):
                result = _save(self, save_dir, tag=tag, client_state=client_state or {})
        # remember the save target: the preemption drain's default sink
        self._last_checkpoint_dir = save_dir
        if self.supervisor is not None:
            # this directory's newest valid tag is now the rollback
            # resume point (resilience/supervisor.py)
            self.supervisor.on_checkpoint(save_dir)
        return result

    def load_checkpoint(
        self, load_dir, tag=None, load_module_strict=True,
        load_optimizer_states=True, load_lr_scheduler_states=True,
    ):
        from .checkpointing import load_checkpoint as _load

        # flags queued before the restore belong to the DISCARDED timeline;
        # reconciling them against the restored counters would corrupt the
        # resumed run's step count and LR schedule. Stash rather than drop:
        # a FAILED load leaves the old timeline running, which still owes
        # its reconciliation.
        stale_flags = self._deferred_overflows
        self._deferred_overflows = []
        try:
            # like save_checkpoint: an in-training restore of a large model
            # can outlast the watchdog timeout
            with self.telemetry.liveness_exempt():
                result = _load(
                    self,
                    load_dir,
                    tag=tag,
                    load_optimizer_states=load_optimizer_states,
                    load_lr_scheduler_states=load_lr_scheduler_states,
                )
        except Exception:
            # a load that raised mid-restore also leaves the old timeline
            # running — put its flags back before re-raising
            self._deferred_overflows = stale_flags
            raise
        if result[0] is None:
            self._deferred_overflows = stale_flags
        else:
            # a successful resume makes this directory the drain's
            # default save target too
            self._last_checkpoint_dir = load_dir
            if self.supervisor is not None:
                self.supervisor.on_checkpoint(load_dir)
        return result
