"""InferenceEngine: the serving façade behind ``deepspeed_tpu.init_inference``.

Glues the three layers together:

  params     — taken from the caller (or loaded through the resilience
               verified-load path when ``inference.checkpoint.load_dir``
               is set: manifest check, host-side parse, newest-valid
               fallback — runtime/checkpointing.load_module_state), cast
               to the serving dtype and PINNED to device shardings
               (tensor-parallel ``param_specs`` or replicated) before the
               first compile, so decode steps never re-place weights.
  decode     — jitted prefill / fixed-shape decode+sample programs over
               inference/decode.py and inference/sampling.py, with the KV
               cache donated through each step (no cache copies) and the
               PRNG key threaded explicitly.
  scheduling — a ContinuousBatchingScheduler (scheduler.py) owning the
               bounded admission queue and the slot table; ``generate``
               is the synchronous convenience over it, ``submit`` +
               ``serve_forever`` the server mode.

Telemetry (infer/* streams, docs/observability.md) registers into the
config-built Telemetry registry when the ``telemetry`` block is enabled
— TTFT and queue-depth export through the same jsonl/Prometheus sinks as
the training engine's streams — and onto a private registry otherwise
(counting is cheap; tests and the bench smoke read it either way).
"""


import numpy as np

import jax
import jax.numpy as jnp

from ..adapters.pool import AdapterPool, AdapterPoolFull, AdapterUnavailable
from ..config import constants as C
from ..config.config import DeepSpeedConfig, DeepSpeedConfigError
from ..models.gpt2 import (
    adapter_pool_partition_specs,
    kv_cache_partition_specs,
    kv_pool_partition_specs,
)
from ..parallel import mesh as mesh_lib
from ..telemetry.manager import build_telemetry, register_inference_metrics
from ..telemetry.registry import MetricsRegistry
from ..telemetry.tracing import phase
from ..utils.logging import log_dist, logger
from .decode import (
    gpt2_decode_step,
    gpt2_decode_step_paged,
    gpt2_prefill,
    gpt2_prefill_suffix,
    init_adapter_pool,
    init_kv_cache,
    init_kv_pool,
    write_prefill_to_cache,
    write_prefill_to_pool,
)
from .paging import NULL_BLOCK, BlockPool, PoolExhausted, hash_full_blocks
from .sampling import sample_tokens
from .scheduler import ContinuousBatchingScheduler, RequestRejected  # noqa: F401  (re-exported)

_BATCH_KEYS = (
    C.TRAIN_BATCH_SIZE,
    C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
    C.GRADIENT_ACCUMULATION_STEPS,
)


class InferenceEngine:
    def __init__(
        self,
        model=None,
        config=None,
        model_parameters=None,
        mesh=None,
        param_specs=None,
        rng_seed=0,
        draft_model=None,
        draft_parameters=None,
    ):
        mcfg = getattr(model, "config", None)
        if mcfg is None or not all(
            hasattr(mcfg, a) for a in ("n_layer", "n_head", "n_embd",
                                       "n_positions", "layer_config")
        ):
            raise DeepSpeedConfigError(
                "init_inference serves the GPT-2 family: pass a "
                "GPT2LMHeadModel (a module whose .config carries "
                "n_layer/n_head/n_embd/n_positions)"
            )
        if getattr(mcfg, "moe_experts", 0) > 0:
            raise DeepSpeedConfigError(
                "KV-cache decode does not support MoE layers yet "
                "(moe_experts > 0)"
            )
        if getattr(mcfg, "pipeline_stages", 1) > 1:
            raise DeepSpeedConfigError(
                "KV-cache decode does not support the pipelined stack yet "
                "(pipeline_stages > 1)"
            )
        if model_parameters is None:
            raise ValueError(
                "model_parameters (the parameter pytree, e.g. freshly "
                "initialized or about to be overwritten by the checkpoint "
                "load) is required"
            )
        self.module = model
        self.model_config = mcfg

        # ---- config (training keys get inert defaults: the batch
        # triangle is meaningless for serving but the shared validator
        # requires one anchor) --------------------------------------
        if config is None:
            raw = {}
        elif isinstance(config, dict):
            raw = dict(config)
        else:  # JSON path, same contract as initialize()
            from ..config.config_utils import load_config_json

            raw = load_config_json(config)
        if not any(k in raw for k in _BATCH_KEYS):
            raw[C.TRAIN_BATCH_SIZE] = 1
        self._mesh = mesh
        if self._mesh is None:
            mesh_block = raw.get(C.MESH, {})
            self._mesh = mesh_lib.build_mesh(
                data_parallel_size=mesh_block.get(
                    C.MESH_DATA_PARALLEL_SIZE
                ),
                model_parallel_size=mesh_block.get(
                    C.MESH_MODEL_PARALLEL_SIZE, 1
                ),
            )
        self.config = DeepSpeedConfig(None, param_dict=raw, world_size=1)
        cfg = self.config
        # one persistent compile cache for train and serve, armed before
        # any engine compile (runtime/compile_cache.py)
        from ..runtime.compile_cache import configure_compile_cache

        configure_compile_cache(cfg)

        # ---- geometry -------------------------------------------------
        self.max_seq_len = cfg.inference_max_seq_len or mcfg.n_positions
        if self.max_seq_len > mcfg.n_positions:
            raise DeepSpeedConfigError(
                f"inference.max_seq_len={self.max_seq_len} exceeds the "
                f"model's n_positions={mcfg.n_positions}"
            )
        self.prefill_len = cfg.inference_prefill_len or self.max_seq_len
        if self.prefill_len > self.max_seq_len:
            # config-level validation only sees an explicit max_seq_len;
            # with the model-derived default the check lands here — fail
            # at init, not as a wpe broadcast error in the first prefill
            raise DeepSpeedConfigError(
                f"inference.prefill_len={self.prefill_len} exceeds the "
                f"resolved max_seq_len={self.max_seq_len} (model "
                f"n_positions={mcfg.n_positions})"
            )
        self.num_slots = cfg.inference_max_batch_slots
        self.compute_dtype = (
            jnp.bfloat16 if cfg.inference_dtype == "bf16" else jnp.float32
        )

        # ---- paged-cache geometry (docs/inference.md "Paged KV cache") -
        self.kv_block_size = int(cfg.inference_kv_block_size)
        self.paged = self.kv_block_size > 0
        if self.paged:
            if self.max_seq_len % self.kv_block_size != 0:
                # config-level validation only sees an explicit
                # max_seq_len; the model-derived default lands here
                raise DeepSpeedConfigError(
                    f"resolved max_seq_len={self.max_seq_len} is not a "
                    f"multiple of inference.kv_block_size="
                    f"{self.kv_block_size} (model n_positions="
                    f"{mcfg.n_positions}); the paged cache's logical "
                    f"extent must equal the contiguous cache's"
                )
            self.blocks_per_slot = self.max_seq_len // self.kv_block_size
            self.kv_pool_blocks = (
                int(cfg.inference_kv_pool_blocks)
                or self.num_slots * self.blocks_per_slot
            )
            enabled = cfg.inference_prefix_cache_enabled
            self.prefix_cache_enabled = True if enabled is None else enabled
            buckets = cfg.inference_prefix_cache_suffix_buckets
            if buckets is None:
                # power-of-two ladder from one page up to the prefill
                # window: each bucket is one compiled suffix-prefill
                # program, so the ladder bounds hit-path compile count
                buckets, b = [], self.kv_block_size
                while b < self.prefill_len:
                    buckets.append(b)
                    b *= 2
                buckets.append(self.prefill_len)
            self._suffix_buckets = sorted(
                {min(int(b), self.prefill_len) for b in buckets}
            )
        else:
            self.blocks_per_slot = 0
            self.kv_pool_blocks = 0
            self.prefix_cache_enabled = False
            self._suffix_buckets = []

        # ---- fused decode attention (docs/inference.md) ---------------
        # the Pallas flash-decode + SGMV path; the XLA gather path stays
        # the greedy-parity reference. A pallas_call inside a plain
        # GSPMD-jitted program is not partitioned (ops/attention.py has
        # the same constraint), so a multi-device mesh falls back to the
        # XLA path rather than silently all-gathering the page pool.
        self.fused_decode = bool(cfg.inference_fused_decode)
        if self.fused_decode and not self.paged:
            # config validation catches the explicit case; engine-derived
            # geometry re-checks here
            raise DeepSpeedConfigError(
                "inference.fused_decode requires the paged cache "
                "(kv_block_size > 0): the kernel streams KV pages "
                "through the block table"
            )
        if (
            self.fused_decode
            and dict(self._mesh.shape).get(C.MODEL_AXIS, 1) > 1
        ):
            # kv_pool_partition_specs shards HEADS over the model axis;
            # a pallas_call inside plain GSPMD jit is not partitioned
            # (XLA would all-gather the whole page pool per step —
            # ops/attention.py documents the same constraint). With the
            # model axis at 1 every operand is effectively replicated
            # and the kernel is safe under any host/device count.
            log_dist(
                "inference.fused_decode requested with a model-parallel "
                "mesh (sharded KV pool heads); a pallas_call is not "
                "GSPMD-partitioned — falling back to the XLA paged "
                "decode path",
                ranks=[0],
            )
            self.fused_decode = False

        # ---- speculative decoding geometry (docs/inference.md) --------
        self.speculative = bool(cfg.inference_speculative_enabled)
        self.spec_k = int(cfg.inference_speculative_k)
        if self.speculative and self.fused_decode:
            # the speculative step's compute is the draft's contiguous
            # decode plus the target's multi-token verify — the
            # single-query flash kernel serves NO tokens there. Disable
            # it (and its gauge) rather than report a kernel that never
            # ran; a fused multi-query verify is the named follow-up.
            log_dist(
                "inference.fused_decode is inert under speculative "
                "decoding (the verify step is multi-token XLA, the "
                "draft rides its own contiguous cache) — disabling the "
                "flag so telemetry reports what actually served",
                ranks=[0],
            )
            self.fused_decode = False
        if self.speculative:
            if not self.paged:
                raise DeepSpeedConfigError(
                    "inference.speculative requires the paged cache "
                    "(kv_block_size > 0): the batched verify step "
                    "writes through the block tables"
                )
            if draft_model is None or draft_parameters is None:
                raise DeepSpeedConfigError(
                    'the "speculative" inference block is configured '
                    "but init_inference received no draft: pass "
                    "draft_model (a smaller GPT-2 module) and "
                    "draft_parameters (its param tree)"
                )
            if not cfg.inference_greedy and cfg.inference_temperature > 0:
                raise DeepSpeedConfigError(
                    "speculative decoding preserves exact output for "
                    "GREEDY decoding only (every committed token is the "
                    "target's own argmax); set inference.sampling.greedy "
                    "or temperature 0"
                )
            dcfg = getattr(draft_model, "config", None)
            if dcfg is None or not all(
                hasattr(dcfg, a) for a in ("n_layer", "n_head", "n_embd",
                                           "n_positions", "layer_config")
            ):
                raise DeepSpeedConfigError(
                    "draft_model must be a GPT-2-family module (a "
                    ".config with n_layer/n_head/n_embd/n_positions)"
                )
            if getattr(dcfg, "vocab_size", None) != getattr(
                mcfg, "vocab_size", None
            ):
                raise DeepSpeedConfigError(
                    f"draft vocab_size={getattr(dcfg, 'vocab_size', None)}"
                    f" != target vocab_size="
                    f"{getattr(mcfg, 'vocab_size', None)}: proposals are "
                    "token ids — the vocabularies must match exactly"
                )
            self.draft_config = dcfg
        else:
            self.draft_config = None

        # ---- multi-tenant LoRA geometry (docs/adapters.md) ------------
        self.multi_lora = bool(cfg.adapters_enabled)
        if self.multi_lora:
            from ..adapters.lora import split_lora_params
            from ..ops.transformer import lora_scaling, resolve_lora_targets

            _, embedded = split_lora_params(model_parameters)
            if embedded:
                # per-tenant adapters ride the in-HBM pool; param-tree
                # *_lora_* leaves would ALSO apply per-layer — a silent
                # double application. (A module config with lora_rank > 0
                # over a BASE tree is fine: a fine-tune engine mutates
                # the shared config, and the per-layer branch no-ops when
                # the leaves are absent.)
                raise DeepSpeedConfigError(
                    "multi-LoRA serving wants the BASE param tree: "
                    "model_parameters carries *_lora_* leaves — split "
                    "them out (adapters.split_lora_params) and load them "
                    "with engine.load_adapter() instead"
                )
            self.adapter_rank = int(cfg.adapters_rank)
            self.adapter_targets = resolve_lora_targets(
                cfg.adapters_targets
            )
            self.adapter_scale = lora_scaling(
                self.adapter_rank, float(cfg.adapters_alpha or 0.0)
            )
            self.adapter_pool_slots = int(cfg.adapters_pool_slots)
        else:
            self.adapter_rank = 0
            self.adapter_targets = ()
            self.adapter_scale = 1.0
            self.adapter_pool_slots = 0

        # ---- telemetry + metrics --------------------------------------
        n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(model_parameters)
        )
        self.telemetry = build_telemetry(
            cfg, rank=jax.process_index(), n_params=n_params
        )
        registry = (
            self.telemetry.registry
            if self.telemetry.enabled else MetricsRegistry()
        )
        self.metrics = register_inference_metrics(registry)
        # request tracer (telemetry/tracing.py): rides the telemetry
        # block's tracing config; the NOOP zero-overhead passthrough
        # otherwise. A fleet tier may swap in ITS tracer (use_tracer) so
        # in-process replica spans land in the router's trace file.
        self.tracer = self.telemetry.tracer
        # per-slot span attrs captured at prefill time (prefix-hit vs
        # cold, suffix bucket, adapter) for the scheduler's prefill span
        self._slot_trace_attrs = {}
        # fleet brownout mode (set_brownout): degraded windows skip the
        # prefix-miss registration work (docs/serving.md "Brownout")
        self._brownout = False

        # ---- params: verified load, cast, pin -------------------------
        import types

        from ..resilience.manager import build_resilience

        # resilience instruments share the inference registry whether or
        # not a telemetry block is configured, so corruption fallbacks on
        # the serving load are observable next to the infer/* streams
        self.resilience = build_resilience(
            cfg,
            telemetry=types.SimpleNamespace(
                enabled=True, registry=self.metrics
            ),
        )

        # ---- host-memory spill tier (docs/inference.md "Host-memory
        # spill tier") ---------------------------------------------------
        # HBM as a cache over host DRAM: evicted prefix pages and adapter
        # rows park D2H (keyed by chain hash / adapter name) and promote
        # back on a hit. peer_sharing joins the process-level share-group
        # tier — the node agent hosts all its replicas' engines in one
        # process, so co-hosted engines warm each other.
        self.host_tier = None
        self.lazy_kv_alloc = False
        if cfg.inference_host_tier_enabled:
            import uuid as _uuid

            from .host_tier import HostTier

            self._tier_client_id = f"engine-{_uuid.uuid4().hex[:8]}"
            place_fn = jax.device_put
            if cfg.inference_host_tier_peer_sharing:
                self.host_tier = HostTier.shared(
                    cfg.inference_host_tier_share_group,
                    max_bytes=cfg.inference_host_tier_max_bytes,
                    place_fn=place_fn,
                )
            else:
                self.host_tier = HostTier(
                    max_bytes=cfg.inference_host_tier_max_bytes,
                    place_fn=place_fn,
                )
            self.host_tier.retain()
            self.lazy_kv_alloc = bool(
                cfg.inference_host_tier_lazy_alloc
            ) and self.paged
            from ..telemetry.manager import register_host_tier_metrics

            register_host_tier_metrics(self.metrics)
        params = model_parameters
        self.loaded_tag = None
        if cfg.inference_checkpoint_load_dir:
            from ..runtime.checkpointing import load_module_state

            loaded, _, tag = load_module_state(
                cfg.inference_checkpoint_load_dir,
                params,
                tag=cfg.inference_checkpoint_tag,
                resilience=self.resilience,
            )
            if loaded is None:
                raise RuntimeError(
                    f"no loadable checkpoint under "
                    f"{cfg.inference_checkpoint_load_dir!r} (see the "
                    f"resilience/corruption_fallbacks counter and logs)"
                )
            params, self.loaded_tag = loaded, tag

        from ..runtime import zero as zero_lib
        from jax.sharding import NamedSharding, PartitionSpec as P

        if param_specs is not None:
            shardings = zero_lib.specs_to_shardings(param_specs, self._mesh)
        else:
            shardings = jax.tree_util.tree_map(
                lambda _: NamedSharding(self._mesh, P()), params
            )
        self.params = jax.device_put(
            jax.tree_util.tree_map(
                lambda p: jnp.asarray(p, self.compute_dtype), params
            ),
            shardings,
        )

        # ---- KV cache + jitted programs -------------------------------
        from .decode import KVCache, KVPool

        if self.paged:
            pool_sharding = NamedSharding(
                self._mesh, kv_pool_partition_specs()
            )
            self._cache_sharding = KVPool(k=pool_sharding, v=pool_sharding)
            # host-side allocator: page free list, prefix-hash registry,
            # refcounts, eviction LRU (inference/paging.py). With the
            # host tier armed, evicted registered pages spill D2H
            # instead of dropping (docs/inference.md "Host-memory spill
            # tier").
            self.block_pool = BlockPool(
                self.kv_pool_blocks, self.kv_block_size,
                spill_fn=self._spill_kv_page if self.host_tier else None,
            )
            self._block_tables = np.zeros(
                (self.num_slots, self.blocks_per_slot), np.int32
            )
            self._slot_blocks = {}  # slot -> this request's page ids
            self._slot_prefix_len = {}  # slot -> cached-prefix tokens
            self._slot_hashes = {}  # slot -> prompt's full-page hash chain
        else:
            cache_sharding = NamedSharding(
                self._mesh, kv_cache_partition_specs()
            )
            # kept for reset_decode_state: driver auto-restart re-inits
            # the cache into the same shardings without touching the
            # pinned params
            self._cache_sharding = KVCache(
                k=cache_sharding, v=cache_sharding
            )
            self.block_pool = None
        self._cache = jax.device_put(
            self._init_cache_host(), self._cache_sharding
        )

        # ---- in-HBM adapter pool + host registry ----------------------
        # docs/adapters.md: {target: (A [L, n+1, in, r], B [L, n+1, r,
        # out])} with row 0 the permanent identity; the host-side
        # AdapterPool owns name->row assignment, per-slot refcounts, and
        # idle-LRU eviction. Rows are written through one jitted
        # index-put whose row index is a TRACED scalar — loading the
        # thousandth adapter compiles nothing new.
        if self.multi_lora:
            pool_specs = adapter_pool_partition_specs(self.adapter_targets)
            self._adapter_shardings = {
                t: tuple(NamedSharding(self._mesh, s) for s in pair)
                for t, pair in pool_specs.items()
            }
            self._adapter_pool = jax.device_put(
                init_adapter_pool(
                    mcfg, self.adapter_pool_slots, self.adapter_rank,
                    self.adapter_targets, self.compute_dtype,
                ),
                self._adapter_shardings,
            )
            self.adapter_registry = AdapterPool(self.adapter_pool_slots)
            self._slot_adapters = np.zeros(self.num_slots, np.int32)
            self._slot_adapter_names = {}  # slot -> adapter name
            # name -> load generation, mirrored at assign time: the
            # registry pops an evicted tenant's generation before assign
            # returns, but the host-tier spill must park the ORIGINAL
            # generation with the rows (the auto-load restore keeps the
            # evicted adapter's salted prefix pages valid)
            self._adapter_generations = {}
            # checkpoint-load template, built lazily from target SHAPES
            # (adapter_host_template) and cached — shapes never change
            self._adapter_template = None

            def _pool_write(pool, rows, idx):
                return jax.tree_util.tree_map(
                    lambda p, r: p.at[:, idx].set(r.astype(p.dtype)),
                    pool, rows,
                )

            # the pool is donated through the row write (like the KV
            # cache through decode): without donation every load briefly
            # holds TWO copies of the whole [L, n+1, ...] pool in HBM.
            # CPU ignores donation; skip it there to keep test logs quiet.
            self._jit_pool_write = jax.jit(
                _pool_write,
                donate_argnums=(
                    (0,) if jax.devices()[0].platform != "cpu" else ()
                ),
            )
        else:
            self._adapter_pool = None
            self.adapter_registry = None
            self._slot_adapters = None
            self._slot_adapter_names = {}
        self._key = jax.random.PRNGKey(rng_seed)
        self._lengths = np.zeros(self.num_slots, np.int32)
        self._last_tokens = np.zeros(self.num_slots, np.int32)
        self._temps = np.full(
            self.num_slots,
            0.0 if cfg.inference_greedy else cfg.inference_temperature,
            np.float32,
        )
        self._sampling_statics = dict(
            vocab_size=getattr(mcfg, "vocab_size", None)
            or int(self.params["transformer"]["wte"].shape[0]),
            top_k=int(cfg.inference_top_k),
            top_p=float(cfg.inference_top_p),
        )

        # cache buffers are donated through every decode step (no copy per
        # token) where the backend honors donation; CPU does not, and the
        # per-call warning would bury test logs
        platform = jax.devices()[0].platform
        donate_cache = platform != "cpu"
        # Multi-LoRA engines append (adapter_pool, adapter_ids) as
        # trailing *args to every program — call sites pass them only in
        # that mode, so each engine traces ONE arity. An adapter-disabled
        # engine therefore traces the EXACT pre-adapter programs (the
        # adapter-off bitwise-parity contract, tests/unit/test_adapters).
        lora_kw = dict(lora_scale=self.adapter_scale)
        # The jitted programs are named for what they serve (a trace's
        # module event is jit_<function name>): readers of a profiler
        # trace find serve_decode / serve_prefill / serve_prefill_suffix /
        # serve_first_token by these names, so keep them.

        def _split_ad(ad):
            # (adapters, adapter_ids) from the trailing args, or Nones
            return ad if ad else (None, None)

        def serve_prefill(p, toks, *ad):
            apool, aids = _split_ad(ad)
            return gpt2_prefill(
                mcfg, p, toks, adapters=apool, adapter_ids=aids, **lora_kw
            )

        self._jit_prefill = jax.jit(serve_prefill)
        if self.paged:
            self._jit_write_prefill = jax.jit(
                write_prefill_to_pool,
                donate_argnums=(0,) if donate_cache else (),
            )

            def serve_decode(p, toks, pos, temps, key, pool, tables, *ad):
                return self._decode_and_sample_paged(
                    p, toks, pos, temps, key, pool, tables, *_split_ad(ad)
                )

            # one compiled suffix-prefill program per suffix bucket (jit
            # specializes on the padded suffix shape); start_pos stays a
            # traced array so every prefix length shares the bucket's
            # program
            def serve_prefill_suffix(p, suf, sp, pool, bt, *ad):
                apool, aids = _split_ad(ad)
                return gpt2_prefill_suffix(
                    mcfg, p, suf, sp, pool, bt, adapters=apool,
                    adapter_ids=aids, **lora_kw,
                )

            self._jit_prefill_suffix = jax.jit(
                serve_prefill_suffix, donate_argnums=(3,) if donate_cache else ()
            )
        else:
            self._jit_write_prefill = jax.jit(
                write_prefill_to_cache,
                donate_argnums=(0,) if donate_cache else (),
            )

            def serve_decode(p, toks, pos, temps, key, cache, *ad):
                return self._decode_and_sample(
                    p, toks, pos, temps, key, cache, *_split_ad(ad)
                )

        self._jit_decode = jax.jit(
            serve_decode, donate_argnums=(5,) if donate_cache else ()
        )
        # first token rides a traced last-prompt-row index so every prompt
        # length reuses ONE compiled program (an eager logits[:, plen-1]
        # slice would compile per distinct length and trip the
        # no-recompile pin)
        def serve_first_token(logits, idx, key, temp):
            return sample_tokens(
                jax.lax.dynamic_slice_in_dim(logits, idx, 1, axis=1)[:, 0, :],
                key, temp, **self._sampling_statics,
            )

        self._jit_first_token = jax.jit(serve_first_token)
        if self.host_tier is not None:
            # host-tier copy programs, all with TRACED indices so the
            # thousandth spill/promotion compiles nothing new:
            #   page gather  — one page's [L, bs, heads, hd] k/v rows D2H
            #   page scatter — a promoted page's rows back into the pool
            #   row gather   — an evicted adapter's A/B rows D2H
            if self.paged:
                self._jit_page_gather = jax.jit(
                    lambda pool, idx: (pool.k[:, idx], pool.v[:, idx])
                )

                def _page_scatter(pool, idx, k_rows, v_rows):
                    return KVPool(
                        k=pool.k.at[:, idx].set(k_rows.astype(pool.k.dtype)),
                        v=pool.v.at[:, idx].set(v_rows.astype(pool.v.dtype)),
                    )

                self._jit_page_scatter = jax.jit(
                    _page_scatter,
                    donate_argnums=(0,) if donate_cache else (),
                )
            if self.multi_lora:
                self._jit_adapter_row_gather = jax.jit(
                    lambda pool, idx: jax.tree_util.tree_map(
                        lambda p: p[:, idx], pool
                    )
                )

        # ---- speculative decoding state (docs/inference.md) -----------
        # the draft rides its own CONTIGUOUS cache (it shares nothing —
        # no paging/prefix machinery needed for a model this small) and
        # the slot/length bookkeeping of the target, so draft state
        # needs no extra accounting: the position-masking invariant
        # makes rejected-proposal cache rows harmless exactly like dead-
        # slot ride-along writes.
        if self.speculative:
            dcfg = self.draft_config
            if dcfg.n_positions < self.max_seq_len:
                raise DeepSpeedConfigError(
                    f"draft n_positions={dcfg.n_positions} < resolved "
                    f"max_seq_len={self.max_seq_len}: the draft must "
                    "reach every position the target serves"
                )
            draft_params = draft_parameters
            if cfg.inference_speculative_draft_checkpoint:
                from ..runtime.checkpointing import load_module_state

                loaded, _, dtag = load_module_state(
                    cfg.inference_speculative_draft_checkpoint,
                    draft_params,
                    resilience=self.resilience,
                )
                if loaded is None:
                    raise RuntimeError(
                        f"no loadable draft checkpoint under "
                        f"{cfg.inference_speculative_draft_checkpoint!r} "
                        "(see the resilience/corruption_fallbacks "
                        "counter and logs)"
                    )
                draft_params = loaded
                log_dist(
                    f"speculative draft serving checkpoint {dtag}",
                    ranks=[0],
                )
            replicated = NamedSharding(self._mesh, P())
            self._draft_params = jax.device_put(
                jax.tree_util.tree_map(
                    lambda p: jnp.asarray(p, self.compute_dtype),
                    draft_params,
                ),
                jax.tree_util.tree_map(lambda _: replicated, draft_params),
            )
            self._draft_cache_sharding = KVCache(
                k=replicated, v=replicated
            )
            self._draft_cache = jax.device_put(
                init_kv_cache(
                    dcfg, self.num_slots, self.max_seq_len,
                    self.compute_dtype,
                ),
                self._draft_cache_sharding,
            )
            # per-slot token at index lengths-1 (the committed token
            # BEFORE the uncached last) — the propose program's sync
            # step re-feeds it to close the full-acceptance cache hole
            self._spec_prev_tokens = np.zeros(self.num_slots, np.int32)
            draft_vocab = int(dcfg.vocab_size)
            spec_k = self.spec_k

            def serve_draft_prefill(dp, toks):
                return gpt2_prefill(dcfg, dp, toks)

            self._jit_draft_prefill = jax.jit(serve_draft_prefill)
            self._jit_draft_write = jax.jit(
                write_prefill_to_cache,
                donate_argnums=(0,) if donate_cache else (),
            )

            def serve_draft_propose(dp, prev_tokens, tokens, positions, cache):
                """One sync step + k greedy draft steps under one
                program: proposals [slots, k]. k is STATIC (the scan
                length) — acceptance is data, so no steady-state
                recompiles.

                The SYNC step re-feeds the token at index
                ``positions - 1`` (the burst's second-to-last commit):
                after a FULLY-accepted cycle the target committed k+1
                tokens but the draft's propose only wrote k cache rows,
                leaving the last accepted proposal's row a hole the
                next propose would attend as garbage (measured: draft
                acceptance collapsed to ~0.67 even with draft ==
                target). For hole-free slots the rewrite recomputes
                bitwise-identical k/v from an identical cache prefix —
                a no-op by value."""
                from .sampling import mask_padded_vocab

                _, cache = gpt2_decode_step(
                    dcfg, dp, prev_tokens,
                    jnp.maximum(positions - 1, 0), cache,
                )

                def body(carry, _):
                    toks, pos, c = carry
                    logits, c = gpt2_decode_step(dcfg, dp, toks, pos, c)
                    nxt = jnp.argmax(
                        mask_padded_vocab(
                            logits.astype(jnp.float32), draft_vocab
                        ),
                        axis=-1,
                    ).astype(jnp.int32)
                    return (nxt, pos + 1, c), nxt

                (_, _, cache), props = jax.lax.scan(
                    body, (tokens, positions, cache), None, length=spec_k
                )
                return jnp.transpose(props), cache  # [slots, k]

            self._jit_draft_propose = jax.jit(
                serve_draft_propose, donate_argnums=(4,) if donate_cache else ()
            )

            def serve_spec_verify(p, toks, start, pool, tables, *ad):
                """ONE fixed-shape batched target step over the k+1
                verify tokens [last, d_1..d_k] per slot: suffix-prefill
                arithmetic against the paged cache (k/v written through
                the block tables, causal attention over prefix +
                verify rows), greedy-argmaxed per row. Row i is the
                target's own next token after consuming verify token i —
                the accept/commit oracle."""
                apool, aids = _split_ad(ad)
                logits, pool = gpt2_prefill_suffix(
                    mcfg, p, toks, start, pool, tables, adapters=apool,
                    adapter_ids=aids, **lora_kw,
                )
                from .sampling import mask_padded_vocab

                greedy = jnp.argmax(
                    mask_padded_vocab(
                        logits.astype(jnp.float32),
                        self._sampling_statics["vocab_size"],
                    ),
                    axis=-1,
                ).astype(jnp.int32)
                return greedy, pool

            self._jit_spec_verify = jax.jit(
                serve_spec_verify, donate_argnums=(3,) if donate_cache else ()
            )

        # ---- KV metric streams ----------------------------------------
        self._kv_occupancy = self.metrics.gauge("infer/kv_pool_occupancy")
        self._kv_bytes = self.metrics.gauge("infer/kv_cache_bytes")
        self._prefix_hits = self.metrics.counter("infer/prefix_hits")
        self._prefix_misses = self.metrics.counter("infer/prefix_misses")
        self._kv_reclaimed = self.metrics.counter("infer/kv_blocks_reclaimed")
        self._reclaimed_synced = 0
        self._kv_bytes.set(
            int(self._cache.k.nbytes) + int(self._cache.v.nbytes)
        )

        # ---- fused/speculative streams (docs/observability.md) --------
        self.metrics.gauge("infer/fused_decode").set(
            1 if self.fused_decode else 0
        )
        self._spec_proposed = self.metrics.counter("infer/spec_proposed")
        self._spec_accepted = self.metrics.counter("infer/spec_accepted")
        self._spec_rate = self.metrics.gauge("infer/spec_acceptance_rate")

        # ---- adapters/* metric streams (docs/observability.md) --------
        if self.multi_lora:
            from ..telemetry.manager import register_adapter_metrics

            register_adapter_metrics(self.metrics)
            self._adapter_occupancy = self.metrics.gauge(
                "adapters/pool_occupancy"
            )
            self.metrics.gauge("adapters/pool_slots").set(
                self.adapter_pool_slots
            )
            self._adapter_loads = self.metrics.counter("adapters/loads")
            self._adapter_evictions = self.metrics.counter(
                "adapters/evictions"
            )
            self._adapter_requests = self.metrics.counter(
                "adapters/requests"
            )

        # ---- host_tier/* metric streams (docs/observability.md) -------
        if self.host_tier is not None:
            self._ht_occupancy = self.metrics.gauge(
                "host_tier/occupancy_bytes"
            )
            self._ht_entries = self.metrics.gauge("host_tier/entries")
            self._ht_spills = self.metrics.counter("host_tier/spills")
            self._ht_promotions = self.metrics.counter(
                "host_tier/promotions"
            )
            self._ht_peer_fetches = self.metrics.counter(
                "host_tier/peer_fetches"
            )
            self._ht_preemptions = self.metrics.counter(
                "host_tier/preemptions"
            )
            self._ht_copy_faults = self.metrics.counter(
                "host_tier/copy_faults"
            )

        # ---- scheduler ------------------------------------------------
        self.scheduler = ContinuousBatchingScheduler(
            self,
            num_slots=self.num_slots,
            max_seq_len=self.max_seq_len,
            queue_depth=cfg.inference_queue_depth,
            queue_timeout=cfg.inference_queue_timeout,
            eos_token_id=cfg.inference_eos_token_id,
            temperature=(
                0.0 if cfg.inference_greedy else cfg.inference_temperature
            ),
            registry=self.metrics,
            telemetry=self.telemetry,
            export_interval=getattr(self.telemetry, "interval", 1) * 16,
            deadline_secs=cfg.inference_deadline_secs,
            driver_restart_budget=cfg.inference_driver_restart_budget,
            degraded_queue_ratio=cfg.inference_degraded_queue_ratio,
            tracer=self.tracer,
        )
        log_dist(
            f"init_inference: {self.num_slots} decode slots x "
            f"max_seq_len {self.max_seq_len} (prefill window "
            f"{self.prefill_len}), dtype "
            f"{cfg.inference_dtype}, queue depth "
            f"{cfg.inference_queue_depth}"
            + (
                f", paged KV cache ({self.kv_pool_blocks} pages x "
                f"{self.kv_block_size} tokens, prefix cache "
                f"{'on' if self.prefix_cache_enabled else 'off'})"
                if self.paged else ", contiguous KV cache"
            )
            + (f", serving checkpoint {self.loaded_tag}"
               if self.loaded_tag else ""),
            ranks=[0],
        )

    def _init_cache_host(self):
        """Fresh zeroed decode cache (host-side values; the caller
        device_puts into the pinned shardings): the contiguous per-slot
        block or the paged page pool, per the engine's mode."""
        if self.paged:
            return init_kv_pool(
                self.model_config, self.kv_pool_blocks, self.kv_block_size,
                self.compute_dtype,
            )
        return init_kv_cache(
            self.model_config, self.num_slots, self.max_seq_len,
            self.compute_dtype,
        )

    # -- device hooks (called by the scheduler) -------------------------
    def _decode_and_sample(self, params, tokens, positions, temps, key,
                           cache, adapters=None, adapter_ids=None):
        logits, cache = gpt2_decode_step(
            self.model_config, params, tokens, positions, cache,
            adapters=adapters, adapter_ids=adapter_ids,
            lora_scale=self.adapter_scale,
        )
        next_tokens = sample_tokens(
            logits, key, temps, **self._sampling_statics
        )
        return next_tokens, cache

    def _decode_and_sample_paged(self, params, tokens, positions, temps,
                                 key, pool, tables, adapters=None,
                                 adapter_ids=None):
        logits, pool = gpt2_decode_step_paged(
            self.model_config, params, tokens, positions, pool, tables,
            adapters=adapters, adapter_ids=adapter_ids,
            lora_scale=self.adapter_scale, fused=self.fused_decode,
        )
        next_tokens = sample_tokens(
            logits, key, temps, **self._sampling_statics
        )
        return next_tokens, pool

    # -- paged-pool accounting (scheduler admission hooks) --------------
    def kv_blocks_needed(self, prompt_len, max_new_tokens):
        """Worst-case pages one request reserves at admission: every
        token it may cache, prompt plus generation budget, capped at the
        sequence limit. Reserving the worst case up front means decode
        NEVER allocates mid-flight — a running request cannot hit pool
        exhaustion between tokens, so admission is the only capacity
        gate (docs/inference.md weighs this against lazy growth)."""
        total = min(int(prompt_len) + int(max_new_tokens), self.max_seq_len)
        return self.block_pool.blocks_for(total)

    def kv_blocks_needed_now(self, prompt_len, max_new_tokens):
        """Pages admission actually reserves: the worst case by default;
        under ``host_tier.lazy_alloc`` only the PROMPT's pages — decode
        grows the slot one page at a time (ensure_decode_capacity) and
        the scheduler preempts under pressure instead of gating
        admission on tokens that may never be generated."""
        if self.lazy_kv_alloc:
            return self.block_pool.blocks_for(
                min(int(prompt_len), self.max_seq_len)
            )
        return self.kv_blocks_needed(prompt_len, max_new_tokens)

    def kv_blocks_available(self):
        """Pages an admission could obtain right now (free + evictable
        cached): the REJECT_CAPACITY gate's denominator."""
        return self.block_pool.available_blocks

    def kv_pool_total_blocks(self):
        return self.block_pool.num_blocks

    def reserve_request(self, slot, prompt_tokens, max_new_tokens):
        """Slot-join page allocation: look up the longest cached prefix
        (acquiring shared references on its pages), then allocate private
        pages for everything else this request may write. Raises
        :class:`paging.PoolExhausted` — the scheduler defers the request
        to the next step boundary — with no pages held. Returns the
        cached prefix length in tokens (0 = cold)."""
        if not self.paged:
            return 0
        plen = len(prompt_tokens)
        needed = self.kv_blocks_needed_now(plen, max_new_tokens)
        # cheap pressure short-circuit BEFORE the O(prompt) hash chain: a
        # deferred request retries here every step, and even a full
        # prefix hit (at most the prompt's full pages minus one) cannot
        # shrink the private need below this floor
        min_private = needed - (plen - 1) // self.kv_block_size
        if self.block_pool.available_blocks < min_private:
            raise PoolExhausted(
                min_private, self.block_pool.available_blocks
            )
        hashes = None
        if self.prefix_cache_enabled:
            # salted by the slot's adapter identity: adapted prefills
            # write adapter-specific k/v, so pages never share across
            # adapters (or across reloads of one adapter's weights)
            hashes = hash_full_blocks(
                prompt_tokens, self.kv_block_size,
                salt=self._adapter_salt(slot),
            )
            prefix_len, shared = self.block_pool.match_prefix(
                prompt_tokens, hashes=hashes
            )
        else:
            prefix_len, shared = 0, []
        # host-tier promotion: extend the device match with the
        # contiguous run of SPILLED pages parked under the same chain
        # (possibly by a peer engine). Promoted pages land in freshly
        # allocated private pages — the tier saves the prefill COMPUTE,
        # not the allocation — then register so they share like any
        # cached prefix.
        promoted = []
        if self.prefix_cache_enabled and self.host_tier is not None:
            promoted = self._promote_chain(hashes, len(shared), plen)
        while promoted:
            # the combined prefix still needs a compiled suffix width;
            # shrink the promotion until one fits (the device-only match
            # re-checks below)
            pl = (len(shared) + len(promoted)) * self.kv_block_size
            if self._suffix_bucket(plen - pl, pl) is not None:
                break
            promoted.pop()
        if not promoted and prefix_len and self._suffix_bucket(
            plen - prefix_len, prefix_len
        ) is None:
            # no compiled suffix width fits this (suffix, prefix)
            # pair — e.g. a small user-configured bucket list, or a
            # bucket that would pad past max_seq_len and clamp its
            # garbage rows into the slot's REAL last page: fall back
            # to the always-correct cold full prefill (a miss, not a
            # hit — the pages still share on the next request)
            self.block_pool.release(shared)
            prefix_len, shared = 0, []
        try:
            private = self.block_pool.alloc(needed - len(shared))
        except Exception:
            if shared:
                self.block_pool.release(shared)
            raise
        if promoted:
            # scatter the parked rows H2D into the first promoted-count
            # private pages (placement was staged asynchronously; the
            # stager overlaps page i+1's device_put with page i's
            # scatter), then publish their hashes — later requests share
            # them like any device-cached prefix
            for i, (h, (k_rows, v_rows), peer) in enumerate(promoted):
                self._cache = self._jit_page_scatter(
                    self._cache, jnp.int32(private[i]), k_rows, v_rows
                )
                self._ht_promotions.inc()
                if peer:
                    self._ht_peer_fetches.inc()
            self.block_pool.register_prefix(
                prompt_tokens,
                [private[i] for i in range(len(promoted))],
                hashes=[h for h, _, _ in promoted],
            )
            prefix_len = (len(shared) + len(promoted)) * self.kv_block_size
        if self.prefix_cache_enabled:
            (self._prefix_hits if prefix_len else self._prefix_misses).inc()
        blocks = shared + private
        self._slot_blocks[slot] = blocks
        self._slot_prefix_len[slot] = prefix_len
        self._slot_hashes[slot] = hashes
        row = np.zeros(self.blocks_per_slot, np.int32)
        row[: len(blocks)] = blocks
        self._block_tables[slot] = row
        self._sync_pool_metrics()
        return prefix_len

    # -- host-tier seams (docs/inference.md "Host-memory spill tier") ---
    def _ht_fault_mode(self):
        """Consult the ``host_tier.copy`` chaos site at a copy seam.
        Returns None (no fault), "oserror" (skip the copy — a spill is
        dropped, a promotion reads cold), or "garble" (park a corrupted
        payload for the checksum walk to catch). Counted either way."""
        spec = self.resilience.faults.fire("host_tier.copy")
        if spec is None:
            return None
        self._ht_copy_faults.inc()
        return spec.args.get("mode", "oserror")

    def _spill_kv_page(self, block_id, chain_hash):
        """BlockPool eviction seam: park the evicted registered page's
        device k/v rows in the host tier D2H while they are still
        intact (the allocator frees the id right after). Never raises —
        a failed spill degrades to the tier-less behavior (the page
        drops) and serving continues."""
        corrupt = False
        mode = self._ht_fault_mode()
        if mode == "garble":
            corrupt = True
        elif mode is not None:
            logger.warning(
                "host-tier spill of page %d skipped (injected "
                "host_tier.copy fault): the page drops as without the "
                "tier", block_id,
            )
            return
        k_rows, v_rows = self._jit_page_gather(
            self._cache, jnp.int32(block_id)
        )
        stored = self.host_tier.put(
            chain_hash,
            (np.asarray(k_rows), np.asarray(v_rows)),
            meta={"kind": "kv"},
            origin=self._tier_client_id,
            corrupt=corrupt,
        )
        if stored:
            self._ht_spills.inc()

    def _promote_chain(self, hashes, start, plen):
        """Fetch the contiguous run of spilled pages extending the
        device prefix match at page index ``start``. Every fetch is
        staged on the tier's async worker first (the WindowStager
        device_put pattern), then consumed in order — page i+1's H2D
        placement overlaps page i's scatter. Returns a list of
        ``(chain_hash, (k_rows, v_rows), is_peer_fetch)``; any failure
        (chaos fault, checksum drop, raced eviction, geometry mismatch)
        truncates the run — the remainder re-prefills cold, wrong pages
        are never served."""
        tier = self.host_tier
        eligible = hashes or []
        if eligible and plen == len(eligible) * self.kv_block_size:
            # same N-1 rule as match_prefix: the whole prompt can never
            # be served from cache
            eligible = eligible[:-1]
        handles = []
        for h in eligible[start:]:
            if not tier.contains(h):
                break
            mode = self._ht_fault_mode()
            if mode is not None:
                logger.warning(
                    "host-tier promotion truncated (injected "
                    "host_tier.copy fault): the remaining prefix "
                    "re-prefills cold"
                )
                break
            handle = tier.fetch_async(h, requester=self._tier_client_id)
            if handle is None:
                break
            handles.append((h, handle))
        out, failed = [], False
        # one page's [L, bs, heads, hd] rows — the pool minus the page axis
        k_shape = (self._cache.k.shape[0],) + tuple(self._cache.k.shape[2:])
        for h, handle in handles:
            if failed:
                try:
                    handle.result()  # drain to unpin the tier entry
                except Exception:
                    pass
                continue
            try:
                k_rows, v_rows = handle.result()
            except Exception:
                self._ht_copy_faults.inc()
                logger.warning(
                    "host-tier promotion of %s failed at placement; "
                    "falling back to cold prefill", h,
                )
                failed = True
                continue
            if tuple(k_rows.shape) != k_shape:
                # a peer with different pool geometry parked this entry
                failed = True
                continue
            out.append((h, (k_rows, v_rows), handle.peer))
        return out

    def ensure_decode_capacity(self, active_slots):
        """Lazy page growth (host_tier.lazy_alloc): before a decode
        step, extend every active slot's page list to cover the rows
        the step will write (one token, or the speculative burst).
        Raises :class:`paging.PoolExhausted` when the pool cannot grow a
        slot even after evicting every cached page — the scheduler
        preempts and retries."""
        if not (self.paged and self.lazy_kv_alloc):
            return
        budget = (self.spec_k + 1) if self.speculative else 1
        for slot in active_slots:
            blocks = self._slot_blocks.get(slot)
            if blocks is None:
                continue
            required = self.block_pool.blocks_for(
                min(int(self._lengths[slot]) + budget, self.max_seq_len)
            )
            while len(blocks) < required:
                new = self.block_pool.alloc(1)
                self._block_tables[slot][len(blocks)] = new[0]
                blocks.extend(new)
        self._sync_pool_metrics()

    def count_preemption(self):
        """Scheduler hook: one request preempted under page pressure
        (its pages parked, the request re-queued for suffix resume)."""
        if self.host_tier is not None:
            self._ht_preemptions.inc()

    def _register_decode_pages(self, slot, final_tokens):
        """Decode-page chain hashing: extend the prefix registry to the
        full pages this request COMPLETED DURING DECODE, so generated
        continuations become shareable/spillable prefixes — and a
        preempted request's resume (prompt + tokens so far) matches
        everything but its final partial page. Runs before the slot's
        pages release (the pages must still be live) and before its
        adapter pin drops (the chain salt needs the adapter identity)."""
        if not self.prefix_cache_enabled or self._brownout:
            return
        blocks = self._slot_blocks.get(slot)
        if not blocks:
            return
        # cache rows hold prompt + tokens[:-1] (the final sampled
        # token's k/v is never written): exactly the first _lengths rows
        valid = [int(t) for t in final_tokens][: int(self._lengths[slot])]
        n_full = len(valid) // self.kv_block_size
        if n_full <= 0:
            return
        self.block_pool.register_prefix(
            valid, blocks[:n_full],
            hashes=hash_full_blocks(
                valid, self.kv_block_size, salt=self._adapter_salt(slot)
            ),
        )

    def release_slot(self, slot, final_tokens=None):
        """Return a finished/evicted request's pages to the pool (shared
        prefix pages decref; full prompt pages stay cached for the next
        request with that prefix) and NULL its block-table row so the
        dead slot's ride-along decode writes sink into the sacrificial
        page instead of pages the pool may hand to someone else. Also
        drops the slot's adapter pin (its id resets to the identity, so
        the dead slot's ride-along gathers read the zero rows).

        ``final_tokens`` (prompt + generated tokens, scheduler-provided)
        arms decode-page chain hashing: the request's full pages —
        including ones completed during decode — register before release
        so they park in the LRU (and spill to the host tier) instead of
        dropping."""
        if self.paged and final_tokens is not None:
            self._register_decode_pages(slot, final_tokens)
        if self.multi_lora:
            name = self._slot_adapter_names.pop(slot, None)
            if name is not None:
                self.adapter_registry.release(name)
            self._slot_adapters[slot] = 0
        if not self.paged:
            return
        blocks = self._slot_blocks.pop(slot, None)
        self._slot_prefix_len.pop(slot, None)
        self._slot_hashes.pop(slot, None)
        if blocks:
            self.block_pool.release(blocks)
        self._block_tables[slot] = NULL_BLOCK
        self._sync_pool_metrics()

    def _sync_pool_metrics(self):
        pool = self.block_pool
        self._kv_occupancy.set(pool.used_blocks)
        if pool.reclaimed > self._reclaimed_synced:
            self._kv_reclaimed.inc(pool.reclaimed - self._reclaimed_synced)
            self._reclaimed_synced = pool.reclaimed
        if self.host_tier is not None:
            self._ht_occupancy.set(self.host_tier.occupancy_bytes)
            self._ht_entries.set(self.host_tier.entries)

    def kv_snapshot(self):
        """Pool/prefix-cache state for ``load_snapshot()`` — the numbers
        the fleet router's placement and per-replica gauges read."""
        if not self.paged:
            out = {}
        else:
            hits = self._prefix_hits.value
            misses = self._prefix_misses.value
            out = {
                "kv_blocks_total": self.block_pool.num_blocks,
                "kv_blocks_free": self.block_pool.available_blocks,
                "kv_blocks_used": self.block_pool.used_blocks,
                "prefix_hits": hits,
                "prefix_misses": misses,
                "prefix_hit_rate": (
                    hits / (hits + misses) if hits + misses else 0.0
                ),
            }
        if self.host_tier is not None:
            # the engine's own counters plus the (possibly peer-shared)
            # tier's occupancy — the fleet router mirrors these to
            # fleet/replica{i}/host_tier_* gauges
            out.update({
                "host_tier_occupancy_bytes": self.host_tier.occupancy_bytes,
                "host_tier_entries": self.host_tier.entries,
                "host_tier_spills": self._ht_spills.value,
                "host_tier_promotions": self._ht_promotions.value,
                "host_tier_peer_fetches": self._ht_peer_fetches.value,
                "host_tier_preemptions": self._ht_preemptions.value,
                "host_tier_copy_faults": self._ht_copy_faults.value,
            })
        return out

    # -- multi-tenant LoRA adapters (docs/adapters.md) ------------------
    def _require_multi_lora(self):
        if not self.multi_lora:
            raise DeepSpeedConfigError(
                'this engine has no adapter pool; enable the "adapters" '
                "config block to serve LoRA adapters"
            )

    def load_adapter(self, name, adapter_state=None, load_dir=None,
                     tag=None):
        """Install (or hot-reload) tenant adapter ``name`` into the
        in-HBM pool and return its pool row index.

        Weights come from ``adapter_state`` — a fine-tuned adapter tree
        (an adapter-mode training engine's ``engine.params``) — or from
        ``load_dir``: an adapter-only checkpoint committed by the
        training engine's atomic protocol, read through the resilience
        verified-load path (manifest check, host-side parse, newest-valid
        fallback) and validated against this pool's rank/targets via the
        checkpoint's self-describing ``adapters`` client state. Loading
        past ``adapters.pool_slots`` evicts the least-recently-used IDLE
        adapter; a pool whose every adapter has live requests raises
        :class:`~deepspeed_tpu.adapters.AdapterPoolFull`. The row write
        is one jitted index-put with a TRACED row index — the thousandth
        load compiles nothing.
        """
        self._require_multi_lora()
        from ..adapters.lora import (
            adapter_host_template,
            adapter_layer_stacks,
        )

        if (adapter_state is None) == (load_dir is None):
            raise ValueError(
                "pass exactly one of adapter_state (a fine-tuned adapter "
                "tree) or load_dir (an adapter-only checkpoint directory)"
            )
        if load_dir is not None:
            from ..runtime.checkpointing import load_module_state

            if self._adapter_template is None:
                # shape-only walk over the PINNED params (no device
                # transfer), cached: target shapes never change between
                # loads
                self._adapter_template = adapter_host_template(
                    self.params, self.adapter_rank, self.adapter_targets
                )
            adapter_state, client_state, ckpt_tag = load_module_state(
                load_dir, self._adapter_template, tag=tag,
                resilience=self.resilience,
            )
            if adapter_state is None:
                raise RuntimeError(
                    f"no loadable adapter checkpoint under {load_dir!r} "
                    "(see the resilience/corruption_fallbacks counter)"
                )
            meta = (client_state or {}).get("adapters")
            if meta is not None:
                from ..ops.transformer import lora_scaling

                # alpha compares as the RESOLVED scale (alpha 0 => rank):
                # a scale mismatch would silently rescale every delta the
                # tenant fine-tuned
                ckpt_scale = lora_scaling(
                    meta.get("rank", self.adapter_rank),
                    meta.get("alpha", 0.0),
                )
                if (
                    int(meta.get("rank", self.adapter_rank))
                    != self.adapter_rank
                    or tuple(meta.get("targets", self.adapter_targets))
                    != tuple(self.adapter_targets)
                    or ckpt_scale != self.adapter_scale
                ):
                    raise DeepSpeedConfigError(
                        f"adapter checkpoint {ckpt_tag!r} was fine-tuned "
                        f"with rank={meta.get('rank')}/alpha="
                        f"{meta.get('alpha')}/targets={meta.get('targets')}"
                        f" but this pool serves rank={self.adapter_rank}/"
                        f"scale={self.adapter_scale}/targets="
                        f"{list(self.adapter_targets)}"
                    )
        stacks = adapter_layer_stacks(adapter_state, self.adapter_targets)
        for t, (a, b) in stacks.items():
            la, lb = self._adapter_pool[t]
            want = (
                (la.shape[0], *la.shape[2:]), (lb.shape[0], *lb.shape[2:]),
            )
            if (tuple(a.shape), tuple(b.shape)) != want:
                raise ValueError(
                    f"adapter {name!r} target {t}: shapes "
                    f"{tuple(a.shape)}/{tuple(b.shape)} do not fit the "
                    f"pool rows {want[0]}/{want[1]} (model/rank mismatch?)"
                )
        idx, evicted = self.adapter_registry.assign(name)
        if evicted is not None:
            # park the outgoing tenant's rows D2H while they are still
            # in the pool (the write below overwrites — and on TPU
            # donates — row idx); a later submit for the evicted name
            # auto-loads from the tier instead of failing
            self._spill_adapter_row(evicted, idx)
        # an explicit (re)load carries FRESH weights under a NEW
        # generation: any tier copy of the old weights is stale — and its
        # salted prefix pages unreachable — so drop it
        if self.host_tier is not None:
            self.host_tier.discard(f"adapter/{name}")
        self._adapter_generations[name] = (
            self.adapter_registry.generation_of(name)
        )
        self._adapter_pool = self._jit_pool_write(
            self._adapter_pool,
            {t: (jnp.asarray(a), jnp.asarray(b))
             for t, (a, b) in stacks.items()},
            jnp.int32(idx),
        )
        self._adapter_loads.inc()
        if evicted is not None:
            self._adapter_evictions.inc()
            log_dist(
                f"adapter pool full: evicted idle adapter {evicted!r} "
                f"for {name!r} (row {idx})", ranks=[0],
            )
        self._adapter_occupancy.set(self.adapter_registry.used_slots)
        log_dist(
            f"loaded adapter {name!r} into pool row {idx} "
            f"({self.adapter_registry.used_slots}/"
            f"{self.adapter_pool_slots} slots)", ranks=[0],
        )
        return idx

    def unload_adapter(self, name):
        """Explicitly evict ``name`` (refused while live requests decode
        against it); frees its pool row for the next load. An explicit
        unload is intentional removal: any host-tier copy drops too, so
        the tenant cannot silently resurrect through auto-load."""
        self._require_multi_lora()
        idx = self.adapter_registry.remove(name)
        self._adapter_generations.pop(name, None)
        if self.host_tier is not None:
            self.host_tier.discard(f"adapter/{name}")
        self._adapter_evictions.inc()
        self._adapter_occupancy.set(self.adapter_registry.used_slots)
        return idx

    def _spill_adapter_row(self, name, idx):
        """Park an evicted adapter's pool rows (still at row ``idx``) in
        the host tier D2H, keyed ``adapter/<name>`` with its load
        generation — the auto-load restore re-installs the SAME weights
        under the SAME generation, so the tenant's salted prefix pages
        stay valid. Never raises (chaos or copy failure drops the park;
        the adapter is then simply gone, as without the tier)."""
        if self.host_tier is None:
            return
        mode = self._ht_fault_mode()
        if mode is not None and mode != "garble":
            logger.warning(
                "host-tier spill of adapter %r skipped (injected "
                "host_tier.copy fault)", name,
            )
            return
        generation = self._adapter_generations.get(name)
        targets = sorted(self._adapter_pool)
        rows = self._jit_adapter_row_gather(
            self._adapter_pool, jnp.int32(idx)
        )
        arrays = []
        for t in targets:
            a, b = rows[t]
            arrays.extend((np.asarray(a), np.asarray(b)))
        stored = self.host_tier.put(
            f"adapter/{name}",
            arrays,
            meta={
                "kind": "adapter",
                "generation": generation,
                "targets": targets,
            },
            origin=self._tier_client_id,
            corrupt=(mode == "garble"),
        )
        if stored:
            self._ht_spills.inc()

    def _auto_load_adapter_from_tier(self, name):
        """Re-install a spilled adapter from the host tier. Returns
        "loaded" (now resident, original generation restored),
        "deferred" (the tier holds it but every pool slot is pinned by
        live requests — retry when traffic drains, exactly like a KV
        page shortfall), or False (not in the tier / promotion failed —
        the adapter is genuinely unavailable)."""
        if not self.multi_lora or self.host_tier is None:
            return False
        key = f"adapter/{name}"
        if not self.host_tier.contains(key):
            return False
        if self._ht_fault_mode() is not None:
            logger.warning(
                "host-tier auto-load of adapter %r skipped (injected "
                "host_tier.copy fault)", name,
            )
            return False
        got = self.host_tier.fetch(key, requester=self._tier_client_id)
        if got is None:
            return False
        arrays, meta, origin = got
        targets = meta.get("targets") or []
        if sorted(self._adapter_pool) != list(targets) or len(arrays) != (
            2 * len(targets)
        ):
            return False
        stacks = {
            t: (arrays[2 * i], arrays[2 * i + 1])
            for i, t in enumerate(targets)
        }
        for t, (a, b) in stacks.items():
            la, lb = self._adapter_pool[t]
            want = (
                (la.shape[0], *la.shape[2:]), (lb.shape[0], *lb.shape[2:]),
            )
            if (tuple(a.shape), tuple(b.shape)) != want:
                return False  # a peer with different pool geometry
        try:
            idx, evicted = self.adapter_registry.assign(
                name, generation=meta.get("generation")
            )
        except AdapterPoolFull:
            return "deferred"
        if evicted is not None:
            self._spill_adapter_row(evicted, idx)
            self._adapter_evictions.inc()
        self._adapter_generations[name] = (
            self.adapter_registry.generation_of(name)
        )
        self._adapter_pool = self._jit_pool_write(
            self._adapter_pool, stacks, jnp.int32(idx)
        )
        # the host copy stays: it is bitwise-identical to the rows just
        # installed, and peer replicas in the share group warm from it
        self._adapter_loads.inc()
        self._ht_promotions.inc()
        if origin is not None and origin != self._tier_client_id:
            self._ht_peer_fetches.inc()
        self._adapter_occupancy.set(self.adapter_registry.used_slots)
        log_dist(
            f"auto-loaded adapter {name!r} from the host tier into pool "
            f"row {idx} (generation "
            f"{self.adapter_registry.generation_of(name)} restored)",
            ranks=[0],
        )
        return "loaded"

    def resolve_adapter(self, name):
        """Submit-time validation + per-adapter accounting: returns the
        adapter's CURRENT pool row. A known-but-not-resident name (its
        rows parked in the host tier) auto-loads here — or, when every
        pool slot is pinned, is accepted anyway (returns None) and the
        slot join retries the auto-load, deferring exactly like a KV
        page shortfall. Raises
        :class:`~deepspeed_tpu.adapters.AdapterUnavailable` (a
        ValueError) for a genuinely unknown name — THIS engine can never
        serve it, but the typed subclass lets a fleet router fall
        through to a replica that holds the adapter."""
        self._require_multi_lora()
        try:
            idx = self.adapter_registry.index_of(name)
        except KeyError:
            state = self._auto_load_adapter_from_tier(name)
            if state == "loaded":
                idx = self.adapter_registry.index_of(name)
            elif state == "deferred":
                self._adapter_requests.inc()
                self.metrics.counter(f"adapters/requests/{name}").inc()
                return None
            else:
                raise AdapterUnavailable(
                    f"adapter {name!r} is not loaded (loaded: "
                    f"{self.adapter_registry.loaded}); call "
                    "engine.load_adapter() first"
                ) from None
        self.adapter_registry.count_request(name)
        self._adapter_requests.inc()
        self.metrics.counter(f"adapters/requests/{name}").inc()
        return idx

    def assign_slot_adapter(self, slot, name):
        """Slot-join hook (scheduler._admit): pin ``name`` for the slot's
        lifetime and point the slot's adapter id at its pool row. Returns
        False when the adapter was evicted between submit and join — the
        scheduler fail-finishes that request instead of serving it the
        identity (or another tenant's) weights. With the host tier, an
        evicted-but-parked adapter auto-loads here instead; a tier hit
        that cannot land because every pool slot is pinned raises
        :class:`~deepspeed_tpu.adapters.AdapterPoolFull`, which the
        scheduler turns into a deferral (retry at the next step
        boundary) exactly like a KV page shortfall."""
        if not self.multi_lora:
            return True
        if name is None:
            # clear any stale name too: the slot's prefix-cache salt must
            # be the BASE salt, not a previous occupant's adapter
            self._slot_adapter_names.pop(slot, None)
            self._slot_adapters[slot] = 0
            return True
        try:
            idx = self.adapter_registry.acquire(name)
        except KeyError:
            state = self._auto_load_adapter_from_tier(name)
            if state == "loaded":
                idx = self.adapter_registry.acquire(name)
            elif state == "deferred":
                raise AdapterPoolFull(self.adapter_pool_slots) from None
            else:
                return False
        self._slot_adapters[slot] = idx
        self._slot_adapter_names[slot] = name
        return True

    def _adapter_salt(self, slot):
        """Prefix-cache hash salt for the slot's adapter: cached k/v are
        a function of the weights that wrote them, so pages only share
        within (adapter name, load generation) — base-model pages salt
        None, and a reloaded adapter's fresh weights never match pages
        its old weights produced."""
        if not self.multi_lora:
            return None
        name = self._slot_adapter_names.get(slot)
        if name is None:
            return None
        return f"{name}@{self.adapter_registry.generation_of(name)}"

    def adapter_snapshot(self):
        """Adapter-pool state for ``load_snapshot()`` — what the fleet
        router's adapter-affinity placement and per-replica gauges read
        (all JSON-safe for the subprocess-replica RPC)."""
        if not self.multi_lora:
            return {}
        reg = self.adapter_registry
        return {
            "adapters_loaded": reg.loaded,
            "adapter_pool_slots": self.adapter_pool_slots,
            "adapter_pool_used": reg.used_slots,
            "adapter_loads": reg.loads,
            "adapter_evictions": reg.evictions,
            "adapter_requests": dict(reg.requests),
        }

    def prefill_request(self, slot, prompt_tokens, temperature):
        """Run one request's prefill into ``slot``: cache rows 0..P-1
        written, first token sampled from the prompt's last logit row.
        On the paged path the pages come from :meth:`reserve_request`
        (already called at slot join); a cached-prefix hit skips the
        shared pages' compute entirely and prefills only the unique
        suffix. Returns the first generated token (a host int)."""
        plen = len(prompt_tokens)
        prefix_len = self._slot_prefix_len.get(slot, 0) if self.paged else 0
        if prefix_len > 0:
            first = self._prefill_suffix(
                slot, prompt_tokens, prefix_len, temperature
            )
        else:
            padded = np.zeros((1, self.prefill_len), np.int32)
            padded[0, :plen] = prompt_tokens
            if self.multi_lora:
                # prefill THROUGH the slot's adapter: the cached k/v that
                # seed decode must already carry the adapted projections
                logits, ks, vs = self._jit_prefill(
                    self.params, jnp.asarray(padded),
                    self._adapter_pool,
                    jnp.asarray(self._slot_adapters[slot:slot + 1]),
                )
            else:
                logits, ks, vs = self._jit_prefill(
                    self.params, jnp.asarray(padded)
                )
            if self.paged:
                # position j -> (its page, its offset); padding rows past
                # the prompt carry the null page
                blocks = self._slot_blocks[slot]
                block_ids = np.zeros(self.prefill_len, np.int32)
                block_ids[:plen] = np.repeat(
                    blocks, self.kv_block_size
                )[:plen]
                offsets = (
                    np.arange(self.prefill_len, dtype=np.int32)
                    % self.kv_block_size
                )
                self._cache = self._jit_write_prefill(
                    self._cache, ks, vs,
                    jnp.asarray(block_ids), jnp.asarray(offsets),
                )
            else:
                self._cache = self._jit_write_prefill(
                    self._cache, jnp.int32(slot), ks, vs
                )
            self._key, sub = jax.random.split(self._key)
            first = self._jit_first_token(
                logits, jnp.int32(plen - 1), sub,
                jnp.full((1,), temperature, jnp.float32),
            )
            first = int(np.asarray(first)[0])
        if self.speculative:
            # the draft mirrors the slot: full prompt prefill into its
            # own contiguous cache (the draft shares no pages, and a
            # target-side prefix HIT says nothing about the draft's
            # cache). The draft is small — this rides inside TTFT
            # without moving it much, and buys every subsequent decode
            # step its k proposals.
            dpad = np.zeros((1, self.prefill_len), np.int32)
            dpad[0, :plen] = prompt_tokens
            _, dks, dvs = self._jit_draft_prefill(
                self._draft_params, jnp.asarray(dpad)
            )
            self._draft_cache = self._jit_draft_write(
                self._draft_cache, jnp.int32(slot), dks, dvs
            )
            # index lengths-1 == the last PROMPT token (already cached
            # by the draft prefill; the sync rewrite is value-identical)
            self._spec_prev_tokens[slot] = int(prompt_tokens[-1])
        if self.paged and self.prefix_cache_enabled and not self._brownout:
            # publish this prompt's full pages so later requests share
            # them (no-op for pages already in the registry; the hash
            # chain was computed once at reserve time). Skipped under
            # fleet brownout (set_brownout): a prefix MISS's speculative
            # registration work — hashing, registry churn, pages parked
            # un-freeable in the LRU — is load the degraded window can't
            # afford; cache HITS still serve suffix-only.
            self.block_pool.register_prefix(
                prompt_tokens, self._slot_blocks[slot],
                hashes=self._slot_hashes.get(slot),
            )
        # what the scheduler's sched.prefill phase says of this prefill
        # (the profiler's annotation takes it with telemetry off too)
        attrs = {
            "prompt_tokens": plen,
            "prefix_hit": prefix_len > 0,
            "prefix_len": int(prefix_len),
        }
        if prefix_len > 0:
            attrs["suffix_bucket"] = self._suffix_bucket(
                plen - prefix_len, prefix_len
            )
        adapter = self._slot_adapter_names.get(slot)
        if adapter is not None:
            attrs["adapter"] = adapter
        self._slot_trace_attrs[slot] = attrs
        self._lengths[slot] = plen
        self._last_tokens[slot] = first
        self._temps[slot] = temperature
        return first

    def prefill_trace_attrs(self, slot):
        """Scheduler hook: the span attrs captured by the slot's latest
        prefill (prefix-hit vs cold, suffix bucket, adapter name) — the
        per-phase facts only the engine knows."""
        return self._slot_trace_attrs.pop(slot, {})

    def set_brownout(self, on):
        """Fleet brownout toggle (docs/serving.md "Brownout"): while on,
        cold prefills skip cross-request prefix REGISTRATION (the
        prefix-miss speculative work) — hits keep serving suffix-only.
        A pure mode flag: no recompiles, instantly reversible."""
        self._brownout = bool(on)

    def use_tracer(self, tracer):
        """Adopt a caller-owned tracer (the fleet router injects its own
        into in-process replicas so scheduler spans land in the SAME
        trace file as the router's root spans). The tracer's lifecycle
        stays with its owner — engine.close() never closes it."""
        self.tracer = tracer
        self.scheduler._tracer = tracer

    def _suffix_bucket(self, suffix_len, prefix_len):
        """Smallest compiled suffix width that (a) holds the suffix and
        (b) keeps every PADDED row's position inside max_seq_len — a
        bucket padding past the sequence limit would clamp its garbage
        rows' block index into the slot's real last page and overwrite
        written prompt k/v. None when no bucket qualifies (the caller
        falls back to the cold full prefill)."""
        for b in self._suffix_buckets:
            if b >= suffix_len and prefix_len + b <= self.max_seq_len:
                return b
        return None

    def _prefill_suffix(self, slot, prompt_tokens, prefix_len, temperature):
        """Prefix-cache hit: prefill ``prompt[prefix_len:]`` only, padded
        to the smallest compiled suffix bucket, attending over the shared
        prefix pages — the near-zero-TTFT path for templated traffic."""
        suffix = prompt_tokens[prefix_len:]
        bucket = self._suffix_bucket(len(suffix), prefix_len)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(suffix)] = suffix
        args = (
            self.params,
            jnp.asarray(padded),
            jnp.full((1,), prefix_len, jnp.int32),
            self._cache,
            jnp.asarray(self._block_tables[slot:slot + 1]),
        )
        if self.multi_lora:
            # a hit only ever matches pages salted with this same
            # adapter, so the suffix continues the adapter's own prefix
            args = args + (
                self._adapter_pool,
                jnp.asarray(self._slot_adapters[slot:slot + 1]),
            )
        logits, self._cache = self._jit_prefill_suffix(*args)
        self._key, sub = jax.random.split(self._key)
        first = self._jit_first_token(
            logits, jnp.int32(len(suffix) - 1), sub,
            jnp.full((1,), temperature, jnp.float32),
        )
        return int(np.asarray(first)[0])

    def reset_decode_state(self):
        """Rebuild the decode-side state (KV cache or page pool, slot
        bookkeeping, block tables) from scratch; the PINNED params are
        untouched — this is the driver auto-restart path after a decode
        crash (scheduler._recover_driver_crash), a cache re-init rather
        than a weight reload."""
        self._cache = jax.device_put(
            self._init_cache_host(), self._cache_sharding
        )
        if self.paged:
            # the pool's pages (and any cached prefixes) died with the
            # cache contents: fresh allocator, nulled tables
            self.block_pool = BlockPool(
                self.kv_pool_blocks, self.kv_block_size,
                spill_fn=(
                    self._spill_kv_page if self.host_tier is not None
                    else None
                ),
            )
            self._reclaimed_synced = 0
            self._block_tables[:] = NULL_BLOCK
            self._slot_blocks.clear()
            self._slot_prefix_len.clear()
            self._slot_hashes.clear()
            self._sync_pool_metrics()
        if self.speculative:
            # the draft's cache died with the crashed step too; its
            # params, like the target's, never left device
            self._draft_cache = jax.device_put(
                init_kv_cache(
                    self.draft_config, self.num_slots, self.max_seq_len,
                    self.compute_dtype,
                ),
                self._draft_cache_sharding,
            )
            self._spec_prev_tokens[:] = 0
        self._lengths[:] = 0
        self._last_tokens[:] = 0
        if self.multi_lora:
            # adapter WEIGHTS survive a decode crash (the pool is pinned
            # state like the params, not KV garbage); only the slot pins
            # die with the fail-finished in-flight requests — which
            # _recover_driver_crash already released via release_slot
            self._slot_adapters[:] = 0
            self._slot_adapter_names.clear()
        log_dist(
            "inference decode state reset from pinned params "
            "(driver restart)", ranks=[0],
        )

    def decode_tokens(self, active_slots):
        """One fixed-shape decode step over ALL slots; commits length /
        last-token bookkeeping for ``active_slots`` and returns their
        sampled tokens as host ints (same order). On a SPECULATIVE
        engine each entry is instead a LIST of 1..k+1 committed tokens
        (the accepted draft prefix plus the target's correction) — the
        scheduler commits them in order."""
        # fault site: decode-driver crash (resilience/faults.py) — raises
        # through the scheduler's step, exercising the auto-restart path
        self.resilience.faults.maybe_raise("decode.step")
        if self.speculative:
            return self._decode_tokens_spec(active_slots)
        self._key, sub = jax.random.split(self._key)
        args = (
            self.params,
            jnp.asarray(self._last_tokens),
            jnp.asarray(self._lengths),
            jnp.asarray(self._temps),
            sub,
            self._cache,
        )
        if self.paged:
            args = args + (jnp.asarray(self._block_tables),)
        if self.multi_lora:
            # per-slot adapter ids: an index ARRAY like the block tables,
            # so slots mixing any adapters never change the program
            args = args + (
                self._adapter_pool, jnp.asarray(self._slot_adapters),
            )
        next_tokens, self._cache = self._jit_decode(*args)
        next_tokens = np.asarray(next_tokens)
        out = []
        for slot in active_slots:
            token = int(next_tokens[slot])
            self._lengths[slot] += 1
            self._last_tokens[slot] = token
            out.append(token)
        return out

    def _decode_tokens_spec(self, active_slots):
        """One speculative decode cycle (docs/inference.md "Speculative
        decoding"): the draft proposes ``k`` greedy tokens per slot
        (one scanned program), the target verifies all of them in ONE
        fixed-shape batched step against the paged cache, and the
        accepted prefix plus the target's correction token commit —
        every committed token is the target's own argmax, so greedy
        output is bitwise-identical to the sequential path by
        construction. Returns one token LIST per active slot.

        Cache hygiene needs no rollback on rejection: rejected
        proposals' k/v sit at positions BEYOND the committed length, so
        the causal position mask hides them until the next cycle's
        verify (target) / propose (draft) overwrites those same rows —
        the dead-slot ride-along argument applied forward in time."""
        k = self.spec_k
        proposed = k * len(active_slots)
        with phase("sched.spec_draft", proposed=proposed):
            props, self._draft_cache = self._jit_draft_propose(
                self._draft_params,
                jnp.asarray(self._spec_prev_tokens),
                jnp.asarray(self._last_tokens),
                jnp.asarray(self._lengths),
                self._draft_cache,
            )
            props = np.asarray(props)  # [slots, k]
        with phase("sched.spec_verify", proposed=proposed):
            # verify tokens per slot: [last, d_1 .. d_k] — row i's argmax
            # is the target's next token after consuming verify token i
            verify_tokens = np.concatenate(
                [self._last_tokens[:, None], props], axis=1
            ).astype(np.int32)
            args = (
                self.params,
                jnp.asarray(verify_tokens),
                jnp.asarray(self._lengths),
                self._cache,
                jnp.asarray(self._block_tables),
            )
            if self.multi_lora:
                args = args + (
                    self._adapter_pool, jnp.asarray(self._slot_adapters),
                )
            greedy, self._cache = self._jit_spec_verify(*args)
            greedy = np.asarray(greedy)  # [slots, k+1]
        out = []
        accepted = committed = 0
        with phase("sched.spec_commit") as commit:
            for slot in active_slots:
                g, pr = greedy[slot], props[slot]
                j = 0
                while j < k and pr[j] == g[j]:
                    j += 1
                # d_1..d_j matched the target's own choices; g[j] is the
                # target's token at the first divergence (the BONUS token
                # when everything matched)
                toks = [int(t) for t in pr[:j]] + [int(g[j])]
                self._lengths[slot] += len(toks)
                # token at the new index lengths-1: the burst's second-
                # to-last commit, or the previous last for a 1-token
                # burst — what the next propose's sync step re-feeds
                self._spec_prev_tokens[slot] = (
                    toks[-2] if len(toks) >= 2 else self._last_tokens[slot]
                )
                self._last_tokens[slot] = toks[-1]
                accepted += j
                committed += len(toks)
                out.append(toks)
            commit.set_attr("accepted", accepted)
            commit.set_attr("committed", committed)
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(accepted)
        total = self._spec_proposed.value
        self._spec_rate.set(
            self._spec_accepted.value / total if total else 0.0
        )
        return out

    # -- serving API ----------------------------------------------------
    def submit(self, prompt_tokens, **kwargs):
        """Front-door submission; see
        :meth:`ContinuousBatchingScheduler.submit`."""
        return self.scheduler.submit(prompt_tokens, **kwargs)

    def load_snapshot(self):
        """Router-facing load/health view; see
        :meth:`ContinuousBatchingScheduler.load_snapshot`."""
        return self.scheduler.load_snapshot()

    def generate(self, prompts, max_new_tokens=32, temperature=None,
                 eos_token_id=None, adapter=None):
        """Synchronous batch generation: submit every prompt (token-id
        lists), drive the scheduler until all finish, return the
        generated token-id lists in prompt order. ``adapter`` names a
        loaded LoRA adapter applied to every prompt (None = base
        model)."""
        requests = []
        try:
            for p in prompts:
                requests.append(self.submit(
                    p, max_new_tokens=max_new_tokens,
                    temperature=temperature, eos_token_id=eos_token_id,
                    adapter=adapter,
                ))
        except Exception:
            # a rejected/invalid later prompt must not orphan the earlier
            # submissions in the queue (they would burn decode work on a
            # future call with nobody holding their handles)
            for r in requests:
                r.cancel()
            raise
        if self.scheduler.driving:
            # a serve_forever thread owns the step loop — driving it from
            # this thread too would race the slot table and the donated
            # cache buffers; just wait for the server to finish ours
            results = [r.result() for r in requests]
        else:
            self.scheduler.run_until_idle()
            results = [r.result() for r in requests]
        for r in requests:
            if r.finish_reason in ("cancelled", "error"):
                # a crashed driver / concurrent close() fail-finished the
                # request mid-flight; partial tokens must not masquerade
                # as a completed generation. A "deadline" finish is NOT an
                # error: the partial tokens are the contract's answer.
                raise RuntimeError(
                    f"generation {r.finish_reason} after {len(r.tokens)} "
                    f"of up to {r.max_new_tokens} tokens (scheduler shut "
                    "down, or its decode driver crashed past the restart "
                    "budget)"
                )
        return results

    def serve_forever(self):
        return self.scheduler.serve_forever()

    def close(self):
        self.scheduler.shutdown()
        if self.host_tier is not None:
            # drop this engine's share-group reference; the LAST engine
            # out closes the tier's stager thread and retires the group
            self.host_tier.release()
            self.host_tier = None
        if self.telemetry.enabled:
            self.telemetry.export()
            self.telemetry.close()


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
    """Build a serving engine around ``model`` (reference analog: the
    training-side ``deepspeed.initialize``; early DeepSpeed had no
    inference entry point — PAPER.md stops at training).

    ``config`` is a dict or JSON path whose ``"inference"`` block sizes
    the engine (docs/inference.md); ``model_parameters`` provides the
    parameter pytree (overwritten in place of value — not structure —
    when ``inference.checkpoint.load_dir`` names a checkpoint to serve).
    ``draft_model``/``draft_parameters`` supply the DRAFT for
    speculative decoding (required when the ``inference.speculative``
    block is configured; ``speculative.draft_checkpoint`` optionally
    replaces the draft parameters through the verified-load path).
    Returns an :class:`InferenceEngine`.
    """
    return InferenceEngine(
        model=model,
        config=config,
        model_parameters=model_parameters,
        mesh=mesh,
        param_specs=param_specs,
        rng_seed=rng_seed,
        draft_model=draft_model,
        draft_parameters=draft_parameters,
    )
