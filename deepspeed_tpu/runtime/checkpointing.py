"""Checkpoint save/load with elastic data-parallel resharding and a
crash-safe commit protocol.

File-layout parity with the reference (reference:
deepspeed/pt/deepspeed_light.py:1095-1360):

  <dir>/<tag>/mp_rank_{MP:02d}_model_states.msgpack   — module params,
      lr-scheduler state, loss-scale state, step counters, dp/mp world
      sizes, client state (the reference's extra dict keys ride along).
  <dir>/<tag>/zero_pp_rank_{DP}_mp_rank_{MP:02d}optim_states.msgpack
      — this dp rank's shard of the optimizer state (one file at stage 0).
  <dir>/<tag>/MANIFEST.json                           — per-file sha256
      commit record (resilience/manifest.py; absent on legacy saves).
  <dir>/latest                                        — tag pointer.

Commit protocol (deepspeed_tpu/resilience/, docs/resilience.md): every
file is written tmp + fsync + ``os.replace``; after the cross-host
barrier, process 0 hashes the completed directory into ``MANIFEST.json``
(written last, atomically), re-verifies it, and only then publishes the
``latest`` pointer — so a kill at ANY instant leaves either the previous
checkpoint or a complete new one, never a torn one. The reference's
barrier-then-tag sequencing (deepspeed_light.py:1315-1360) protected
against racing writers but not against torn writes or mid-save kills.

Loads are TRANSACTIONAL: every file is read and parsed into host memory
(manifest-verified first when present) before a single engine field
mutates — a truncated optimizer shard can no longer leave the engine
half-loaded. When the ``latest``-driven tag is corrupt or missing, the
load walks back to the newest valid tag instead of crashing.

Elastic semantics (the subtlest part of the reference,
deepspeed_zero_optimizer.py:1360-1538 / zero_optimizer_stage1.py:821-996):
a ZeRO checkpoint saved at dp world size N can be loaded at a different dp
size M. Here that falls out of the sharding design: each optimizer-state
leaf records which axis was sharded over the ``data`` mesh axis; on save
the leaf is sliced into N pieces along that axis (one per file), on load
ALL saved pieces are concatenated back to the full leaf and ``device_put``
with the *current* mesh's shardings — merge-and-reshard with no
alignment-padding bookkeeping, because leaves are never flattened.

Master weights are always saved in fp32 (the engine keeps fp32 masters), so
``load_from_fp32_weights`` (reference deepspeed_light.py:311-312) is
implicitly the lossless path.
"""

import logging
import os
import time

import jax
import numpy as np
from flax import serialization

from ..parallel import mesh as mesh_lib
from ..resilience import atomic_io
from ..resilience import manifest as manifest_lib
from ..resilience import retention
from ..resilience.manager import ResilienceManager
from ..telemetry.registry import count_suppressed
from ..utils.logging import log_dist, warn_once

MODEL_FILE = "mp_rank_{mp:02d}_model_states.msgpack"
OPTIM_FILE = "zero_pp_rank_{dp}_mp_rank_{mp:02d}optim_states.msgpack"
LATEST_FILE = "latest"

# engines built before the resilience wiring (or bare test doubles) share
# one default-policy manager rather than growing one per call
_default_manager = None


def _resilience_of(engine):
    global _default_manager
    manager = getattr(engine, "resilience", None)
    if manager is not None:
        return manager
    if _default_manager is None:
        _default_manager = ResilienceManager()
    return _default_manager


def _write_blob(res, path, data):
    """One checkpoint file write under the active protocol: atomic +
    fsynced + retried when resilience is enabled, the legacy bare write
    otherwise. The ``checkpoint.write`` fault site fires INSIDE the
    retried operation — injected storage flakes exercise the same
    backoff/escalation path a real one would."""
    def op():
        res.faults.maybe_raise("checkpoint.write")
        atomic_io.atomic_write_bytes(path, data, fsync=res.fsync)

    if res.enabled:
        res.retrying(op, op_name=f"write:{os.path.basename(path)}")
    else:
        res.faults.maybe_raise("checkpoint.write")
        with open(path, "wb") as f:
            f.write(data)


def _read_blob(res, path):
    def op():
        res.faults.maybe_raise("checkpoint.read")
        return atomic_io.read_bytes(path)

    if res.enabled:
        return res.retrying(op, op_name=f"read:{os.path.basename(path)}")
    res.faults.maybe_raise("checkpoint.read")
    with open(path, "rb") as f:
        return f.read()


_FLAT_BLOCK = 2048  # elements per scale in the flat format of before PR 27


def _flat_moment_twin(template_tree):
    """``template_tree`` as a checkpoint written before PR 27 holds it:
    every first-moment leaf of an int8 engine a flat ``{'q','scale'}``
    pair (placeholders: only the structure is read)."""
    from ..ops.quant import is_quantized

    def twin(node):
        if isinstance(node, dict) and "inner" in node:
            return {**node, "inner": twin(node["inner"])}
        return {
            **node,
            "mu": jax.tree_util.tree_map(
                lambda m: {"q": 0, "scale": 0}, node["mu"],
                is_leaf=is_quantized,
            ),
        }

    return twin(template_tree)


def _moments_to_template(saved_tree, template_tree):
    """Bring saved int8 moments into the engine template's format.

    Until PR 27 a quantized moment was stored FLAT over the flattened
    parameter: ``q`` int8[nb * 2048] and ``scale`` f32[nb], the block count
    padded to a policy's multiple, a zero tail behind the data. Today's
    leaf has the parameter's shape (ops/quant.py) or, for a leaf with no
    run to quantize over, is a bf16 array. A flat pair is decoded to
    float32, cut to the parameter's size and encoded again as the
    template stores it; leaves already in that format pass through."""
    import jax.numpy as jnp

    from ..ops.quant import encode_moment, is_quantized

    if saved_tree is None:
        return None

    def fit(saved, tmpl):
        if not (isinstance(saved, dict) and set(saved) == {"q", "scale"}):
            return saved
        q, scale = np.asarray(saved["q"]), np.asarray(saved["scale"])
        shape = tuple(tmpl["q"].shape if is_quantized(tmpl) else tmpl.shape)
        if is_quantized(tmpl) and q.shape == shape:
            return saved
        value = q.reshape(-1, _FLAT_BLOCK).astype(np.float32) * scale[:, None]
        value = value.reshape(-1)[: int(np.prod(shape))].reshape(shape)
        return jax.tree_util.tree_map(
            np.asarray, encode_moment(jnp.asarray(value), tmpl)
        )

    return jax.tree_util.tree_map(
        fit, saved_tree, template_tree,
        is_leaf=lambda x: isinstance(x, dict) and set(x) == {"q", "scale"},
    )


def _rng_key_host(engine):
    """The engine's RNG key chain as a host array (typed keys serialize
    their key_data), or None for engines without one. Persisting the
    chain makes a resume — and the supervisor's in-process rollback —
    bitwise-reproducible: the replayed run splits the exact keys the
    original would have."""
    rng = getattr(engine, "_rng", None)
    if rng is None:
        return None
    try:
        import jax.numpy as jnp

        if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(rng))
    except Exception as e:  # pragma: no cover - key API drift
        count_suppressed("checkpointing.rng_key_host", e)
    return np.asarray(rng)


def _restore_rng_key(engine, data):
    """Adopt a checkpoint's ``rng_key`` into the engine, matching the
    engine's current key flavor (typed rbg keys on TPU, raw PRNGKey
    arrays elsewhere). A mismatched key (checkpoint from a different
    backend's impl) keeps the engine's current RNG with a warning rather
    than failing the whole load — only replay bitwiseness is lost."""
    cur = getattr(engine, "_rng", None)
    if cur is None:
        return
    import jax.numpy as jnp

    arr = np.asarray(data)
    try:
        if jnp.issubdtype(cur.dtype, jax.dtypes.prng_key):
            cur_data = jax.random.key_data(cur)
            if tuple(arr.shape) != tuple(cur_data.shape):
                raise ValueError(
                    f"saved key data shape {arr.shape} != engine key "
                    f"shape {tuple(cur_data.shape)}"
                )
            engine._rng = jax.random.wrap_key_data(
                jnp.asarray(arr, cur_data.dtype),
                impl=jax.random.key_impl(cur),
            )
        else:
            if tuple(arr.shape) != tuple(np.asarray(cur).shape):
                raise ValueError(
                    f"saved key shape {arr.shape} != engine key shape "
                    f"{tuple(np.asarray(cur).shape)}"
                )
            engine._rng = jnp.asarray(arr, cur.dtype)
    except Exception as e:
        warn_once(
            "rng-key-restore-failed",
            "checkpoint rng_key could not be adopted (%s); keeping the "
            "engine's current RNG — the resumed/rolled-back run will not "
            "replay bitwise", e,
        )


def _data_axis_of(leaf):
    """Index of the dim sharded over the data axis, or -1 if replicated."""
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return -1
    for i, entry in enumerate(spec):
        names = entry if isinstance(entry, tuple) else (entry,)
        if mesh_lib.DATA_AXIS in [n for n in names if n]:
            return i
    return -1


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _to_host(leaf):
    """Fetch a (possibly multi-host-sharded) array to host memory."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
    return np.asarray(jax.device_get(leaf))


def _barrier(name):
    """Cross-host barrier (reference sequences checkpoint writers with
    dist barriers, deepspeed_light.py:1315-1324). No-op single-process."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def _canonical_opt_state(engine):
    """The checkpoint's optimizer-state tree: {"master", "inner"} whenever
    an fp32 master distinct from the module file exists. Master-mode
    engines hold this shape already; bf16/fp16 engines without master
    mode (dp=1) synthesize it from their fp32 params so a later
    master-mode load resumes exactly. Pure-fp32 engines save the bare
    inner tree — their module file IS the master, and the load path's
    legacy branch re-derives it, so duplicating ~4 bytes/param into the
    optim shards would buy nothing."""
    import jax.numpy as jnp

    if getattr(engine, "master_in_opt", False):
        return engine.optimizer_state
    if engine.compute_dtype == jnp.float32:
        return engine.optimizer_state  # bare inner (legacy layout)
    master = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32), engine.params
    )
    return {"master": master, "inner": engine.optimizer_state}


def save_checkpoint(engine, save_dir, tag=None, client_state=None):
    """Multi-host write discipline (reference deepspeed_light.py:1282-1360)
    hardened into a commit protocol: process 0 writes the model-states
    file; optimizer shard files are distributed round-robin over processes
    (the analog of every dp rank writing its own zero_pp_rank file);
    everyone barriers; process 0 then writes + verifies ``MANIFEST.json``
    and only afterwards publishes the ``latest`` tag — so the tag never
    points at a half-written OR torn checkpoint. Raises
    :class:`~deepspeed_tpu.resilience.CheckpointCorruptionError` when the
    post-save verification fails (the tag is not published)."""
    res = _resilience_of(engine)
    started = time.monotonic()
    if tag is None:
        tag = f"global_step{engine.global_steps}"
    mp_rank = 0  # tensor-parallel state is global under GSPMD: one file
    proc = jax.process_index()
    n_proc = jax.process_count()
    ckpt_dir = os.path.join(save_dir, str(tag))
    os.makedirs(ckpt_dir, exist_ok=True)

    # ---- model states file (process 0 only) -------------------------
    params_np = jax.tree_util.tree_map(_to_host, engine.params)
    scaler = engine.loss_scale_state
    state = {
        "module": serialization.to_state_dict(params_np),
        "global_steps": engine.global_steps,
        "skipped_steps": engine.skipped_steps,
        "micro_steps": engine.micro_steps,
        "dp_world_size": engine.dp_world_size,
        "mp_world_size": engine.mp_world_size,
        "zero_stage": engine.zero_stage,
        "loss_scaler": {
            "loss_scale": float(scaler.loss_scale),
            "good_steps": int(scaler.good_steps),
            "hysteresis": int(scaler.hysteresis),
        },
        "lr_scheduler": (
            engine.lr_scheduler.state_dict()
            if engine.lr_scheduler is not None
            and hasattr(engine.lr_scheduler, "state_dict")
            else None
        ),
        "client_state": client_state or {},
    }
    rng_key = _rng_key_host(engine)
    if rng_key is not None:
        # the RNG key chain rides in the model-states file so resumes and
        # supervisor rollbacks replay bitwise (ignored by older readers)
        state["rng_key"] = rng_key
    if proc == 0:
        model_path = os.path.join(ckpt_dir, MODEL_FILE.format(mp=mp_rank))
        _write_blob(res, model_path, serialization.msgpack_serialize(state))

    # ---- optimizer shard files (round-robin over processes) ---------
    # Gather ONE leaf at a time and slice it into every owned rank's
    # payload immediately: peak host memory is one full leaf, not the whole
    # optimizer state (which ZeRO sharded precisely because it doesn't fit
    # in one place). Production multi-host pods should still prefer
    # addressable-shard streaming writers; process_allgather here is the
    # correct-but-chatty fallback.
    #
    # The on-disk layout is CANONICAL regardless of the engine's in-memory
    # placement: {"master": fp32 weights, "inner": optimizer moments} —
    # the reference's fp32-partitions-in-optim-files layout
    # (deepspeed_light.py:1355-1360, load_from_fp32_weights). Engines
    # without master_in_opt synthesize the master from their fp32 params,
    # so a checkpoint saved at dp=1 (no master mode) loads at dp=8 (master
    # mode) and vice versa.
    leaves, _ = _flatten(_canonical_opt_state(engine))
    axes = [_data_axis_of(l) for l in leaves]
    dp = engine.dp_world_size if engine.zero_stage >= 1 else 1
    owned_ranks = [r for r in range(dp) if r % n_proc == proc]
    rank_leaves = {r: [] for r in owned_ranks}
    splittable = []
    for leaf, ax in zip(leaves, axes):
        arr = _to_host(leaf)
        can_split = bool(ax >= 0 and dp > 1 and arr.shape[ax] % dp == 0)
        splittable.append(can_split)
        for rank in owned_ranks:
            if can_split:
                # copy: array_split returns VIEWS that would pin the full
                # gathered leaf, defeating the leaf-at-a-time peak-memory
                # bound this loop exists for
                rank_leaves[rank].append(
                    np.ascontiguousarray(np.array_split(arr, dp, axis=ax)[rank])
                )
            else:
                # replicated (or unsplittable) leaves ride in rank 0 only
                rank_leaves[rank].append(arr if rank == 0 else np.zeros((0,)))
        del arr
    for rank in owned_ranks:
        payload = {
            "num_shards": dp,
            "shard_axes": [int(a) for a in axes],
            "splittable": splittable,
            "leaves": {str(i): a for i, a in enumerate(rank_leaves[rank])},
        }
        path = os.path.join(ckpt_dir, OPTIM_FILE.format(dp=rank, mp=mp_rank))
        _write_blob(res, path, serialization.msgpack_serialize(payload))

    # every writer finishes before the tag becomes visible
    _barrier(f"ckpt_save_{tag}")
    if proc == 0:
        if res.enabled:
            # commit record LAST: hash the completed directory, publish
            # the manifest atomically, then re-verify the whole checkpoint
            # from disk before the tag becomes reachable
            manifest_lib.write_manifest(
                ckpt_dir, tag,
                meta={"global_steps": int(engine.global_steps)},
                fsync=res.fsync, retry=res.retry, on_retry=res.on_retry,
            )
            status, reason = manifest_lib.verify_checkpoint(ckpt_dir)
            if status != manifest_lib.VALID:
                raise manifest_lib.CheckpointCorruptionError(
                    f"post-save verification of {ckpt_dir} failed "
                    f"({reason}); 'latest' not published — the previous "
                    "checkpoint remains the resume point"
                )
            res.retrying(
                lambda: atomic_io.atomic_write_text(
                    os.path.join(save_dir, LATEST_FILE), str(tag),
                    fsync=res.fsync,
                ),
                op_name="publish_latest",
            )
        else:
            with open(os.path.join(save_dir, LATEST_FILE), "w") as f:
                f.write(str(tag))
        if res.enabled and res.keep_last_n > 0:
            retention.prune_checkpoints(
                save_dir, res.keep_last_n, protect={str(tag)},
                on_delete=res.count_pruned,
            )
    res.observe_save(started)
    log_dist(f"Saved checkpoint {tag} to {save_dir}", ranks=[0])
    return True


# ---------------------------------------------------------------------------
# load: stage (parse everything on host) -> apply (mutate the engine)
# ---------------------------------------------------------------------------
class _Staged:
    """Host-side parse of one checkpoint candidate: nothing here has
    touched the engine yet."""

    __slots__ = ("tag", "ckpt_dir", "state", "shards")

    def __init__(self, tag, ckpt_dir, state, shards):
        self.tag = tag
        self.ckpt_dir = ckpt_dir
        self.state = state
        self.shards = shards  # list of shard payloads, or None


def _stage_checkpoint(load_dir, tag, load_optimizer_states, res):
    """Read and parse EVERY file of checkpoint ``tag`` into host memory.

    Raises on any verification/read/parse failure — the caller decides
    whether that means fallback (latest-driven load) or a failed load
    (explicitly requested tag). The engine is untouched either way.
    """
    ckpt_dir = os.path.join(load_dir, str(tag))
    mp_rank = 0
    if res.enabled and res.verify_on_load:
        status, reason = manifest_lib.verify_checkpoint(ckpt_dir)
        if status in (manifest_lib.CORRUPT, manifest_lib.MISSING):
            raise manifest_lib.CheckpointCorruptionError(
                f"checkpoint {tag}: {reason}"
            )
        if status == manifest_lib.LEGACY:
            warn_once(
                ("legacy-checkpoint", ckpt_dir),
                "checkpoint %s has no manifest (pre-resilience save); "
                "loading with parse-time validation only", ckpt_dir,
            )
    model_path = os.path.join(ckpt_dir, MODEL_FILE.format(mp=mp_rank))
    if not os.path.exists(model_path):
        raise manifest_lib.CheckpointCorruptionError(
            f"checkpoint {tag}: model-states file {model_path} not found"
        )
    state = serialization.msgpack_restore(_read_blob(res, model_path))

    shards = None
    if load_optimizer_states:
        saved_dp = (
            int(state["dp_world_size"]) if state["zero_stage"] >= 1 else 1
        )
        rank0_path = os.path.join(
            ckpt_dir, OPTIM_FILE.format(dp=0, mp=mp_rank)
        )
        if os.path.exists(rank0_path):
            shards = []
            for rank in range(saved_dp):
                p = os.path.join(
                    ckpt_dir, OPTIM_FILE.format(dp=rank, mp=mp_rank)
                )
                if not os.path.exists(p):
                    # saved with fewer shard files (e.g. stage 0): stop
                    break
                shards.append(serialization.msgpack_restore(_read_blob(res, p)))
            num_shards = int(shards[0]["num_shards"])
            if len(shards) < num_shards:
                # the payload itself declares how many rank files a
                # complete save produces; fewer on disk means a kill
                # between shard writes (legacy save) or deleted files —
                # merging a partial set would concatenate short leaves
                raise manifest_lib.CheckpointCorruptionError(
                    f"checkpoint {tag}: optimizer state declares "
                    f"{num_shards} shard files but only {len(shards)} "
                    "are present"
                )
    return _Staged(str(tag), ckpt_dir, state, shards)


def _apply_checkpoint(
    engine, staged, load_optimizer_states, load_lr_scheduler_states
):
    """Mutate the engine from a fully staged checkpoint. Every input was
    already parsed on host, so no file I/O (and no torn-state abort path)
    exists past this point."""
    state = staged.state
    # ---- module params ----------------------------------------------
    params_np = serialization.from_state_dict(
        jax.tree_util.tree_map(np.asarray, engine.params), state["module"]
    )
    engine.params = jax.device_put(
        jax.tree_util.tree_map(
            # keep the engine's storage dtype (compute dtype when the fp32
            # master lives in the optimizer state, fp32 otherwise)
            lambda p, cur: np.asarray(p, cur.dtype),
            params_np, engine.params,
        ),
        engine._param_shardings,
    )
    # ---- counters / scaler / scheduler ------------------------------
    engine.global_steps = int(state["global_steps"])
    engine.skipped_steps = int(state["skipped_steps"])
    engine.micro_steps = int(state["micro_steps"])
    # saves reconcile first (keep_last=False), so the persisted
    # global_steps IS the settled count — resync the monitor step index
    engine._settled_steps = engine.global_steps
    import jax.numpy as jnp

    sc = state["loss_scaler"]
    engine.loss_scale_state = engine._place_scaler(
        engine.loss_scale_state._replace(
            loss_scale=jnp.float32(sc["loss_scale"]),
            good_steps=jnp.int32(sc["good_steps"]),
            hysteresis=jnp.int32(sc["hysteresis"]),
        )
    )
    # RNG key chain (absent on pre-PR5 checkpoints: the engine keeps its
    # current chain and only replay bitwiseness is lost)
    if state.get("rng_key") is not None:
        _restore_rng_key(engine, state["rng_key"])
    if (
        load_lr_scheduler_states
        and state.get("lr_scheduler") is not None
        and engine.lr_scheduler is not None
        and hasattr(engine.lr_scheduler, "load_state_dict")
    ):
        engine.lr_scheduler.load_state_dict(state["lr_scheduler"])

    # ---- optimizer state: merge all saved shards, reshard -----------
    # On-disk layout is the canonical {"master", "inner"} tree (see
    # save_checkpoint); adapt it to the engine's in-memory placement so
    # checkpoints cross master/non-master layouts (dp=1 <-> dp>1, bf16 <->
    # fp32) as well as dp sizes.
    master_restored = False
    if load_optimizer_states:
        if getattr(engine, "master_in_opt", False):
            inner_template = engine.optimizer_state["inner"]
        else:
            inner_template = engine.optimizer_state
        canonical_template = {
            "master": jax.tree_util.tree_map(np.asarray, engine.params),
            "inner": inner_template,
        }
        canonical = None
        shards = staged.shards
        if shards:
            num_shards = int(shards[0]["num_shards"])
            axes = shards[0]["shard_axes"]
            splittable = shards[0]["splittable"]
            n_saved = len(shards[0]["leaves"])

            def merge(i):
                ax, can_split = int(axes[i]), bool(splittable[i])
                if can_split and num_shards > 1:
                    pieces = [np.asarray(s["leaves"][str(i)]) for s in shards]
                    return np.concatenate(pieces, axis=ax)
                return np.asarray(shards[0]["leaves"][str(i)])

            # the moments' format of before PR 27 has another leaf count
            # wherever a leaf now keeps a bf16 moment: tried second
            templates = [canonical_template]
            if "mu" in inner_template:
                templates.append(_flat_moment_twin(canonical_template))
            structures = [
                (
                    jax.tree_util.tree_structure(template),
                    jax.tree_util.tree_structure(template["inner"]),
                )
                for template in templates
            ]
            match = next(
                (pair for pair in structures
                 if n_saved in (pair[0].num_leaves, pair[1].num_leaves)),
                None,
            )
            if match is not None:
                whole, inner = match
                merged = [merge(i) for i in range(n_saved)]
                if n_saved == whole.num_leaves:
                    canonical = jax.tree_util.tree_unflatten(whole, merged)
                    master_restored = True
                else:
                    # legacy layout: bare inner tree, no master partition —
                    # restore moments, master re-derives from module weights
                    canonical = {
                        "master": None,
                        "inner": jax.tree_util.tree_unflatten(inner, merged),
                    }
            if canonical is None:
                log_dist(
                    f"optimizer checkpoint has {n_saved} leaves; engine "
                    f"expects {structures[0][0].num_leaves} (or legacy "
                    f"{structures[0][1].num_leaves}) — "
                    "skipping optimizer restore",
                    ranks=[0],
                )
        if canonical is not None:
            canonical["inner"] = _moments_to_template(
                canonical["inner"], inner_template
            )
            if engine.master_in_opt:
                inner_dev = jax.device_put(
                    canonical["inner"], engine._opt_shardings["inner"]
                )
                if master_restored:
                    master_dev = jax.device_put(
                        canonical["master"], engine._opt_shardings["master"]
                    )
                    engine.optimizer_state = {
                        "master": master_dev, "inner": inner_dev,
                    }
                else:
                    engine.optimizer_state = {
                        "master": engine.optimizer_state["master"],
                        "inner": inner_dev,
                    }
            else:
                engine.optimizer_state = jax.device_put(
                    canonical["inner"], engine._opt_shardings
                )
                if master_restored:
                    # exact fp32 resume: the master partition overrides the
                    # (possibly down-cast) module weights — the reference's
                    # load_from_fp32_weights=True path.  Dtype source is the
                    # ENGINE's storage dtype (engine.params, fp32 for
                    # non-master engines), NOT the module file's dtype —
                    # a bf16 module file from a master-mode save must not
                    # truncate this engine's fp32 storage.
                    engine.params = jax.device_put(
                        jax.tree_util.tree_map(
                            lambda m, cur: np.asarray(m).astype(cur.dtype),
                            canonical["master"], engine.params,
                        ),
                        engine._param_shardings,
                    )

    if getattr(engine, "master_in_opt", False) and not master_restored:
        # no fp32 master came from disk (model-only checkpoint, legacy
        # layout, or load_optimizer_states=False): derive it from the
        # loaded module weights so the next step cannot silently publish
        # init-time values (reference load_from_fp32_weights=False path,
        # deepspeed_light.py:1214-1222)
        engine.optimizer_state = {
            "master": jax.device_put(
                jax.tree_util.tree_map(
                    lambda p: np.asarray(p, np.float32), params_np
                ),
                engine._opt_shardings["master"],
            ),
            "inner": engine.optimizer_state["inner"],
        }


def _stage_with_fallback(load_dir, tag, load_optimizer_states, res):
    """Resolve ``tag`` (None => the 'latest' pointer), walk candidates
    newest-first on corruption, stage the first loadable one entirely on
    host, and agree on the staged tag across hosts. The shared verified-
    load front half: the training engine's ``load_checkpoint`` applies the
    result to engine state; the inference engine's ``load_module_state``
    maps only the module tree. Returns a ``_Staged`` or None."""
    explicit_tag = tag is not None
    if tag is None:
        latest = os.path.join(load_dir, LATEST_FILE)
        if not os.path.exists(latest):
            log_dist(f"No 'latest' file in {load_dir}", ranks=[0])
            return None
        # same retry discipline as every other checkpoint read: one
        # transient flake on the pointer must not fail the whole resume
        if res.enabled:
            tag = res.retrying(
                lambda: atomic_io.read_text(latest), op_name="read:latest"
            ).strip()
        else:
            tag = atomic_io.read_text(latest).strip()

    # ---- candidate order --------------------------------------------
    # The requested tag first; for latest-driven loads with fallback
    # enabled, every other tag in the directory follows, newest first —
    # corruption then degrades the resume point instead of killing the
    # job. An EXPLICITLY requested tag never silently substitutes.
    candidates = [str(tag)]
    if not explicit_tag and res.enabled and res.fallback_on_corruption:
        candidates += [
            t for t in manifest_lib.ordered_tags(load_dir)
            if t != str(tag)
        ]

    staged = None
    for candidate in candidates:
        try:
            staged = _stage_checkpoint(
                load_dir, candidate, load_optimizer_states, res
            )
            break
        except Exception as e:
            level = (
                logging.ERROR
                if candidate == str(tag)
                else logging.WARNING
            )
            log_dist(
                f"checkpoint {candidate} in {load_dir} is not loadable: "
                f"{e}",
                ranks=[0], level=level,
            )
            res.count_corruption_fallback()
            continue
    if staged is None:
        log_dist(
            f"no loadable checkpoint found in {load_dir} "
            f"(tried {len(candidates)} candidate tag(s))",
            ranks=[0], level=logging.ERROR,
        )
        return None
    if staged.tag != str(tag):
        log_dist(
            f"FALLBACK: checkpoint {tag} was corrupt/missing; resuming "
            f"from newest valid tag {staged.tag}",
            ranks=[0], level=logging.WARNING,
        )

    # ---- cross-host agreement on the resume tag ---------------------
    # The candidate walk is per-process; on a flaky shared mount hosts
    # can see DIFFERENT corruption (stale attribute caches, partial
    # visibility) and stage different tags — silently training on from
    # mixed checkpoints. All hosts compare their staged tag and, on any
    # mismatch, every host fails the load identically (the allgather
    # gives all ranks the same view, so the outcome is consistent).
    if jax.process_count() > 1:
        import hashlib

        from jax.experimental import multihost_utils

        digest = hashlib.sha256(staged.tag.encode()).digest()[:8]
        mine = np.frombuffer(digest, dtype=np.int64)
        everyone = multihost_utils.process_allgather(mine)
        if len(np.unique(everyone.reshape(-1))) > 1:
            log_dist(
                f"checkpoint tag disagreement across hosts (this host "
                f"staged {staged.tag}); failing the load on every rank — "
                "inspect the shared filesystem and retry",
                ranks=[-1], level=logging.ERROR,
            )
            return None
    return staged


def load_checkpoint(
    engine, load_dir, tag=None, load_optimizer_states=True,
    load_lr_scheduler_states=True,
):
    res = _resilience_of(engine)
    started = time.monotonic()
    staged = _stage_with_fallback(load_dir, tag, load_optimizer_states, res)
    if staged is None:
        return None, {}

    # ---- transactional apply ----------------------------------------
    # everything parsed; only now does the engine mutate
    _apply_checkpoint(
        engine, staged, load_optimizer_states, load_lr_scheduler_states
    )

    res.observe_load(started)
    log_dist(f"Loaded checkpoint {staged.tag} from {load_dir}", ranks=[0])
    return (
        os.path.join(staged.ckpt_dir, ""),
        staged.state.get("client_state", {}),
    )


def load_module_state(load_dir, params_template, tag=None, resilience=None):
    """Verified MODEL-state load for serving (the init_inference() param
    path): the same manifest-verify + host-side parse + newest-valid
    fallback discipline as ``load_checkpoint``, but only the module tree
    is read (no optimizer shards) and nothing mutates — the restored
    params map onto ``params_template``'s structure and return as host
    numpy arrays for the caller to cast/shard/pin.

    Returns ``(params, client_state, tag)``; ``(None, {}, None)`` when no
    loadable checkpoint exists.
    """
    res = resilience if resilience is not None else _resilience_of(None)
    started = time.monotonic()
    staged = _stage_with_fallback(
        load_dir, tag, False, res  # load_optimizer_states=False
    )
    if staged is None:
        return None, {}, None
    params = serialization.from_state_dict(
        jax.tree_util.tree_map(np.asarray, params_template),
        staged.state["module"],
    )
    res.observe_load(started)
    log_dist(
        f"Loaded model state {staged.tag} from {load_dir} for inference",
        ranks=[0],
    )
    return params, staged.state.get("client_state", {}), staged.tag
