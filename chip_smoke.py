"""chip_smoke.py — the standing proof that the train and serve paths start
on the chip.

    python chip_smoke.py             # one TPU chip, GPT-2 large, ~10 min cold
    python chip_smoke.py --chips 4   # four-chip host: ZeRO-2 / ZeRO-3 / one-chip twins only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # toy size, control flow only

One process, every phase in it (a chip belongs to the first process that
touches jax; nothing here forks a child that needs it). Each phase prints
one JSON line; the LAST stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as jax reports it. Any failed check, any exception, or a
platform other than TPU ends the run with ``"ok": false`` and a non-zero
exit; a failed phase stops the run (no later phase runs on top of it).
``--rehearse`` is the only way to a toy size or a CPU: it exists for the
sandbox rehearsal and the unit test, its last line says
``"platform": "cpu"`` and its ``ok`` speaks for the rehearsal only.
Weights and data come from ``--seed``. Timings printed here are set-up
information for the next PR's budget (compile seconds per program), never
results.

Phases (docs/TESTING.md "Running on the chip"):

  device  what jax sees, versions, compile-cache directory in force,
          host_ops.HAVE_NATIVE (the smoke must pass without the extension)
  train   deepspeed_tpu.initialize() on GPT2Config.large, the recipe of
          benchmark/configs/gpt2-large.json, micro 8 x seq 1024: forward/backward/
          step windows, then fused train_batch() windows, flash-vs-XLA
          twin loss, checkpoint -> fresh engine -> resume
  serve   deepspeed_tpu.init_inference() on the same shape, bf16, paged KV,
          fused_decode on: 4 prompts + a mid-decode join through submit()
          and the scheduler, checked against a full-forward use_flash=False
          reference; then the same mix on the XLA paged path
  zero    (--chips 4 only) dp=4 ZeRO-2 and ZeRO-3 against the same global
          batch on a one-device mesh
"""

import argparse
import contextlib
import gc
import itertools
import json
import os
import shutil
import sys
import tempfile
import time

# the remat policy of benchmark/configs/gpt2-large.json: keep the
# no-batch-dim matmul outputs and the flash kernel's residuals,
# recompute the rest
GPT2_POLICY = "dots_with_no_batch_dims_saveable+flash_out+flash_lse"

# bf16 tolerances, stated once. The model's logits have std ~0.7 at this
# init; bf16 carries 8 bits, and the flash/decode kernels accumulate in a
# different order than the XLA reference.
TWIN_LOSS_TOL = 0.02      # |flash - XLA| eval loss on the same 2 rows (loss ~10.9)
TOKEN_MARGIN = 0.08       # a token may differ from the reference argmax only
                          # where the reference's own margin is below this
MAX_NEAR_TIE_SHARE = 0.15  # "small": share of generated positions allowed to sit on such a tie
ZERO_FIRST_TOL = 0.02     # first-window loss, stage 2 vs stage 3 vs one chip
ZERO_LAST_TOL = 0.05      # loss after 3 windows (dropout masks differ by layout)
DEVICE_SHARE_SPREAD = 1.25  # max/min bytes_in_use across the four chips

REAL = dict(
    model=None,  # GPT2Config.large: 36 layers, n_embd 1280, 20 heads, vocab 50257, 1024 positions
    micro=8, seq=1024, windows=3,
    slots=8, max_seq_len=1024, prefill_len=512, kv_block=16,
    prompts=(17, 64, 200, 512), join=33, new_tokens=32,
)
# --rehearse only: same phases, same comparisons, seconds on a CPU
TOY = dict(
    model=dict(vocab_size=512, n_positions=256, n_embd=64, n_layer=2, n_head=4),
    micro=2, seq=256, windows=3,
    slots=4, max_seq_len=64, prefill_len=32, kv_block=8,
    prompts=(3, 8, 17, 32), join=5, new_tokens=8,
)


DEVICE = {}  # what jax reports; main() fills it before any phase runs


def emit(phase, **fields):
    """One phase line; every one names the device it ran on."""
    print(json.dumps({"phase": phase, **fields, "device": DEVICE}),
          flush=True)


def release(jax):
    """Drop what a deleted engine still pins: its compiled programs, then
    the cycles that hold its device arrays."""
    jax.clear_caches()
    gc.collect()


def model_config(size, **overrides):
    from deepspeed_tpu.models import GPT2Config

    if size["model"] is None:
        return GPT2Config.large(**overrides)
    return GPT2Config(**size["model"], **overrides)


def host_init(jax, size, seed):
    """Parameters from ``seed`` on the host CPU device, through the
    use_flash=False twin (shapes do not depend on the attention path)."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import GPT2LMHeadModel
    from deepspeed_tpu.utils.device import host_cpu_device

    cfg = model_config(size, use_flash=False)
    ids = np.zeros((1, 8), np.int32)
    t0 = time.time()
    with jax.default_device(host_cpu_device()):
        # one jitted program: an eager init dispatches (and compiles)
        # hundreds of tiny ops
        params = jax.jit(GPT2LMHeadModel(cfg).init)(
            {"params": jax.random.PRNGKey(seed),
             "dropout": jax.random.PRNGKey(seed + 1)},
            jnp.asarray(ids), jnp.asarray(ids),
        )["params"]
    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    return params, n, time.time() - t0


def train_config(micro, accum, stage, telemetry_dir, reduced_state=True):
    """The recipe of benchmark/configs/gpt2-large.json (kept in step by
    hand, ROADMAP D16): bf16, Adam, int8 moments + compensated masters, bf16 grad
    accumulation, data_pipeline on. ``train_batch_size`` is left to the
    engine (micro x accum x dp)."""
    return {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": accum,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "data_types": {
            "optimizer_state_dtype": "int8" if reduced_state else "fp32",
            "grad_accum_dtype": "bf16" if reduced_state else "fp32",
            "master_dtype": "compensated" if reduced_state else "fp32",
        },
        "steps_per_print": 10_000,
        "data_pipeline": {"enabled": True},
        "compile_cache": {"enabled": True},
        "telemetry": {
            "enabled": True,
            "output_path": telemetry_dir,
            "job_name": "chip_smoke",
            "watchdog": {"enabled": False},
        },
    }


def build_train_engine(size, params_host, config, mesh=None):
    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel

    # a FRESH model config per engine: initialize() writes the mesh (and
    # at stage 3 the gather seam) into it
    cfg = model_config(size, remat=True, remat_policy=GPT2_POLICY)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg), model_parameters=params_host,
        config_params=config, mesh=mesh,
    )
    return engine


def close_train_engine(engine):
    engine.close_data_pipeline()
    engine.telemetry.close()


def as_shapes(jax, tree):
    """Shapes in place of arrays; an array jax left uncommitted on the
    default device keeps no sharding (jit places it with the rest)."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype,
            sharding=x.sharding if getattr(x, "committed", False) else None,
        ),
        tree,
    )


def lower_train_window(jax, engine, batch, accum):
    """The fused window program, lowered from shapes (no device memory):
    the text that says whether the flash kernels went in as Mosaic
    custom calls, and — compiled — which collectives the partitioner
    put in."""
    import jax.numpy as jnp

    from deepspeed_tpu.runtime.engine import _split_window_keys

    stacked = engine._shard_window_batch(
        engine._stack_window([batch] * accum)
    )
    _, keys = _split_window_keys(engine._rng, accum)
    return engine._jit_train_window.lower(
        as_shapes(jax, engine.params),
        as_shapes(jax, engine.optimizer_state),
        as_shapes(jax, engine.loss_scale_state),
        as_shapes(jax, stacked),
        as_shapes(jax, keys),
        jnp.float32(1e-4), jnp.float32(0.9),
    )


def fused_window(jax, engine, it):
    t0 = time.time()
    loss = float(engine.train_batch(it))
    jax.block_until_ready(engine.params)
    return loss, time.time() - t0


def median(xs):
    xs = sorted(xs)
    return xs[len(xs) // 2] if xs else None


# ---------------------------------------------------------------------------
# phase: device
# ---------------------------------------------------------------------------
def phase_device(jax, rehearse, want_chips):
    import importlib.metadata as md

    import jaxlib

    from deepspeed_tpu.runtime import host_ops
    from deepspeed_tpu.runtime.compile_cache import (
        CACHE_DIR_ENV, arm_compile_cache,
    )

    stats = jax.local_devices()[0].memory_stats()
    cache_dir = arm_compile_cache()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    checks = {
        "platform_is_tpu": DEVICE["platform"] == "tpu" or rehearse,
        "device_count": DEVICE["count"] == want_chips,
        "compile_cache_in_force": bool(cache_dir)
        and jax.config.jax_compilation_cache_dir == cache_dir,
    }
    if DEVICE["platform"] == "tpu":
        checks["memory_stats_present"] = bool(stats)
    emit(
        "device", ok=all(checks.values()), checks=checks,
        have_native_host_ops=bool(host_ops.HAVE_NATIVE),
        memory_stats_keys=sorted(stats) if stats else None,
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": libtpu},
        compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(os.environ.get(CACHE_DIR_ENV)),
        jax_platforms=os.environ.get("JAX_PLATFORMS"),
        libtpu_init_args=os.environ.get("LIBTPU_INIT_ARGS"),
        rehearsal=rehearse,
    )
    return all(checks.values())


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------
def phase_train(jax, size, seed, scratch, params_host, n_params):
    import numpy as np

    from deepspeed_tpu.models import GPT2LMHeadModel
    from deepspeed_tpu.telemetry.manager import hbm_peak_bytes
    from deepspeed_tpu.utils import device

    micro, seq, windows = size["micro"], size["seq"], size["windows"]
    vocab = model_config(size).vocab_size
    ids = np.random.default_rng(seed).integers(
        0, vocab, (micro, seq)
    ).astype(np.int32)
    config = train_config(
        micro, 1, 2, os.path.join(scratch, "telemetry_train")
    )
    engine = build_train_engine(size, params_host, config)
    snap = engine.telemetry.registry.snapshot
    timings = {}

    # -- flash vs the plain reference, same params, first two rows, eval
    # mode (dropout off: the two paths draw different masks) -------------
    def eval_loss(use_flash):
        model = GPT2LMHeadModel(model_config(size, use_flash=use_flash))
        fn = jax.jit(
            lambda p, x: model.apply({"params": p}, x, x, train=False)
        )
        t0 = time.time()
        out = float(fn(engine.params, ids[:2]))
        return out, time.time() - t0

    loss_flash, timings["eval_flash_first_call_s"] = eval_loss(True)
    loss_ref, timings["eval_reference_first_call_s"] = eval_loss(False)

    # -- route 1: forward / backward / step ------------------------------
    losses, split_s = [], []
    recompiles_after_first = {}
    for w in range(windows):
        t0 = time.time()
        loss = engine(ids, ids)
        engine.backward(loss)
        engine.step()
        jax.block_until_ready(engine.params)
        losses.append(float(loss))
        split_s.append(time.time() - t0)
        if w == 0:
            recompiles_after_first["split"] = snap()["jax/recompiles"]
    recompiles_end_split = snap()["jax/recompiles"]

    # -- route 2: the fused window ---------------------------------------
    lowered = lower_train_window(jax, engine, (ids, ids), 1)
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    # compiled here once, by hand, for the compiler's own account of the
    # program (the engine's first train_batch() then finds it in the
    # persistent cache)
    t0 = time.time()
    memory = lowered.compile().memory_analysis()
    timings["fused_window_compile_s"] = round(time.time() - t0, 2)
    window_program = {
        "peak_bytes": memory.peak_memory_in_bytes,
        "argument_bytes": memory.argument_size_in_bytes,
        "temp_bytes": memory.temp_size_in_bytes,
        "code_bytes": memory.generated_code_size_in_bytes,
    }
    stats = jax.local_devices()[0].memory_stats() or {}
    it = itertools.cycle([(ids, ids)])
    fused_s = []
    for w in range(windows):
        loss, dt = fused_window(jax, engine, it)
        losses.append(loss)
        fused_s.append(dt)
        if w == 0:
            recompiles_after_first["fused"] = snap()["jax/recompiles"]
    recompiles_end_fused = snap()["jax/recompiles"]

    # -- checkpoint, one more window, then the same window after resume --
    ckpt = os.path.join(scratch, "ckpt")
    t0 = time.time()
    engine.save_checkpoint(ckpt)
    timings["save_checkpoint_s"] = round(time.time() - t0, 2)
    next_loss, _ = fused_window(jax, engine, it)
    skipped = int(engine.skipped_steps)
    steps = int(engine.global_steps)
    cache_hits_first_engine = snap()["jax/compile_cache_hits"]
    hbm_peak = hbm_peak_bytes()
    stats_after = jax.local_devices()[0].memory_stats()
    close_train_engine(engine)
    del engine, snap, lowered, memory
    release(jax)

    engine = build_train_engine(size, params_host, config)
    t0 = time.time()
    loaded_from, _ = engine.load_checkpoint(ckpt)
    timings["load_checkpoint_s"] = round(time.time() - t0, 2)
    resumed_steps = int(engine.global_steps)
    resumed_loss, timings["resumed_first_window_s"] = fused_window(
        jax, engine, itertools.cycle([(ids, ids)])
    )
    cache_hits = engine.telemetry.registry.snapshot()[
        "jax/compile_cache_hits"
    ]
    close_train_engine(engine)
    del engine
    release(jax)
    shutil.rmtree(ckpt)

    on_tpu = device.on_tpu()
    checks = {
        # compiled Mosaic kernels in the window on the chip; the
        # interpreter (which leaves no custom call) only in a rehearsal
        "flash_compiled": mosaic_calls > 0 if on_tpu else mosaic_calls == 0,
        "losses_finite": bool(np.all(np.isfinite(losses + [next_loss]))),
        "no_skipped_steps": skipped == 0 and steps == 2 * windows + 1,
        "loss_fell": losses[-1] < losses[0],
        "flash_matches_reference": abs(loss_flash - loss_ref) <= TWIN_LOSS_TOL,
        "recompiles_flat_split":
            recompiles_end_split == recompiles_after_first["split"],
        "recompiles_flat_fused":
            recompiles_end_fused == recompiles_after_first["fused"],
        "checkpoint_loaded": loaded_from is not None
        and resumed_steps == 2 * windows,
        "resume_loss_equal": resumed_loss == next_loss,
    }
    timings.update(
        split_first_window_s=round(split_s[0], 2),
        split_window_s=round(median(split_s[1:]), 3),
        fused_first_window_s=round(fused_s[0], 2),
        fused_window_s=round(median(fused_s[1:]), 3),
    )
    emit(
        "train", ok=all(checks.values()), checks=checks,
        n_params=n_params, micro=micro, seq=seq,
        losses=[round(x, 5) for x in losses],
        next_loss=next_loss, resumed_loss=resumed_loss,
        eval_loss_flash=loss_flash, eval_loss_reference=loss_ref,
        twin_loss_tol=TWIN_LOSS_TOL, mosaic_custom_calls=mosaic_calls,
        hbm_peak_bytes=hbm_peak, hbm_bytes_limit=stats.get("bytes_limit"),
        memory_stats_after_training=stats_after,
        fused_window_program=window_program,
        compile_cache_hits=int(cache_hits_first_engine),
        compile_cache_hits_resumed_engine=int(cache_hits),
        smoke_timings_not_results=timings,
    )
    return all(checks.values())


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------
def serve_round(engine, prompts, join_prompt, new_tokens):
    """The traffic mix through the normal path: submit + scheduler, the
    last request joining while the others decode."""
    requests = [
        engine.submit(p, max_new_tokens=new_tokens) for p in prompts
    ]
    for _ in range(4):
        engine.scheduler.step()
    requests.append(engine.submit(join_prompt, max_new_tokens=new_tokens))
    engine.scheduler.run_until_idle()
    return requests


def phase_serve(jax, size, seed, params_host):
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import GPT2LMHeadModel

    cfg0 = model_config(size)
    vocab = cfg0.vocab_size
    new_tokens = size["new_tokens"]
    rng = np.random.default_rng(seed + 7)
    prompts = [
        [int(t) for t in rng.integers(0, vocab, n)] for n in size["prompts"]
    ]
    join_prompt = [int(t) for t in rng.integers(0, vocab, size["join"])]
    n_requests = len(prompts) + 1
    blocks_per_slot = size["max_seq_len"] // size["kv_block"]

    def build(fused):
        cfg = model_config(size, dropout=0.0)
        return deepspeed_tpu.init_inference(
            model=GPT2LMHeadModel(cfg), model_parameters=params_host,
            config={
                "inference": {
                    "dtype": "bf16",
                    "max_batch_slots": size["slots"],
                    "max_seq_len": size["max_seq_len"],
                    "prefill_len": size["prefill_len"],
                    "kv_block_size": size["kv_block"],
                    # 8 slots' worst case: every slot at max_seq_len
                    "kv_pool_blocks": size["slots"] * blocks_per_slot,
                    "fused_decode": fused,
                    "sampling": {"greedy": True},
                },
                "compile_cache": {"enabled": True},
            },
        )

    # -- the reference: full forward, use_flash=False, teacher-forced over
    # what the engine produced; one fixed shape -------------------------
    ref_len = size["prefill_len"] + new_tokens
    ref_model = GPT2LMHeadModel(
        model_config(size, dropout=0.0, use_flash=False)
    )

    @jax.jit
    def reference(p, toks, chosen):
        logits = ref_model.apply({"params": p}, toks, train=False)
        logits = logits[0, :, :vocab].astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        picked = jnp.take_along_axis(logits, chosen[0][:, None], axis=-1)
        return jnp.argmax(logits, axis=-1), top - picked[:, 0]

    def verify(engine, prompt, tokens):
        """(differing positions, worst margin among them): position i of
        the answer must be the reference's argmax given prompt +
        tokens[:i], or the reference's own margin for it stays under
        TOKEN_MARGIN."""
        seq = list(prompt) + list(tokens)
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(seq)] = seq
        chosen = np.zeros((1, ref_len), np.int32)
        chosen[0, :len(seq) - 1] = seq[1:]
        arg, gap = reference(engine.params, toks, chosen)
        arg, gap = np.asarray(arg), np.asarray(gap)
        lo = len(prompt) - 1
        differ = [
            i for i in range(len(tokens)) if arg[lo + i] != tokens[i]
        ]
        worst = max([float(gap[lo + i]) for i in differ], default=0.0)
        return len(differ), worst

    def run(fused, rounds):
        t0 = time.time()
        engine = build(fused)
        build_s = time.time() - t0
        recompiles = engine.metrics.counter("jax/recompiles")
        out = []
        for _ in range(rounds):
            t0 = time.time()
            before = recompiles.value
            reqs = serve_round(engine, prompts, join_prompt, new_tokens)
            out.append({
                "requests": reqs,
                "compiles": recompiles.value - before,
                "seconds": round(time.time() - t0, 2),
            })
        return engine, out, build_s

    # round 1 is cold (prefill + decode programs compile); round 2 repeats
    # the mix, so every prompt is a prefix-cache hit and the suffix-bucket
    # programs compile; round 3 must compile nothing (SKILL.md's warm-up
    # rule: dress-rehearse the SAME mix before counting recompiles)
    engine, rounds, build_s = run(True, 3)
    snap = engine.metrics.snapshot()
    all_requests = [r for rd in rounds for r in rd["requests"]]
    differ = worst = 0
    for rd in (rounds[0], rounds[2]):  # the cold path and the hit path
        for req, prompt in zip(rd["requests"], prompts + [join_prompt]):
            d, w = verify(engine, prompt, req.tokens)
            differ, worst = differ + d, max(worst, w)
    fused_tokens = [list(r.tokens) for r in rounds[0]["requests"]]
    fused_gauge = snap["infer/fused_decode"]
    ttft_count = snap["infer/ttft_ms/count"]
    engine.close()
    del engine
    release(jax)

    # -- the XLA paged path is the kernel's reference: same mix, once ----
    xla, xla_rounds, xla_build_s = run(False, 1)
    xla_requests = xla_rounds[0]["requests"]
    xla_differ = xla_worst = 0
    path_forks = path_fork_worst = 0
    for req, prompt, ftoks in zip(
        xla_requests, prompts + [join_prompt], fused_tokens
    ):
        d, w = verify(xla, prompt, req.tokens)
        xla_differ, xla_worst = xla_differ + d, max(xla_worst, w)
        fork = next(
            (i for i, (a, b) in enumerate(zip(ftoks, req.tokens)) if a != b),
            None,
        )
        if fork is not None:
            # the two paths part ways here: allowed only on a near tie of
            # the reference, given their common prefix
            _, w_f = verify(xla, prompt, ftoks[:fork + 1])
            _, w_x = verify(xla, prompt, list(req.tokens)[:fork + 1])
            path_forks += 1
            path_fork_worst = max(path_fork_worst, w_f, w_x)
    xla_gauge = xla.metrics.snapshot()["infer/fused_decode"]
    xla.close()
    del xla
    release(jax)

    positions = 2 * n_requests * new_tokens
    reasons = sorted({r.finish_reason for r in all_requests + xla_requests})
    checks = {
        # the scheduler's clean finishes (max_new_tokens is how a request
        # that asked for N tokens ends; "length" is the max_seq_len cap)
        "all_finished": set(reasons) <= {"max_new_tokens", "length", "eos"},
        "answer_lengths": all(
            len(r.tokens) == new_tokens for r in all_requests + xla_requests
        ),
        "tokens_match_reference": worst < TOKEN_MARGIN
        and differ <= MAX_NEAR_TIE_SHARE * positions,
        "fused_decode_gauge": fused_gauge == 1 and xla_gauge == 0,
        "ttft_count": ttft_count == 3 * n_requests,
        "recompiles_flat_after_rehearsal": rounds[2]["compiles"] == 0,
        "xla_path_matches_reference": xla_worst < TOKEN_MARGIN
        and xla_differ <= MAX_NEAR_TIE_SHARE * n_requests * new_tokens,
        "fused_equals_xla_path": path_fork_worst < TOKEN_MARGIN,
    }
    emit(
        "serve", ok=all(checks.values()), checks=checks,
        requests_per_round=n_requests, new_tokens=new_tokens,
        prompt_lengths=list(size["prompts"]) + [size["join"]],
        finish_reasons=reasons,
        token_margin=TOKEN_MARGIN,
        positions_checked=positions,
        positions_off_reference_argmax=differ,
        worst_reference_margin_there=round(worst, 5),
        xla_positions_off_reference_argmax=xla_differ,
        xla_worst_reference_margin_there=round(xla_worst, 5),
        requests_where_fused_and_xla_fork=path_forks,
        worst_reference_margin_at_fork=round(path_fork_worst, 5),
        compiles_per_round=[rd["compiles"] for rd in rounds],
        smoke_timings_not_results={
            "engine_build_s": round(build_s, 2),
            "round_s": [rd["seconds"] for rd in rounds],
            "xla_engine_build_s": round(xla_build_s, 2),
            "xla_round_s": xla_rounds[0]["seconds"],
        },
    )
    return all(checks.values())


# ---------------------------------------------------------------------------
# phase: zero (--chips 4)
# ---------------------------------------------------------------------------
def per_layer_gathers(hlo_text):
    """All-gathers the partitioner put inside the stage-3 layer scan
    (models/gpt2.py:_zero3_stack): the just-in-time gather of each
    layer's weights, found by the scope XLA keeps in op metadata."""
    return sum(
        "_zero3_stack/while/body" in line
        for line in hlo_text.splitlines()
        if " all-gather(" in line or " all-gather-start(" in line
    )


def phase_zero(jax, size, seed, scratch, params_host, n_params, stderr_log):
    import numpy as np

    from deepspeed_tpu.config import constants as C
    from deepspeed_tpu.parallel.mesh import build_mesh
    from deepspeed_tpu.runtime import zero as zero_lib
    from deepspeed_tpu.utils import device
    from deepspeed_tpu.utils.timers import _device_sync

    micro, seq, windows = size["micro"], size["seq"], size["windows"]
    dp = 4
    vocab = model_config(size).vocab_size
    ids = np.random.default_rng(seed).integers(
        0, vocab, (dp * micro, seq)
    ).astype(np.int32)
    on_tpu = device.on_tpu()

    def sharded_over_data(tree):
        leaves = [
            x for x in jax.tree_util.tree_leaves(tree)
            if zero_lib.has_axis(x.sharding.spec, C.DATA_AXIS)
        ]
        on_four = all(
            len({s.device for s in x.addressable_shards}) == dp
            for x in leaves
        )
        return len(leaves), on_four

    def run(label, stage, mesh, accum):
        config = train_config(
            micro, accum, stage,
            os.path.join(scratch, f"telemetry_{label}"),
        )
        t0 = time.time()
        engine = build_train_engine(size, params_host, config, mesh=mesh)
        rows = micro * engine.dp_world_size
        batches = [
            (ids[i * rows:(i + 1) * rows],) * 2 for i in range(accum)
        ]
        it = itertools.cycle(batches)
        losses, secs = [], []
        for _ in range(windows):
            loss, dt = fused_window(jax, engine, it)
            losses.append(loss)
            secs.append(dt)
        rec = {
            "label": label, "stage": stage, "dp": engine.dp_world_size,
            "accum": accum, "losses": losses,
            "first_window_s": round(secs[0], 2),
            "window_s": round(median(secs[1:]), 3),
            "build_s": round(time.time() - t0 - sum(secs), 2),
            "skipped_steps": int(engine.skipped_steps),
        }
        if engine.dp_world_size > 1:
            # the fence reaches every chip: dispatch a window, fence with
            # the timers' generic fence, and every shard of the new state
            # must already be there (ROADMAP D9)
            engine.train_batch(it)
            _device_sync()
            rec["fence_covers_all_devices"] = all(
                s.data.is_ready()
                for x in jax.tree_util.tree_leaves(engine.optimizer_state)
                for s in x.addressable_shards
            )
            n_opt, opt_four = sharded_over_data(engine.optimizer_state)
            n_par, par_four = sharded_over_data(engine.params)
            rec.update(
                optstate_leaves_over_data=n_opt,
                optstate_on_four_devices=opt_four,
                param_leaves_over_data=n_par,
                params_on_four_devices=par_four,
                zero3_gather_enabled=bool(engine.zero3_gather_enabled),
            )
            stats = [d.memory_stats() or {} for d in jax.local_devices()]
            rec["bytes_in_use_per_device"] = [
                int(s.get("bytes_in_use", 0)) for s in stats
            ]
            rec["peak_bytes_per_device"] = [
                int(s.get("peak_bytes_in_use", 0)) for s in stats
            ]
            lowered = lower_train_window(jax, engine, batches[0], accum)
            text = lowered.as_text()
            rec["mosaic_custom_calls"] = text.count("tpu_custom_call")
            # shard_map lowers to a Shardy manual computation
            rec["shard_map_regions"] = text.count("sdy.manual_computation")
            hlo = lowered.compile().as_text()
            rec["collectives"] = {
                op: hlo.count(f" {op}(") + hlo.count(f" {op}-start(")
                for op in ("all-gather", "reduce-scatter", "all-reduce")
            }
            rec["per_layer_gathers"] = per_layer_gathers(hlo)
            del lowered, text, hlo
        close_train_engine(engine)
        del engine
        release(jax)
        return rec

    four = build_mesh()  # what initialize() builds by default: data = 4
    assert dict(four.shape)[C.DATA_AXIS] == dp, dict(four.shape)
    z2 = run("zero2_dp4", 2, None, 1)
    z3 = run("zero3_dp4", 3, None, 1)
    one = run(
        "one_chip", 2, build_mesh(devices=jax.devices()[:1]), dp
    )

    stderr_log.flush()
    with open(stderr_log.name) as fd:
        involuntary_remat = fd.read().count(
            "Involuntary full rematerialization"
        )

    def agree(i, tol):
        vals = [r["losses"][i] for r in (z2, z3, one)]
        return max(vals) - min(vals) <= tol

    def even(r):
        used = r["bytes_in_use_per_device"]
        if not on_tpu:  # the CPU backend keeps no memory stats
            return True
        return min(used) > 0 and max(used) <= DEVICE_SHARE_SPREAD * min(used)

    checks = {
        "losses_finite": bool(np.all(np.isfinite(
            z2["losses"] + z3["losses"] + one["losses"]
        ))),
        "no_skipped_steps": not any(
            r["skipped_steps"] for r in (z2, z3, one)
        ),
        "first_window_agrees": agree(0, ZERO_FIRST_TOL),
        "last_window_agrees": agree(-1, ZERO_LAST_TOL),
        "zero2_optstate_sharded": z2["optstate_leaves_over_data"] > 0
        and z2["optstate_on_four_devices"],
        "zero3_optstate_sharded": z3["optstate_leaves_over_data"] > 0
        and z3["optstate_on_four_devices"],
        "zero3_params_sharded": z3["param_leaves_over_data"] > 0
        and z3["params_on_four_devices"] and z3["zero3_gather_enabled"],
        "zero2_collectives": z2["collectives"]["all-gather"] > 0
        and (z2["collectives"]["reduce-scatter"] > 0
             or z2["collectives"]["all-reduce"] > 0),
        "zero3_per_layer_gather": z3["per_layer_gathers"] > 0
        and z2["per_layer_gathers"] == 0,
        "memory_spread_even": even(z2) and even(z3),
        # flash_attention_sharded: Mosaic kernels inside shard_map
        # regions. Off the chip training dropout sends attention down the
        # XLA path (the interpreter has no PRNG), so a rehearsal has none.
        "flash_through_shard_map": all(
            r["mosaic_custom_calls"] > 0 and r["shard_map_regions"] > 0
            for r in (z2, z3)
        ) if on_tpu else True,
        "fence_covers_all_devices": z2["fence_covers_all_devices"]
        and z3["fence_covers_all_devices"],
    }
    emit(
        "zero", ok=all(checks.values()), checks=checks, n_params=n_params,
        global_batch=dp * micro, seq=seq,
        first_tol=ZERO_FIRST_TOL, last_tol=ZERO_LAST_TOL,
        # a finding, not a failure: XLA replicated a tensor mid-step to
        # change its sharding (ROADMAP S8)
        involuntary_full_rematerialization_warnings=involuntary_remat,
        runs=[z2, z3, one],
    )
    return all(checks.values())


# ---------------------------------------------------------------------------
@contextlib.contextmanager
def stderr_tee(path):
    """Send fd 2 to ``path`` for the duration (XLA's partitioner warnings
    come from C++ and bypass sys.stderr), then replay it to the real
    stderr."""
    sys.stderr.flush()
    saved = os.dup(2)
    with open(path, "w+") as log:
        os.dup2(log.fileno(), 2)
        try:
            yield log
        finally:
            sys.stderr.flush()
            os.dup2(saved, 2)
            os.close(saved)
            log.flush()
            log.seek(0)
            sys.stderr.write(log.read())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run ONLY the ZeRO data-parallel phase, on exactly four "
             "devices (the builder runs this; the driver never does)",
    )
    parser.add_argument(
        "--rehearse", action="store_true",
        help="toy size, any platform: control flow only, proves nothing "
             "about the chip",
    )
    args = parser.parse_args(argv)

    import jax

    # in a directory that holds this file and nothing else of the repo,
    # the run ends here: no result line, non-zero exit
    from deepspeed_tpu.utils import device

    DEVICE.update(device.describe())
    size = TOY if args.rehearse else REAL
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    passed = False  # stays False if anything below raises
    try:
        ok = phase_device(jax, args.rehearse, args.chips)
        if ok:
            # weights from --seed, made once on the host CPU device (which
            # must exist beside the TPU backend) and shared by the phases
            params_host, n_params, init_s = host_init(jax, size, args.seed)
            emit("host_init", ok=True, n_params=n_params,
                 smoke_timings_not_results={"host_init_s": round(init_s, 2)})
        if ok and args.chips == 4:
            with stderr_tee(os.path.join(scratch, "stderr.log")) as log:
                ok = phase_zero(
                    jax, size, args.seed, scratch, params_host, n_params, log
                )
        elif ok:
            ok = phase_train(
                jax, size, args.seed, scratch, params_host, n_params
            )
            ok = ok and phase_serve(jax, size, args.seed, params_host)
        passed = bool(ok)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
        # always the last stdout line; an exception above still
        # propagates (and exits non-zero) after it
        print(json.dumps({"ok": passed, "device": DEVICE}), flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
