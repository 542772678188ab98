"""Continuous-batching inference engine tests (deepspeed_tpu/inference/,
docs/inference.md): decode correctness against the training forward,
slot lifecycle, front-door overload shedding, the fixed-shape
no-recompile pin, the verified param-load path, and config validation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config.config import DeepSpeedConfigError
from deepspeed_tpu.inference import (
    RequestRejected,
    gpt2_prefill,
    init_kv_cache,
)
from deepspeed_tpu.models.gpt2 import (
    GPT2Config,
    GPT2LMHeadModel,
    kv_cache_partition_specs,
)

VOCAB = 97


def _small_model(seed=0, **kw):
    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2, n_head=4,
        dropout=0.0, use_flash=False, **kw,
    )
    model = GPT2LMHeadModel(cfg)
    ids0 = jnp.asarray(
        np.random.default_rng(seed).integers(0, VOCAB, (1, 8)), jnp.int32
    )
    params = model.init(
        {"params": jax.random.PRNGKey(seed),
         "dropout": jax.random.PRNGKey(seed + 1)},
        ids0, ids0,
    )["params"]
    return cfg, model, params


def _engine(model, params, inference=None, **kw):
    block = {"max_batch_slots": 4, "max_seq_len": 48, "prefill_len": 16,
             "sampling": {"greedy": True}}
    block.update(inference or {})
    return deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={"inference": block}, **kw,
    )


def _prompt(n=8, seed=1):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


def _reference_rollout(model, params, prompt, steps):
    """Full-sequence forward argmax rollout — the training model itself,
    jitted (the regime every engine program runs under)."""
    fwd = jax.jit(lambda p, t: model.apply({"params": p}, t, train=False))
    seq = list(prompt)
    out = []
    for _ in range(steps):
        logits = fwd(params, jnp.asarray([seq], jnp.int32))
        nxt = int(jnp.argmax(logits[0, -1, :VOCAB]))
        out.append(nxt)
        seq.append(nxt)
    return out


# ---------------------------------------------------------------------------
# decode correctness
# ---------------------------------------------------------------------------
def test_prefill_logits_bitwise_match_full_forward():
    """The KV-cache prefill IS the training forward: same params, same
    jitted arithmetic, bit-identical logits (plus per-layer k/v out)."""
    cfg, model, params = _small_model()
    prompt = jnp.asarray([_prompt(8)], jnp.int32)
    full = jax.jit(
        lambda p, t: model.apply({"params": p}, t, train=False)
    )(params, prompt)
    pre, ks, vs = jax.jit(
        lambda p, t: gpt2_prefill(cfg, p, t)
    )(params, prompt)
    np.testing.assert_array_equal(np.asarray(pre), np.asarray(full))
    assert ks.shape == (cfg.n_layer, 1, cfg.n_head, 8,
                        cfg.n_embd // cfg.n_head)
    assert vs.shape == ks.shape


def test_right_padded_prefill_matches_unpadded_rows():
    """Causality makes the fixed prefill window's padding columns inert.

    Two pins. WITHIN one program shape the property is exact: whatever
    sits in the padding columns, every real row's logits are bitwise the
    same. ACROSS shapes ([1,6] vs [1,16]) the two runs are different XLA
    programs, and XLA is free to tile and order their reductions
    differently (jax 0.9.0 does: 1 element of 768 off by 6e-8, two ulps
    at its magnitude), so that comparison gets four ulps of the largest
    logit — a bitwise pin there tests the compiler, not the padding."""
    cfg, model, params = _small_model()
    prompt = _prompt(6)
    jit_pre = jax.jit(lambda p, t: gpt2_prefill(cfg, p, t))
    plain, _, _ = jit_pre(params, jnp.asarray([prompt], jnp.int32))
    padded, _, _ = jit_pre(params, jnp.asarray([prompt + [0] * 10], jnp.int32))
    other, _, _ = jit_pre(
        params, jnp.asarray([prompt + _prompt(10, seed=9)], jnp.int32)
    )
    np.testing.assert_array_equal(
        np.asarray(padded[:, :6]), np.asarray(other[:, :6])
    )
    plain = np.asarray(plain)
    few_ulps = 4 * np.spacing(np.abs(plain).max())
    np.testing.assert_allclose(
        np.asarray(padded[:, :6]), plain, rtol=0, atol=few_ulps
    )


def test_greedy_decode_parity_with_full_forward():
    """Acceptance pin: prefill + 16 KV-cache decode steps reproduce the
    full-sequence forward's argmax rollout exactly."""
    cfg, model, params = _small_model()
    prompt = _prompt(8)
    engine = _engine(model, params)
    out = engine.generate([prompt], max_new_tokens=16)[0]
    engine.close()
    assert len(out) == 16
    assert out == _reference_rollout(model, params, prompt, 16)


def test_concurrent_requests_decode_independently():
    """Continuous batching must not cross-contaminate slots: two prompts
    decoded in the SAME slot batch produce exactly what each produces
    alone."""
    cfg, model, params = _small_model()
    p1, p2 = _prompt(8, seed=1), _prompt(5, seed=2)
    engine = _engine(model, params)
    together = engine.generate([p1, p2], max_new_tokens=10)
    engine.close()
    for prompt, got in zip((p1, p2), together):
        assert got == _reference_rollout(model, params, prompt, 10)


def test_mid_flight_join_keeps_running_request_exact():
    """A request admitted while another is mid-decode (the continuous-
    batching moment) must not perturb the running request's trajectory,
    and must itself decode exactly."""
    cfg, model, params = _small_model()
    p1, p2 = _prompt(8, seed=3), _prompt(7, seed=4)
    engine = _engine(model, params, inference={"max_batch_slots": 2})
    r1 = engine.submit(p1, max_new_tokens=12)
    for _ in range(4):  # r1 alone for 4 steps
        engine.scheduler.step()
    r2 = engine.submit(p2, max_new_tokens=8)  # joins mid-flight
    engine.scheduler.run_until_idle()
    engine.close()
    assert r1.result(0) == _reference_rollout(model, params, p1, 12)
    assert r2.result(0) == _reference_rollout(model, params, p2, 8)


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------
def test_eos_finishes_request_and_slot_is_reused():
    cfg, model, params = _small_model()
    prompt = _prompt(8)
    ref = _reference_rollout(model, params, prompt, 8)
    eos = ref[3]  # the greedy trajectory reaches this token
    expected = ref[: ref.index(eos) + 1]  # truncated AT its first hit

    engine = _engine(model, params, inference={"max_batch_slots": 1})
    r1 = engine.submit(prompt, max_new_tokens=8, eos_token_id=eos)
    engine.scheduler.run_until_idle()
    assert r1.finish_reason == "eos"
    assert r1.result(0) == expected
    assert engine.scheduler.active_slots == []

    # the single slot frees and serves the next request correctly even
    # though the cache still holds the finished request's rows
    p2 = _prompt(6, seed=9)
    r2 = engine.submit(p2, max_new_tokens=6)
    engine.scheduler.run_until_idle()
    engine.close()
    assert r2.finish_reason == "max_new_tokens"
    assert r2.result(0) == _reference_rollout(model, params, p2, 6)


def test_length_cap_finishes_request():
    cfg, model, params = _small_model()
    engine = _engine(
        model, params, inference={"max_seq_len": 12, "prefill_len": 8}
    )
    r = engine.submit(_prompt(8), max_new_tokens=100)
    engine.scheduler.run_until_idle()
    engine.close()
    assert r.finish_reason == "length"
    assert len(r.result(0)) == 12 - 8


# ---------------------------------------------------------------------------
# the front door
# ---------------------------------------------------------------------------
def test_queue_overload_rejection():
    cfg, model, params = _small_model()
    engine = _engine(
        model, params,
        inference={"max_batch_slots": 1, "queue_depth": 2,
                   "queue_timeout_secs": 0.0},
    )
    # no scheduler steps run, so submissions pile up in the queue
    engine.submit(_prompt(4), max_new_tokens=4)
    engine.submit(_prompt(4), max_new_tokens=4)
    with pytest.raises(RequestRejected):
        engine.submit(_prompt(4), max_new_tokens=4)
    snap = engine.metrics.snapshot()
    assert snap["infer/requests_rejected"] == 1
    assert snap["infer/requests_admitted"] == 2
    # shed load drains once the scheduler runs again
    engine.scheduler.run_until_idle()
    engine.close()


def test_failed_generate_submit_cancels_earlier_prompts():
    """A rejected later prompt must not orphan the earlier submissions:
    they cancel instead of burning decode work on a future call with
    nobody holding their handles."""
    cfg, model, params = _small_model()
    engine = _engine(model, params, inference={"prefill_len": 8})
    with pytest.raises(ValueError, match="prefill_len"):
        engine.generate([_prompt(4), _prompt(9)], max_new_tokens=4)
    engine.scheduler.run_until_idle()
    snap = engine.metrics.snapshot()
    assert snap["infer/tokens_generated"] == 0
    assert engine.scheduler.active_slots == []
    # the engine still serves normally afterwards
    out = engine.generate([_prompt(4)], max_new_tokens=4)
    engine.close()
    assert len(out[0]) == 4


def test_prefill_window_validated_against_model_positions():
    """prefill_len larger than the model-derived max_seq_len must fail at
    init_inference, not as a wpe broadcast error in the first prefill."""
    cfg, model, params = _small_model()  # n_positions=64
    with pytest.raises(DeepSpeedConfigError, match="prefill_len"):
        deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": {"prefill_len": 128}},
        )


def test_prompt_longer_than_prefill_window_rejected():
    cfg, model, params = _small_model()
    engine = _engine(model, params, inference={"prefill_len": 8})
    with pytest.raises(ValueError, match="prefill_len"):
        engine.submit(_prompt(9))
    with pytest.raises(ValueError, match="empty"):
        engine.submit([])
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(_prompt(4), max_new_tokens=0)
    engine.close()


def test_server_mode_generate_and_shutdown_release_waiters():
    """generate() on a serve_forever engine waits on the server thread
    instead of racing it, and shutdown fail-finishes outstanding requests
    so result() waiters never hang."""
    cfg, model, params = _small_model()
    engine = _engine(model, params, inference={"max_batch_slots": 2})
    engine.serve_forever()
    out = engine.generate([_prompt(6)], max_new_tokens=5)
    assert out[0] == _reference_rollout(model, params, _prompt(6), 5)
    # park requests (they may be queued or decoding), then shut down:
    # every handle must resolve, none may hang
    rs = [engine.submit(_prompt(4, seed=s), max_new_tokens=30)
          for s in range(4)]
    engine.close()
    for r in rs:
        r.result(timeout=5)  # raises TimeoutError on a hung waiter
        assert r.done
    assert engine.scheduler.active_slots == []
    # a closed scheduler sheds new submissions instead of queueing them
    # for a driver that no longer exists
    with pytest.raises(RequestRejected, match="shut down"):
        engine.submit(_prompt(4), max_new_tokens=2)


# ---------------------------------------------------------------------------
# fixed-shape pin: joins never recompile
# ---------------------------------------------------------------------------
def test_decode_steps_do_not_recompile_on_joins():
    """After the first request warms every program (prefill, cache write,
    decode+sample, first-token), requests of DIFFERENT prompt lengths
    joining and leaving must add zero XLA backend compiles — the
    continuous-batching engine's core latency invariant."""
    cfg, model, params = _small_model()
    engine = _engine(model, params, inference={"max_batch_slots": 3})
    recompiles = engine.metrics.counter("jax/recompiles")
    engine.generate([_prompt(8)], max_new_tokens=4)
    warm = recompiles.value
    assert warm > 0

    r1 = engine.submit(_prompt(5, seed=5), max_new_tokens=6)
    engine.scheduler.step()
    r2 = engine.submit(_prompt(11, seed=6), max_new_tokens=5)
    r3 = engine.submit(_prompt(3, seed=7), max_new_tokens=7)
    engine.scheduler.run_until_idle()
    engine.close()
    assert all(r.done for r in (r1, r2, r3))
    assert recompiles.value == warm, (
        f"decode path recompiled: {recompiles.value - warm} new backend "
        "compiles after warmup"
    )


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------
def test_inference_telemetry_streams_populate_and_export(tmp_path):
    cfg, model, params = _small_model()
    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={
            "inference": {"max_batch_slots": 2, "max_seq_len": 48,
                          "prefill_len": 16, "sampling": {"greedy": True}},
            "telemetry": {
                "enabled": True,
                "output_path": str(tmp_path),
                "job_name": "infer",
                "watchdog": {"enabled": False},
            },
        },
    )
    engine.generate([_prompt(8), _prompt(6, seed=2)], max_new_tokens=8)
    snap = engine.metrics.snapshot()
    engine.close()
    assert snap["infer/ttft_ms/count"] == 2
    assert snap["infer/tokens_per_sec"] > 0
    assert snap["infer/token_latency_ms/count"] >= 7
    assert snap["infer/tokens_generated"] == 16
    assert snap["infer/requests_completed"] == 2
    assert snap["infer/slot_occupancy"] == 0
    # infer/* streams ride the SAME exporters as the training engine's
    import json

    lines = [
        json.loads(l)
        for l in open(tmp_path / "infer" / "metrics.jsonl").read().splitlines()
    ]
    tags = {l["tag"] for l in lines}
    assert {"infer/ttft_ms", "infer/token_latency_ms",
            "infer/tokens_per_sec", "infer/queue_depth",
            "infer/slot_occupancy"} <= tags
    ttft = [l for l in lines if l["tag"] == "infer/ttft_ms"][-1]
    assert ttft["kind"] == "histogram" and ttft["count"] == 2
    prom = open(tmp_path / "infer" / "metrics.prom").read()
    assert "infer_ttft_ms_bucket" in prom
    assert "infer_tokens_per_sec" in prom


# ---------------------------------------------------------------------------
# verified param load
# ---------------------------------------------------------------------------
def test_init_inference_serves_checkpoint_through_verified_load(tmp_path):
    """Params load through the resilience verified-load path: the trained
    checkpoint's weights (not the fresh init) answer generation, and a
    corrupt 'latest' falls back to the newest valid tag."""
    cfg, model, params = _small_model()
    trainer, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "steps_per_print": 10_000,
        },
    )
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, VOCAB, (8, 16)), jnp.int32
    )
    for _ in range(2):
        loss = trainer(ids, ids)
        trainer.backward(loss)
        trainer.step()
    save_dir = str(tmp_path / "ckpt")
    trainer.save_checkpoint(save_dir, tag="step2")
    trained = jax.tree_util.tree_map(np.asarray, trainer.params)

    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={
            "inference": {
                "max_batch_slots": 2, "max_seq_len": 48, "prefill_len": 16,
                "sampling": {"greedy": True},
                "checkpoint": {"load_dir": save_dir},
            },
        },
    )
    assert engine.loaded_tag == "step2"
    for got, want in zip(
        jax.tree_util.tree_leaves(engine.params),
        jax.tree_util.tree_leaves(trained),
    ):
        np.testing.assert_allclose(
            np.asarray(got), want, rtol=0, atol=0
        )
    out = engine.generate([_prompt(8)], max_new_tokens=4)[0]
    engine.close()
    ref = _reference_rollout(
        model, jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(params),
            jax.tree_util.tree_leaves(trained),
        ),
        _prompt(8), 4,
    )
    assert out == ref


def test_init_inference_verified_load_falls_back_on_corruption(tmp_path):
    cfg, model, params = _small_model()
    trainer, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config_params={
            "train_batch_size": 8,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
            "steps_per_print": 10_000,
        },
    )
    save_dir = str(tmp_path / "ckpt")
    trainer.save_checkpoint(save_dir, tag="good")
    ids = jnp.asarray(
        np.random.default_rng(3).integers(0, VOCAB, (8, 16)), jnp.int32
    )
    loss = trainer(ids, ids)
    trainer.backward(loss)
    trainer.step()
    trainer.save_checkpoint(save_dir, tag="bad")
    # corrupt the newest checkpoint's model states
    import os

    victim = os.path.join(save_dir, "bad", "mp_rank_00_model_states.msgpack")
    with open(victim, "wb") as f:
        f.write(b"torn write")

    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={
            "inference": {
                "max_batch_slots": 2, "max_seq_len": 48, "prefill_len": 16,
                "checkpoint": {"load_dir": save_dir},
            },
        },
    )
    assert engine.loaded_tag == "good"
    assert engine.metrics.snapshot()["resilience/corruption_fallbacks"] >= 1
    engine.close()


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------
def test_sampling_modes():
    from deepspeed_tpu.inference.sampling import sample_tokens

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    key = jax.random.PRNGKey(0)
    zeros = jnp.zeros((4,), jnp.float32)
    ones = jnp.ones((4,), jnp.float32)

    # temperature 0 => greedy, and the vocab padding can never win even
    # when it holds the largest raw logit
    spiked = logits.at[:, 100:].set(100.0)
    greedy = sample_tokens(spiked, key, zeros, vocab_size=100)
    assert np.all(np.asarray(greedy) < 100)
    np.testing.assert_array_equal(
        np.asarray(greedy), np.argmax(np.asarray(spiked)[:, :100], axis=-1)
    )
    # top_k=1 collapses sampling onto argmax
    topk1 = sample_tokens(logits, key, ones, vocab_size=100, top_k=1)
    np.testing.assert_array_equal(
        np.asarray(topk1), np.argmax(np.asarray(logits)[:, :100], axis=-1)
    )
    # a tiny nucleus keeps the argmax reachable and excludes the tail
    topp = sample_tokens(logits, key, ones, vocab_size=100, top_p=1e-6)
    np.testing.assert_array_equal(
        np.asarray(topp), np.argmax(np.asarray(logits)[:, :100], axis=-1)
    )
    # same key + same inputs => bit-identical draw (explicit threading)
    a = sample_tokens(logits, key, ones, vocab_size=100)
    b = sample_tokens(logits, key, ones, vocab_size=100)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # mixed greedy/sampled rows in one call
    mixed_t = jnp.asarray([0.0, 1.0, 0.0, 1.0], jnp.float32)
    mixed = np.asarray(sample_tokens(logits, key, mixed_t, vocab_size=100))
    am = np.argmax(np.asarray(logits)[:, :100], axis=-1)
    assert mixed[0] == am[0] and mixed[2] == am[2]


# ---------------------------------------------------------------------------
# config + cache plumbing
# ---------------------------------------------------------------------------
def test_kv_cache_layout_and_specs():
    cfg, _, _ = _small_model()
    cache = init_kv_cache(cfg, num_slots=4, max_len=32)
    assert cache.k.shape == (2, 4, 4, 32, 8)
    assert cache.num_slots == 4 and cache.max_len == 32
    spec = kv_cache_partition_specs()
    assert spec[2] == "model" and spec[0] is None and spec[3] is None


@pytest.mark.parametrize("block", [
    {"max_batch_slots": 0},
    {"max_batch_slots": "four"},
    {"queue_depth": 0},
    {"queue_timeout_secs": -1},
    {"dtype": "fp64"},
    {"sampling": {"temperature": -0.5}},
    {"sampling": {"top_p": 0.0}},
    {"sampling": {"top_p": 2.0}},
    {"sampling": {"greedy": "yes"}},
    {"eos_token_id": "eos"},
    {"max_seq_len": 8, "prefill_len": 16},
    {"checkpoint": {"load_dir": 7}},
])
def test_inference_config_validation_rejects(block):
    from deepspeed_tpu.config.config import DeepSpeedConfig

    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedConfig(
            None,
            param_dict={"train_batch_size": 8, "inference": block},
            world_size=1,
        )


def test_init_inference_rejects_unsupported_stacks():
    cfg, model, params = _small_model()
    moe_model = GPT2LMHeadModel(
        GPT2Config(
            vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=2,
            n_head=4, dropout=0.0, moe_experts=2,
        )
    )
    with pytest.raises(DeepSpeedConfigError, match="MoE"):
        deepspeed_tpu.init_inference(
            model=moe_model, model_parameters=params, config={}
        )
    with pytest.raises(DeepSpeedConfigError, match="n_positions"):
        deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": {"max_seq_len": 1024}},
        )
    with pytest.raises(ValueError, match="model_parameters"):
        deepspeed_tpu.init_inference(model=model, config={})


# ---------------------------------------------------------------------------
# self-healing serving: deadlines, health-state machine, driver restart
# (docs/inference.md "Self-healing serving")
# ---------------------------------------------------------------------------
import time as _time


def _healing_engine(inference=None, resilience=None):
    cfg, model, params = _small_model()
    block = {"max_batch_slots": 2, "max_seq_len": 48, "prefill_len": 16,
             "sampling": {"greedy": True}, "queue_depth": 4}
    block.update(inference or {})
    config = {"inference": block}
    if resilience:
        config["resilience"] = resilience
    return deepspeed_tpu.init_inference(
        model=model, model_parameters=params, config=config,
    )


def test_unmeetable_deadline_rejected_at_admission():
    """A request whose deadline is already unmeetable finishes with
    reason 'deadline' at admission — the slot is never taken and no
    prefill runs for it."""
    eng = _healing_engine()
    try:
        req = eng.submit(_prompt(6), max_new_tokens=8, deadline_secs=1e-4)
        _time.sleep(0.01)  # the deadline passes while queued
        eng.scheduler.step()
        assert req.finish_reason == "deadline"
        assert req.tokens == []
        snap = eng.metrics.snapshot()
        assert snap["infer/deadline_misses"] == 1
        assert snap["infer/requests_completed"] == 0
        assert snap["infer/slot_occupancy"] == 0
    finally:
        eng.close()


def test_inflight_deadline_frees_slot_within_one_step():
    eng = _healing_engine()
    try:
        req = eng.submit(_prompt(6), max_new_tokens=500, deadline_secs=30.0)
        eng.scheduler.step()  # admit + first decode step
        assert eng.scheduler.active_slots == [0]
        produced = len(req.tokens)
        assert produced >= 1
        # force the deadline into the past; the NEXT step must reclaim
        req.deadline = _time.monotonic() - 0.001
        eng.scheduler.step()
        assert req.finish_reason == "deadline"
        assert eng.scheduler.active_slots == []
        assert req.tokens[:produced] == req.tokens[:produced]  # partial kept
        assert eng.metrics.snapshot()["infer/deadline_misses"] == 1
    finally:
        eng.close()


def test_submit_rejects_nonpositive_deadline():
    eng = _healing_engine()
    try:
        with pytest.raises(ValueError):
            eng.submit(_prompt(6), deadline_secs=0)
        with pytest.raises(ValueError):
            eng.submit(_prompt(6), deadline_secs=-1.5)
    finally:
        eng.close()


def test_config_default_deadline_applies_to_requests():
    eng = _healing_engine(inference={"deadline_secs": 30.0})
    try:
        req = eng.submit(_prompt(6), max_new_tokens=1)
        assert req.deadline is not None
        eng.scheduler.run_until_idle()
        assert req.finish_reason == "max_new_tokens"
    finally:
        eng.close()


def test_degraded_health_sheds_low_priority_only():
    from deepspeed_tpu.inference.scheduler import (
        HEALTH_DEGRADED,
        HEALTH_HEALTHY,
    )

    eng = _healing_engine(inference={"degraded_queue_ratio": 0.5})
    try:
        assert eng.scheduler.health == HEALTH_HEALTHY
        a = eng.submit(_prompt(6), max_new_tokens=2)
        b = eng.submit(_prompt(6), max_new_tokens=2)
        # queue 2/4 >= 0.5 ratio: degraded — priority > 0 shed at the door
        assert eng.scheduler.health == HEALTH_DEGRADED
        with pytest.raises(RequestRejected):
            eng.submit(_prompt(6), max_new_tokens=2, priority=1)
        c = eng.submit(_prompt(6), max_new_tokens=2, priority=0)
        snap = eng.metrics.snapshot()
        assert snap["infer/requests_shed"] == 1
        assert snap["infer/health_state"] == HEALTH_DEGRADED
        eng.scheduler.run_until_idle()
        assert {a.finish_reason, b.finish_reason, c.finish_reason} == {
            "max_new_tokens"
        }
        assert eng.scheduler.health == HEALTH_HEALTHY
    finally:
        eng.close()


def test_drain_stops_admission_finishes_inflight():
    from deepspeed_tpu.inference.scheduler import HEALTH_DRAINING

    eng = _healing_engine()
    try:
        req = eng.submit(_prompt(6), max_new_tokens=3)
        eng.scheduler.drain()
        assert eng.metrics.snapshot()["infer/health_state"] == HEALTH_DRAINING
        with pytest.raises(RequestRejected):
            eng.submit(_prompt(6), max_new_tokens=2)
        eng.scheduler.run_until_idle()
        assert req.finish_reason == "max_new_tokens"
    finally:
        eng.close()


def test_decode_crash_auto_restarts_within_budget():
    """An injected decode crash fails the in-flight request (its KV rows
    died), resets the decode state from the pinned params, and the
    scheduler keeps serving — the next request completes normally."""
    eng = _healing_engine(
        inference={"driver_restart_budget": 1},
        resilience={"fault_injection": {"enabled": True, "faults": [
            {"site": "decode.step", "after": 1, "times": 1},
        ]}},
    )
    try:
        r1 = eng.submit(_prompt(6), max_new_tokens=6)
        eng.scheduler.run_until_idle()  # decode traversal 2 crashes
        snap = eng.metrics.snapshot()
        assert snap["infer/driver_restarts"] == 1
        assert r1.finish_reason == "error"
        assert len(r1.tokens) >= 1  # prefill token landed before the crash
        # post-restart the engine serves from the same pinned params
        r2 = eng.submit(_prompt(6), max_new_tokens=4)
        eng.scheduler.run_until_idle()
        assert r2.finish_reason == "max_new_tokens"
        assert len(r2.tokens) == 4
    finally:
        eng.close()


def test_decode_crash_exhausted_budget_drains():
    from deepspeed_tpu.inference.scheduler import HEALTH_DRAINING

    eng = _healing_engine(
        resilience={"fault_injection": {"enabled": True, "faults": [
            {"site": "decode.step", "after": 1, "times": 0},
        ]}},
    )
    try:
        r1 = eng.submit(_prompt(6), max_new_tokens=6)
        eng.serve_forever()
        r1.result(timeout=30)  # fail-finished, never hangs
        assert r1.finish_reason in ("cancelled", "error")
        deadline = _time.monotonic() + 5
        while eng.scheduler.driving and _time.monotonic() < deadline:
            _time.sleep(0.01)
        assert not eng.scheduler.driving
        assert eng.metrics.snapshot()["infer/health_state"] == HEALTH_DRAINING
        with pytest.raises(RequestRejected):
            eng.submit(_prompt(6), max_new_tokens=2)
    finally:
        eng.close()


def test_restarted_decode_matches_clean_engine_greedy():
    """Driver restart serves from the PINNED params: a post-restart
    greedy generation is bitwise what a never-crashed engine produces."""
    prompt = _prompt(8, seed=3)
    eng = _healing_engine(
        inference={"driver_restart_budget": 1},
        resilience={"fault_injection": {"enabled": True, "faults": [
            {"site": "decode.step", "times": 1},
        ]}},
    )
    clean = _healing_engine()
    try:
        crash = eng.submit(_prompt(6), max_new_tokens=4)
        eng.scheduler.run_until_idle()  # first decode step crashes
        assert crash.finish_reason == "error"
        out = eng.generate([prompt], max_new_tokens=8)[0]
        ref = clean.generate([prompt], max_new_tokens=8)[0]
        assert out == ref
    finally:
        eng.close()
        clean.close()


def test_prefill_crash_does_not_orphan_request():
    """A prefill that raises must leave the popped request reachable by
    the recovery sweeps — its result() waiter gets an answer instead of
    hanging forever (the request owns its slot before prefill runs)."""
    eng = _healing_engine(inference={"driver_restart_budget": 1})
    try:
        orig = eng.prefill_request
        calls = {"n": 0}

        def crashing_prefill(slot, tokens, temperature):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected prefill crash")
            return orig(slot, tokens, temperature)

        eng.prefill_request = crashing_prefill
        req = eng.submit(_prompt(6), max_new_tokens=3)
        eng.scheduler.run_until_idle()  # crash -> auto-restart
        assert req.done  # NOT hanging
        assert req.finish_reason == "error"
        assert eng.metrics.snapshot()["infer/driver_restarts"] == 1
        # and the restarted driver still serves
        req2 = eng.submit(_prompt(6), max_new_tokens=3)
        eng.scheduler.run_until_idle()
        assert req2.finish_reason == "max_new_tokens"
    finally:
        eng.close()


def test_queued_request_past_deadline_expires_without_free_slot():
    """Deadline expiry reaches QUEUED requests too: with every slot busy
    on a long generation, an expired queued request gets its 'deadline'
    finish at the next step boundary, not when a slot eventually frees."""
    eng = _healing_engine(inference={"max_batch_slots": 1})
    try:
        long_req = eng.submit(_prompt(6), max_new_tokens=30)
        eng.scheduler.step()  # long_req occupies the only slot
        queued = eng.submit(_prompt(6), max_new_tokens=5, deadline_secs=60)
        queued.deadline = _time.monotonic() - 0.001  # force expiry
        eng.scheduler.step()  # slot still busy; queued must expire NOW
        assert queued.finish_reason == "deadline"
        assert long_req.finish_reason is None  # untouched
        eng.scheduler.run_until_idle()
        assert long_req.finish_reason == "max_new_tokens"
        # the expired husk was discarded at admission, never decoded
        assert queued.tokens == []
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# router-facing surface: rejection reason codes + load_snapshot
# (deepspeed_tpu/serving/ builds on exactly these)
# ---------------------------------------------------------------------------
def test_rejections_carry_machine_readable_reason_codes():
    """Every RequestRejected raise site classifies itself with a REJECT_*
    code — the router and tests branch on exc.reason, not on prose."""
    cfg, model, params = _small_model()
    eng = _engine(
        model, params,
        inference={"max_batch_slots": 1, "queue_depth": 1,
                   "queue_timeout_secs": 0.0},
    )
    try:
        eng.submit(_prompt(4), max_new_tokens=4)
        with pytest.raises(RequestRejected) as exc:
            eng.submit(_prompt(4), max_new_tokens=4)
        assert exc.value.reason == "overload"
        eng.scheduler.run_until_idle()
        eng.scheduler.drain()
        with pytest.raises(RequestRejected) as exc:
            eng.submit(_prompt(4), max_new_tokens=4)
        assert exc.value.reason == "draining"
    finally:
        eng.close()
    with pytest.raises(RequestRejected) as exc:
        eng.submit(_prompt(4), max_new_tokens=4)  # shut down
    assert exc.value.reason == "draining"


def test_request_rejected_rejects_unknown_reason():
    with pytest.raises(ValueError, match="unknown rejection reason"):
        RequestRejected("msg", reason="bogus")


def test_degraded_shed_reason_is_overload():
    eng = _healing_engine(
        inference={"queue_depth": 4, "degraded_queue_ratio": 0.5}
    )
    try:
        for _ in range(2):  # 2/4 fills to the degraded ratio
            eng.submit(_prompt(4), max_new_tokens=2)
        with pytest.raises(RequestRejected) as exc:
            eng.submit(_prompt(4), max_new_tokens=2, priority=1)
        assert exc.value.reason == "overload"
        eng.scheduler.run_until_idle()
    finally:
        eng.close()


def test_load_snapshot_reports_live_idle_state():
    """load_snapshot() is the router's placement input: queue depth and
    slot occupancy must be LIVE values even when no drive loop is
    running — and sampling must refresh the infer/queue_depth gauge an
    idle replica would otherwise leave stale."""
    cfg, model, params = _small_model()
    eng = _engine(model, params, inference={"max_batch_slots": 2})
    try:
        snap = eng.load_snapshot()
        assert snap["queue_depth"] == 0
        assert snap["active_slots"] == 0
        assert snap["free_slots"] == 2
        assert snap["health"] == 0
        assert snap["driving"] is False
        assert snap["stopped"] is False
        assert snap["driver_failed"] is False
        assert snap["mean_prefill_ms"] == 0.0

        # completion-progress markers: the fleet tier's zombie detection
        # watches these move (docs/serving.md "Zombie detection")
        assert snap["requests_completed"] == 0
        assert snap["tokens_generated"] == 0

        # pile submissions up WITHOUT stepping: an idle replica, loaded
        for _ in range(3):
            eng.submit(_prompt(4), max_new_tokens=2)
        snap = eng.load_snapshot()
        assert snap["queue_depth"] == 3
        # the gauge refreshed from the snapshot sample, not a drive loop
        assert eng.metrics.snapshot()["infer/queue_depth"] == 3

        eng.scheduler.run_until_idle()
        snap = eng.load_snapshot()
        assert snap["queue_depth"] == 0
        assert snap["mean_prefill_ms"] > 0.0
        assert snap["mean_decode_ms"] > 0.0
        assert eng.metrics.snapshot()["infer/queue_depth"] == 0
        # progress moved with the completed work, JSON-safe ints
        assert snap["requests_completed"] == 3
        assert snap["tokens_generated"] == 6
        assert isinstance(snap["requests_completed"], int)
        assert isinstance(snap["tokens_generated"], int)
    finally:
        eng.close()
