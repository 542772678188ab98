"""Speculative decoding (docs/inference.md "Speculative decoding"):
draft-proposes-k / target-verifies-in-one-step with exact greedy parity
BY CONSTRUCTION — pinned here against the sequential non-speculative
engine across both acceptance regimes (an independent random draft that
mostly rejects, and a truncated agreeing draft that mostly accepts),
plus the zero-recompile pin across acceptance lengths, the length-cap
null-redirect (verify writes near max_seq_len must not corrupt shared
prefix pages), and the config/API guard rails."""

import copy

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.config.config import DeepSpeedConfigError
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

VOCAB = 97


def _small_model(seed=0, n_layer=2, n_embd=32):
    cfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=n_embd, n_layer=n_layer,
        n_head=4, dropout=0.0, use_flash=False,
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


def _engine(model, params, extra=None, **kw):
    block = {"max_batch_slots": 4, "max_seq_len": 48, "prefill_len": 32,
             "kv_block_size": 8, "sampling": {"greedy": True}}
    block.update(extra or {})
    return deepspeed_tpu.init_inference(
        model=model, model_parameters=params,
        config={"inference": block}, **kw,
    )


def _prompt(n=8, seed=1):
    return [int(t) for t in np.random.default_rng(seed).integers(0, VOCAB, n)]


def _agreeing_pair(seed=0, keep_layers=1):
    """(target model/params, draft model/params) that AGREE on every
    greedy choice by construction: the draft carries the target's first
    ``keep_layers`` blocks + embeddings/ln_f, and the target's REMAINING
    blocks have zero attn_ow/output_w (+ biases) — pre-LN residual
    blocks with zero output projections contribute exactly 0.0 to the
    stream, so target logits equal draft logits while the target still
    pays full-depth compute. The high-acceptance regime with no
    training. The same construction (and the same residual-path key
    set) lives in tests/drills/_common.py:agreeing_draft_target — keep them in
    sync."""
    cfg, model, params = _small_model(seed=seed)
    tparams = jax.tree_util.tree_map(np.asarray, params)
    t2 = copy.deepcopy(tparams)
    h = t2["transformer"]["h"]
    for key in ("attn_ow", "output_w", "attn_ob", "output_b"):
        arr = np.array(h[key])
        arr[keep_layers:] = 0.0
        h[key] = arr
    dcfg = GPT2Config(
        vocab_size=VOCAB, n_positions=64, n_embd=32, n_layer=keep_layers,
        n_head=4, dropout=0.0, use_flash=False,
    )
    dmodel = GPT2LMHeadModel(dcfg)
    dparams = copy.deepcopy(tparams)
    dparams["transformer"]["h"] = {
        k: np.array(v)[:keep_layers]
        for k, v in tparams["transformer"]["h"].items()
    }
    return model, t2, dmodel, dparams


# ---------------------------------------------------------------------------
# greedy parity across acceptance regimes
# ---------------------------------------------------------------------------
def test_spec_parity_with_rejecting_random_draft():
    """An INDEPENDENT random draft (frequent rejections) must still
    yield bitwise-identical greedy tokens: every committed token is the
    target's own argmax whatever the draft proposed."""
    cfg, model, params = _small_model()
    _, dmodel, dparams = _small_model(seed=7, n_layer=1)
    e_ref = _engine(model, params)
    e_spec = _engine(
        model, params, {"speculative": {"k": 3}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        prompts = [_prompt(9, 1), _prompt(5, 2), _prompt(13, 3)]
        assert e_ref.generate(prompts, max_new_tokens=10) == \
            e_spec.generate(prompts, max_new_tokens=10)
        snap = e_spec.metrics.snapshot()
        assert snap["infer/spec_proposed"] > 0
        assert 0.0 <= snap["infer/spec_acceptance_rate"] <= 1.0
    finally:
        e_ref.close()
        e_spec.close()


def test_spec_parity_and_acceptance_with_agreeing_draft():
    """The high-acceptance regime: a truncated draft that agrees with
    the target by construction. Parity still holds, the acceptance rate
    approaches 1, and each scheduler step commits multiple tokens."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    e_ref = _engine(model, tparams)
    e_spec = _engine(
        model, tparams, {"speculative": {"k": 4}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        prompts = [_prompt(9, 1), _prompt(5, 2)]
        assert e_ref.generate(prompts, max_new_tokens=12) == \
            e_spec.generate(prompts, max_new_tokens=12)
        snap = e_spec.metrics.snapshot()
        assert snap["infer/spec_acceptance_rate"] > 0.8, snap
        # k+1 tokens per accepted cycle => far fewer decode steps than
        # tokens: the whole point of the stack
        assert snap["infer/token_latency_ms/count"] * 2 <= \
            snap["infer/tokens_generated"]
    finally:
        e_ref.close()
        e_spec.close()


def test_spec_parity_mid_flight_join_and_eos_reuse():
    """The continuous-batching matrix on the speculative path: a
    mid-flight join and EOS slot reuse produce the sequential engine's
    exact tokens, and EOS mid-burst discards the burst's tail."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    e_ref = _engine(model, tparams)
    e_spec = _engine(
        model, tparams, {"speculative": {"k": 3}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        r1r = e_ref.submit(_prompt(8, 4), max_new_tokens=12)
        r1s = e_spec.submit(_prompt(8, 4), max_new_tokens=12)
        for _ in range(2):
            e_ref.scheduler.step()
        e_spec.scheduler.step()
        r2r = e_ref.submit(_prompt(7, 5), max_new_tokens=8)
        r2s = e_spec.submit(_prompt(7, 5), max_new_tokens=8)
        e_ref.scheduler.run_until_idle()
        e_spec.scheduler.run_until_idle()
        assert r1r.result(0) == r1s.result(0)
        assert r2r.result(0) == r2s.result(0)

        # EOS: pick a token the reference emits mid-stream; the burst
        # containing it must truncate exactly there
        ref = e_ref.generate([_prompt(8, 6)], max_new_tokens=8)[0]
        eos = ref[3]
        ar = e_ref.submit(_prompt(8, 6), max_new_tokens=8, eos_token_id=eos)
        asp = e_spec.submit(_prompt(8, 6), max_new_tokens=8, eos_token_id=eos)
        e_ref.scheduler.run_until_idle()
        e_spec.scheduler.run_until_idle()
        assert ar.finish_reason == asp.finish_reason == "eos"
        assert ar.result(0) == asp.result(0)
        # the freed slot serves the next request exactly
        assert e_ref.generate([_prompt(6, 9)], max_new_tokens=6) == \
            e_spec.generate([_prompt(6, 9)], max_new_tokens=6)
    finally:
        e_ref.close()
        e_spec.close()


def test_spec_disables_inert_fused_flag_and_prefix_cache_composes():
    """fused_decode configured on a speculative engine is INERT (the
    verify step is multi-token XLA, the draft rides a contiguous
    cache) — the engine disables it so infer/fused_decode reports what
    actually served. The prefix cache, by contrast, genuinely composes:
    hits still serve suffix-only under speculation, with parity."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    e_ref = _engine(model, tparams)
    e_both = _engine(
        model, tparams,
        {"speculative": {"k": 3}, "fused_decode": True},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        assert e_both.speculative and not e_both.fused_decode
        assert e_both.metrics.gauge("infer/fused_decode").value == 0
        shared = _prompt(16, 7)
        for tail_seed in (8, 9):
            p = [shared + _prompt(3, tail_seed)]
            assert e_ref.generate(p, max_new_tokens=6) == \
                e_both.generate(p, max_new_tokens=6)
        assert e_both.metrics.counter("infer/prefix_hits").value >= 1
    finally:
        e_ref.close()
        e_both.close()


def test_spec_length_cap_null_redirect_protects_shared_pages():
    """A speculative request finishing AT the length cap: its verify
    step's would-be writes past max_seq_len redirect to the null page
    instead of clamping into the slot's real last page — which can be a
    SHARED prefix page. Pinned by serving the same long shared prefix
    again afterwards and comparing against a never-shared engine."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    e_spec = _engine(
        model, tparams, {"speculative": {"k": 4}, "prefill_len": 40},
        draft_model=dmodel, draft_parameters=dparams,
    )
    e_cold = _engine(
        model, tparams,
        {"prefix_cache": {"enabled": False}, "prefill_len": 40},
    )
    try:
        shared = _prompt(32, 11)  # 4 full pages of shared prefix
        pa = shared + _prompt(2, 12)
        # run to the cap: 34 prompt + up to 30 => hits max_seq_len=48
        ra = e_spec.submit(pa, max_new_tokens=30)
        e_spec.scheduler.run_until_idle()
        assert ra.finish_reason == "length"
        assert ra.result(0) == e_cold.generate(
            [pa], max_new_tokens=30
        )[0]
        # the shared pages must still hold the PREFIX's k/v: a second
        # request hitting them decodes exactly like a cache-less engine
        pb = shared + _prompt(2, 13)
        hot = e_spec.generate([pb], max_new_tokens=6)[0]
        assert e_spec.metrics.counter("infer/prefix_hits").value >= 1
        assert hot == e_cold.generate([pb], max_new_tokens=6)[0]
    finally:
        e_spec.close()
        e_cold.close()


# ---------------------------------------------------------------------------
# zero steady-state recompiles across acceptance lengths
# ---------------------------------------------------------------------------
def test_spec_decode_does_not_recompile_across_acceptance_lengths():
    """k is static, acceptance length is DATA: scheduler steps whose
    bursts commit varying token counts (an INDEPENDENT random draft
    makes acceptance genuinely data-dependent per step) add zero XLA
    backend compiles after warmup."""
    cfg, model, params = _small_model()
    _, dmodel, dparams = _small_model(seed=7, n_layer=1)
    e_spec = _engine(
        model, params, {"speculative": {"k": 3}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        recompiles = e_spec.metrics.counter("jax/recompiles")
        e_spec.generate([_prompt(8, 1)], max_new_tokens=6)
        warm = recompiles.value
        assert warm > 0
        seen_commits = set()
        for seed in range(2, 8):
            r = e_spec.submit(
                _prompt(5 + seed, seed), max_new_tokens=6 + seed % 3
            )
            steps_before = e_spec.metrics.snapshot()[
                "infer/token_latency_ms/count"
            ]
            e_spec.scheduler.run_until_idle()
            steps = e_spec.metrics.snapshot()[
                "infer/token_latency_ms/count"
            ] - steps_before
            seen_commits.add((len(r.result(0)), int(steps)))
        # the acceptance/commit pattern genuinely varied across requests
        assert len(seen_commits) > 1, seen_commits
        assert recompiles.value == warm, (
            f"speculative path recompiled: {recompiles.value - warm} new "
            "backend compiles across varied acceptance lengths"
        )
    finally:
        e_spec.close()


# ---------------------------------------------------------------------------
# telemetry + tracing
# ---------------------------------------------------------------------------
def test_spec_streams_and_phase_spans(tmp_path):
    """infer/spec_* streams move and the tracer's ring carries the
    sched.spec_draft/spec_verify/spec_commit phase spans a flight dump
    would show (docs/observability.md)."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    engine = deepspeed_tpu.init_inference(
        model=model, model_parameters=tparams,
        config={
            "inference": {
                "max_batch_slots": 2, "max_seq_len": 48,
                "prefill_len": 32, "kv_block_size": 8,
                "sampling": {"greedy": True},
                "speculative": {"k": 3},
            },
            "telemetry": {
                "enabled": True, "output_path": str(tmp_path),
                "job_name": "spec_spans", "exporters": [],
                "watchdog": {"enabled": False},
                "tracing": {"enabled": True, "ring_events": 1024,
                            "export": "none"},
            },
        },
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        engine.generate([_prompt(8, 1)], max_new_tokens=6)
        names = {s["name"] for s in engine.tracer.flight_snapshot()}
        for want in (
            "sched.decode_step", "sched.spec_draft", "sched.spec_verify",
            "sched.spec_commit",
        ):
            assert want in names, f"{want} missing from {sorted(names)}"
        snap = engine.metrics.snapshot()
        assert snap["infer/spec_proposed"] > 0
        assert snap["infer/spec_accepted"] > 0
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------
def test_spec_requires_draft_model():
    cfg, model, params = _small_model()
    with pytest.raises(DeepSpeedConfigError, match="draft"):
        _engine(model, params, {"speculative": {"k": 2}})


def test_spec_requires_greedy_sampling():
    cfg, model, params = _small_model()
    _, dmodel, dparams = _small_model(seed=7, n_layer=1)
    with pytest.raises(DeepSpeedConfigError, match="[Gg]reedy"):
        _engine(
            model, params,
            {"speculative": {"k": 2},
             "sampling": {"greedy": False, "temperature": 0.8}},
            draft_model=dmodel, draft_parameters=dparams,
        )


def test_spec_submit_rejects_nonzero_temperature():
    model, tparams, dmodel, dparams = _agreeing_pair()
    engine = _engine(
        model, tparams, {"speculative": {"k": 2}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        with pytest.raises(ValueError, match="speculative"):
            engine.submit(_prompt(6), temperature=0.7)
    finally:
        engine.close()


def test_spec_rejects_vocab_mismatch():
    cfg, model, params = _small_model()
    dcfg = GPT2Config(
        vocab_size=VOCAB + 1, n_positions=64, n_embd=32, n_layer=1,
        n_head=4, dropout=0.0, use_flash=False,
    )
    dmodel = GPT2LMHeadModel(dcfg)
    ids0 = jnp.zeros((1, 8), jnp.int32)
    dparams = dmodel.init(
        {"params": jax.random.PRNGKey(3), "dropout": jax.random.PRNGKey(4)},
        ids0, ids0,
    )["params"]
    with pytest.raises(DeepSpeedConfigError, match="vocab"):
        _engine(
            model, params, {"speculative": {"k": 2}},
            draft_model=dmodel, draft_parameters=dparams,
        )


def test_spec_driver_restart_resets_draft_cache_and_serves_on():
    """A decode crash on the speculative path restarts like any other:
    fresh target pool AND fresh draft cache from pinned params, queue
    preserved, post-restart output exactly a clean engine's."""
    model, tparams, dmodel, dparams = _agreeing_pair()
    engine = _engine(
        model, tparams,
        {"speculative": {"k": 3}, "driver_restart_budget": 1},
        draft_model=dmodel, draft_parameters=dparams,
    )
    clean = _engine(
        model, tparams, {"speculative": {"k": 3}},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        engine.generate([_prompt(8, 1)], max_new_tokens=4)
        original = engine.decode_tokens

        def crash_once(active):
            engine.decode_tokens = original
            raise RuntimeError("injected decode crash")

        r = engine.submit(_prompt(9, 2), max_new_tokens=6)
        engine.decode_tokens = crash_once
        engine.scheduler.run_until_idle()
        assert r.finish_reason == "error"
        assert engine.scheduler.restarts_used == 1
        out = engine.generate([_prompt(10, 3)], max_new_tokens=6)
        assert out == clean.generate([_prompt(10, 3)], max_new_tokens=6)
    finally:
        engine.close()
        clean.close()
