"""The control and the broken path, at a size a test run can hold.

1. The reference computed in fp8 in the program's place reads numbers that
   the sound program (bf16 against float32) does not come near.
2. A run driven past the harness's look for a chip, with the timed path
   broken underneath, comes out with ``correct`` false: a training step
   that returns its state unchanged; a served token altered where it is
   produced.
"""

import json

import jax
import pytest

from benchmark import control, run
from benchmark.loops import serve, train


def _correct(result):
    return all(c["ok"] for c in result["checks"])


def _checks(result):
    return {c["name"]: c for c in result["checks"]}


def test_lower_precision_reads_far_from_sound_training(sound_training):
    sound = {c["name"]: c["value"] for c in sound_training["result"]["checks"]}
    ctx = run.context("gpt2-large.train-seq1024", 11, 1.0, rehearse=True)
    lower = control.train_control(ctx)
    # at this toy depth the two lie closer than at the cell's own size (PERF.md gives those readings)
    assert lower["grad_norm_gap"] > 2 * sound["grad_norm_gap"]


def test_training_step_that_returns_its_state_unchanged(monkeypatch):
    from benchmark import program

    real = program.build_train

    def broken(*args, **kw):
        engine = real(*args, **kw)
        step = engine._jit_train_window.__wrapped__
        engine._jit_train_window = jax.jit(
            lambda p, o, s, *rest: (p, o, s) + step(p, o, s, *rest)[3:])
        return engine

    monkeypatch.setattr(program, "build_train", broken)
    ctx = run.context("gpt2-large.train-seq1024", 12, 1.0, rehearse=True)
    result = train.run(ctx)
    assert not _correct(result)
    checks = _checks(result)
    assert not checks["change_norm_gap"]["ok"]
    assert checks["change_norm_gap"]["value"] == pytest.approx(1.0)


def test_update_of_the_wrong_size_fails_after_one_step(monkeypatch):
    """BERT's LAMB steps from random weights amplify rounding, so the limits
    on what three steps leave are loose (PERF.md); the change after ONE step
    holds the size of the update: a rate 1.4 times the configuration's."""
    from benchmark import program

    real = program.build_train

    def broken(config, *args, **kw):
        config = json.loads(json.dumps(config))
        config["train"]["engine"]["optimizer"]["params"]["lr"] *= 1.4
        return real(config, *args, **kw)

    monkeypatch.setattr(program, "build_train", broken)
    ctx = run.context("bert-large.pretrain-seq128", 15, 1.0, rehearse=True)
    result = train.run(ctx)
    checks = _checks(result)
    assert not _correct(result)
    assert not checks["first_change_norm_gap"]["ok"]
    assert checks["first_change_norm_gap"]["value"] == pytest.approx(0.4, abs=0.03)


def test_served_token_altered_where_it_is_produced(monkeypatch, serve_toy):
    from benchmark import program

    real = program.build_serve

    def broken(*args, **kw):
        engine = real(*args, **kw)
        decode = engine.decode_tokens
        engine.decode_tokens = lambda slots: [
            (int(t) + 1) % 400 for t in decode(slots)]
        return engine

    monkeypatch.setattr(program, "build_serve", broken)
    ctx = run.context(serve_toy, 13, 3.0, rehearse=True)
    result = serve.run(ctx)
    assert not _correct(result)
    assert not _checks(result)["widest_gap_below_best"]["ok"]


def test_sound_rehearsal_is_correct(serve_toy):
    ctx = run.context(serve_toy, 14, 3.0, rehearse=True)
    ctx["t_process"] -= 1000.0    # set-up is counted from the loop's devices
    result = serve.run(ctx)
    assert _correct(result), json.dumps(result["checks"])
    assert 0 < result["end_to_end"]["setup_s"] < 1000.0
    assert result["end_to_end"]["ttft_p90_ms"] > 0
