"""The readers of the PROGRAM's own phases and scopes (PR 24), on
``phases.xplane.pb``: three staged fused training windows of a two-layer
GPT-2 at seq 1024 on a TPU v5 lite, telemetry off, recorded by
``record_phases.py``, which also read the numbers below off the same file
with ``jax.profiler.ProfileData`` (``by_hand.json``; it shares no code with
``xplane.py``). The scoped-collective arithmetic is checked on a hand-made
trace, as ``test_exposed_collective_time_on_a_made_trace`` does."""

import json
import os

import pytest

from benchmark import program_trace, trace, xplane
from benchmark.readers import (collective_scope_time, idle_under_phase,
                               phase_time, phase_total)

HERE = os.path.dirname(os.path.abspath(__file__))
NS = 1e-9


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "phases.xplane.pb")
    device = trace.Trace(None, path=path)
    with open(os.path.join(HERE, "phases_by_hand.json")) as fd:
        hand = json.load(fd)
    return {"trace": device,
            "program_trace": program_trace.ProgramTrace(path, device.window)
            }, hand


def test_the_program_phases_are_in_the_trace_by_thread(recorded):
    ctx, hand = recorded
    found = ctx["program_trace"]
    assert len(found.windows) == hand["windows"] == 3
    caller = found.caller
    for w in found.windows:
        inside = [e.name for e in caller if e is not w
                  and w.start_ps <= e.start_ps and e.end_ps <= w.end_ps]
        assert inside.count("train.dispatch") == 1
        assert inside.count("train.finish_step") == 1
        assert inside.count("train.stage_wait") == 1
    # the stager's worker is another thread
    staged = found.named("train.stage_window")
    assert staged and not any(e in caller for e in staged)
    assert {"stage.pull", "stage.stack", "stage.h2d"} <= {
        e.name for t in found.threads for e in t}


def test_phase_time_against_the_hand_reading(recorded):
    ctx, hand = recorded
    n = hand["windows"]
    window = hand["window_ns"] * NS * 1e3 / n
    wait = hand["stage_wait_ns"] * NS * 1e3 / n
    settle = hand["settle_ns"] * NS * 1e3 / n
    # the hand reading is in whole nanoseconds
    assert phase_time.read(ctx, None, "train.window", "per_window") == \
        pytest.approx(window, rel=1e-5)
    assert phase_time.read(ctx, None, "train.stage_wait", "per_window") == \
        pytest.approx(wait, rel=1e-3, abs=1e-5)
    assert phase_time.read(
        ctx, None, "train.window", "per_window",
        minus=["train.settle", "train.stage_wait"]) == \
        pytest.approx(window - wait - settle, rel=1e-4)
    assert phase_time.read(ctx, None, "train.stage_window", "median") == \
        pytest.approx(hand["stage_window_median_ns"] * NS * 1e3, rel=1e-4)
    assert phase_time.read(ctx, None, "train.no_such", "median") is None
    assert phase_time.read(ctx, None, "train.no_such", "per_window") == 0.0


def test_idle_inside_the_programs_windows(recorded, capsys):
    ctx, hand = recorded
    value = idle_under_phase.read(ctx, None, "train.window")
    assert value == pytest.approx(
        hand["idle_inside_windows_ns"] * NS * 1e3 / hand["windows"],
        rel=1e-3)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["note"] == "idle_by_program_phase"
    # every idle picosecond of the traced window goes to one name
    assert sum(note["seconds"].values()) == pytest.approx(
        hand["idle_ns"] * NS, rel=1e-4)
    assert set(note["seconds"]) <= {
        "outside the program", "train.window", "train.stage_wait",
        "train.dispatch", "train.finish_step", "train.settle"}
    assert idle_under_phase.read(ctx, None, "train.no_such") is None


def test_a_program_without_phases_reads_as_nothing():
    """The parent of PR 24 under this PR's benchmark files: the recorded
    ``probe.xplane.pb`` of PR 23 holds no program phase."""
    path = os.path.join(HERE, "probe.xplane.pb")
    device = trace.Trace(None, path=path, whole=True)
    ctx = {"trace": device,
           "program_trace": program_trace.ProgramTrace(path, device.window)}
    assert ctx["program_trace"].threads == []
    assert phase_time.read(ctx, None, "train.window", "per_window") is None
    assert phase_time.read(ctx, None, "train.stage_window", "median") is None
    assert idle_under_phase.read(ctx, None, "train.window") is None
    # one chip: no collective at all, scoped or not
    assert collective_scope_time.read(
        ctx, None, "train_window", "window_fwd_bwd") is None


def made_trace():
    """One run of ``jit_w`` over [0, 200) ps. The gradients' all-reduce
    (40 ps, 15 of them under a fusion), the update's gather (30 ps, alone),
    the norm's all-reduce (10 ps, 5 under a fusion), an async gather of the
    update (30 ps, 10 under a fusion) and a permute without metadata (5
    ps, alone)."""
    Ev = xplane.Event
    fwd = {"tf_op": "jit(w)/window_fwd_bwd/while/body/transpose(jvp(m))/dot_general:"}
    norm = {"tf_op": "jit(w)/window_optimizer_update/update_grad_norm/reduce_sum:"}
    apply_ = {"tf_op": "jit(w)/window_optimizer_update/update_apply/sub:"}
    dev = trace.Device(xplane.Plane("/device:TPU:0", [
        xplane.Line("XLA Modules", [Ev("jit_w(1)", "", 0, 200, {})]),
        xplane.Line("XLA Ops", [
            Ev("fusion.1", "", 0, 45, {}),
            Ev("all-reduce.3", "", 30, 40, fwd),
            Ev("fusion.2", "", 80, 20, {}),
            Ev("all-gather.5", "", 100, 30, apply_),
            Ev("all-reduce.9", "", 130, 10, norm),
            Ev("fusion.3", "", 135, 15, {}),
            Ev("all-gather-start.7", "", 150, 1, apply_),
            Ev("fusion.4", "", 160, 10, {}),
            Ev("all-gather-done.7", "", 179, 1, apply_),
            Ev("collective-permute.1", "", 185, 5, {}),
        ]),
        xplane.Line("Async XLA Ops", [Ev("all-gather.7", "", 150, 30, apply_)]),
    ]))
    t = trace.Trace.__new__(trace.Trace)
    t.devices, t.window, t.host = [dev], (0, 200), []
    return t


def test_scoped_collective_time_on_a_made_trace(capsys):
    t = made_trace()
    ctx = {"trace": t}
    grads = collective_scope_time.read(ctx, None, "w", "window_fwd_bwd")
    update = collective_scope_time.read(
        ctx, None, "w", "window_optimizer_update")
    assert grads == pytest.approx(25e-9)           # ms: 25 ps
    assert update == pytest.approx((30 + 5 + 20) * 1e-9)
    assert collective_scope_time.read(
        ctx, None, "w", "update_grad_norm") == pytest.approx(5e-9)
    assert collective_scope_time.read(ctx, None, "w", "no_such") is None
    # with the permute that carries no scope they are all of the exposed time
    total, exposed = t.collectives("w")
    assert total == pytest.approx(115e-12)
    assert exposed == pytest.approx((grads + update) * 1e-3 + 5e-12)
    # one note a run, however many metrics read it: every collective by name
    notes = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    (note,) = [n for n in notes if n["note"] == "collectives_by_scope"]
    rows = {r["name"]: r for r in note["collectives"]}
    assert set(rows) == {"all-reduce.3", "all-gather.5", "all-reduce.9",
                         "all-gather.7", "collective-permute.1"}
    assert rows["all-reduce.3"]["tf_op"].endswith("dot_general:")
    assert collective_scope_time.brief(
        "jit(f)/window_fwd_bwd/while/body/closed_call/transpose(jvp(M))/"
        "transformer/while/body/h/checkpoint/reduce_sum:") == \
        "jit(f)/window_fwd_bwd/while/.../h/checkpoint/reduce_sum:"
    assert rows["all-reduce.3"]["exposed_ms_per_run"] == pytest.approx(25e-9)
    assert rows["collective-permute.1"]["tf_op"] == ""


def test_per_window_and_idle_on_a_made_trace():
    """Two windows of 100 ps on the calling thread, each with a 10 ps wait;
    a worker's event; the device idle for 20 ps inside the first window's
    wait, 5 ps inside the second's dispatch and 30 ps between them."""
    Ev = xplane.Event
    found = program_trace.ProgramTrace.__new__(program_trace.ProgramTrace)
    found.adopt((0, 300), [
        [Ev("train.window", "", 10, 100, {}),
         Ev("train.stage_wait", "", 10, 10, {}),
         Ev("train.dispatch", "", 20, 30, {}),
         Ev("train.window", "", 150, 100, {}),
         Ev("train.stage_wait", "", 150, 10, {}),
         Ev("train.dispatch", "", 160, 30, {})],
        [Ev("train.stage_window", "", 40, 50, {}),
         Ev("train.stage_wait", "", 40, 7, {})],   # a loader's, elsewhere
    ])
    assert found.per_window("train.window") == pytest.approx(100e-12)
    assert found.per_window("train.stage_wait") == pytest.approx(10e-12)
    dev = trace.Device(xplane.Plane("/device:TPU:0", [
        xplane.Line("XLA Ops", [Ev("fusion.1", "", 0, 5, {}),
                                Ev("fusion.2", "", 25, 95, {}),
                                Ev("fusion.3", "", 150, 15, {}),
                                Ev("fusion.4", "", 170, 130, {})]),
    ]))
    t = trace.Trace.__new__(trace.Trace)
    t.devices, t.window, t.host = [dev], (0, 300), []
    by_phase, inside = found.idle_by_phase(t)
    assert by_phase == {"train.stage_wait": 20, "outside the program": 30,
                        "train.dispatch": 5}
    assert inside == 25


def test_phase_total_sums_what_the_named_phases_paid(capsys):
    from deepspeed_tpu.telemetry import tracing

    tracing.reset_phase_totals()
    for name, seconds in (("compile.backend@train.dispatch", 2.0),
                          ("compile.cache_load@train.dispatch", 1.5),
                          ("compile.trace@init.build_steps", 0.25),
                          ("compile.lower@-", 4.0),
                          ("init.build_steps", 0.5),
                          ("train.dispatch", 3.0)):
        tracing.add_phase_time(name, seconds)
    args = {"kinds": ["compile.trace", "compile.lower", "compile.backend"],
            "charged_to": ["init.", "train."], "also": ["init."]}
    assert phase_total.read({}, None, **args) == pytest.approx(2.25)
    note = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert note["beside"] == {"compile.cache_load@train.dispatch": 1.5,
                              "compile.lower@-": 4.0,
                              "init.build_steps": 0.5}
    tracing.reset_phase_totals()
    assert phase_total.read({}, None, **args) is None
