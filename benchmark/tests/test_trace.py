"""The trace reduction on a small recorded trace: ``probe.xplane.pb``, made
on a TPU v5 lite by ``record_trace.py`` (PR 23): two fused training windows
of a two-layer GPT-2 at seq 1024, then eight scheduler steps of its paged
decode. The numbers below were read off the same file by hand with
``jax.profiler.ProfileData``, which shares no code with ``xplane.py``."""

import os

import pytest

from benchmark import trace, xplane
from benchmark.readers import kernel_time, module_gap, scope_time

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def probe():
    return trace.Trace(None, path=os.path.join(HERE, "probe.xplane.pb"),
                       whole=True)


def ctx_of(probe):
    return {"trace": probe}


def test_runs_and_gaps_between_them(probe):
    runs = probe.runs("train_window")
    assert [r.duration_ps for r in runs] == [1391690000, 1391191250]
    assert probe.gaps_between_runs("train_window") == pytest.approx(
        [4919505e-9], rel=1e-6)
    assert module_gap.read(ctx_of(probe), None, "train_window") == \
        pytest.approx(4.919505, rel=1e-6)
    assert len(probe.runs("decode_fn")) == 5


def test_kernel_time_per_run(probe):
    c = ctx_of(probe)
    assert len(probe.kernel_events("flash_fwd")) == 8
    assert kernel_time.read(c, None, "train_window", ["flash_fwd"]) == \
        pytest.approx(466891e-6 / 2, rel=1e-5)
    assert kernel_time.read(c, None, "decode_fn", ["paged_flash_decode"]) == \
        pytest.approx(63431e-6 / 5, rel=3e-4)  # the hand reading is in whole ns
    # BERT's rule: a kernel that never ran reads as nothing, not as zero
    assert kernel_time.read(c, None, "decode_fn", ["flash_fwd"]) is None
    assert kernel_time.read(c, None, "no_such_program", ["flash_fwd"]) is None


def test_a_bypassed_kernel_found_in_the_trace_fails_the_run(probe):
    from benchmark import run

    cell = {"trace_must_not_run": ["flash_fwd", "no_such_kernel"]}
    found, absent = run.bypassed_kernels(cell, probe)
    assert found == {"name": "flash_fwd_calls_in_trace", "value": 8,
                     "limit": 0, "ok": False}
    assert absent["ok"] and absent["value"] == 0
    assert run.bypassed_kernels({}, probe) == []


def test_scope_time_counts_each_operation_once(probe):
    c = ctx_of(probe)
    fwd_bwd = scope_time.read(c, None, "train_window", "window_fwd_bwd")
    update = scope_time.read(c, None, "train_window", "window_optimizer_update")
    run_ms = 1.39144
    assert 0 < update < fwd_bwd
    # the while loops' bodies are not counted a second time with the loops
    assert 0.9 * run_ms < fwd_bwd + update <= run_ms
    assert scope_time.read(c, None, "train_window", "no_such_scope") == 0.0


def test_self_times_and_union():
    Ev = xplane.Event
    outer = Ev("while.1", "", 0, 100, {})
    a, b = Ev("fusion.1", "", 10, 30, {}), Ev("fusion.2", "", 50, 40, {})
    own = {e.name: t for e, t in trace.self_times([b, outer, a])}
    assert own == {"while.1": 30, "fusion.1": 30, "fusion.2": 40}
    total, merged = trace.union([(0, 10), (5, 20), (30, 40)])
    assert total == 30 and merged == [[0, 20], [30, 40]]
    assert trace.gaps(merged, (0, 50)) == [(20, 30), (40, 50)]
    assert trace.base_name("flash_fwd.7") == "flash_fwd"


def test_idle_share_and_breakdown(probe):
    busy, merged = probe.busy(probe.devices[0])
    width = probe.window[1] - probe.window[0]
    assert 0 < busy < width
    assert probe.idle_share() == pytest.approx(1 - busy / width)
    # most of this recording is the host between programs
    assert probe.idle_share() > 0.9
    report = probe.busy_and_window()
    assert report["busy_s"] == pytest.approx(busy * 1e-12)
    down = probe.breakdown()
    assert len(down["device_ops"]) == 10 and down["device_ops"][0][1] > 0
    names = {n for n, _ in down["idle_gaps"]}
    assert names <= {"bench.submit", "bench.readback", "bench.stage",
                     "bench.step", "no benchmark span"}
    assert {"bench.step", "bench.readback"} & names


def test_exposed_collective_time_on_a_made_trace():
    """No recorded four-chip trace yet (PERF.md, Open questions): the
    arithmetic on a hand-made one. A 40 ps all-reduce, 15 ps of it under a
    fusion: 25 ps exposed."""
    Ev = xplane.Event
    dev = trace.Device(xplane.Plane("/device:TPU:0", [
        xplane.Line("XLA Modules", [Ev("jit_w(1)", "", 0, 100, {})]),
        xplane.Line("XLA Ops", [Ev("fusion.1", "", 0, 45, {}),
                                Ev("all-reduce.3", "", 30, 40, {}),
                                Ev("fusion.2", "", 80, 20, {})]),
    ]))
    t = trace.Trace.__new__(trace.Trace)
    t.devices, t.window, t.host = [dev], (0, 100), []
    total, exposed = t.collectives("w")
    assert total == pytest.approx(40e-12) and exposed == pytest.approx(25e-12)
