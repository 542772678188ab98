"""The cells ISSUE 26 added: the rehearsal of the hybrid-stack cell and of
``gpt2-large.train-accum1`` (toy size, CPU, control flow only), the lower
-precision control on the new reference at a size a test can hold, and the
reader and cost function that came with them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import control, harness, run
from benchmark.readers import scope_roofline_share

NEW = "nemotron3-super-120b-a12b.train-seq8192"


@pytest.mark.parametrize("cell", [NEW, "gpt2-large.train-accum1"])
def test_rehearsal_runs_to_a_result(cell):
    """Control flow only: a limit set at the real size need not hold at the
    toy size (GPT-2's change-norm limit does not, in the cells before too)."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", "4300000007", "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
    # through the command itself: set-up as the earlier line accounts for it,
    # and every number compared beside its limit, last in the line and on
    # standard error
    (setup,) = [json.loads(n) for n in out.stdout.splitlines()
                if n.startswith('{"note": "setup"')]
    assert line["metrics"]["setup_s"]["value"] == setup["setup_s"]
    assert setup["process_to_first_window_s"] == pytest.approx(
        setup["setup_s"] + setup["before_the_loop_s"], abs=1e-9)
    assert list(line)[-1] == "compared" and "steps_taken" in line["compared"]
    # (in this order; whatever else jax or the interpreter says on the way
    # out is not the benchmark's)
    assert [n for n in out.stderr.splitlines() if n.startswith("compared ")] == [
        f"compared {name} {c['value']!r} limit {c['limit']!r}"
        for name, c in line["compared"].items()]


def test_lower_precision_moves_the_new_reference(capsys):
    """fp8 in the reference's products moves every compared number off the
    float32 reading at toy size: the control has something to fail."""
    ctx = run.context(NEW, 5, 1.0, 0, True, chips=1)
    numbers = control.train_control(ctx)
    assert numbers["first_loss_gap"] > 1e-5
    assert numbers["grad_norm_gap"] > 1e-3


def test_scope_roofline_reads_the_scope_and_the_cost(monkeypatch):
    cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, False)
    flops, nbytes = harness.plugin("costs", "moe_experts").per_window(cell, size)
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    monkeypatch.setattr(
        scope_roofline_share.scope_time, "read",
        lambda ctx, result, module, scope: 40.0 if scope == "moe_experts" else None)
    ctx = {"cell": cell, "size": size, "peaks": peaks}
    share = scope_roofline_share.read(
        ctx, None, "train_window", "moe_experts", "moe_experts")
    least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
    assert share == pytest.approx(100.0 * least / 0.040)
    assert 0 < share < 100
    assert scope_roofline_share.read(
        ctx, None, "train_window", "no_such_scope", "moe_experts") is None
