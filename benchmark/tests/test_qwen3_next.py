"""The cells ISSUE 30 added: the configuration resolves through the harness
and holds 625,667,136 parameters at the cut, the three cost functions count
what their docstrings say, the lower-precision control moves the new
reference, and both new cells rehearse on the CPU (toy size, control flow
only)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import control, harness, run

NEW = "qwen3-next-80b-a3b.train-seq16384"
BERT512 = "bert-large.pretrain-seq512"


def test_configuration_resolves_and_counts_its_parameters():
    cell, config, bench = harness.load_cell(NEW)
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 16384, 2, 1)
    size = harness.sizes(config, False)
    ref = harness.plugin("reference", config["reference"])
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 625_667_136
    assert set(shapes) == set(config["program"]["params"])
    assert set(config["program"]["config_args"].values()) <= set(size)
    toy = ref.shapes(harness.sizes(config, True))
    assert set(toy) == set(shapes)
    # every per-layer metric that lists the cell has its file and its reader
    for entry in bench["per_layer"]:
        if NEW in entry.get("workloads", []):
            spec = harness.load_json("layer_metrics", entry["name"] + ".json")
            harness.plugin("readers", spec["reader"])


def test_costs_count_what_they_say():
    cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, False)
    flops, nbytes = harness.plugin("costs", "gated_experts").per_window(
        cell, size)
    assignments = 32768 * 10 * 32 / 512
    assert flops == 2 * 4 * assignments * 9 * 2 * 2048 * 512
    assert nbytes == 2 * 4 * (32 * 3 * 2048 * 512 * 8 + assignments * 2048 * 8)
    flops, nbytes = harness.plugin("costs", "flash_fwd_heads").per_call(
        cell, size)
    assert flops == 2 * 16 * (16384 * 16385 // 2) * 4 * 256
    assert nbytes == 2 * 16 * (4 * 16384 * 256 * 2 + 16384 * 4)
    flops, nbytes = harness.plugin("costs", "gdn_scan").per_window(cell, size)
    c, dk, dv = 64, 128, 128
    other = c * c * dk + c * c * dk + c * c * dv + 3 * c * dk * dv + c * c * dv
    products = (10 * c ** 3 + other) + (2 * c ** 3 + 2 * other)
    assert flops == 2 * 3 * (2 * 256) * 32 * 2 * products
    rows = 32768 * (2 * 2048 * 2 + 2 * 4096 * 2 + 2 * 32 * 4)
    assert nbytes == 2 * 3 * (3 * rows + 2 * 2 * 256 * 32 * dk * dv * 4)
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    # memory-bound by this count: the states and the rows take longer than
    # the products
    assert nbytes / peaks["hbm_bytes_per_s"] > flops / peaks["bf16_flops_per_s"]


def test_lower_precision_moves_the_new_reference():
    """fp8 in the reference's products moves every compared number off the
    float32 reading at toy size: the control has something to fail."""
    ctx = run.context(NEW, 5, 1.0, 0, True, chips=1)
    numbers = control.train_control(ctx)
    assert numbers["first_loss_gap"] > 1e-5
    assert numbers["grad_norm_gap"] > 1e-3


@pytest.mark.parametrize("cell", [NEW, BERT512])
def test_rehearsal_runs_to_a_result(cell):
    """Control flow only: a limit set at the real size need not hold at the
    toy size."""
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
