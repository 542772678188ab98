"""The cell ISSUE 32 added: the configuration resolves through the harness
and holds 817,991,681 parameters at the cut, the reference's exit
distribution and loss are the equations written out, the flash cost reads
the cell's heads, the lower-precision control moves the new reference, and
the cell rehearses on the CPU (toy size, control flow only)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import control, harness, run
from benchmark.reference import ops
from benchmark.reference import ouro as ref

NEW = "ouro-2.6b.train-seq8192"


def test_configuration_resolves_and_counts_its_parameters():
    cell, config, bench = harness.load_cell(NEW)
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        1, 8192, 2, 1)
    size = harness.sizes(config, False)
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 817_991_681
    assert set(shapes) == set(config["program"]["params"])
    assert set(config["program"]["config_args"].values()) <= set(size)
    assert set(ref.shapes(harness.sizes(config, True))) == set(shapes)
    assert len(config["train"]["model_args"]["pattern"]) == \
        2 * size["num_hidden_layers"]
    # every per-layer metric that lists the cell has its file and its reader
    listed = [e["name"] for e in bench["per_layer"]
              if NEW in e.get("workloads", [])]
    assert {"swiglu_ffn_ms.train", "loop_head_loss_ms.train",
            "flash_fwd_d128_roofline.train", "attn_mixer_ms.train",
            "flash_ms.train"} <= set(listed)
    for name in listed:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.plugin("readers", spec["reader"])


def test_flash_cost_reads_the_cells_heads():
    cell, config, _bench = harness.load_cell(NEW)
    flops, nbytes = harness.plugin("costs", "flash_fwd_heads").per_call(
        cell, harness.sizes(config, False))
    assert flops == 16 * (8192 * 8193 // 2) * 4 * 128
    assert nbytes == 16 * (4 * 8192 * 128 * 2 + 8192 * 4)


def test_reference_loss_is_the_equations_written_out():
    """Two passes of one layer at toy size, every step spelled out here:
    sandwich norms, whole-head rotary, the norm inside the loop, the gate,
    the exit distribution and the entropy term."""
    cfg = {**harness.sizes(harness.load_cell(NEW)[1], True),
           "num_hidden_layers": 1, "total_ut_steps": 2}
    dot = ops.make_dot("float32")
    p = ref.init_params(ops.seed_key(3), cfg)
    p["gate.b"] = p["gate.b"] + 0.4
    ids = np.random.default_rng(0).integers(0, 512, (2, 24)).astype(np.int32)
    eps, heads, d = cfg["rms_norm_eps"], 2, 16

    def rms(x, g):
        return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def rope(x):   # [B, S, H, D]
        pos = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None]
        inv = cfg["rope_theta"] ** (-jnp.arange(0, d, 2) / d)
        a, b = x[..., :d // 2], x[..., d // 2:]
        cos, sin = jnp.cos(pos * inv), jnp.sin(pos * inv)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def one_pass(x):
        u = rms(x, p["attn.norm_a.g"][0])
        q, k, v = (jnp.reshape(u @ p[f"attn.{w}"][0], (2, 24, heads, d))
                   for w in ("wq", "wk", "wv"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", rope(q), rope(k)) / d ** 0.5
        scores = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), scores, -1e30)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + rms(ctx.reshape(2, 24, heads * d) @ p["attn.wo"][0],
                    p["attn.norm_b.g"][0])
        u = rms(x, p["ffn.norm_a.g"][0])
        f = (jax.nn.silu(u @ p["ffn.wg"][0]) * (u @ p["ffn.wu"][0])) \
            @ p["ffn.wd"][0]
        return rms(x + rms(f, p["ffn.norm_b.g"][0]), p["norm_f.g"])

    h1 = one_pass(p["embed"][ids])
    h2 = one_pass(h1)
    lam1 = jax.nn.sigmoid(h1 @ p["gate.w"] + p["gate.b"])
    probs = jnp.stack([lam1, 1.0 - lam1])
    nll = jnp.stack([ops.nll(h @ p["head"].T, jnp.roll(ids, -1, 1))
                     for h in (h1, h2)])
    per_position = jnp.sum(probs * nll, 0) \
        + cfg["exit_entropy_weight"] * jnp.sum(probs * jnp.log(probs), 0)
    want = jnp.sum(per_position[:, :-1])
    with jax.default_matmul_precision("highest"):
        got = ref.loss_sums(p, {"input_ids": ids}, cfg, dot)[0]
    np.testing.assert_allclose(got, want, rtol=2e-6)
    assert ref.counts({"input_ids": ids}) == (2 * 23,)
    np.testing.assert_allclose(
        ref.logits(p, ids, cfg, dot), h2 @ p["head"].T, atol=1e-5)


def test_lower_precision_moves_the_new_reference():
    """fp8 in the reference's products moves every compared number off the
    float32 reading at toy size: the control has something to fail."""
    ctx = run.context(NEW, 5, 1.0, 0, True, chips=1)
    numbers = control.train_control(ctx)
    assert numbers["first_loss_gap"] > 1e-5
    assert numbers["grad_norm_gap"] > 1e-3


def test_rehearsal_runs_to_a_result():
    """Control flow only: a limit set at the real size need not hold at the
    toy size."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         NEW, "--seed", "4300000007", "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
