"""The cells ISSUE 38 added: the SDAR configuration resolves through the
harness and holds 645,623,296 parameters at the cut; the reference's loss is
the equations written out by hand over a DENSE mask at a tiny size; the cost
functions count what an enumeration of the mask counts; the lower-precision
control moves the new reference; the generator's batches; and both new cells
rehearse on the CPU (toy size, control flow only)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import control, harness, run
from benchmark.reference import ops
from benchmark.reference import sdar as ref
from benchmark.traffic import block_diffusion_tokens as traffic

NEW = "sdar-30b-a3b-chat.train-blockdiff-seq8192"
SQUAD = "bert-large.squad-seq384"


def test_configuration_resolves_and_counts_its_parameters():
    cell, config, bench = harness.load_cell(NEW)
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    size = harness.sizes(config, False)
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 645_623_296
    assert set(shapes) == set(config["program"]["params"])
    assert set(config["program"]["config_args"].values()) <= set(size)
    assert set(ref.shapes(harness.sizes(config, True))) == set(shapes)
    assert len(config["train"]["model_args"]["pattern"]) == \
        2 * size["num_hidden_layers"]
    assert config["program"]["feed"] == [
        "noisy_ids", "clean_ids", "loss_weights"]
    listed = [e["name"] for e in bench["per_layer"]
              if NEW in e.get("workloads", [])]
    assert {"flash_fwd_blockdiff_roofline.train",
            "flash_bwd_blockdiff_roofline.train",
            "blockdiff_experts_roofline.train", "flash_ms.train",
            "attn_mixer_ms.train", "moe_route_ms.train",
            "moe_experts_ms.train", "stack_scan_ms.train"} <= set(listed)
    assert "moe_shared_ms.train" not in listed      # no shared expert
    for name in listed:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.plugin("readers", spec["reader"])


def test_squad_cell_is_data_on_what_the_benchmark_had():
    cell, config, bench = harness.load_cell(SQUAD)
    twin = harness.load_json("workloads", "bert-large.pretrain-seq512.json")
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        8, 384, 8, 1)
    for key in ("loop", "traffic", "mlm_share", "trace_seconds", "toy"):
        assert cell[key] == twin[key], key
    assert set(cell["check"]["limits"]) == set(twin["check"]["limits"])
    assert config["name"] == "bert-large"
    for entry in bench["end_to_end"] + bench["per_layer"]:
        cells = entry.get("workloads", [])
        assert (SQUAD in cells) == ("bert-large.pretrain-seq512" in cells), \
            entry["name"]


def dense_mask(length, block):
    """[2 L, 2 L] by the four rules, position by position."""
    allowed = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            bi, bj = (i % length) // block, (j % length) // block
            if i < length and j < length:
                allowed[i, j] = bi == bj
            elif i < length:
                allowed[i, j] = bj < bi
            elif j >= length:
                allowed[i, j] = bj <= bi
    return allowed


def test_mask_is_the_four_rules_and_the_costs_count_it():
    for length, block in ((16, 4), (24, 8), (12, 2)):
        at = np.arange(2 * length)
        got = np.asarray(ref.mask_allowed(
            at[:, None], at[None, :], length, block))
        want = dense_mask(length, block)
        np.testing.assert_array_equal(got, want)
        fwd = harness.plugin("costs", "flash_fwd_blockdiff")
        assert fwd.pairs(length, block) == want.sum()
    cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, False)
    pairs = 8192 * 8192 + 8192 * 4
    flops, nbytes = harness.plugin("costs", "flash_fwd_blockdiff").per_call(
        cell, size)
    assert flops == 2 * 32 * pairs * 4 * 128
    assert nbytes == 2 * 32 * (4 * 16384 * 128 * 2 + 16384 * 4)
    # a quarter of the (2L)^2 square, half of a causal walk over 2L
    assert abs(pairs / 16384 ** 2 - 0.25) < 2e-4
    flops, nbytes = harness.plugin("costs", "flash_bwd_blockdiff").per_call(
        cell, size)
    assert flops == 2 * 32 * pairs * 10 * 128
    assert nbytes == 2 * 32 * (7 * 16384 * 128 * 2 + 2 * 16384 * 4)
    # the experts see the 2 L positions of a row: one held expert a position
    flops, nbytes = harness.plugin("costs", "blockdiff_experts").per_window(
        cell, size)
    positions = 2 * 2 * 8192
    assert flops == 2 * 6 * (positions * 8 * 16 / 128) * 9 * 2 * 2048 * 768
    halved, _ = harness.plugin("costs", "gated_experts").per_window(cell, size)
    assert flops == 2 * halved


def test_reference_loss_is_the_equations_written_out():
    """One layer at toy widths, a row of 8 tokens in blocks of 4, every
    step spelled out here over the dense mask: q/k norms before rotary on
    all lanes at position ids 0..7 twice, grouped heads, the routed sum of
    the held experts with the weights renormalised over all chosen, the
    final norm, the noisy half's head, no shift, the weighted sum."""
    cfg = {**harness.sizes(harness.load_cell(NEW)[1], True),
           "num_hidden_layers": 1, "router_force_level": 0}
    dot = ops.make_dot("float32")
    p = ref.init_params(ops.seed_key(3), cfg)
    length, block = 8, cfg["block_length"]
    batch, = [next(traffic.micro_batches(
        1, {"micro": 2, "chips": 1, "seq": length}, cfg))]
    ids = np.concatenate([batch["noisy_ids"], batch["clean_ids"]], axis=1)
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g

    with jax.default_matmul_precision("highest"):
        x = np.asarray(p["embed"])[ids]                       # [2, 16, E]
        xn = rms(x, np.asarray(p["attn.norm.g"][0]))
        q = (xn @ np.asarray(p["attn.wq"][0])).reshape(2, 16, hq, d)
        k = (xn @ np.asarray(p["attn.wk"][0])).reshape(2, 16, hkv, d)
        v = (xn @ np.asarray(p["attn.wv"][0])).reshape(2, 16, hkv, d)
        q = rms(q, np.asarray(p["attn.q_norm.g"][0]))
        k = rms(k, np.asarray(p["attn.k_norm.g"][0]))
        pos = np.arange(16) % length
        inv = float(cfg["rope_theta"]) ** (-np.arange(0, d, 2) / d)
        ang = pos[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]

        def rot(t):
            a, b = t[..., :d // 2], t[..., d // 2:]
            return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

        q, k = rot(q), rot(k)
        allowed = dense_mask(length, block)
        ctx = np.zeros((2, 16, hq, d))
        for h in range(hq):
            kv = h // (hq // hkv)
            s = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, kv]) / np.sqrt(d)
            s = np.where(allowed[None], s, -np.inf)
            w = np.exp(s - s.max(-1, keepdims=True))
            w = w / w.sum(-1, keepdims=True)
            ctx[:, :, h] = np.einsum("bqk,bkd->bqd", w, v[:, :, kv])
        x = x + ctx.reshape(2, 16, hq * d) @ np.asarray(p["attn.wo"][0])
        xn = rms(x, np.asarray(p["moe.norm.g"][0]))
        logits = xn @ np.asarray(p["moe.router"][0])
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs = probs / probs.sum(-1, keepdims=True)
        top = np.argsort(-probs, -1)[..., :cfg["num_experts_per_tok"]]
        out = np.zeros_like(x)
        for b in range(2):
            for t in range(16):
                total = probs[b, t, top[b, t]].sum()
                for e in top[b, t]:
                    if e < cfg["num_experts"]:          # held here
                        g = xn[b, t] @ np.asarray(p["moe.wg"][0, e])
                        u = xn[b, t] @ np.asarray(p["moe.wu"][0, e])
                        out[b, t] += probs[b, t, e] / total * (
                            (g / (1 + np.exp(-g)) * u)
                            @ np.asarray(p["moe.wd"][0, e]))
        x = rms(x + out, np.asarray(p["norm_f.g"]))
        lg = x[:, :length] @ np.asarray(p["head"]).T
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        picked = np.take_along_axis(
            lg, batch["clean_ids"][..., None], axis=-1)[..., 0]
        want = float(((lse - picked) * batch["loss_weights"]).sum())
        got = float(ref.loss_sums(p, batch, cfg, dot)[0])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert ref.counts(batch) == (2 * length,)
    assert (batch["loss_weights"] > 0).any()


def test_generator_follows_the_seed():
    _cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, True)
    cell = {"micro": 3, "chips": 1, "seq": 64}
    a, b = (next(traffic.micro_batches(11, cell, size)) for _ in range(2))
    c = next(traffic.micro_batches(2 ** 31 + 5, cell, size))
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
        assert not np.array_equal(a[name], c[name])
    assert a["clean_ids"].max() < size["vocab_size"] - 1
    assert set(np.unique(a["noisy_ids"][a["loss_weights"] > 0])) == {
        size["vocab_size"] - 1}
    assert traffic.tokens_per_micro_batch(cell) == 3 * 64


def test_lower_precision_moves_the_new_reference():
    """fp8 in the reference's products moves every compared number off the
    float32 reading at toy size: the control has something to fail."""
    ctx = run.context(NEW, 5, 1.0, 0, True, chips=1)
    numbers = control.train_control(ctx)
    assert numbers["first_loss_gap"] > 1e-5
    assert numbers["grad_norm_gap"] > 1e-3


@pytest.mark.parametrize("cell", [NEW, SQUAD])
def test_rehearsal_runs_to_a_result(cell):
    """Control flow only: a limit set at the real size need not hold at the
    toy size."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         cell, "--seed", "4300000038", "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
