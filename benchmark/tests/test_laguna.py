"""The cell ISSUE 40 added: the Laguna configuration resolves through the
harness and holds 811,017,216 parameters at the cut; the reference's loss is
the equations written out by hand over DENSE masks at a tiny size (both
attention kinds, the band, YaRN, the per-head gate, the dense layer, the
scaled experts and the ungated shared one); the cost functions count what an
enumeration of the band counts; the reader that tells one kernel's calls apart
by scope, on stand-in events, and nothing on a trace without the scope; the
lower-precision control moves the new reference; and the cell rehearses on the
CPU (toy size, control flow only)."""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np

from benchmark import control, harness, run
from benchmark.reference import laguna as ref
from benchmark.reference import ops
from benchmark.traffic import lm_tokens as traffic

NEW = "laguna-s-2.1.train-seq8192"


def test_configuration_resolves_and_counts_its_parameters():
    cell, config, bench = harness.load_cell(NEW)
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    size = harness.sizes(config, False)
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 811_017_216
    assert set(shapes) == set(config["program"]["params"])
    assert set(config["program"]["config_args"].values()) <= set(size)
    assert set(ref.shapes(harness.sizes(config, True))) == set(shapes)
    assert len(config["train"]["model_args"]["pattern"]) == \
        2 * size["num_hidden_layers"]
    listed = [e["name"] for e in bench["per_layer"]
              if NEW in e.get("workloads", [])]
    assert {"attn_full_ms.train", "attn_window_ms.train",
            "flash_fwd_window_roofline.train",
            "flash_bwd_window_roofline.train", "flash_ms.train",
            "attn_mixer_ms.train", "swiglu_ffn_ms.train", "moe_route_ms.train",
            "moe_experts_ms.train", "moe_shared_ms.train",
            "qk_prep_ms.train"} <= set(listed)
    assert "stack_scan_ms.train" not in listed      # five layers unroll
    for name in listed:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.plugin("readers", spec["reader"])


def test_costs_count_the_band_by_enumeration():
    fwd = harness.plugin("costs", "flash_fwd_window")
    for seq, window in ((16, 4), (24, 24), (12, 1), (10, 50)):
        at = np.arange(seq)
        seen = (at[None, :] <= at[:, None]) & (
            at[None, :] > at[:, None] - window)
        assert fwd.pairs(seq, window) == seen.sum()
    cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, False)
    pairs = 8192 * 512 - 512 * 511 // 2
    flops, nbytes = fwd.per_call(cell, size)
    assert flops == 2 * 72 * pairs * 4 * 128
    assert nbytes == 2 * 72 * (4 * 8192 * 128 * 2 + 8192 * 4)
    flops, nbytes = harness.plugin("costs", "flash_bwd_window").per_call(
        cell, size)
    assert flops == 2 * 72 * pairs * 10 * 128
    assert nbytes == 2 * 72 * (7 * 8192 * 128 * 2 + 2 * 8192 * 4)
    # 6% of the square: both kernels are bound by memory at the chip's peaks
    assert abs(pairs / 8192 ** 2 - 0.0606) < 1e-4


def test_reference_loss_is_the_equations_written_out():
    """Five layers at toy widths (full + dense, three sliding + experts, full
    + experts), rows of 40 tokens under a window of 24, every step spelled
    out here over dense masks."""
    cfg = {**harness.sizes(harness.load_cell(NEW)[1], True),
           "router_force_level": 0}
    dot = ops.make_dot("float32")
    p = {k: np.asarray(v, np.float64)
         for k, v in ref.init_params(ops.seed_key(3), cfg).items()}
    batch = next(traffic.micro_batches(
        1, {"micro": 2, "chips": 1, "seq": 40}, cfg))
    ids = batch["input_ids"]
    s, eps, d = ids.shape[1], cfg["rms_norm_eps"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(x, wg, wu, wd):
        return (silu(x @ wg) * (x @ wu)) @ wd

    at = np.arange(s)
    causal = at[None, :] <= at[:, None]
    band = causal & (at[None, :] > at[:, None] - cfg["sliding_window"])
    # YaRN by hand over the toy's 4 pairs
    n, base = cfg["full_rotary_lanes"], float(cfg["full_rope_theta"])
    length = cfg["yarn_original_positions"]
    pair = lambda r: n * np.log(length / (2 * np.pi * r)) / (2 * np.log(base))
    low = max(np.floor(pair(cfg["yarn_beta_fast"])), 0)
    high = min(np.ceil(pair(cfg["yarn_beta_slow"])), n - 1)
    i = np.arange(n // 2)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0, 1)
    yarn = base ** (-2 * i / n) * ((1 - ramp) + ramp / cfg["yarn_factor"])
    plain = float(cfg["sliding_rope_theta"]) ** (-np.arange(0, d, 2) / d)

    def rot(t, inv, factor):
        half = len(inv)
        ang = at[:, None] * inv[None, :]
        cos, sin = np.cos(ang)[:, None] * factor, np.sin(ang)[:, None] * factor
        a, b = t[..., :half], t[..., half:2 * half]
        return np.concatenate(
            [a * cos - b * sin, b * cos + a * sin, t[..., 2 * half:]], -1)

    def attention(x, kind, j):
        hq = ref.heads(cfg, kind)
        w = {k.split(".", 1)[1]: v[j] for k, v in p.items()
             if k.startswith(kind + ".")}
        xn = rms(x, w["norm.g"])
        q = (xn @ w["wq"]).reshape(2, s, hq, d)
        k = (xn @ w["wk"]).reshape(2, s, hkv, d)
        v = (xn @ w["wv"]).reshape(2, s, hkv, d)
        inv, factor, seen = (yarn, cfg["yarn_attention_factor"], causal) \
            if kind == "full" else (plain, 1.0, band)
        q, k = rot(q, inv, factor), rot(k, inv, factor)
        gate = 1 / (1 + np.exp(-(xn @ w["wg"])))               # [2, s, hq]
        ctx = np.zeros((2, s, hq, d))
        for h in range(hq):
            kv = h // (hq // hkv)
            sc = np.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, kv]) / np.sqrt(d)
            sc = np.where(seen[None], sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr = pr / pr.sum(-1, keepdims=True)
            ctx[:, :, h] = np.einsum(
                "bqk,bkd->bqd", pr, v[:, :, kv]) * gate[:, :, h, None]
        return x + ctx.reshape(2, s, hq * d) @ w["wo"]

    def experts(x, j):
        w = {k.split(".", 1)[1]: v[j] for k, v in p.items()
             if k.startswith("moe.")}
        xn = rms(x, w["norm.g"])
        logits = xn @ w["router"]
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs = probs / probs.sum(-1, keepdims=True)
        top = np.argsort(-probs, -1)[..., :cfg["num_experts_per_tok"]]
        out = ffn(xn, w["shared_wg"], w["shared_wu"], w["shared_wd"])
        for b in range(2):
            for t in range(s):
                total = probs[b, t, top[b, t]].sum()
                for e in top[b, t]:
                    if e < cfg["num_experts"]:          # held here
                        out[b, t] += cfg["moe_routed_scaling_factor"] \
                            * probs[b, t, e] / total * ffn(
                                xn[b, t], w["wg"][e], w["wu"][e], w["wd"][e])
        return x + out

    x = p["embed"][ids]
    seen = {"full": 0, "win": 0, "moe": 0}
    for layer, (kind, rest) in enumerate(ref.layer_kinds(cfg)):
        assert kind == ("full" if layer in (0, 4) else "win")
        x = attention(x, kind, seen[kind])
        seen[kind] += 1
        if rest == "ffn":
            assert layer == 0
            x = x + ffn(rms(x, p["ffn.norm.g"][0]), p["ffn.wg"][0],
                        p["ffn.wu"][0], p["ffn.wd"][0])
        else:
            x = experts(x, seen["moe"])
            seen["moe"] += 1
    x = rms(x, p["norm_f.g"])
    lg = x[:, :-1] @ p["head"].T
    lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) + lg.max(-1)
    picked = np.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    want = float((lse - picked).sum())
    with jax.default_matmul_precision("highest"):
        got = float(ref.loss_sums(
            {k: np.asarray(v, np.float32) for k, v in p.items()}, batch, cfg,
            dot)[0])
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert ref.counts(batch) == (2 * (s - 1),)


def test_kernels_calls_are_told_apart_by_scope():
    """Two kinds of layer run ``flash_fwd``: the reader takes the calls whose
    path holds the scope, and gives nothing where no call does (the parent's
    program, which opens no such scope)."""
    reader = harness.plugin("readers", "scope_kernel_roofline_share")
    cell, config, _bench = harness.load_cell(NEW)

    def event(path, microseconds):
        return types.SimpleNamespace(
            meta={"tf_op": path}, duration_ps=int(microseconds * 1e6))

    events = [event("jit(train_window)/attn_mixer/attn_window/flash_fwd", 800),
              event("jit(train_window)/attn_mixer/attn_window/flash_fwd", 1200),
              event("jit(train_window)/attn_mixer/attn_full/flash_fwd", 9000)]
    ctx = {"trace": types.SimpleNamespace(kernel_events=lambda name: events),
           "cell": cell, "size": harness.sizes(config, False),
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"]}
    args = dict(kernel="flash_fwd", scope="attn_window",
                cost="flash_fwd_window")
    flops, nbytes = harness.plugin("costs", "flash_fwd_window").per_call(
        cell, ctx["size"])
    least = max(flops / 197e12, nbytes / 819e9)
    np.testing.assert_allclose(
        reader.read(ctx, None, **args), 100 * least / 1000e-6, rtol=1e-9)
    events[:] = events[2:]
    assert reader.read(ctx, None, **args) is None
    events[:] = []
    assert reader.read(ctx, None, **args) is None


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
         NEW, "--seed", "4300000040", "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
