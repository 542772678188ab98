"""The cell ISSUE 44 added: the JoyAI-LLM-Flash configuration resolves through
the harness and holds 787,533,312 parameters at the cut; the reference's TWO
loss numerators are the equations written out by hand over dense arrays at a
tiny size (latent attention with its two low-rank chains, the rotation of the
last lanes, the one shared rotated key part and v's own width; the dense
layer; sigmoid scores, a selection bias and the scaled weights; the module on
the shared table and head against the token after the next); the cost
functions count what an enumeration of the causal pairs counts, at 192 + 128
lanes; the reader tells the latent mixers' kernel calls by their scope, and
reads nothing on a trace without it; the lower-precision control moves the new
reference; and the cell rehearses on the CPU (toy size, control flow only)."""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np

from benchmark import control, harness, run
from benchmark.reference import joyai as ref
from benchmark.reference import ops
from benchmark.traffic import lm_tokens as traffic

NEW = "joyai-llm-flash.train-seq8192"


def test_configuration_resolves_and_counts_its_parameters():
    cell, config, bench = harness.load_cell(NEW)
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    size = harness.sizes(config, False)
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 787_533_312
    assert set(shapes) == set(config["program"]["params"])
    assert set(config["program"]["config_args"].values()) <= set(size)
    assert set(ref.shapes(harness.sizes(config, True))) == set(shapes)
    pattern = config["train"]["model_args"]["pattern"]
    assert len(pattern) == 2 * size["num_hidden_layers"]
    assert pattern == "LF" + "LB" * 5
    listed = [e["name"] for e in bench["per_layer"]
              if NEW in e.get("workloads", [])]
    assert {"attn_mla_ms.train", "mtp_ms.train", "mtp_head_loss_ms.train",
            "flash_fwd_mla_roofline.train", "flash_bwd_mla_roofline.train",
            "flash_ms.train", "attn_mixer_ms.train", "swiglu_ffn_ms.train",
            "moe_route_ms.train", "moe_experts_ms.train",
            "moe_shared_ms.train", "head_loss_ms.train",
            "stack_scan_ms.train"} <= set(listed)
    assert "qk_prep_ms.train" not in listed   # 192- and 64-lane heads: XLA
    for name in listed:
        spec = harness.load_json("layer_metrics", name + ".json")
        harness.plugin("readers", spec["reader"])
    # every accepted metric lists its cells; no file that was there is edited
    assert all("workloads" in e for e in bench["per_layer"])


def test_costs_count_the_causal_pairs_at_both_widths():
    fwd = harness.plugin("costs", "flash_fwd_mla")
    bwd = harness.plugin("costs", "flash_bwd_mla")
    for seq in (1, 7, 16):
        at = np.arange(seq)
        assert fwd.pairs(seq) == (at[None, :] <= at[:, None]).sum()
    cell, config, _bench = harness.load_cell(NEW)
    size = harness.sizes(config, False)
    assert fwd.widths(size) == (32, 192, 128)
    # six calls at 8,192 positions and the module's, whose last has no target
    mean = (6 * fwd.pairs(8192) + fwd.pairs(8191)) / 7
    assert fwd.mean_pairs(cell, size) == mean
    flops, nbytes = fwd.per_call(cell, size)
    assert flops == 2 * 32 * mean * 2 * (192 + 128)
    assert nbytes == 2 * 32 * (8192 * (2 * 192 + 2 * 128) * 2 + 8192 * 4)
    flops_b, nbytes_b = bwd.per_call(cell, size)
    assert flops_b == 2 * 32 * mean * 2 * (3 * 192 + 2 * 128)
    assert nbytes_b == 2 * 32 * (
        8192 * (4 * 192 + 3 * 128) * 2 + 2 * 8192 * 4)
    # both kernels are bound by compute at the chip's peaks, and padding v to
    # 192 lanes or q and k to 256 would be work these costs do not count
    peaks = harness.load_json("peaks.json")["TPU v5 lite"]
    for f, n in ((flops, nbytes), (flops_b, nbytes_b)):
        assert f / peaks["bf16_flops_per_s"] > 5 * n / peaks["hbm_bytes_per_s"]
    assert abs(4 * 192 / (2 * (192 + 128)) - 1.2) < 1e-12
    assert abs(2 * (256 + 128) / (2 * (192 + 128)) - 1.2) < 1e-12


def test_reference_loss_terms_are_the_equations_written_out():
    """Six layers and the module at toy widths, rows of 20 tokens, every step
    spelled out here in float64 over dense arrays."""
    cfg = {**harness.sizes(harness.load_cell(NEW)[1], True),
           "router_force_level": 0}
    dot = ops.make_dot("float32")
    p = {k: np.asarray(v, np.float64)
         for k, v in ref.init_params(ops.seed_key(3), cfg).items()}
    # a seeded bias: the choice must differ from the scores' own
    rng = np.random.default_rng(0)
    for name in ("moe.router_bias", "mtp.moe.router_bias"):
        p[name] = np.asarray(
            rng.normal(size=p[name].shape).astype(np.float32) * 0.25,
            np.float64)
    batch = next(traffic.micro_batches(
        1, {"micro": 2, "chips": 1, "seq": 20}, cfg))
    ids = batch["input_ids"]
    s, eps = ids.shape[1], cfg["rms_norm_eps"]
    h, rkv = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])

    def rms(x, g):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g

    def silu(x):
        return x / (1 + np.exp(-x))

    def ffn(x, wg, wu, wd):
        return (silu(x @ wg) * (x @ wu)) @ wd

    inv = float(cfg["rope_theta"]) ** (-np.arange(0, rope, 2) / rope)
    ang = np.arange(s)[:, None] * inv[None, :]

    def rot(t):
        """t [2, S, ..., rope]: lane i pairs with lane i + rope / 2."""
        cos, sin = np.cos(ang), np.sin(ang)
        while cos.ndim < t.ndim - 1:
            cos, sin = cos[:, None], sin[:, None]
        a, b = t[..., :rope // 2], t[..., rope // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    causal = np.tril(np.ones((s, s), bool))

    def attention(x, w):
        xn = rms(x, w["norm.g"])
        q = (rms(xn @ w["wqa"], w["q_norm.g"]) @ w["wqb"]).reshape(
            2, s, h, nope + rope)
        q = np.concatenate([q[..., :nope], rot(q[..., nope:])], -1)
        latent = xn @ w["wkva"]
        k_r = rot(latent[..., rkv:])                    # [2, S, rope]: ONE
        kv = (rms(latent[..., :rkv], w["kv_norm.g"]) @ w["wkvb"]).reshape(
            2, s, h, nope + dv)
        ctx = np.zeros((2, s, h, dv))
        for head in range(h):
            k = np.concatenate([kv[:, :, head, :nope], k_r], -1)
            sc = np.einsum("bqd,bkd->bqk", q[:, :, head], k) / np.sqrt(
                nope + rope)
            sc = np.where(causal[None], sc, -np.inf)
            pr = np.exp(sc - sc.max(-1, keepdims=True))
            pr = pr / pr.sum(-1, keepdims=True)
            ctx[:, :, head] = np.einsum("bqk,bkd->bqd", pr, kv[:, :, head, nope:])
        return x + ctx.reshape(2, s, h * dv) @ w["wo"]

    def experts(x, w):
        xn = rms(x, w["norm.g"])
        scores = 1 / (1 + np.exp(-(xn @ w["router"])))
        top = np.argsort(-(scores + w["router_bias"]), -1)[
            ..., :cfg["num_experts_per_tok"]]
        assert (top != np.argsort(-scores, -1)[
            ..., :cfg["num_experts_per_tok"]]).any()
        out = ffn(xn, w["shared_wg"], w["shared_wu"], w["shared_wd"])
        for b in range(2):
            for t in range(s):
                total = scores[b, t, top[b, t]].sum()
                for e in top[b, t]:
                    if e < cfg["n_routed_experts"]:          # held here
                        out[b, t] += cfg["routed_scaling_factor"] \
                            * scores[b, t, e] / total * ffn(
                                xn[b, t], w["wg"][e], w["wu"][e], w["wd"][e])
        return x + out

    def leaves(prefix, j):
        return {k[len(prefix) + 1:]: v[j] for k, v in p.items()
                if k.startswith(prefix + ".")}

    x = p["embed"][ids]
    for layer, kind in enumerate(ref.layer_kinds(cfg)):
        assert kind == ("ffn" if layer == 0 else "moe")
        x = attention(x, leaves("mla", layer))
        if kind == "ffn":
            w = leaves("ffn", 0)
            x = x + ffn(rms(x, w["norm.g"]), w["wg"], w["wu"], w["wd"])
        else:
            x = experts(x, leaves("moe", layer - 1))
    z = rms(x, p["norm_f.g"])

    def nll_sum(states, targets):
        lg = states @ p["head"].T
        top = lg.max(-1)
        lse = np.log(np.exp(lg - top[..., None]).sum(-1)) + top
        return float((lse - np.take_along_axis(
            lg, targets[..., None], -1)[..., 0]).sum())

    # the module over the S - 1 positions that have a next token, as the
    # equations have it (the reference and the program run S: the same
    # numbers at the scored positions)
    s = s - 1
    ang, causal = ang[:s], causal[:s, :s]
    w = leaves("mtp", 0)
    u = np.concatenate([rms(p["embed"][ids[:, 1:]], w["embed_norm.g"]),
                        rms(z[:, :-1], w["state_norm.g"])], -1) @ w["proj"]
    u = experts(attention(u, leaves("mtp.mla", 0)), leaves("mtp.moe", 0))
    state = rms(u, w["norm.g"])
    want = (nll_sum(z[:, :-1], ids[:, 1:]),
            cfg["mtp_loss_weight"] * nll_sum(state[:, :-1], ids[:, 2:]))
    with jax.default_matmul_precision("highest"):
        got = ref.loss_sums(
            {k: np.asarray(v, np.float32) for k, v in p.items()}, batch, cfg,
            dot)
    np.testing.assert_allclose([float(g) for g in got], want, rtol=2e-5)
    assert ref.counts(batch) == (2 * 19, 2 * 18)


def test_kernel_calls_under_the_latent_scope():
    """The reader takes the ``flash_fwd`` calls whose path holds ``attn_mla``
    (the main stack's under the scan, the module's under ``mtp``), and gives
    nothing where no call does (the parent's program, which opens no such
    scope)."""
    reader = harness.plugin("readers", "scope_kernel_roofline_share")
    cell, config, _bench = harness.load_cell(NEW)

    def event(path, microseconds):
        return types.SimpleNamespace(
            meta={"tf_op": path}, duration_ps=int(microseconds * 1e6))

    events = [
        event("jit(train_window)/stack_scan/attn_mixer/attn_mla/flash_fwd", 18000),
        event("jit(train_window)/mtp/attn_mixer/attn_mla/flash_fwd", 19000),
        event("jit(train_window)/attn_mixer/attn_full/flash_fwd", 9000)]
    ctx = {"trace": types.SimpleNamespace(kernel_events=lambda name: events),
           "cell": cell, "size": harness.sizes(config, False),
           "peaks": harness.load_json("peaks.json")["TPU v5 lite"]}
    args = dict(kernel="flash_fwd", scope="attn_mla", cost="flash_fwd_mla")
    flops, nbytes = harness.plugin("costs", "flash_fwd_mla").per_call(
        cell, ctx["size"])
    least = max(flops / 197e12, nbytes / 819e9)
    share = reader.read(ctx, None, **args)
    np.testing.assert_allclose(share, 100 * least / 18500e-6, rtol=1e-9)
    assert 0 < share < 100
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
         NEW, "--seed", "4400000044", "--seconds", "3", "--trace", "0",
         "--rehearse"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"setup_s", "train_tokens_per_s_per_chip"}
    assert line["device"]["platform"] == "cpu"
