"""The laguna stack (models/hybrid.py pattern ``HF WU WU WU HU``: a leading
dense layer and one period of sliding, sliding, sliding, full over routed
experts) against its plain float32 reference (benchmark/reference/laguna.py)
at toy size on the CPU: the whole model's loss and every leaf's gradient, on
the XLA path and through the flash kernels; two steps through ``initialize()``
and the fused ``train_batch()`` window against the reference's follower, with
the ``attn/...`` and ``moe/...`` counters; a pattern with a prefix, a repeated
run and a tail scanned against the same layers unrolled; the configuration
file."""

import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import HybridCausalLM, HybridLMConfig
from deepspeed_tpu.parallel.mesh import build_mesh

attn_ops = importlib.import_module("deepspeed_tpu.ops.attention")
hybrid = importlib.import_module("deepspeed_tpu.models.hybrid")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, program  # noqa: E402
from benchmark.reference import laguna as ref  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import train as follower  # noqa: E402
from benchmark.traffic import lm_tokens as traffic  # noqa: E402

CELL = "laguna-s-2.1.train-seq8192"
CONFIG_FILE = os.path.join(ROOT, "benchmark/configs/laguna-s-2.1.json")
with open(CONFIG_FILE) as fd:
    CONFIG = json.load(fd)
TOY = {**harness.sizes(CONFIG, True), "router_force_level": 0}
DOT = ref_ops.make_dot("float32")
ENGINE = {
    "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 6,
}


def program_config(size=TOY, **kw):
    args = {arg: size[key]
            for arg, key in CONFIG["program"]["config_args"].items()}
    args.update(CONFIG["train"]["model_args"], remat=False, ce_block_rows=16)
    args.update(kw)
    return HybridLMConfig(**args)


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(ref_ops.seed_key(5), TOY)


def batches(n, seq=32, seed=0, rows=2):
    gen = traffic.micro_batches(
        seed, {"micro": rows, "chips": 1, "seq": seq}, TOY)
    return [next(gen) for _ in range(n)]


def compare(model, config, size, params, batch, atol=3e-5):
    def theirs(p):
        return ref.loss_sums(p, batch, size, DOT)[0] / ref.counts(batch)[0]

    def ours(p):
        return model.apply(
            {"params": program.to_tree(config, p)},
            *program.feed(config, batch))[0]

    l_ref, g_ref = jax.value_and_grad(theirs)(params)
    l_our, g_our = jax.value_and_grad(ours)(params)
    np.testing.assert_allclose(l_our, l_ref, rtol=2e-6)
    assert set(g_our) == set(ref.shapes(size))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=atol, err_msg=name)
    return l_our, g_our


@pytest.mark.parametrize("flash", [False, True])
def test_model_loss_and_every_leaf_gradient(weights, flash, monkeypatch):
    """Rows of 256 tokens, a window of 24. ``flash``: the kernels in
    interpret mode on a 4 x 4 grid of blocks, causal at 4 heads and banded at
    6 in one program; else the XLA path. The tolerances are float32 sums in
    another order."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 64)
    batch, = batches(1, seq=256)
    compare(HybridCausalLM(program_config()), CONFIG, TOY, weights, batch)


def deep(layers, period=4):
    """The toy sizes at another depth, and the pattern of that depth: the
    published rule (full attention every ``period``-th layer from layer 0,
    one leading dense layer) written in the stack's letters."""
    size = {**TOY, "num_hidden_layers": layers, "full_attention_period": period}
    pattern = "".join(
        {"full": "H", "win": "W"}[a] + {"ffn": "F", "moe": "U"}[b]
        for a, b in ref.layer_kinds(size))
    return size, pattern


def test_prefix_run_and_tail_scanned_equal_the_layers_unrolled(monkeypatch):
    """12 layers = 1 + 5 x 2 + 1, the published stack's shape in small
    (``HF`` + 5 x ``WUHU`` + ``WU``, full attention every second layer): the
    scan over the run with the prefix and the tail unrolled, against the
    reference's loss, and against the same program walked layer by layer
    (``stack_plan`` held by the test): loss, every leaf's gradient and the
    counters. The published 48 layers plan as 1 + 11 x 4 + 3, and the cell's
    five unroll."""
    size, pattern = deep(48)
    assert hybrid.stack_plan(pattern) == ("HF", "WUWUWUHU", 11, "WUWUWU")
    assert hybrid.stack_plan(CONFIG["train"]["model_args"]["pattern"]) == (
        "", "HFWUWUWUHU", 1, "")
    # what accepted configurations run: pure repetitions scan, the rest unroll
    for accepted in ("ASASASASASAS", "RF" * 12, "DXDXDXGX", "MEMEMEMEM*E"):
        unit, reps = hybrid.period(accepted)
        assert hybrid.stack_plan(accepted) == ("", unit, reps, "")
    size, pattern = deep(12, period=2)
    assert hybrid.stack_plan(pattern) == ("HF", "WUHU", 5, "WU")
    params = ref.init_params(ref_ops.seed_key(7), size)
    batch, = batches(1, seq=32)
    feed = program.feed(CONFIG, batch)
    model = HybridCausalLM(program_config(size, pattern=pattern, remat=True))

    def run():
        fn = jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": program.to_tree(CONFIG, p)}, *feed), has_aux=True))
        return fn, fn(params)

    fn, ((loss, counters), grads) = run()
    assert "stack_scan" in fn.lower(params).as_text(debug_info=True)
    np.testing.assert_allclose(
        loss, ref.loss_sums(params, batch, size, DOT)[0]
        / ref.counts(batch)[0], rtol=2e-6)
    monkeypatch.setattr(hybrid, "stack_plan", lambda p: ("", p, 1, ""))
    fn, ((loss_u, counters_u), grads_u) = run()
    assert "stack_scan" not in fn.lower(params).as_text(debug_info=True)
    np.testing.assert_allclose(loss_u, loss, rtol=1e-6)
    assert set(counters) == set(counters_u)
    for name in counters:
        np.testing.assert_allclose(counters[name], counters_u[name], rtol=1e-6)
    assert int(counters["moe/tiles"]) > 0
    assert int(counters["attn/window_heads"]) == 6 * 6
    assert int(counters["attn/full_heads"]) == 6 * 4
    for name in grads:
        scale = float(jnp.max(jnp.abs(grads[name]))) or 1.0
        np.testing.assert_allclose(
            grads_u[name] / scale, grads[name] / scale, atol=2e-5,
            err_msg=name)


@pytest.mark.parametrize("pattern,plan", [
    ("HF" + "WUWUWUHU" * 11 + "WUWUWU", ("HF", "WUWUWUHU", 11, "WUWUWU")),
    ("HFWUWUWUHU", ("", "HFWUWUWUHU", 1, "")),
    ("F" + "AS" * 6, ("F", "AS", 6, "")),
    ("AS" * 6 + "RF", ("", "AS", 6, "RF")),
    ("ASAS", ("", "AS", 2, "")),
    ("FASASR", ("", "FASASR", 1, "")),
    ("MEM*E", ("", "MEM*E", 1, "")),
])
def test_stack_plan(pattern, plan):
    """A pure repetition from two; inside a longer pattern a run that takes
    two thirds of the bodies out of the program, else nothing."""
    assert hybrid.stack_plan(pattern) == plan
    prefix, unit, reps, tail = plan
    assert prefix + unit * reps + tail == pattern


@pytest.mark.parametrize("bad", [
    dict(window=0), dict(window_attn_heads=5), dict(objective="block_diffusion"),
    dict(yarn_original_positions=0), dict(rotary_lanes=7)])
def test_config_refuses_what_the_kinds_cannot_run(bad):
    with pytest.raises(ValueError):
        program_config(**bad)


def make_engine(weights, extra=None, **model_kw):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(**model_kw)),
        model_parameters=program.to_tree(CONFIG, weights),
        config_params=dict(ENGINE, **(extra or {})),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def test_two_steps_through_initialize_follow_the_reference(weights):
    """float32 through ``initialize()`` and two fused ``train_batch()``
    windows of 2 micro-batches under per-sublayer remat and the staged data
    pipeline: each step's loss, the first gradient's norm leaf by leaf (from
    Adam's first moment), the parameters' change after two steps, against
    the reference's own follower with the same Adam; the counters of the
    window."""
    engine = make_engine(
        weights, remat=True,
        remat_policy="nothing_saveable+flash_out+flash_lse+moe_plan",
        extra={"data_pipeline": {"enabled": True},
               "telemetry": {"enabled": True, "interval": 1, "exporters": []}})
    kept = batches(4)
    feed = iter([program.feed(CONFIG, b) for b in kept])
    losses = [float(engine.train_batch(feed))]
    grad = program.first_moment_norms(CONFIG, ref, engine, 0.9)
    losses.append(float(engine.train_batch(feed)))
    init = ref_ops.initializer(ref, TOY)
    key = ref_ops.seed_key(5)
    change = program.change_norms(CONFIG, ref, engine, init, key)
    counters = engine.last_aux[0]
    # a micro-step's counters, [accum]: the band and the heads of each kind
    # (three windowed layers of 6 heads, two full ones of 4)
    assert counters["attn/max_window"].tolist() == [24, 24]
    assert counters["attn/window_heads"].tolist() == [18, 18]
    assert counters["attn/full_heads"].tolist() == [8, 8]
    np.testing.assert_allclose(
        counters["attn/max_window_visited_share"],
        [attn_ops.flash_tiling(32, 32, 32, 32, True, window=24)[
            "visited_share"]] * 2)
    assert attn_ops.window_visited_share(8192, 512) == 93 / 1024
    assert int(counters["moe/overflow"].sum()) == 0
    # four sparse layers, 2 x 32 positions, top-4 of 16 with 2 held
    assert 0 < int(counters["moe/local_assignments"][0]) < 4 * 64 * 4
    reg = engine.telemetry.registry
    assert reg.gauge("attn/max_window").value == 24
    assert reg.counter("attn/window_heads").value == 4 * 18
    program.close_train(engine)

    adam = {"type": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "weight_decay": 0.0}
    want_losses, want_grad, _first, want_change = follower.follow(
        ref, TOY, lambda: init(key), [kept[:2], kept[2:]], adam, DOT, 1)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert set(grad) == set(want_grad)
    for name in want_grad:
        np.testing.assert_allclose(
            grad[name], want_grad[name], rtol=2e-3, err_msg=name)
        np.testing.assert_allclose(
            change[name], want_change[name], rtol=5e-2, err_msg=name)


def test_bf16_engine_at_the_cells_data_types(weights):
    """Cell 1's data types through the engine: a finite loss, the two kinds'
    leaves with their own head counts, no shared gate."""
    extra = {"bf16": {"enabled": True},
             "data_types": {"optimizer_state_dtype": "int8",
                            "grad_accum_dtype": "bf16",
                            "master_dtype": "compensated"}}
    engine = make_engine(weights, extra=extra, remat=True)
    kept = batches(2, seed=9)
    loss = float(engine.train_batch(
        iter([program.feed(CONFIG, b) for b in kept])))
    assert np.isfinite(loss)
    leaves = engine.params["model"]
    assert leaves["hattn_wg"].shape == (2, 64, 4)
    assert leaves["wattn_wg"].shape == (3, 64, 6)
    assert leaves["hattn_wq"].shape[-1] == 4 * 16
    assert leaves["wattn_wq"].shape[-1] == 6 * 16
    assert leaves["umoe_shared_wg"].shape[0] == 4 and "ffn_wg" in leaves
    assert not any("shared_gate" in k or "q_norm" in k for k in leaves)
    program.close_train(engine)


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` under its own key, but the
    ``reduced`` keys; the numbers that restate the lists and the nested rope
    group; the parameter count at the cut; the cell."""
    layer_types = (["full_attention"] + ["sliding_attention"] * 3) * 12
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                "original_max_position_embeddings": 8192, "beta_slow": 1,
                "beta_fast": 32, "attention_factor": 1.4852030263919618,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}},
        "layer_types": layer_types,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        bench = json.load(fd)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    lists = ["layer_types", "mlp_layer_types", "gating_types",
             "num_attention_heads_per_layer"]
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"]) \
        == ["num_hidden_layers", *lists, "num_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/laguna-s-2.1.json"
    assert entry["source"] == CONFIG["source"]
    # the form BENCHMARK.json's lines are held to: 1 to 200 printable
    # characters each (a 211-character ``why`` refused this PR's first check)
    cell = next(w for w in bench["workloads"] if w["config"] == CONFIG["name"])
    for line in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable()
    for key, value in published.items():
        where = CONFIG["published"] if key in CONFIG["reduced"] else CONFIG
        assert where[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 8, 12544)
    for key in lists:
        assert CONFIG[key] == published[key][:5], key
    # the numbers that stand for the lists and the nested group
    assumed, rope = CONFIG["assumed"], published["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert [t == "full_attention" for t in layer_types] == [
        i % assumed["full_attention_period"] == 0 for i in range(48)]
    assert published["mlp_only_layers"] == list(
        range(assumed["leading_dense_layers"]))
    assert {48: "full", 72: "sliding"} == {
        heads: kind.split("_")[0] for heads, kind in zip(
            published["num_attention_heads_per_layer"], layer_types)}
    assert assumed["sliding_attention_heads"] == 72
    assert (assumed["full_rope_theta"], assumed["yarn_factor"],
            assumed["yarn_original_positions"], assumed["yarn_beta_fast"],
            assumed["yarn_beta_slow"], assumed["yarn_attention_factor"]) == (
        full["rope_theta"], full["factor"],
        full["original_max_position_embeddings"], full["beta_fast"],
        full["beta_slow"], full["attention_factor"])
    assert assumed["full_rotary_lanes"] == int(
        full["partial_rotary_factor"] * published["head_dim"])
    assert assumed["sliding_rope_theta"] == sliding["rope_theta"]
    assert (assumed["experts_routed_over"], assumed["expert_offset"],
            assumed["router_force_level"]) == (256, 0, 1)
    for key in ("restated_numbers_why", "norm_placement", "gate", "qk_norm",
                "window_edge", "routing", "rotary", "weights",
                "auxiliary_loss", "router_force_level_why",
                "remat_policy_why"):
        assert len(assumed[key]) > 40, key
    size = harness.sizes(CONFIG, False)
    assert ref.layer_kinds(size) == [
        ("full", "ffn"), ("win", "moe"), ("win", "moe"), ("win", "moe"),
        ("full", "moe")]
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 811_017_216

    def layer(*kinds):
        return sum(int(np.prod(s[1:])) for k, s in shapes.items()
                   if k.split(".")[0] in kinds)

    assert layer("full") == 44_187_648 + 3072
    assert layer("win") == 63_135_744 + 3072
    assert layer("ffn") == 113_246_208 + 3072
    assert layer("win", "moe") == 148_862_976
    assert layer("full", "moe") == 129_914_880
    assert layer("full", "ffn") == 157_440_000
    # the uncut model by the same formulas: the published "118B"
    whole = dict(size, num_hidden_layers=48, num_experts=256,
                 vocab_size=100352)
    assert sum(int(np.prod(s)) for s in ref.shapes(whole).values()) \
        == 117_561_953_280
    # the program's tree at the cut holds the same leaves and shapes
    model = HybridCausalLM(program_config(size, ce_block_rows=512))
    ids = jnp.zeros((1, 64), jnp.int32)
    tree = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    ours = {k: v.shape for k, v in program.from_tree(CONFIG, tree).items()}
    assert ours == {k: tuple(s) for k, s in shapes.items()}
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(tree)) == 811_017_216
    cell = harness.load_json("workloads", CELL + ".json")
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    assert cell["traffic"] == {"generator": "lm_tokens"}
    assert traffic.tokens_per_micro_batch(cell) * cell["accum"] == 32768
    assert CELL in next(m for m in bench["end_to_end"] if m["name"]
                        == "train_tokens_per_s_per_chip")["workloads"]
    # the cell reports the new metrics and none whose cost file reads keys
    # this configuration gives another meaning
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert {"attn_full_ms.train", "attn_window_ms.train",
            "flash_fwd_window_roofline.train",
            "flash_bwd_window_roofline.train", "attn_mixer_ms.train",
            "flash_ms.train", "qk_prep_ms.train", "unscoped_ms.train"} <= listed
    assert not {"flash_fwd_d128_roofline.train", "stack_scan_ms.train",
                "gated_experts_roofline.train"} & listed


def test_the_costs_count_the_allowed_pairs_of_the_band():
    from benchmark.costs import flash_bwd_window, flash_fwd_window

    cell = harness.load_json("workloads", CELL + ".json")
    size = harness.sizes(CONFIG, False)
    pairs = 8192 * 512 - 512 * 511 // 2
    assert flash_fwd_window.pairs(8192, 512) == pairs
    assert flash_fwd_window.pairs(64, 512) == 64 * 65 // 2    # causal
    assert int(np.sum(np.asarray(attn_ops.band_mask(96, 96, 20)) == 0)) \
        == flash_fwd_window.pairs(96, 20)
    flops, nbytes = flash_fwd_window.per_call(cell, size)
    assert flops == 2 * 72 * pairs * 4 * 128
    assert nbytes == 2 * 72 * (4 * 8192 * 128 * 2 + 8192 * 4)
    flops_b, nbytes_b = flash_bwd_window.per_call(cell, size)
    assert flops_b == 2 * 72 * pairs * 10 * 128
    assert nbytes_b == 2 * 72 * (7 * 8192 * 128 * 2 + 2 * 8192 * 4)
    # the walk visits half as many pairs again as are allowed (256-square
    # sub-tiles since PR 50; twice as many at 512): a perfect kernel at the
    # walk's own count would read 67%
    visited = attn_ops.flash_tiling(
        8192, 8192, 1024, 1024, True, window=512)["visited_share"] * 8192 ** 2
    assert 0.66 < pairs / visited < 0.68
