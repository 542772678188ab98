"""The JoyAI-LLM-Flash stack (models/hybrid.py pattern ``LF`` + 5 x ``LB`` and
one multi-token-prediction module: a leading dense layer, five sparse layers
of latent attention over sigmoid-routed experts, the module on the shared
table and head) against its plain float32 reference
(benchmark/reference/joyai.py) at toy size on the CPU: the loss, BOTH of its
terms and every leaf's gradient, on the XLA path and through the flash kernels
at unequal widths; the module's target is the token after the next and its
table and head ARE the main ones; ``mtp_depth`` 0 is the stack it was; two
steps through ``initialize()`` and the fused ``train_batch()`` window against
the reference's follower, with the counters; ZeRO-2 over the CPU's device
mesh; a checkpoint saved and loaded gives the same next loss; the
configuration file."""

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
from benchmark.reference import joyai as ref  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import train as follower  # noqa: E402
from benchmark.traffic import lm_tokens as traffic  # noqa: E402

CELL = "joyai-llm-flash.train-seq8192"
with open(os.path.join(ROOT, "benchmark/configs/joyai-llm-flash.json")) as fd:
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


def reference_terms(params, batch, size=TOY):
    """(main mean nll, the module's mean nll times its weight)."""
    return tuple(s / c for s, c in zip(
        ref.loss_sums(params, batch, size, DOT), ref.counts(batch)))


def our_loss(model, params, batch):
    return model.apply(
        {"params": program.to_tree(CONFIG, params)},
        *program.feed(CONFIG, batch))


@pytest.mark.parametrize("flash", [False, True])
def test_model_loss_both_terms_and_every_leaf_gradient(
        weights, flash, monkeypatch):
    """Rows of 128 tokens. ``flash``: the kernels in interpret mode on a 4 x 4
    grid of blocks, q and k 24 lanes wide and v 16; else the XLA path. The
    tolerances are float32 sums in another order (bf16 anywhere reads
    1e-2)."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 32)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 32)
    batch, = batches(1, seq=128)
    model = HybridCausalLM(program_config())
    (l_ref, terms), g_ref = jax.value_and_grad(
        lambda p: (sum(reference_terms(p, batch)), reference_terms(p, batch)),
        has_aux=True)(weights)
    (l_our, counters), g_our = jax.value_and_grad(
        lambda p: our_loss(model, p, batch), has_aux=True)(weights)
    np.testing.assert_allclose(l_our, l_ref, rtol=2e-6)
    # the two terms apart: the counter is the module's own mean nll
    np.testing.assert_allclose(
        counters["mtp/loss"], terms[1] / TOY["mtp_loss_weight"], rtol=2e-6)
    np.testing.assert_allclose(
        l_our - TOY["mtp_loss_weight"] * counters["mtp/loss"], terms[0],
        rtol=2e-6)
    assert int(counters["mtp/depth"]) == 1
    assert int(counters["attn/mla_heads"]) == 7 * 4     # six layers + module
    assert set(g_our) == set(ref.shapes(TOY))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=3e-5, err_msg=name)
    # the selection bias chooses and takes no gradient, in either
    for name in ("moe.router_bias", "mtp.moe.router_bias"):
        assert float(jnp.max(jnp.abs(g_our[name]))) == 0.0
        assert float(jnp.max(jnp.abs(g_ref[name]))) == 0.0


def test_the_module_reads_the_main_table_and_head_and_scores_two_ahead(weights):
    """(1) The module's target at position i is ``t_{i+2}``: moving the LAST
    label moves the module's term (it is position S-3's target) and the main
    one (position S-2's), while as an input that token reaches no scored
    state: the main head's state at S-1 has no target and the module looks it
    up for position S-2 only; the reference, written from the equations,
    agrees term by term. (2) The table and the head are the main ones: the
    tree has no second table and no second head, and the gradient of each is
    the sum of its two uses: the stack without the module gives the main
    term's, the reference the module term's, and they add up to the whole."""
    batch, = batches(1, seq=32)
    model = HybridCausalLM(program_config())
    tree = program.to_tree(CONFIG, weights)["model"]
    assert sorted(k for k in tree if "embed" in k or "head" in k) == [
        "embed", "head", "mtp_embed_norm"]

    def both(ids):
        loss, counters = model.apply(
            {"params": program.to_tree(CONFIG, weights)}, ids, ids)
        return loss - TOY["mtp_loss_weight"] * counters["mtp/loss"], \
            counters["mtp/loss"]

    ids = jnp.asarray(batch["input_ids"])
    main, module = both(ids)
    last = ids.at[:, -1].set((ids[:, -1] + 1) % TOY["vocab_size"])
    main_last, module_last = both(last)
    assert abs(float(main_last - main)) > 1e-6
    assert abs(float(module_last - module)) > 1e-6

    def scored_states(ids):
        x, _head, _c, states = hybrid.HybridModel(
            model.config, name="model").apply(
                {"params": program.to_tree(CONFIG, weights)["model"]}, ids,
                mtp=True)
        return x[:, :-1], states[0, :, :-2]

    for a, b in zip(scored_states(ids), scored_states(last)):
        np.testing.assert_array_equal(a, b)
    want = reference_terms(weights, {"input_ids": np.asarray(last)})
    np.testing.assert_allclose(main_last, want[0], rtol=2e-6)
    np.testing.assert_allclose(
        module_last, want[1] / TOY["mtp_loss_weight"], rtol=2e-6)
    # the shared leaves' gradients are the sums of both uses
    plain = HybridCausalLM(program_config(mtp_depth=0))
    main_only = {k: v for k, v in weights.items() if not k.startswith("mtp.")}

    def main_term(p):
        return plain.apply({"params": {"model": {
            k: v for k, v in program.to_tree(
                CONFIG, {**weights, **p})["model"].items()
            if not k.startswith("mtp_")}}}, ids, ids)[0]

    g_main = jax.grad(main_term)(main_only)
    g_module = jax.grad(lambda p: reference_terms(p, batch)[1])(weights)
    g_all = jax.grad(lambda p: our_loss(model, p, batch)[0])(weights)
    for name in ("embed", "head"):
        scale = float(jnp.max(jnp.abs(g_all[name])))
        assert float(jnp.max(jnp.abs(g_module[name]))) > 1e-2 * scale, name
        np.testing.assert_allclose(
            g_all[name] / scale, (g_main[name] + g_module[name]) / scale,
            atol=3e-5, err_msg=name)


def test_a_target_got_wrong_by_one_fails_by_a_wide_margin(weights):
    """What holds the shift by two: the module scored against ``t_{i+1}``
    (the main head's target) reads a module term several 1e-3 away from the
    reference's (seeded weights put every target near ln 512), hundreds of
    times the 1.3e-5 that the tolerance above allows."""
    batch, = batches(1, seq=64)
    model = HybridCausalLM(program_config())
    ids = jnp.asarray(batch["input_ids"])
    x, head, _c, states = hybrid.HybridModel(model.config, name="model").apply(
        {"params": program.to_tree(CONFIG, weights)["model"]}, ids, mtp=True)
    logp = jax.nn.log_softmax(states[0] @ head.T, -1)
    wrong = -jnp.mean(jnp.take_along_axis(
        logp[:, :-1], ids[:, 1:, None], -1))
    right = -jnp.mean(jnp.take_along_axis(
        logp[:, :-2], ids[:, 2:, None], -1))
    want = reference_terms(weights, batch)[1] / TOY["mtp_loss_weight"]
    np.testing.assert_allclose(right, want, rtol=2e-6)
    assert abs(float(wrong - want)) > 2e-3


def test_depth_zero_is_the_stack_without_the_module(weights):
    """``mtp_depth`` 0 (every other configuration): no ``mtp_*`` leaf, no
    ``mtp/...`` counter, and the loss is the main term of the same weights.
    ``labels=None`` gives the main logits whatever the depth."""
    batch, = batches(1, seq=32)
    plain = HybridCausalLM(program_config(mtp_depth=0))
    main = {k: v for k, v in weights.items() if not k.startswith("mtp.")}
    tree = {"params": {"model": {
        k: v for k, v in program.to_tree(CONFIG, weights)["model"].items()
        if not k.startswith("mtp_")}}}
    ids = jnp.asarray(batch["input_ids"])
    shapes = jax.eval_shape(
        lambda: plain.init(jax.random.PRNGKey(0), ids, ids))["params"]["model"]
    assert set(shapes) == set(tree["params"]["model"])
    loss, counters = plain.apply(tree, ids, ids)
    assert not any(k.startswith("mtp/") for k in counters)
    np.testing.assert_allclose(
        loss, reference_terms(weights, batch)[0], rtol=2e-6)
    with_module = HybridCausalLM(program_config())
    np.testing.assert_array_equal(
        with_module.apply({"params": program.to_tree(CONFIG, weights)}, ids),
        plain.apply(tree, ids))
    np.testing.assert_allclose(
        plain.apply(tree, ids), ref.logits(main | weights, ids, TOY, DOT),
        atol=3e-5)


@pytest.mark.parametrize("bad", [
    dict(mtp_depth=1, passes=2),
    dict(mtp_depth=-1), dict(mla_rope_dim=7),
    dict(objective="block_diffusion")])
def test_config_refuses_what_the_kinds_cannot_run(bad):
    with pytest.raises(ValueError):
        program_config(**bad)


def test_the_stack_plans_a_prefix_and_a_scanned_run():
    pattern = CONFIG["train"]["model_args"]["pattern"]
    assert hybrid.stack_plan(pattern) == ("LF", "LB", 5, "")
    assert program_config().mtp_letters == "LB"


def make_engine(weights, extra=None, devices=1, **model_kw):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(**model_kw)),
        model_parameters=program.to_tree(CONFIG, weights),
        config_params=dict(ENGINE, **(extra or {})),
        mesh=build_mesh(devices=jax.devices()[:devices]))
    return engine


ADAM = {"type": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
        "weight_decay": 0.0}


def test_two_steps_through_initialize_follow_the_reference(weights):
    """float32 through ``initialize()`` and two fused ``train_batch()``
    windows of 2 micro-batches under the cell's remat policy and the staged
    data pipeline: each step's loss, the first gradient's norm leaf by leaf
    (from Adam's first moment), the parameters' change after two steps,
    against the reference's own follower with the same Adam (which weighs the
    reference's TWO numerators by their two denominators); the counters of
    the window."""
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
    # a micro-step's counters, [accum]: seven latent mixers of 4 heads
    assert counters["attn/mla_heads"].tolist() == [28, 28]
    assert counters["mtp/depth"].tolist() == [1, 1]
    assert all(5.0 < v < 7.0 for v in counters["mtp/loss"].tolist())
    assert int(counters["moe/overflow"].sum()) == 0
    # six expert sublayers (the module's among them), 2 x 32 positions,
    # top-4 of 32 with 2 held
    assert 0 < int(counters["moe/local_assignments"][0]) < 6 * 64 * 4
    reg = engine.telemetry.registry
    assert reg.counter("attn/mla_heads").value == 4 * 28
    assert reg.counter("mtp/depth").value == 4
    program.close_train(engine)

    want_losses, want_grad, _first, want_change = follower.follow(
        ref, TOY, lambda: init(key), [kept[:2], kept[2:]], ADAM, DOT, 1)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert set(grad) == set(want_grad)
    for name in want_grad:
        if name.endswith("router_bias"):
            assert not grad[name].any() and not want_grad[name].any()
            assert not change[name].any() and not want_change[name].any()
            continue
        np.testing.assert_allclose(
            grad[name], want_grad[name], rtol=2e-3, err_msg=name)
        np.testing.assert_allclose(
            change[name], want_change[name], rtol=5e-2, err_msg=name)


def test_zero2_over_the_device_mesh_gives_the_one_device_steps(weights):
    """ZeRO-2 over four of the CPU's devices, the batch data-parallel: the
    specs take the module's leaves with no special case (every leaf is
    sharded or replicated by the same rule), and two windows read the losses
    that one device reads on the same four micro-batches."""
    kept = batches(4, rows=4)
    losses = {}
    for devices in (1, 4):
        engine = make_engine(
            weights, devices=devices,
            extra={"train_micro_batch_size_per_gpu": 4 // devices})
        feed = iter([program.feed(CONFIG, b) for b in kept])
        losses[devices] = [float(engine.train_batch(feed)) for _ in range(2)]
        if devices == 4:
            state = jax.tree_util.tree_leaves_with_path(engine.optimizer_state)
            assert any("mtp_lattn_wqb" in jax.tree_util.keystr(p)
                       for p, _ in state)
        program.close_train(engine)
    np.testing.assert_allclose(losses[4], losses[1], rtol=2e-5)
    assert losses[1][1] != losses[1][0]


def test_a_checkpoint_saved_and_loaded_gives_the_same_next_loss(
        weights, tmp_path):
    """One window, save, one more window; a fresh engine that loads the
    checkpoint reads that second loss on the same batches: the module's
    leaves and their optimizer state travel as every other leaf does."""
    kept = batches(4, seed=3)
    first = make_engine(weights)
    feed = iter([program.feed(CONFIG, b) for b in kept])
    first.train_batch(feed)
    first.save_checkpoint(str(tmp_path), tag="one")
    want = float(first.train_batch(feed))
    program.close_train(first)
    second = make_engine(ref.init_params(ref_ops.seed_key(6), TOY))
    second.load_checkpoint(str(tmp_path), tag="one")
    got = float(second.train_batch(
        iter([program.feed(CONFIG, b) for b in kept[2:]])))
    program.close_train(second)
    assert got == want


def test_configuration_file_keeps_the_published_numbers():
    """Every key of the catalog's ``config`` under its own name, but the
    ``reduced`` keys; the parameter count at the cut; the cell."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        bench = json.load(fd)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"]) \
        == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/joyai-llm-flash.json"
    assert entry["source"] == CONFIG["source"]
    cell = next(w for w in bench["workloads"] if w["config"] == CONFIG["name"])
    for line in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(line) <= 200 and line.isascii() and line.isprintable()
    for key, value in published.items():
        where = CONFIG["published"] if key in CONFIG["reduced"] else CONFIG
        assert where[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (6, 16, 16160)
    assumed = CONFIG["assumed"]
    assert (assumed["experts_routed_over"], assumed["expert_offset"],
            assumed["router_force_level"], assumed["mtp_loss_weight"]) == (
                256, 0, 1, 0.3)
    for key in ("mtp_loss_weight_why", "mtp_inputs", "norm_placement",
                "rotary", "routing", "router_bias", "weights", "moe_tile_why",
                "router_force_level_why", "remat_policy_why"):
        assert len(assumed[key]) > 40, key
    size = harness.sizes(CONFIG, False)
    assert size["qk_nope_head_dim"] + size["qk_rope_head_dim"] \
        == size["qk_head_dim"] == 192
    assert ref.layer_kinds(size) == ["ffn"] + ["moe"] * 5
    shapes = ref.shapes(size)
    total = sum(int(np.prod(s)) for s in shapes.values())
    assert total == 787_533_312
    assert f"{total:,}" in CONFIG["deployment"]

    def layer(*prefixes):
        return sum(int(np.prod(s[1:])) for k, s in shapes.items()
                   if k.startswith(prefixes))

    assert layer("mla.") == 26_349_568
    assert layer("mla.", "ffn.") == 70_391_808
    assert layer("mla.", "moe.") == 107_092_224
    assert layer("mtp.") == 115_486_976
    assert sum(int(np.prod(shapes[k])) for k in ("embed", "head")) \
        == 66_191_360
    # the uncut model by the same formulas: the published "48B" without the
    # module, which the count in a model's name leaves out
    whole = dict(size, num_hidden_layers=40, n_routed_experts=256,
                 vocab_size=129280)
    uncut = ref.shapes(whole)
    assert sum(int(np.prod(s)) for k, s in uncut.items()
               if not k.startswith("mtp.")) == 48_942_542_592
    # the program's tree at the cut holds the same leaves and shapes
    model = HybridCausalLM(program_config(size, ce_block_rows=512))
    ids = jnp.zeros((1, 64), jnp.int32)
    tree = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids))["params"]
    ours = {k: v.shape for k, v in program.from_tree(CONFIG, tree).items()}
    assert ours == {k: tuple(s) for k, s in shapes.items()}
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(tree)) == total
    cell = harness.load_json("workloads", CELL + ".json")
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    assert cell["traffic"] == {"generator": "lm_tokens"}
    assert traffic.tokens_per_micro_batch(cell) * cell["accum"] == 32768
    assert CELL in next(m for m in bench["end_to_end"] if m["name"]
                        == "train_tokens_per_s_per_chip")["workloads"]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert {"attn_mla_ms.train", "mtp_ms.train", "mtp_head_loss_ms.train",
            "flash_fwd_mla_roofline.train", "flash_bwd_mla_roofline.train",
            "attn_mixer_ms.train", "flash_ms.train", "stack_scan_ms.train",
            "swiglu_ffn_ms.train", "moe_route_ms.train", "head_loss_ms.train",
            "unscoped_ms.train"} <= listed
    # costs/gated_experts.py reads num_experts, which this file does not have
    assert not {"gated_experts_roofline.train", "qk_prep_ms.train",
                "flash_fwd_d128_roofline.train"} & listed
    assert all("workloads" in m for m in bench["per_layer"])
