"""The sdar_moe stack under its block-diffusion objective (models/hybrid.py
pattern ``AS`` x 6, ``objective="block_diffusion"``) against its plain
float32 reference (benchmark/reference/sdar.py) at toy size on the CPU: the
whole model's loss and every leaf's gradient, on the XLA path and through
the flash kernels; two steps through ``initialize()`` and the fused
``train_batch()`` window on the three-array feed (one of them float32)
against the reference's follower, with the ``diffusion/...`` counters; the
traffic generator; the configuration file."""

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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, program  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402
from benchmark.reference import train as follower  # noqa: E402
from benchmark.traffic import block_diffusion_tokens as traffic  # noqa: E402

CELL = "sdar-30b-a3b-chat.train-blockdiff-seq8192"
CONFIG_FILE = os.path.join(ROOT, "benchmark/configs/sdar-30b-a3b-chat.json")
with open(CONFIG_FILE) as fd:
    CONFIG = json.load(fd)
TOY = {**harness.sizes(CONFIG, True), "router_force_level": 0}
DOT = ref_ops.make_dot("float32")
ENGINE = {
    "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 6,
}


def program_config(**kw):
    args = {arg: TOY[key]
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


@pytest.mark.parametrize("flash", [False, True])
def test_model_loss_and_every_leaf_gradient(weights, flash, monkeypatch):
    """A row of 128 tokens (2 x 128 positions through the stack, 32 blocks
    a side). ``flash``: the three kernels under the mask in interpret mode
    on a 4 x 4 grid of blocks; else the XLA path under the dense mask. The
    tolerances are float32 sums in another order."""
    if flash:
        monkeypatch.setattr(attn_ops, "FLASH_MODE", "always")
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "DEFAULT_BLOCK_K", 64)
    batch, = batches(1, seq=128)
    model = HybridCausalLM(program_config())

    def theirs(p):
        return ref.loss_sums(p, batch, TOY, DOT)[0] / ref.counts(batch)[0]

    def ours(p):
        return model.apply(
            {"params": program.to_tree(CONFIG, p)},
            *program.feed(CONFIG, batch))[0]

    l_ref, g_ref = jax.value_and_grad(theirs)(weights)
    l_our, g_our = jax.value_and_grad(ours)(weights)
    np.testing.assert_allclose(l_our, l_ref, rtol=2e-6)
    assert set(g_our) == set(ref.shapes(TOY))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=3e-5, err_msg=name)


def test_the_clean_half_sees_no_noise_and_the_head_no_clean_half(weights):
    """What the mask and the objective promise, on the model: the loss does
    not move with the noisy ids of a LATER block, nor with weights of 0."""
    batch, = batches(1, seq=32, seed=3)
    model = HybridCausalLM(program_config())
    params = {"params": program.to_tree(CONFIG, weights)}

    def loss(noisy, clean, weights):
        return float(model.apply(params, noisy, clean, weights)[0])

    noisy, clean, w = program.feed(CONFIG, batch)
    base = loss(noisy, clean, w)
    # only the first block's loss is counted; change the noise in block 3
    first = np.zeros_like(w)
    first[:, :4] = np.maximum(w[:, :4], 1.0)
    moved = noisy.copy()
    moved[:, 12:16] = (moved[:, 12:16] + 7) % 500
    assert loss(noisy, clean, first) == loss(moved, clean, first)
    assert loss(moved, clean, w) != base
    # ... and the clean ids of the SAME or a later block do not reach it
    later = clean.copy()
    later[:, 4:] = (later[:, 4:] + 3) % 500
    np.testing.assert_allclose(
        loss(noisy, clean, first), loss(noisy, np.concatenate(
            [clean[:, :4], later[:, 4:]], axis=1), first), rtol=1e-6)


@pytest.mark.parametrize("bad", [
    dict(pattern="DSAS"), dict(pattern="ASRF"), dict(passes=2),
    dict(objective="masked"), dict(pattern="A*")])
def test_config_refuses_what_the_objective_cannot_run(bad):
    with pytest.raises(ValueError):
        program_config(**bad)


def test_objective_needs_its_three_arrays(weights):
    model = HybridCausalLM(program_config())
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="noisy_ids, clean_ids"):
        model.apply({"params": program.to_tree(CONFIG, weights)}, ids, ids)


def make_engine(weights, extra=None, **model_kw):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(**model_kw)),
        model_parameters=program.to_tree(CONFIG, weights),
        config_params=dict(ENGINE, **(extra or {})),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def test_two_steps_through_initialize_follow_the_reference(weights):
    """float32 through ``initialize()`` and two fused ``train_batch()``
    windows of 2 micro-batches under per-layer remat, the staged data
    pipeline carrying the float32 leaf: each step's loss, the first
    gradient's norm leaf by leaf (from Adam's first moment), the
    parameters' change after two steps, against the reference's own
    follower with the same Adam; the counters of the window."""
    engine = make_engine(
        weights, remat=True,
        remat_policy=CONFIG["train"]["model_args"]["remat_policy"],
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
    assert counters["diffusion/positions"].tolist() == [64, 64]   # [accum]
    last = kept[2:]
    assert counters["diffusion/masked_positions"].tolist() == [
        int((b["loss_weights"] > 0).sum()) for b in last]
    np.testing.assert_allclose(
        counters["diffusion/loss_weight_sum"],
        [b["loss_weights"].sum() for b in last], rtol=1e-6)
    assert int(counters["moe/overflow"].sum()) == 0
    reg = engine.telemetry.registry
    assert reg.counter("diffusion/positions").value == 4 * 64
    assert reg.counter("diffusion/masked_positions").value == sum(
        int((b["loss_weights"] > 0).sum()) for b in kept)
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


def test_bf16_engine_reads_the_weights_the_reference_reads(weights):
    """Cell 1's data types: the engine hands floating batch leaves to the
    model in bfloat16, and the generator's weights survive that, so the
    counter reads their float32 sum to the bit."""
    extra = {"bf16": {"enabled": True},
             "data_types": {"optimizer_state_dtype": "int8",
                            "grad_accum_dtype": "bf16",
                            "master_dtype": "compensated"}}
    engine = make_engine(weights, extra=extra, remat=True)
    kept = batches(2, seed=9)
    loss = float(engine.train_batch(
        iter([program.feed(CONFIG, b) for b in kept])))
    assert np.isfinite(loss)
    np.testing.assert_array_equal(
        engine.last_aux[0]["diffusion/loss_weight_sum"],
        [b["loss_weights"].sum(dtype=np.float32) for b in kept])
    assert {"qattn_wq", "qattn_q_norm", "smoe_wg", "smoe_router"} <= set(
        engine.params["model"])
    assert not any("shared" in k for k in engine.params["model"])
    program.close_train(engine)


def test_generator_makes_what_the_configuration_states():
    cell = {"micro": 4, "chips": 1, "seq": 4096}
    size = dict(harness.sizes(CONFIG, False))
    one, two = (next(traffic.micro_batches(77, cell, size)) for _ in range(2))
    other = next(traffic.micro_batches(78, cell, size))
    for name in ("noisy_ids", "clean_ids", "loss_weights"):
        np.testing.assert_array_equal(one[name], two[name])
        assert not np.array_equal(one[name], other[name])
    stream = traffic.micro_batches(77, cell, size)
    assert not np.array_equal(next(stream)["clean_ids"],
                              next(stream)["clean_ids"])
    mask_id, block, t_min = 18991, 4, size["t_min"]
    clean, noisy, w = one["clean_ids"], one["noisy_ids"], one["loss_weights"]
    assert clean.dtype == noisy.dtype == np.int32 and w.dtype == np.float32
    assert clean.shape == noisy.shape == w.shape == (4, 4096)
    assert 0 <= clean.min() and clean.max() == mask_id - 1   # never the mask
    assert len(np.unique(clean, axis=0)) == 4                # rows differ
    masked = w > 0
    np.testing.assert_array_equal(noisy[masked], mask_id)
    np.testing.assert_array_equal(noisy[~masked], clean[~masked])
    # one level a block; 1 / t on bfloat16's grid, t in [t_min, 1]
    by_block = w.reshape(4, -1, block)
    level = by_block.max(-1)
    assert ((by_block == level[..., None]) | (by_block == 0)).all()
    seen = level[level > 0]
    assert 1.0 <= seen.min() and seen.max() <= 1 / t_min
    np.testing.assert_array_equal(
        seen, seen.astype(jnp.bfloat16).astype(np.float32))
    # t uniform on [0.2, 1]: the masked share is its mean, and the weights
    # make the sum an unbiased count of the positions
    assert abs(masked.mean() - (1 + t_min) / 2) < 0.02
    assert abs(w.sum() / w.size - 1.0) < 0.03
    assert traffic.tokens_per_micro_batch({**cell, "micro": 2}) == 2 * 4096


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` under its own key, but the
    three ``reduced`` keys; the parameter count at the cut; the cell."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        bench = json.load(fd)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"]) \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/sdar-30b-a3b-chat.json"
    assert entry["source"] == CONFIG["source"]
    for key, value in published.items():
        where = CONFIG["published"] if key in CONFIG["reduced"] else CONFIG
        assert where[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (6, 16, 18992)
    assumed = CONFIG["assumed"]
    assert (assumed["experts_routed_over"], assumed["expert_offset"],
            assumed["block_length"], assumed["router_force_level"]) == (
                128, 0, 4, 1)
    for key in ("block_length_why", "schedule_why", "mask_token_id_why",
                "logit_shift", "router_force_level_why", "remat_policy_why"):
        assert len(assumed[key]) > 40, key
    size = harness.sizes(CONFIG, False)
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 645_623_296
    layer = sum(int(np.prod(s[1:])) for k, s in shapes.items()
                if ref.stacked(k))
    assert layer == 94_638_336
    # the program's tree at the cut holds the same leaves and shapes
    kwargs = {arg: size[key]
              for arg, key in CONFIG["program"]["config_args"].items()}
    kwargs.update(CONFIG["train"]["model_args"])
    model = HybridCausalLM(HybridLMConfig(**kwargs))
    ids = jnp.zeros((1, 64), jnp.int32)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), ids, ids, jnp.zeros((1, 64))))["params"]
    ours = {k: v.shape for k, v in program.from_tree(CONFIG, tree).items()}
    assert ours == {k: tuple(s) for k, s in shapes.items()}
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(tree)) == 645_623_296
    cell = harness.load_json("workloads", CELL + ".json")
    assert (cell["micro"], cell["seq"], cell["accum"], cell["chips"]) == (
        2, 8192, 2, 1)
    assert cell["traffic"] == {"generator": "block_diffusion_tokens"}
    assert traffic.tokens_per_micro_batch(cell) * cell["accum"] == 32768
    assert CELL in next(m for m in bench["end_to_end"] if m["name"]
                        == "train_tokens_per_s_per_chip")["workloads"]
