"""The qwen3_next stack (models/hybrid.py pattern ``DXDXDXGX``: Gated
DeltaNet, gated attention, gated experts, zero-centred norms) against its
plain float32 reference (benchmark/reference/qwen3_next.py) at toy size on
the CPU: the whole model's loss and every leaf's gradient, two steps through
``initialize()`` and the fused ``train_batch()`` window against the
reference's follower, checkpoints and ZeRO specs over its tree, and the
configuration file."""

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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import program  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402
from benchmark.reference import train as follower  # noqa: E402

CONFIG_FILE = os.path.join(ROOT, "benchmark/configs/qwen3-next-80b-a3b.json")
with open(CONFIG_FILE) as fd:
    CONFIG = json.load(fd)
TOY = {**{k: v for k, v in CONFIG.items() if isinstance(v, (int, float))},
       **{k: v for k, v in CONFIG["assumed"].items()
          if isinstance(v, (int, float))},
       **CONFIG["toy"], "router_force_level": 0}
DOT = ref_ops.make_dot("float32")
ENGINE = {
    "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 6,
}


def program_config(**kw):
    args = {arg: TOY[key]
            for arg, key in CONFIG["program"]["config_args"].items()}
    args.update(pattern="DXDXDXGX", norm_zero_centered=True, ce_block_rows=16)
    args.update(kw)
    return HybridLMConfig(**args)


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(ref_ops.seed_key(5), TOY)


def test_model_loss_and_every_leaf_gradient(weights):
    """40 positions: no multiple of the DeltaNet chunk of 16."""
    ids = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    model = HybridCausalLM(program_config())
    batch = {"input_ids": ids}

    def theirs(p):
        return ref.loss_sums(p, batch, TOY, DOT)[0] / ref.counts(batch)[0]

    def ours(p):
        return model.apply(
            {"params": program.to_tree(CONFIG, p)}, ids, ids)[0]

    l_ref, g_ref = jax.value_and_grad(theirs)(weights)
    l_our, g_our = jax.value_and_grad(ours)(weights)
    np.testing.assert_allclose(l_our, l_ref, rtol=1e-6)
    assert set(g_our) == set(ref.shapes(TOY))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=3e-5, err_msg=name)


@pytest.mark.parametrize("bad", [
    dict(gdn_key_heads=3, gdn_value_heads=4), dict(rotary_lanes=5),
    dict(rotary_lanes=32, head_dim=16), dict(pattern="DXQ")])
def test_config_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        HybridLMConfig(**bad)


def test_chunk_that_is_no_power_of_two_is_refused_when_the_layer_runs():
    model = HybridCausalLM(program_config(gdn_chunk=24))
    ids = jnp.zeros((1, 48), jnp.int32)
    with pytest.raises(ValueError, match="power of two"):
        model.init(jax.random.PRNGKey(0), ids, ids)


def windows(n, seed=0):
    rng = np.random.default_rng(seed)
    return [{"input_ids": rng.integers(0, 512, (2, 32)).astype(np.int32)}
            for _ in range(n)]


def make_engine(weights, extra=None, **model_kw):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(**model_kw)),
        model_parameters=program.to_tree(CONFIG, weights),
        config_params=dict(ENGINE, **(extra or {})),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def test_two_steps_through_initialize_follow_the_reference(weights):
    """float32 through ``initialize()`` and two fused ``train_batch()``
    windows of 2 micro-batches under per-sublayer remat: each step's loss,
    the first gradient's norm leaf by leaf (from Adam's first moment), and
    the parameters' change after two steps, against the reference's own
    follower with the same Adam."""
    engine = make_engine(weights, remat=True,
                         remat_policy="nothing_saveable+moe_plan")
    kept = windows(4)
    feed = iter([program.feed(CONFIG, b) for b in kept])
    losses = [float(engine.train_batch(feed))]
    grad = program.first_moment_norms(CONFIG, ref, engine, 0.9)
    losses.append(float(engine.train_batch(feed)))
    init = ref_ops.initializer(ref, TOY)
    key = ref_ops.seed_key(5)
    change = program.change_norms(CONFIG, ref, engine, init, key)
    counters = engine.last_aux[0]
    assert counters["moe/overflow"].shape == (2,)
    assert int(counters["moe/overflow"].sum()) == 0
    program.close_train(engine)

    adam = {"type": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "weight_decay": 0.0}
    want_losses, want_grad, _first, want_change = follower.follow(
        ref, TOY, lambda: init(key), [kept[:2], kept[2:]], adam, DOT, 1)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert set(grad) == set(want_grad) and "gdn.vectors" in grad
    for name in want_grad:
        np.testing.assert_allclose(
            grad[name], want_grad[name], rtol=2e-3, err_msg=name)
        np.testing.assert_allclose(
            change[name], want_change[name], rtol=5e-2, err_msg=name)


def test_int8_moments_checkpoint_and_zero_specs_take_the_tree(weights, tmp_path):
    """Cell 1's optimizer data types over the new leaves (expert-stacked
    [layers, held, E, F], per-head vectors), a save and a load, and the
    ZeRO-2 partition specs of the tree as it is."""
    extra = {"bf16": {"enabled": True},
             "data_types": {"optimizer_state_dtype": "int8",
                            "grad_accum_dtype": "bf16",
                            "master_dtype": "compensated"}}
    engine = make_engine(weights, extra=extra, remat=True)
    feed = iter([program.feed(CONFIG, b) for b in windows(4)])
    first = float(engine.train_batch(feed))
    assert abs(first - np.log(512)) < 0.2
    assert np.isfinite(float(engine.train_batch(feed)))
    leaves = set(engine.params["model"])
    assert {"gdn_in_qkvz", "gattn_wq", "gmoe_wg", "gmoe_shared_gate",
            "norm_f"} <= leaves
    assert set(engine.optimizer_state["mu"]["model"]) == leaves
    engine.save_checkpoint(str(tmp_path))
    batch = program.feed(CONFIG, windows(1, seed=7)[0])

    def loss(e):
        e.eval()
        out = e(*batch)
        return float(out[0] if isinstance(out, tuple) else out)

    other = make_engine(weights, extra=extra, remat=True)
    other.load_checkpoint(str(tmp_path))
    assert loss(other) == loss(engine)
    for e in (engine, other):
        program.close_train(e)
    from deepspeed_tpu.runtime.zero import zero_optstate_specs

    specs = zero_optstate_specs(
        program.to_tree(CONFIG, weights), 2, 2)["model"]
    assert all("data" in str(specs[k])
               for k in ("gmoe_wg", "gdn_in_qkvz", "gattn_wq", "embed"))


def test_configuration_file_keeps_the_published_numbers():
    """Every number of the catalog's ``config`` under its own key, but the
    three ``reduced`` keys; the parameter count at the cut."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        entry = next(c for c in json.load(fd)["configs"]
                     if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"]) \
        == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["file"] == "benchmark/configs/qwen3-next-80b-a3b.json"
    for key, value in published.items():
        where = CONFIG["published"] if key in CONFIG["reduced"] else CONFIG
        assert where[key] == value, key
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (4, 32, 18992)
    assert CONFIG["assumed"]["experts_routed_over"] == 512
    assert CONFIG["assumed"]["rotary_lanes"] == \
        CONFIG["partial_rotary_factor"] * CONFIG["head_dim"]
    size = {**CONFIG, **CONFIG["assumed"]}
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 625_667_136
    assert ref.kinds(size) == ["gdn", "gdn", "gdn", "gattn"]
    # the program's tree at the cut holds the same leaves and shapes
    kwargs = {arg: size[key]
              for arg, key in CONFIG["program"]["config_args"].items()}
    kwargs.update(CONFIG["train"]["model_args"])
    model = HybridCausalLM(HybridLMConfig(**kwargs))
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32),
        jnp.zeros((1, 64), jnp.int32)))["params"]
    ours = {k: v.shape for k, v in program.from_tree(CONFIG, tree).items()}
    assert ours == {k: tuple(s) for k, s in shapes.items()}
    assert sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(tree)) == 625_667_136
