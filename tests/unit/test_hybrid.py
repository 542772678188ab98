"""The hybrid stack (models/hybrid.py: Mamba-2, latent MoE that drops no
token, grouped-query attention) against its plain float32 reference
(benchmark/reference/nemotron_h.py), at toy size on the CPU: each layer kind
alone, the whole model's loss and every leaf's gradient, the chunked SSD
scan against the step-by-step recurrence, the sum of an expert layer's
shares, a skewed router, and the path through ``initialize()``."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import HybridCausalLM, HybridLMConfig
from deepspeed_tpu.ops import moe as moe_ops
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.models.hybrid import attention_spec
from deepspeed_tpu.ops.transformer import attention_mixer, rms_norm
from deepspeed_tpu.parallel.mesh import build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h as ref  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402

TOY = dict(
    hidden_size=64, vocab_size=512, hybrid_override_pattern="MEM*E",
    mamba_num_heads=4, mamba_head_dim=16, n_groups=1, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, n_routed_experts=2, experts_routed_over=8,
    expert_offset=0, num_experts_per_tok=3, routed_scaling_factor=5.0,
    moe_latent_size=32, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=96, num_attention_heads=2,
    num_key_value_heads=1, head_dim=16, layer_norm_epsilon=1e-5,
    initializer_range=0.02, time_step_min=0.001, time_step_max=0.1)
DOT = ref_ops.make_dot("float32")


def program_config(cfg=TOY, **kw):
    args = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        pattern=cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        mamba_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        chunk_size=cfg["chunk_size"], n_experts_held=cfg["n_routed_experts"],
        n_experts_routed=cfg["experts_routed_over"],
        expert_offset=cfg["expert_offset"], top_k=cfg["num_experts_per_tok"],
        moe_latent=cfg["moe_latent_size"],
        moe_intermediate=cfg["moe_intermediate_size"],
        moe_shared_intermediate=cfg["moe_shared_expert_intermediate_size"],
        attn_heads=cfg["num_attention_heads"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_tile=8, ce_block_rows=16)
    args.update(kw)
    return HybridLMConfig(**args)


def program_name(name):
    return (name[:-2] if name.endswith(".g") else name).replace(".", "_")


def to_tree(flat):
    return {"model": {program_name(k): v for k, v in flat.items()}}


def layer_of(flat, kind, i=0):
    """One layer's leaves under the program's names and the reference's."""
    theirs = ref.layer_params(flat, kind, i)
    ours = {(k[:-2] if k.endswith(".g") else k): v for k, v in theirs.items()}
    return ours, theirs


def mixers(cfg=TOY):
    pc = program_config(cfg)
    return {
        "mamba": lambda p, x: ssm.mamba2_mixer(
            p, x, heads=pc.mamba_heads, head_dim=pc.mamba_head_dim,
            groups=pc.mamba_groups, state=pc.ssm_state, chunk=pc.chunk_size,
            eps=pc.norm_eps),
        "moe": lambda p, x: moe_ops.latent_moe_mixer(
            p, x, top_k=pc.top_k, scale=pc.routed_scaling,
            held=pc.n_experts_held, offset=pc.expert_offset, tile=pc.moe_tile)[0],
        "attn": lambda p, x: attention_mixer(p, x, attention_spec(pc, "*")),
    }


@pytest.fixture(scope="module")
def weights():
    return ref.init_params(ref_ops.seed_key(5), TOY)


def normal(seed, shape):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("kind", ["mamba", "moe", "attn"])
def test_layer_kind_matches_reference(weights, kind):
    """Output and input-gradient of one mixer alone; 40 positions is not a
    multiple of the chunk of 16."""
    ours_p, theirs_p = layer_of(weights, kind)
    x = normal(1, (2, 40, TOY["hidden_size"]))
    probe = normal(2, x.shape)
    ref_mixer = ref.MIXERS[{"mamba": "M", "moe": "E", "attn": "*"}[kind]][1]

    def ours(x):
        return mixers()[kind](ours_p, rms_norm(x, ours_p["norm"], 1e-5))

    def theirs(x):
        return ref_mixer(theirs_p, ref.rms_norm(x, theirs_p["norm.g"], 1e-5),
                         TOY, DOT)

    np.testing.assert_allclose(ours(x), theirs(x), rtol=2e-4, atol=2e-6)
    g_ours = jax.grad(lambda x: jnp.sum(ours(x) * probe))(x)
    g_theirs = jax.grad(lambda x: jnp.sum(theirs(x) * probe))(x)
    np.testing.assert_allclose(g_ours, g_theirs, rtol=2e-4, atol=2e-6)


def test_model_loss_and_every_leaf_gradient(weights):
    ids = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    model = HybridCausalLM(program_config())
    batch = {"input_ids": ids}

    def theirs(p):
        return ref.loss_sums(p, batch, TOY, DOT)[0] / ref.counts(batch)[0]

    def ours(p):
        return model.apply({"params": to_tree(p)}, ids, ids)[0]

    l_ref, g_ref = jax.value_and_grad(theirs)(weights)
    l_our, g_our = jax.value_and_grad(ours)(weights)
    np.testing.assert_allclose(l_our, l_ref, rtol=1e-6)
    assert set(g_our) == set(ref.shapes(TOY))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=2e-5, err_msg=name)
    assert float(jnp.max(jnp.abs(g_our["moe.router_bias"]))) == 0.0


@pytest.mark.parametrize("seq", [16, 37, 40, 64])
def test_chunked_ssd_matches_the_recurrence(seq):
    b, h, p, g, n = 2, 4, 8, 2, 16
    x = normal(1, (b, seq, h, p))
    dt = jax.nn.softplus(normal(2, (b, seq, h)))
    a = -jnp.exp(normal(3, (h,)))
    bm, cm = normal(4, (b, seq, g, n)), normal(5, (b, seq, g, n))

    def sequential(x, dt, bm, cm):
        rep = lambda t: jnp.repeat(t, h // g, axis=2)  # noqa: E731
        return ref.recurrence(
            jnp.exp(dt * a), dt[..., None] * rep(bm), x, rep(cm))

    def chunked(x, dt, bm, cm):
        return ssm.ssd_chunked(x, dt, a, bm, cm, 16)

    np.testing.assert_allclose(
        chunked(x, dt, bm, cm), sequential(x, dt, bm, cm), rtol=1e-4, atol=1e-4)
    probe = normal(6, x.shape)
    for i in range(4):
        g1, g2 = (jax.grad(lambda *args: jnp.sum(f(*args) * probe), argnums=i)(
            x, dt, bm, cm) for f in (chunked, sequential))
        np.testing.assert_allclose(g1, g2, rtol=1e-3, atol=1e-3)


def test_expert_shares_add_up_to_the_uncut_layer(weights):
    """The E layer's outputs over all four shares of a toy deployment (8
    experts, 2 to a chip), the shared expert counted once, add up to the
    uncut reference's output for the whole layer."""
    whole = dict(TOY, n_routed_experts=8)
    flat = ref.init_params(ref_ops.seed_key(9), whole)
    _, theirs_p = layer_of(flat, "moe")
    x = normal(3, (2, 24, TOY["hidden_size"]))
    xn = ref.rms_norm(x, theirs_p["norm.g"], 1e-5)
    uncut = ref.moe_mixer(theirs_p, xn, whole, DOT)
    shared = DOT(ref.relu2(DOT(xn, theirs_p["shared_w1"], ref_ops.X_W)),
                 theirs_p["shared_w2"], ref_ops.X_W)
    total = shared
    for offset in range(0, 8, 2):
        share = {k: (v[offset:offset + 2] if k in ("w1", "w2") else v)
                 for k, v in theirs_p.items() if k != "norm.g"}
        out, counters = moe_ops.latent_moe_mixer(
            share, xn, top_k=3, scale=5.0, held=2, offset=offset, tile=8)
        assert int(counters["moe/overflow"]) == 0
        total = total + (out - shared)
        np.testing.assert_allclose(     # the reference, given the same share
            out, ref.moe_mixer(share, xn, dict(TOY, expert_offset=offset), DOT),
            rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(total, uncut, rtol=2e-4, atol=2e-6)


def test_skewed_router_drops_no_token():
    """A selection bias that sends every token to one held expert: 64
    experts routed over, top-3, so a level router gives each expert 3/64 of
    the tokens and this one takes 21x that. No overflow, every assignment
    has a row, and the result still equals the reference."""
    cfg = dict(TOY, experts_routed_over=64, n_routed_experts=4, expert_offset=8)
    flat = ref.init_params(ref_ops.seed_key(11), cfg)
    ours_p, theirs_p = layer_of(flat, "moe")
    bias = theirs_p["router_bias"].at[9].set(10.0)
    ours_p, theirs_p = dict(ours_p, router_bias=bias), dict(
        theirs_p, router_bias=bias)
    x = normal(4, (2, 32, TOY["hidden_size"]))
    xn = ref.rms_norm(x, theirs_p["norm.g"], 1e-5)
    out, counters = moe_ops.latent_moe_mixer(
        ours_p, xn, top_k=3, scale=5.0, held=4, offset=8, tile=8)
    tokens = 64
    assert int(counters["moe/max_expert_load"]) == tokens > 10 * tokens * 3 / 64
    assert int(counters["moe/overflow"]) == 0
    assert int(counters["moe/tokens_without_held_expert"]) == 0
    assert int(counters["moe/local_assignments"]) >= tokens
    np.testing.assert_allclose(
        out, ref.moe_mixer(theirs_p, xn, cfg, DOT), rtol=2e-4, atol=2e-6)


def test_expert_layer_on_a_mesh_of_several_devices_computes_the_same():
    """A kernel is not partitioned: on a mesh of more than one device the
    grouped products run whole on each (``shard_map`` over replicated
    operands, as ``ops/pallas.py:adam_leaf_update`` wraps its kernel),
    values and gradients those of one device, the tokens sharded or not."""
    from jax.sharding import NamedSharding, PartitionSpec

    flat = ref.init_params(ref_ops.seed_key(11), TOY)
    ours_p, theirs_p = layer_of(flat, "moe")
    xn = ref.rms_norm(normal(7, (2, 32, TOY["hidden_size"])),
                      theirs_p["norm.g"], 1e-5)
    kw = dict(top_k=TOY["num_experts_per_tok"],
              scale=TOY["routed_scaling_factor"],
              held=TOY["n_routed_experts"], offset=0, tile=8)
    mesh = build_mesh(devices=jax.devices()[:2])

    def summed(mesh):
        return jax.jit(jax.value_and_grad(lambda p, x: jnp.sum(
            moe_ops.latent_moe_mixer(p, x, mesh=mesh, **kw)[0] ** 2), (0, 1)))

    want = summed(None)(ours_p, xn)
    got = summed(mesh)(ours_p, jax.device_put(
        xn, NamedSharding(mesh, PartitionSpec("data"))))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_forced_level_selection_ignores_the_weights_and_matches_reference():
    """``router_force_level``: the same chosen sets whatever the router's
    weights are (so the held experts' load cannot drift or collapse), near
    tokens * k / routed an expert, and the reference computes the same."""
    cfg = dict(TOY, experts_routed_over=32, n_routed_experts=4,
               router_force_level=1)
    flat = ref.init_params(ref_ops.seed_key(13), cfg)
    ours_p, theirs_p = layer_of(flat, "moe")
    x = normal(5, (2, 96, TOY["hidden_size"]))
    xn = ref.rms_norm(x, theirs_p["norm.g"], 1e-5)
    kw = dict(top_k=3, scale=5.0, held=4, offset=0, tile=8, force_level=True)
    out, counters = moe_ops.latent_moe_mixer(ours_p, xn, **kw)
    np.testing.assert_allclose(
        out, ref.moe_mixer(theirs_p, xn, cfg, DOT), rtol=2e-4, atol=2e-6)
    skewed = dict(ours_p, router_bias=ours_p["router_bias"].at[1].set(10.0),
                  router=-ours_p["router"])
    _, again = moe_ops.latent_moe_mixer(skewed, 3.0 * xn, **kw)
    assert {k: int(v) for k, v in again.items()} == {
        k: int(v) for k, v in counters.items()}
    level = 2 * 96 * 3 * 4 / 32
    assert 0.7 * level < int(counters["moe/local_assignments"]) < 1.3 * level
    assert int(counters["moe/max_expert_load"]) < 2 * level / 4
    chosen, _ = moe_ops.route_sigmoid_topk(
        xn.reshape(-1, 64), ours_p["router"], ours_p["router_bias"], 3, 5.0,
        level=jnp.arange(192) % 96)
    np.testing.assert_array_equal(chosen[:96], chosen[96:])   # by position


@pytest.mark.parametrize("tile,held", [(1, 3), (4, 3), (8, 3), (4, 6)])
def test_plan_sorts_every_held_assignment_into_tiles(tile, held):
    rng = np.random.default_rng(tile)
    tokens, k, routed, offset = 50, 3, 8, 2
    chosen = np.stack([rng.permutation(routed)[:k] for _ in range(tokens)])
    plan, sizes = jax.tree_util.tree_map(np.asarray, moe_ops.plan_held_rows(
        jnp.asarray(chosen, jnp.int32), held, offset, tile))
    want = sorted((e - offset) * tokens + t for t in range(tokens)
                  for e in chosen[t] if 0 <= e - offset < held)
    n = len(want)
    assert list(plan["keys"][:n]) == want and np.all(
        plan["keys"][n:] == held * tokens)
    np.testing.assert_array_equal(sizes, np.bincount(
        [key // tokens for key in want], minlength=held))
    assert int(plan["n_tiles"]) == int(np.sum(-(-sizes // tile)))
    seen = []
    for t in range(int(plan["n_tiles"])):          # a tile holds ONE expert's rows
        first, rows = plan["tile_first"][t], plan["tile_rows"][t]
        mine = plan["keys"][first:first + rows]
        assert 0 < rows <= tile and set(mine // tokens) == {plan["tile_expert"][t]}
        seen.extend(mine)
    assert seen == want                            # each exactly once


def test_expert_layer_works_under_per_layer_remat(weights):
    """DeepSpeedMoETransformerLayer refuses remat (the GShard path); the
    expert-share layer must run under the per-layer remat the cell uses."""
    ids = np.random.default_rng(1).integers(0, 512, (2, 32)).astype(np.int32)
    tree = to_tree(weights)

    def grads(**kw):
        model = HybridCausalLM(program_config(**kw))
        return jax.grad(lambda p: model.apply({"params": p}, ids, ids)[0])(tree)

    plain = grads()
    for policy in ("nothing_saveable",
                   "dots_with_no_batch_dims_saveable+flash_out+flash_lse+moe_plan"):
        again = grads(remat=True, remat_policy=policy)
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(again)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-8)


def test_attention_repeats_kv_heads_for_grouped_queries():
    q, k, v = normal(1, (2, 4, 24, 8)), normal(2, (2, 2, 24, 8)), normal(
        3, (2, 2, 24, 8))
    grouped = attention(q, k, v, causal=True)
    repeated = attention(
        q, jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1), causal=True)
    np.testing.assert_array_equal(grouped, repeated)


@pytest.mark.parametrize("bad", [
    dict(pattern="MEZ"), dict(n_experts_held=4, expert_offset=6),
    dict(attn_heads=3, kv_heads=2), dict(mamba_heads=3, mamba_groups=2)])
def test_config_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        HybridLMConfig(**bad)


def test_model_initializes_itself_and_gives_logits():
    pc = program_config()
    model = HybridCausalLM(pc)
    ids = np.zeros((1, 16), np.int32)
    params = model.init(jax.random.PRNGKey(0), ids, ids)["params"]
    shapes = {k: v.shape for k, v in params["model"].items()}
    assert shapes["moe_w1"] == (2, 2, 32, 48)      # [E layers, held, L, F]
    assert shapes["mamba_in_proj"] == (2, 64, 64 + 96 + 4)
    assert shapes["attn_wk"] == (1, 64, 16)
    assert model.apply({"params": params}, ids).shape == (1, 16, 512)
    loss, counters = model.apply({"params": params}, ids, ids)
    assert np.isfinite(float(loss)) and int(counters["moe/overflow"]) == 0


def test_zero_specs_shard_the_expert_stacked_leaves(weights):
    from deepspeed_tpu.runtime.zero import zero_optstate_specs

    specs = zero_optstate_specs(to_tree(weights), 2, 2)["model"]
    assert "data" in str(specs["moe_w1"]) and "data" in str(specs["embed"])


ENGINE = {
    "train_micro_batch_size_per_gpu": 2,
    "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "bf16": {"enabled": True},
    "zero_optimization": {"stage": 2},
    "data_types": {"optimizer_state_dtype": "int8", "grad_accum_dtype": "bf16",
                   "master_dtype": "compensated"},
    "steps_per_print": 10 ** 6,
}


def make_engine(weights, extra=None, **model_kw):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(**model_kw)),
        model_parameters=to_tree(weights),
        config_params=dict(ENGINE, **(extra or {})),
        mesh=build_mesh(devices=jax.devices()[:1]))
    return engine


def windows(n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        ids = rng.integers(0, 512, (2, 32)).astype(np.int32)
        yield (ids, ids)


def test_fused_window_through_initialize_with_int8_moments(weights, tmp_path):
    """Cell 1's optimizer data types over expert-stacked leaves, per-layer
    remat, and the routing counters: out of the compiled window beside the
    loss, into the registry at the export boundary."""
    engine = make_engine(
        weights, remat=True,
        extra={"telemetry": {"enabled": True, "interval": 1,
                             "exporters": []}})
    feed = windows(4)
    first = float(engine.train_batch(feed))
    second = float(engine.train_batch(feed))
    assert np.isfinite(first) and np.isfinite(second)
    assert abs(first - np.log(512)) < 0.2
    # int8 per run of the minor axis where a leaf has a run (ops/quant.py:
    # a width of 128 or more), bf16 where it has none
    from deepspeed_tpu.ops import quant

    stored = {
        k: (m["q"].dtype if quant.is_quantized(m) else m.dtype,
            quant.quantized_run(engine.params["model"][k].shape))
        for k, m in engine.optimizer_state["mu"]["model"].items()
    }
    assert all(
        dtype == (jnp.int8 if run else jnp.bfloat16)
        for dtype, run in stored.values()
    ), stored
    assert any(run for _, run in stored.values()), stored
    counters = engine.last_aux[0]
    assert counters["moe/local_assignments"].shape == (2,)     # [accum]
    reg = engine.telemetry.registry
    assigned = reg.counter("moe/local_assignments").value
    # 2 windows x 2 micro-steps x 64 tokens x 2 E layers, 3 of 8 chosen, 2 held
    assert 0.5 < assigned / (2 * 2 * 64 * 2 * 3 * 2 / 8) < 1.5
    assert reg.counter("moe/overflow").value == 0
    assert reg.gauge("moe/max_expert_load").value >= 64 * 3 / 8
    engine.close_data_pipeline()
    engine.telemetry.close()


def test_save_then_load_gives_the_same_loss(weights, tmp_path):
    engine = make_engine(weights)
    feed = windows(3)
    engine.train_batch(feed)
    engine.save_checkpoint(str(tmp_path))
    batch = next(windows(1, seed=7))
    engine.eval()
    before = float(engine(*batch)[0] if isinstance(engine(*batch), tuple)
                   else engine(*batch))
    other = make_engine(weights)
    other.load_checkpoint(str(tmp_path))
    other.eval()
    out = other(*batch)
    after = float(out[0] if isinstance(out, tuple) else out)
    assert before == after


def test_reference_reads_the_pattern_from_a_number():
    assert ref.pattern({"layer_kinds": 12121212132}) == "MEMEMEMEM*E"
    assert ref.pattern(TOY) == "MEM*E"


def test_configuration_file_keeps_the_published_widths():
    with open(os.path.join(
            ROOT, "benchmark/configs/nemotron3-super-120b-a12b.json")) as fd:
        cfg = json.load(fd)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        entry = next(c for c in json.load(fd)["configs"]
                     if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == list(cfg["published"])
    widths = dict(hidden_size=4096, head_dim=128, mamba_head_dim=64,
                  ssm_state_size=128, conv_kernel=4, chunk_size=128,
                  moe_latent_size=1024, moe_intermediate_size=2688,
                  moe_shared_expert_intermediate_size=5376,
                  num_experts_per_tok=22, routed_scaling_factor=5,
                  layer_norm_epsilon=1e-5)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["assumed"]["experts_routed_over"] == cfg["published"][
        "n_routed_experts"] == 512
    assert ref.pattern(cfg["assumed"]) == cfg["hybrid_override_pattern"]
    assert cfg["hybrid_override_pattern"] in cfg["published"][
        "hybrid_override_pattern"]
    shapes = ref.shapes({**cfg, **cfg["assumed"]})
    assert sum(int(np.prod(s)) for s in shapes.values()) == 921_066_480


def test_cost_of_the_held_experts_is_the_expectation():
    from benchmark.costs import moe_experts

    cell = {"micro": 2, "seq": 8192, "accum": 2}
    size = {"layer_kinds": 12121212132, "n_routed_experts": 16,
            "experts_routed_over": 512, "num_experts_per_tok": 22,
            "moe_latent_size": 1024, "moe_intermediate_size": 2688}
    flops, nbytes = moe_experts.per_window(cell, size)
    assignments = 16384 * 22 * 16 / 512
    assert flops == 2 * 5 * assignments * 6 * 2 * 1024 * 2688
    assert nbytes > 2 * 5 * 16 * 2 * 1024 * 2688 * 8
