"""The looped stack (models/hybrid.py ``passes`` > 1, kinds ``R`` and ``F``
with a gain after the mixer, the exit gate and the weighted head loss)
against its plain float32 reference (benchmark/reference/ouro.py) at toy
size on the CPU: the whole model's loss and every leaf's gradient, the loop
as a tied stack, the exit distribution and the weighted loss against the
plain computation, two fused windows through ``initialize()`` against the
reference's follower with the ``loop/...`` counters in the registry, the
accepted hybrid configurations' programs unchanged, and the configuration
file."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import HybridCausalLM, HybridLMConfig
from deepspeed_tpu.models.hybrid import HybridModel, looped_loss, period
from deepspeed_tpu.ops.cross_entropy import (
    blocked_lm_head_loss,
    exit_log_probs,
    weighted_lm_head_loss,
)
from deepspeed_tpu.parallel.mesh import build_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import harness, program  # noqa: E402
from benchmark.reference import ops as ref_ops  # noqa: E402
from benchmark.reference import ouro as ref  # noqa: E402
from benchmark.reference import train as follower  # noqa: E402


def load_config(name):
    return harness.load_json("configs", name + ".json")


CONFIG = load_config("ouro-2.6b")
LAYERS = 3
TOY = {**harness.sizes(CONFIG, True), "num_hidden_layers": LAYERS}
DOT = ref_ops.make_dot("float32")
ENGINE = {
    "train_micro_batch_size_per_gpu": 2, "gradient_accumulation_steps": 2,
    "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
    "zero_optimization": {"stage": 2}, "steps_per_print": 10 ** 6,
}


def program_config(**kw):
    args = {arg: TOY[key]
            for arg, key in CONFIG["program"]["config_args"].items()}
    args.update(pattern="RF" * LAYERS, post_norm=True, ce_block_rows=16)
    args.update(kw)
    return HybridLMConfig(**args)


@pytest.fixture(scope="module")
def weights():
    """The seeded weights with the gate moved off 1/2, so that the four
    passes weigh differently."""
    out = ref.init_params(ref_ops.seed_key(5), TOY)
    out["gate.b"] = out["gate.b"] + 0.5
    return out


def tokens(rows=2, seq=40, seed=0):
    return np.random.default_rng(seed).integers(
        0, 512, (rows, seq)).astype(np.int32)


def test_model_loss_and_every_leaf_gradient(weights):
    """Per-sublayer remat inside the two scans, as the cell runs it."""
    ids = tokens()
    model = HybridCausalLM(program_config(remat=True))
    batch = {"input_ids": ids}

    def theirs(p):
        return ref.loss_sums(p, batch, TOY, DOT)[0] / ref.counts(batch)[0]

    def ours(p):
        return model.apply(
            {"params": program.to_tree(CONFIG, p)}, ids, ids)[0]

    l_ref, g_ref = jax.value_and_grad(theirs)(weights)
    l_our, g_our = jax.jit(jax.value_and_grad(ours))(weights)
    np.testing.assert_allclose(l_our, l_ref, rtol=1e-6)
    assert set(g_our) == set(ref.shapes(TOY))
    for name in g_ref:
        scale = float(jnp.max(jnp.abs(g_ref[name]))) or 1.0
        np.testing.assert_allclose(
            g_our[name] / scale, g_ref[name] / scale, atol=3e-5, err_msg=name)
    logits = model.apply({"params": program.to_tree(CONFIG, weights)}, ids)
    np.testing.assert_allclose(
        logits, ref.logits(weights, ids, TOY, DOT), atol=2e-5)


@pytest.mark.parametrize("pattern,want", [
    ("RFRFRF", ("RF", 3)), ("DXDXDXGX", ("DXDXDXGX", 1)),
    ("MEMEMEMEM*E", ("MEMEMEMEM*E", 1)), ("MM", ("M", 2)), ("R", ("R", 1))])
def test_period_of_a_pattern(pattern, want):
    assert period(pattern) == want


def test_the_loop_is_the_tied_stack(weights):
    """R passes over L layers are one pass over R x L layers whose
    parameters are copies, the final norm after every L of them; each shared
    leaf's gradient is the sum of its copies'. The unrolled side is the
    reference's own layer, applied R x L times to untied copies."""
    ids = tokens(seed=1)
    passes = TOY["total_ut_steps"]
    eps = TOY["rms_norm_eps"]
    shared = {k: v for k, v in weights.items() if ref.stacked(k)}
    copies = {k: jnp.tile(v, (passes,) + (1,) * (v.ndim - 1))
              for k, v in shared.items()}

    def untied(layers):
        """The states after each L layers of the R x L untied stack."""
        x, out = weights["embed"][ids], []
        for i in range(passes * LAYERS):
            x = ref.layer(x, {k: v[i] for k, v in layers.items()}, TOY, DOT)
            if (i + 1) % LAYERS == 0:
                x = ref.norm(x, weights["norm_f.g"], eps)
                out.append(x)
        return jnp.stack(out)

    def looped_states(layers):
        tree = program.to_tree(CONFIG, {**weights, **layers})["model"]
        return HybridModel(program_config()).apply({"params": tree}, ids)[0]

    probe = jax.random.normal(
        jax.random.PRNGKey(3), (passes,) + ids.shape + (TOY["hidden_size"],))
    want, g_copies = jax.value_and_grad(
        lambda p: jnp.sum(untied(p) * probe))(copies)
    got, g_shared = jax.value_and_grad(
        lambda p: jnp.sum(looped_states(p) * probe))(shared)
    np.testing.assert_allclose(
        looped_states(shared), untied(copies), atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, g in g_shared.items():
        summed = g_copies[name].reshape((passes,) + g.shape).sum(0)
        scale = float(jnp.max(jnp.abs(summed)))
        np.testing.assert_allclose(
            g / scale, summed / scale, atol=3e-5, err_msg=name)


def test_exit_distribution_sums_to_one_and_survives_saturation():
    z = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 50)) * 3.0
    z = z.at[0, 0, 0].set(80.0).at[1, 0, 1].set(-80.0).at[:, 1, 2].set(-200.0)
    log_p = exit_log_probs(z)
    p = np.asarray(jnp.exp(log_p))
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    assert np.isfinite(np.asarray(log_p)).all()
    lam = np.asarray(jax.nn.sigmoid(z))
    np.testing.assert_allclose(
        p, np.asarray(ref.exit_distribution(jnp.asarray(lam))), atol=1e-6)
    # one pass: the only exit, whatever its gate says
    np.testing.assert_array_equal(np.asarray(exit_log_probs(z[:1])), 0.0)
    # a gate shut on every pass but the last leaves everything to the last
    np.testing.assert_allclose(p[:, 1, 2], [0, 0, 0, 1], atol=1e-6)


@pytest.mark.parametrize("seq,block", [(40, 16), (32, 16), (24, 512)])
def test_weighted_head_loss_is_the_plain_computation(seq, block):
    """Against per-position softmax cross-entropy written out, padded and
    unpadded blocks, an ignored label; its gradient to the states, the
    table and the WEIGHTS."""
    rng = np.random.default_rng(seq)
    states = jnp.asarray(rng.normal(size=(4, 2, seq, 32)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(64, 32)) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 64, (2, seq)), jnp.int32)
    labels = labels.at[1, 3].set(-100)
    weights = jnp.asarray(rng.uniform(size=(4, 2, seq)), jnp.float32)

    def plain(states, table, weights):
        logp = jax.nn.log_softmax(states @ table.T, axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(labels, 0)[None, ..., None], axis=-1)[..., 0]
        counted = labels != -100
        return jnp.sum(jnp.sum(weights * nll, 0) * counted) / jnp.sum(counted)

    def blocked(states, table, weights):
        return weighted_lm_head_loss(
            states, table, labels, weights, block_rows=block)

    want, g_want = jax.value_and_grad(plain, (0, 1, 2))(states, table, weights)
    got, g_got = jax.value_and_grad(blocked, (0, 1, 2))(states, table, weights)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_pass_of_weight_one_is_the_plain_head_loss_bit_for_bit(dtype):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 40, 32)), dtype)
    table = jnp.asarray(rng.normal(size=(64, 32)) * 0.2, dtype)
    labels = jnp.asarray(rng.integers(0, 64, (2, 40)), jnp.int32)
    labels = labels.at[0, 7].set(-1)

    def plain(x, table):
        return blocked_lm_head_loss(x, table, labels, block_rows=16)

    def weighted(x, table):
        return weighted_lm_head_loss(
            x[None], table, labels, jnp.ones((1,) + labels.shape),
            block_rows=16)

    want, g_want = jax.value_and_grad(plain, (0, 1))(x, table)
    got, g_got = jax.value_and_grad(weighted, (0, 1))(x, table)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    for a, b in zip(g_got, g_want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_looped_loss_is_the_objective_written_out():
    rng = np.random.default_rng(4)
    states = jnp.asarray(rng.normal(size=(4, 2, 24, 32)), jnp.float32)
    table = jnp.asarray(rng.normal(size=(64, 32)) * 0.2, jnp.float32)
    labels = jnp.asarray(rng.integers(0, 64, (2, 24)), jnp.int32)
    gate_w = jnp.asarray(rng.normal(size=(32,)) * 0.3, jnp.float32)
    gate_b = jnp.asarray([0.2], jnp.float32)
    loss, counters = looped_loss(
        states, table, labels, gate_w, gate_b, entropy_weight=0.1,
        block_rows=16)
    p = ref.exit_distribution(jax.nn.sigmoid(states @ gate_w + gate_b))
    nll = jnp.stack([ref_ops.nll(s @ table.T, labels) for s in states])
    entropy = -jnp.sum(p * jnp.log(p), 0)
    np.testing.assert_allclose(
        loss, jnp.mean(jnp.sum(p * nll, 0) - 0.1 * entropy), rtol=1e-6)
    assert int(counters["loop/passes"]) == 4
    shares = [float(counters[f"loop/exit_share_{t}"]) for t in (1, 2, 3, 4)]
    np.testing.assert_allclose(shares, p.mean((1, 2)), rtol=1e-5)
    np.testing.assert_allclose(sum(shares), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        counters["loop/exit_entropy"], entropy.mean(), rtol=1e-5)


def test_two_windows_through_initialize_follow_the_reference(weights):
    """float32 through ``initialize()`` and two fused ``train_batch()``
    windows of 2 micro-batches under per-sublayer remat: each step's loss,
    the first gradient's norm leaf by leaf (from Adam's first moment), and
    the parameters' change after two steps, against the reference's own
    follower with the same Adam; the ``loop/...`` counters leave the window
    [accum]-stacked and reach the registry."""
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=HybridCausalLM(program_config(remat=True)),
        model_parameters=program.to_tree(CONFIG, weights),
        config_params=dict(ENGINE, telemetry={
            "enabled": True, "interval": 1, "exporters": []}),
        mesh=build_mesh(devices=jax.devices()[:1]))
    kept = [{"input_ids": tokens(2, 32, seed)} for seed in range(4)]
    feed = iter([program.feed(CONFIG, b) for b in kept])
    losses = [float(engine.train_batch(feed))]
    grad = program.first_moment_norms(CONFIG, ref, engine, 0.9)
    losses.append(float(engine.train_batch(feed)))
    key = ref_ops.seed_key(5)

    def start(key):
        out = ref.init_params(key, TOY)
        out["gate.b"] = out["gate.b"] + 0.5
        return out

    change = program.change_norms(CONFIG, ref, engine, start, key)
    counters = engine.last_aux[0]
    assert counters["loop/exit_share_1"].shape == (2,)     # [accum]
    reg = engine.telemetry.registry
    assert reg.counter("loop/passes").value == 2 * 2 * 4   # windows x micro
    shares = [reg.counter(f"loop/exit_share_{t}").value for t in (1, 2, 3, 4)]
    np.testing.assert_allclose(sum(shares), 2 * 2, rtol=1e-5)
    assert shares[0] > shares[1] > shares[2] > 0           # the gate is open
    assert 0 < reg.counter("loop/exit_entropy").value < 4 * np.log(4)
    program.close_train(engine)

    adam = {"type": "adam", "lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "weight_decay": 0.0}
    want_losses, want_grad, _first, want_change = follower.follow(
        ref, TOY, lambda: start(key), [kept[:2], kept[2:]], adam, DOT, 1)
    np.testing.assert_allclose(losses, want_losses, rtol=2e-5)
    assert set(grad) == set(want_grad) and "gate" in grad
    for name in want_grad:
        np.testing.assert_allclose(
            grad[name], want_grad[name], rtol=2e-3, err_msg=name)
        np.testing.assert_allclose(
            change[name], want_change[name], rtol=5e-2, err_msg=name)


# sha256 of the StableHLO of value_and_grad(loss) at the configuration's toy
# widths under its own recipe, recorded on the parent commit of the PR that
# brought ``passes`` and the scan over a pattern's period (PR 32): a pattern
# with one period and one pass takes the Python loop it always took.
# PR 37 moved both on purpose and recorded them again on its own tree: the
# held experts' grouped products left XLA's ``while`` body for the kernels
# ``moe_ffn_fwd`` / ``moe_ffn_bwd`` (ops/moe.py), which every E and X layer
# runs, and the latent layer keeps the routed sum with the plan (remat runs
# no kernel again); before it they were 75b651a6...46cf7 and 28e39618...99631.
# PR 41 moved the Qwen3-Next one on purpose and recorded it again on its own
# tree: ``over_positions`` is gone from the Gated DeltaNet mixer, so at toy
# widths its glue runs as ``gdn_inputs`` / ``gated_head_rms_norm`` over the
# whole array where a row was taken under ``jax.vmap`` (it was
# 782645bc...d505e); the Nemotron one holds.
# PR 42 moved both on purpose and recorded them again on its own tree: the
# blocked head loss is a ``custom_vjp`` whose forward takes the chunk's
# gradient (ops/cross_entropy.py), so the checkpointed chunk and the scan's
# transposition are gone from every program that calls it (they were
# 822ad3b3...c42ad and fa45d5d6...a2806).
# PR 43 added the Ouro, SDAR and Laguna ones as its first commit, on its
# parent's code (70e5656: 05be95d6...846fa, e5c7f978...dae93 and
# 6a72d3c4...1d604), before it made the five attention mixers one
# (ops/transformer.py:attention_mixer). The Nemotron (kind ``*``) and Ouro
# (``R``) ones HOLD across it. Three moved and are recorded again on its own
# tree, because the five functions' XLA branches traced q, k and v in three
# different orders and one function has one (a projection's product,
# reshape, norm, rotary and head transpose for q, then for k, then v: kind
# ``*``'s order, which is also every REAL-size Nemotron window's). SDAR
# (``A``: the three products first, then the passes, then the transposes)
# and Laguna (``H``/``W``: v before the passes, the gate before the
# context's transpose) are the same dataflow graph as before in another
# order of independent operations (a Merkle hash over operations, operands
# and regions is equal, and no line differs once SSA names are normalised:
# CHANGES.md, PR 43). Qwen3-Next (``G``, it was 17d8c4fc...936cd) is a
# changed program at toy widths only: the XLA branch rotated q and k AFTER
# the head transpose ([B, H, S, D]) and now rotates before it ([B, S, H, D]),
# as the other kinds always did: the same arithmetic on the same elements
# (tests/unit/test_gated_attention.py holds it to the plain reference). At
# real size all five cells run the kernels' branch, and
# tools/window_program.py reads the parent's sha256 in four of them and the
# same graph in the fifth (Laguna).
ACCEPTED_PROGRAMS = {
    "laguna-s-2.1":
        "26e1b35a11d49acc8ca1a5240a7310dfb9a0b2373ad844d748c033b7c312cb91",
    "nemotron3-super-120b-a12b":
        "9f986de3c115df17c112ff43a7024f947ff3bc183959de30ea3da75316adea62",
    "ouro-2.6b":
        "05be95d6131629ed74b2708d367e3f1f11188e9fdaf9449db4dcc4baf11846fa",
    "qwen3-next-80b-a3b":
        "99af8133cba3f6af7d5ec4d3bd4b116d3f418c763f56bb38392220cc8a92e433",
    "sdar-30b-a3b-chat":
        "88cd9a67b66606a4296118ad3bf6630e061c4aa4002968a3b7cf572685e21db7",
}


@pytest.mark.parametrize("name", sorted(ACCEPTED_PROGRAMS))
def test_accepted_hybrid_configurations_compile_what_they_compiled(name):
    config = load_config(name)
    size = harness.sizes(config, True)
    args = {arg: size[key]
            for arg, key in config["program"]["config_args"].items()}
    args.update(config["train"]["model_args"])
    cfg = HybridLMConfig(**args)
    model = HybridCausalLM(cfg)
    ids = jnp.zeros((2, 64), jnp.int32)
    # block diffusion trains on (noisy_ids, clean_ids, loss_weights)
    weights = () if cfg.objective == "next_token" \
        else (jnp.ones(ids.shape, jnp.float32),)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids, ids, *weights))

    def loss(p, ids, *weights):
        return model.apply(p, ids, ids, *weights)[0]

    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, ids, *weights).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        ACCEPTED_PROGRAMS[name]


def test_program_size_does_not_grow_with_passes_or_layers():
    """The period's bodies are traced once however deep the stack and
    however many passes: the lowered program of 2 layers x 2 passes and of
    6 layers x 4 passes differ by less than a tenth in length (shapes)."""
    ids = jnp.zeros((1, 32), jnp.int32)

    def lowered(layers, passes):
        model = HybridCausalLM(program_config(
            pattern="RF" * layers, passes=passes, remat=True))
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids, ids))
        return jax.jit(jax.value_and_grad(
            lambda p, ids: model.apply(p, ids, ids)[0])).lower(
                params, ids).as_text()

    small, large = lowered(2, 2), lowered(6, 4)
    assert abs(len(large) - len(small)) < 0.1 * len(small)
    assert small.count("stablehlo.while") == large.count("stablehlo.while")


@pytest.mark.parametrize("bad", [
    dict(passes=0), dict(pattern="RF", head_dim=15), dict(pattern="RFZ")])
def test_config_refuses_what_it_cannot_run(bad):
    with pytest.raises(ValueError):
        HybridLMConfig(**bad)


def test_one_pass_of_the_new_kinds_is_a_plain_decoder(weights):
    """``passes=1``: no gate, the plain head loss, and the same layers: the
    loss of the first pass's state."""
    ids = tokens(seed=2)
    model = HybridCausalLM(program_config(passes=1))
    tree = program.to_tree(CONFIG, weights)["model"]
    tree = {k: v for k, v in tree.items() if not k.startswith("gate_")}
    loss = model.apply({"params": {"model": tree}}, ids, ids)
    first = ref.hiddens(weights, ids, TOY, DOT)[0]
    want = jnp.mean(ref.next_token_nll(
        weights, first, jnp.roll(ids, -1, axis=1), DOT)[:, :-1])
    np.testing.assert_allclose(loss, want, rtol=1e-6)


def test_the_second_gain_is_there_only_where_asked_for():
    """``post_norm`` off: ``R`` and ``F`` are the plain pre-norm sublayers,
    their trees hold no second gain, and a stack of them trains."""
    cfg = program_config(post_norm=False, passes=1)
    shapes = cfg.leaf_shapes()
    assert "post_norm" not in shapes["rattn"] and "post_norm" not in shapes["ffn"]
    assert "post_norm" in program_config().leaf_shapes()["ffn"]
    model, ids = HybridCausalLM(cfg), tokens(seed=3)
    params = model.init(jax.random.PRNGKey(0), ids, ids)
    assert not any("post_norm" in k or "gate" in k for k in params["params"]["model"])
    loss, grads = jax.value_and_grad(
        lambda p: model.apply(p, ids, ids))(params)
    assert abs(float(loss) - np.log(512)) < 0.2
    assert all(bool(jnp.isfinite(g).all()) and float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(grads))


def test_configuration_file_keeps_the_published_numbers():
    """Every key of the catalog's ``config`` under its own name, but the one
    ``reduced`` key; the parameter count at the cut; the program's tree."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    entry = next(c for c in harness.load_benchmark()["configs"]
                 if c["name"] == CONFIG["name"])
    assert entry["reduced"] == CONFIG["reduced"] == list(CONFIG["published"]) \
        == ["num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/ouro-2.6b.json"
    assert entry["source"] == CONFIG["source"]
    for key, value in published.items():
        where = CONFIG["published"] if key in CONFIG["reduced"] else CONFIG
        assert where[key] == value, key
    assert CONFIG["num_hidden_layers"] == 12
    for item in ("exit_entropy_weight", "post_norms", "final_norm",
                 "exit_gate", "early_exit", "attention", "rotary", "weights"):
        assert item in CONFIG["assumed"], item
    size = {**CONFIG, **CONFIG["assumed"]}
    shapes = ref.shapes(size)
    assert sum(int(np.prod(s)) for s in shapes.values()) == 817_991_681
    per_layer = sum(int(np.prod(s[1:])) for k, s in shapes.items()
                    if ref.stacked(k))
    assert per_layer == 51_388_416
    kwargs = {arg: size[key]
              for arg, key in CONFIG["program"]["config_args"].items()}
    kwargs.update(CONFIG["train"]["model_args"])
    assert kwargs["pattern"] == "RF" * 12 and kwargs["passes"] == 4
    model = HybridCausalLM(HybridLMConfig(**kwargs))
    tree = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32),
        jnp.zeros((1, 64), jnp.int32)))["params"]
    ours = {k: v.shape for k, v in program.from_tree(CONFIG, tree).items()}
    assert ours == {k: tuple(s) for k, s in shapes.items()}
    assert len(jax.tree_util.tree_leaves(tree)) == len(shapes)
