"""The reduced-state Adam update (PR 27): int8 first moments stored in the
parameter's own layout (ops/quant.py), the one-pass Pallas kernel that
updates them (ops/pallas.py:adam_leaf_update, interpret mode here) against
the plain whole-leaf XLA update and against float32 arithmetic written out
in numpy, which leaves take which path, and the ``adam_update_path`` line.

The grid (state format x compensated x layout) is the one the chunked-loop
and flat-domain tests of tests/unit/test_memory_savers.py covered before
both of those paths went: 12 + 4 + 1 cases.
"""

import importlib
import json
import logging
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.ops import quant
from deepspeed_tpu.ops.optimizers import Adam
from deepspeed_tpu.runtime import zero as zero_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# three shapes the kernel serves, one whose width is no multiple of 128
# (two runs of 1,160 that cut through a lane tile), two whose rows are no
# multiple of 128 (GPT-2 1.5B's 1,600; a token table's 12,576 rows a chip
# under dp 4: the last tile is ragged, on one device and in a shard), two
# that the chip stores rows-minor and the kernel takes transposed, a run
# down the sublanes (GPT-2 1.5B's 1,600-wide stacks), one that must fall back
SHAPES = {
    "stacked": (4, 256, 640),
    "matrix": (512, 256),
    "experts": (6, 2, 128, 384),
    "odd_width": (2, 128, 2320),
    "odd_rows": (2, 160, 384),
    "table": (576, 256),
    "rows_minor": (2, 256, 160),
    "rows_minor_runs": (256, 2240),
    "vectors": (5, 16),
}
ON_KERNEL = set(SHAPES) - {"vectors"}
LR = 1e-2


def _tree(seed, dtype, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        k: jnp.asarray(
            rng.normal(size=s) * scale * (1 + np.arange(s[-1]) % 7), dtype
        )
        for k, s in SHAPES.items()
    }


def _layout(layout, params):
    """(mesh, specs) the way the engine lays ZeRO state out, or None."""
    if layout == "one_device":
        return None
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    # one leaf carries a tensor-parallel spec on its rows (beside the data
    # axis), one on its width (whose shards hold no whole run: that leaf
    # must take the plain update)
    model_specs = {
        "matrix": PartitionSpec(("data", "model"), None),
        "stacked": PartitionSpec(None, None, "model"),
    }
    specs = zero_lib.zero_optstate_specs(
        params, 2, 1, model_specs=model_specs, prefer_leading=True
    )
    return mesh, specs


def _place(tree, state, shard):
    if shard is None:
        return tree, state
    mesh, specs = shard
    put = lambda t, s: jax.device_put(
        t, zero_lib.specs_to_shardings(s, mesh)
    )
    state_specs = zero_lib.optstate_specs_like(
        state, specs, tree, axis_sizes=dict(mesh.shape)
    )
    return put(tree, specs), put(state, state_specs)


def _step(opt, params, grads, state, shard, kernel, **kw):
    fn = jax.jit(
        lambda p, g, s: opt.apply(
            p, g, s, jnp.float32(LR), shard=shard, kernel=kernel, **kw
        )[:2]
    )
    return fn(params, grads, state)


def _masters(opt, params, state):
    if not opt.master_compensation:
        return {k: np.asarray(v, np.float32) for k, v in params.items()}
    return {
        k: np.asarray(quant.decode_master(params[k], state["comp"][k]))
        for k in params
    }


def _moments(state, name):
    return {
        k: np.asarray(quant.decode_moment(v))
        for k, v in state[name].items()
    }


def _started(opt, dtype, shard):
    """Parameters, a state with one step behind it, and the next gradient."""
    params = _tree(0, dtype)
    params, state = _place(params, opt.init(params), shard)
    params, state = _step(
        opt, params, _tree(1, dtype, 0.1), state, shard, kernel=False
    )
    return params, state, _tree(2, dtype, 0.1)


def _numpy_step(opt, masters, grads, mu, nu, step, grad_scale=1.0, b1=None):
    """Adam in float64 on the decoded state: what any path must land on
    to within its storage format's rounding."""
    b1 = opt.b1 if b1 is None else b1
    out = {}
    for k, p in masters.items():
        p, g = p.astype(np.float64), np.float64(grad_scale) * grads[k]
        if opt.weight_decay and not opt.adam_w_mode:
            g = g + opt.weight_decay * p
        m = b1 * mu[k] + (1 - b1) * g
        v = opt.b2 * nu[k] + (1 - opt.b2) * g * g
        update = (m / (1 - b1 ** step)) / (
            np.sqrt(v / (1 - opt.b2 ** step)) + opt.eps
        )
        if opt.weight_decay and opt.adam_w_mode:
            update = update + opt.weight_decay * p
        out[k] = (p - LR * update, m, v)
    return out


# one code of the compensation is 2^-8 / 127 of the master; a bf16 master
# (no compensation, bf16 parameters never occur here) is not compared
_MASTER_RTOL = {True: 4e-5, False: 2e-6}


def _check_against_numpy(opt, new_params, new_state, want, state_dtype):
    masters = _masters(opt, new_params, new_state)
    mu, nu = _moments(new_state, "mu"), _moments(new_state, "nu")
    for k, (p, m, v) in want.items():
        np.testing.assert_allclose(
            masters[k], p, rtol=_MASTER_RTOL[opt.master_compensation],
            atol=1e-6, err_msg=k,
        )
        if state_dtype == "fp32":
            np.testing.assert_allclose(mu[k], m, rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(nu[k], v, rtol=1e-5, atol=1e-9)
            continue
        np.testing.assert_allclose(nu[k], v, rtol=5e-3, atol=1e-9)
        if state_dtype == "bf16" or not quant.is_quantized(new_state["mu"][k]):
            np.testing.assert_allclose(mu[k], m, rtol=5e-3, atol=1e-7)
        else:  # half a code of the run's scale
            run = quant.quantized_run(m.shape)
            absmax = np.abs(m).reshape(m.shape[:-1] + (-1, run)).max(-1)
            err = np.abs(mu[k] - m).reshape(absmax.shape + (run,)).max(-1)
            assert (err <= absmax / 254 * 1.001 + 1e-12).all(), k


def _check_kernel_against_plain(opt, on_kernel, plain):
    (p1, s1), (p2, s2) = on_kernel, plain
    m1, m2 = _masters(opt, p1, s1), _masters(opt, p2, s2)
    for k in m1:
        np.testing.assert_allclose(
            m1[k], m2[k], rtol=_MASTER_RTOL[opt.master_compensation],
            atol=1e-6, err_msg=k,
        )
        np.testing.assert_allclose(
            np.asarray(s1["nu"][k], np.float32),
            np.asarray(s2["nu"][k], np.float32), rtol=2 ** -7, err_msg=k,
        )
        a, b = s1["mu"][k], s2["mu"][k]
        if quant.is_quantized(a):
            np.testing.assert_allclose(
                np.asarray(a["scale"]), np.asarray(b["scale"]), rtol=1e-5
            )
            # a rounding tie may fall the other way: one code
            codes = np.abs(
                np.asarray(a["q"], np.int32) - np.asarray(b["q"], np.int32)
            )
            assert codes.max() <= 1 and (codes > 0).mean() < 1e-3, k


@pytest.fixture
def path_lines():
    """The ``adam_update_path`` lines logged while the test runs."""
    optimizers = importlib.import_module("deepspeed_tpu.ops.optimizers")
    seen = []

    class Grab(logging.Handler):
        def emit(self, record):
            if record.getMessage().startswith("adam_update_path"):
                seen.append(record.getMessage())

    handler, level = Grab(), optimizers.logger.level
    optimizers.logger.addHandler(handler)
    optimizers.logger.setLevel(logging.DEBUG)
    optimizers._log_update_path.cache_clear()
    yield seen
    optimizers.logger.removeHandler(handler)
    optimizers.logger.setLevel(level)


@pytest.mark.parametrize("layout", ["one_device", "sharded"])
@pytest.mark.parametrize("compensated", [False, True])
@pytest.mark.parametrize("state_dtype", ["fp32", "bf16", "int8"])
def test_update_matches_float32_arithmetic(
    state_dtype, compensated, layout, path_lines
):
    """Every state format, with and without compensated masters, on one
    device and under a (data, model) mesh with the engine's ZeRO specs:
    the update lands where float64 Adam on the decoded state lands, to the
    format's rounding; int8 leaves take the kernel and agree with the
    plain XLA update to a rounding tie."""
    dtype = jnp.bfloat16 if compensated else jnp.float32
    opt = Adam(state_dtype=state_dtype, master_compensation=compensated)
    shard = _layout(layout, _tree(0, dtype))
    params, state, grads = _started(opt, dtype, shard)
    want = _numpy_step(
        opt, _masters(opt, params, state),
        {k: np.asarray(g, np.float64) for k, g in grads.items()},
        _moments(state, "mu"), _moments(state, "nu"), step=2,
    )
    got = _step(opt, params, grads, state, shard, kernel=True)
    _check_against_numpy(opt, *got, want, state_dtype)
    if state_dtype != "int8":
        assert not path_lines  # no quantized leaf: the question never arises
        return
    assert not quant.is_quantized(state["mu"]["vectors"])  # bf16 fallback
    _check_kernel_against_plain(
        opt, got, _step(opt, params, grads, state, shard, kernel=False)
    )
    on_kernel = ON_KERNEL - ({"stacked"} if shard else set())
    elements = sum(int(np.prod(SHAPES[k])) for k in on_kernel)
    assert len(path_lines) == 1
    assert f"kernel={len(on_kernel)} leaves {elements} elements" in path_lines[0]
    if shard is not None:  # the state stays where ZeRO put it
        mesh, specs = shard
        for k, leaf in got[1]["mu"].items():
            q = leaf["q"] if quant.is_quantized(leaf) else leaf
            assert q.sharding.is_equivalent_to(
                NamedSharding(mesh, specs[k]), q.ndim
            ), k


@pytest.mark.parametrize("layout", ["one_device", "sharded"])
@pytest.mark.parametrize("compensated", [False, True])
def test_update_carries_every_hyperparameter(compensated, layout):
    """What the engine threads through the update reaches the kernel as it
    reaches the plain path: ``grad_scale``, OneCycle's ``mom``, an open
    ``gate``, and weight decay in both modes."""
    dtype = jnp.bfloat16 if compensated else jnp.float32
    for adam_w_mode in (True, False):
        opt = Adam(
            state_dtype="int8", master_compensation=compensated,
            weight_decay=0.1, adam_w_mode=adam_w_mode,
        )
        shard = _layout(layout, _tree(0, dtype))
        params, state, grads = _started(opt, dtype, shard)
        kw = dict(
            grad_scale=jnp.float32(0.25), mom=jnp.float32(0.8),
            gate=jnp.bool_(True),
        )
        want = _numpy_step(
            opt, _masters(opt, params, state),
            {k: np.asarray(g, np.float64) for k, g in grads.items()},
            _moments(state, "mu"), _moments(state, "nu"), step=2,
            grad_scale=0.25, b1=np.float64(np.float32(0.8)),
        )
        got = _step(opt, params, grads, state, shard, kernel=True, **kw)
        _check_against_numpy(opt, *got, want, "int8")
        _check_kernel_against_plain(
            opt, got,
            _step(opt, params, grads, state, shard, kernel=False, **kw),
        )


@pytest.mark.parametrize("layout", ["one_device", "sharded"])
def test_closed_gate_rewrites_the_old_bytes(layout):
    """A skipped step (fp16 overflow) is a bit-exact no-op on every stored
    array: ``p``, ``comp``, ``q``, ``scale``, ``nu``, and the step count."""
    opt = Adam(state_dtype="int8", master_compensation=True)
    shard = _layout(layout, _tree(0, jnp.bfloat16))
    params, state, grads = _started(opt, jnp.bfloat16, shard)
    new_params, new_state = _step(
        opt, params, grads, state, shard, kernel=True, gate=jnp.bool_(False)
    )
    for a, b in zip(
        jax.tree_util.tree_leaves((params, state)),
        jax.tree_util.tree_leaves((new_params, new_state)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "width", [128, 512, 1024, 1280, 2688, 3840, 4096, 5120, 5376, 16384,
              464, 2320, 50257]
)
def test_run_length_divides_the_width_and_never_passes_2048(width):
    run = quant.run_length(width)
    assert 128 <= run <= 2048 == quant.BLOCK and width % run == 0
    if width % 128 == 0:
        assert run % 128 == 0
        assert not any(
            width % r == 0 for r in range(run + 128, 2048 + 1, 128)
        )
    else:
        assert not any(width % r == 0 for r in range(run + 1, 2048 + 1))


@pytest.mark.parametrize("shape", [(5, 16), (1280,), (36, 96), (7, 50261)])
def test_leaves_with_no_run_keep_a_bf16_moment(shape):
    assert quant.quantized_run(shape) is None
    mu = quant.moments_zeros_like({"w": jnp.zeros(shape)}, "int8", "mu")["w"]
    assert mu.dtype == jnp.bfloat16 and mu.shape == shape


def test_scale_lies_rows_on_lanes_and_roundtrips():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(3, 256, 2320)) * 0.01, jnp.float32)
    leaf = quant.quantize(x)
    assert leaf["q"].shape == x.shape and leaf["q"].dtype == jnp.int8
    assert leaf["scale"].shape == (3, 2, 256)  # two runs of 1,160 a row
    err = np.abs(np.asarray(quant.dequantize(leaf)) - np.asarray(x))
    absmax = np.abs(np.asarray(x)).reshape(3, 256, 2, 1160).max(-1)
    assert (err.reshape(3, 256, 2, 1160).max(-1) <= absmax / 254 * 1.001).all()


def _cell_parameter_shapes(name):
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness, program

    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as fd:
        config = json.load(fd)
    model = program.model(
        config, harness.sizes(config, False), config["train"]["model_args"]
    )
    ids = jnp.zeros((1, 128), jnp.int32)
    return jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, ids, ids)
    )["params"]


@pytest.mark.parametrize(
    "config, parameters, dp",
    [
        ("gpt2-large", 774_090_240, 1),
        ("nemotron3-super-120b-a12b", 921_066_480, 1),
        ("gpt2-large", 774_090_240, 4),
    ],
)
def test_cells_parameters_take_the_kernel(config, parameters, dp, path_lines):
    """Shapes only, no weights: under the cells' recipe (int8 moments,
    compensated bf16 masters) at least 99.9% of the elements of GPT-2
    large's and of the hybrid stack's parameter trees take the one-pass
    kernel, and the line says so once. With ``dp`` 4 the question is put
    to each chip's SHARD of the state as ``gpt2-large.zero2-dp4`` lays it
    out (the token table's 12,576 rows a chip are no multiple of 128)."""
    shapes = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16),
        _cell_parameter_shapes(config),
    )
    assert sum(
        int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes)
    ) == parameters
    opt = Adam(state_dtype="int8", master_compensation=True)
    shard = None
    if dp > 1:
        mesh = Mesh(np.asarray(jax.devices()[:dp]), ("data",))
        shard = mesh, zero_lib.zero_optstate_specs(
            shapes, dp, 2, prefer_leading=True
        )

    def window(params):
        state = opt.init(params)
        for _ in range(2):  # traced twice, logged once
            params, state, _ = opt.apply(
                params, params, state, jnp.float32(1e-4),
                grad_scale=jnp.float32(1.0), gate=jnp.bool_(True),
                shard=shard,
            )
        return params

    jax.eval_shape(window, shapes)
    assert len(path_lines) == 1
    assert float(path_lines[0].split("kernel_share=")[1].split()[0]) >= 0.999
    runs = dict(
        map(int, pair.split(":"))
        for pair in path_lines[0].split("run_by_width=")[1].split(",")
    )
    for width, run in runs.items():
        assert run == quant.run_length(width) and run <= 2048
    wide = {"gpt2-large": {1280: 1280, 3840: 1920, 5120: 1280},
            "nemotron3-super-120b-a12b": {4096: 2048, 2688: 896, 2320: 1160}}
    assert wide[config].items() <= runs.items()
