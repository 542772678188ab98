"""The plain operations both references are written in.

Plain ``jax.numpy`` in float32. Every matrix product goes through one
``dot`` so that the same forward and backward passes can be computed in a
lower precision: that is the control which ``correct`` has to fail
(PERF.md, "How correct is decided"). Nothing here imports the program.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST

# dimension numbers of the three products a transformer needs
X_W = (((-1,), (0,)), ((), ()))          # [..., k] x [k, n]
Q_KT = (((3,), (3,)), ((0, 1), (0, 1)))  # [b,h,q,d] x [b,h,k,d] -> [b,h,q,k]
P_V = (((3,), (2,)), ((0, 1), (0, 1)))   # [b,h,q,k] x [b,h,k,d] -> [b,h,q,d]


def _dims(a, dn):
    (ca, cb), (ba, bb) = dn
    return ((tuple(c % a.ndim for c in ca), cb), (ba, bb))


def _exact(a, b, dn):
    return lax.dot_general(
        a, b, _dims(a, dn), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _round_bf16(x):
    # lax.reduce_precision IS the rounding; an astype round trip is folded
    # away under jit by XLA's excess-precision simplification (my chip run,
    # PR 23: weights "rounded" that way were not on the bfloat16 grid)
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _round_fp8(x):
    """Per-tensor scaled e4m3 (4 exponent bits, 3 mantissa bits, largest
    finite value 240, subnormals down to 2^-9): the value grid an fp8
    matmul's inputs lie on."""
    scale = 240.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * scale
    normal = lax.reduce_precision(y, exponent_bits=4, mantissa_bits=3)
    subnormal = jnp.round(y * 512.0) / 512.0
    return jnp.where(jnp.abs(y) < 2.0 ** -6, subnormal, normal) / scale


ROUNDINGS = {"bfloat16": _round_bf16, "fp8": _round_fp8}


def make_dot(precision="float32"):
    """``dot(a, b, dims)``. ``float32`` multiplies exactly (six bf16 passes
    on a TPU). Any other name rounds both inputs of every product, in the
    backward pass too, to that precision's grid and accumulates in float32,
    which is what a matmul unit fed that type computes."""
    if precision == "float32":
        return _exact
    rnd = ROUNDINGS[precision]

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def dot(a, b, dn):
        return _exact(rnd(a), rnd(b), dn)

    def fwd(a, b, dn):
        return dot(a, b, dn), (a, b)

    def bwd(dn, res, g):
        a, b = res
        _, vjp = jax.vjp(lambda x, y: _exact(x, y, dn), rnd(a), rnd(b))
        return vjp(rnd(g))

    dot.defvjp(fwd, bwd)
    return dot


def layer_norm(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def gelu_tanh(x):
    """GPT-2's ``gelu_new``. BERT publishes the erf form; the program runs
    the tanh form for both families, and the reference follows what runs."""
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(dot, q, k, v, bias):
    """softmax(q k^T / sqrt(d) + bias) v, heads on axis 1."""
    scores = dot(q, k, Q_KT) / jnp.sqrt(jnp.float32(q.shape[-1]))
    probs = jax.nn.softmax(scores + bias, axis=-1)
    return dot(probs, v, P_V)


def split_heads(x, heads):
    b, s, e = x.shape
    return x.reshape(b, s, heads, e // heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def nll(logits, labels):
    """-log softmax(logits)[label], per position."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - picked


def seed_key(seed):
    """A PRNG key from ``--seed`` (any whole number: the part above 31 bits
    is folded in). Made outside the jitted initializer, which then is the
    same program for every seed and is found in the compile cache."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed % (2 ** 31)), seed // (2 ** 31))


def seeded_normals(key, shapes, std, mean=None):
    """{name: float32 array} of N(mean, std) values that bfloat16 holds
    exactly: the program keeps or computes its weights in bfloat16, so
    both sides then start from the very same numbers."""
    mean = mean or {}
    out = {}
    for i, name in enumerate(sorted(shapes)):
        x = mean.get(name, 0.0) + std * jax.random.normal(
            jax.random.fold_in(key, i), shapes[name], jnp.float32)
        out[name] = _round_bf16(x)
    return out


def initializer(model, cfg):
    """``init(key)``: the model's seeded weights, as one jitted call."""
    return jax.jit(lambda key: model.init_params(key, cfg))
