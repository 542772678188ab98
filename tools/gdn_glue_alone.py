"""The Gated DeltaNet mixer's glue alone at the Qwen3-Next cell's shape (2 x
16,384 rows, 16 key heads on 32 value heads of 128, 4 taps, bf16), through the
kernels ``gdn_in_fwd`` / ``gdn_in_bwd`` / ``gdn_out_fwd`` / ``gdn_out_bwd``
(ops/gdn_glue.py) and through the XLA functions they stand for:

    chiprun -- python3 tools/gdn_glue_alone.py [times] [sweep] [gaps] [mixer]

``times``: ms a call of each kernel against its bytes' time at the chip's 819
GB/s, and of the XLA functions forward and forward + backward. ``sweep``: the
same four kernels over rows a grid step x rows a walk, the source of
``BLOCK_ELEMENTS``, ``IN_WALK_ELEMENTS`` and ``OUT_WALK_ELEMENTS``. ``gaps``: the largest difference from
the XLA functions in steps of bfloat16 (one row of 16,384), against them in
bf16 and against them over float32 operands. ``mixer``: the whole mixer
forward + backward under the cell's remat policy, both ways. One JSON line a
part, also written under ``chiprun_out/``. Outside a model XLA lays arrays out
differently than inside a cell's window: trust the window's trace
(``tools/window_ops.py``)."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from deepspeed_tpu.ops import gdn_glue as G
from deepspeed_tpu.ops import linear_attention as la

BF, F32 = jnp.bfloat16, jnp.float32
CELL = dict(B=2, S=16384, E=2048, Hk=16, Hv=32, dk=128, dv=128, K=4, chunk=64, eps=1e-6,
            policy="nothing_saveable+flash_out+flash_lse+moe_plan+gdn_segments")
TOY = dict(CELL, B=1, S=256, E=256, Hk=2, Hv=4)
HBM = 819e9


def timed(fn, *args, n=10):
    out = fn(*args); jax.block_until_ready(out)
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def operands(c, batch=None, dtype=BF, seed=41):
    b, s, qk, vz = batch or c["B"], c["S"], c["Hk"] * c["dk"], c["Hv"] * c["dv"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    rnd = lambda k, *shape: jax.random.normal(k, shape, F32).astype(dtype)
    return dict(
        mixed=rnd(ks[0], b, s, 2 * qk + vz), conv_w=(0.5 * jax.random.normal(ks[1], (c["K"], 2 * qk + vz))).astype(dtype),
        dq=rnd(ks[2], b, s, qk), dk=rnd(ks[3], b, s, qk), dv=rnd(ks[4], b, s, vz),
        o=rnd(ks[5], b, s, vz), z=rnd(ks[6], b, s, vz), dy=rnd(ks[7], b, s, vz),
        gain=(1.0 + 0.1 * jax.random.normal(ks[8], (c["dv"],))).astype(dtype))


def xla_inputs(c):
    return lambda mixed, conv_w: la.gdn_inputs(mixed, conv_w, c["Hk"], c["dk"])


def xla_output(c):
    def run(o, z, gain):
        heads = o.shape[:2] + (c["Hv"], c["dv"])
        return la.gated_head_rms_norm(o.reshape(heads), z.reshape(heads), gain, c["eps"]).reshape(o.shape)
    return run


def fused_inputs(c):
    return lambda mixed, conv_w: G.gdn_inputs_fused(mixed, conv_w, key_heads=c["Hk"], key_dim=c["dk"])


def fused_output(c):
    return lambda o, z, gain: G.gdn_output_fused(o, z, gain, c["eps"])


def kernels(c, t):
    """The four kernels as jitted calls of one kernel each (fresh functions:
    the block constants are read when a call is traced) and the bytes each
    reads and writes."""
    w32, g32 = t["conv_w"].astype(F32), t["gain"].astype(F32)[None, :]
    size = lambda *names: sum(t[n].size * t[n].dtype.itemsize for n in names)
    return {
        "gdn_in_fwd": (jax.jit(lambda m, w: G._inputs_fwd(m, w, c["Hk"], c["dk"])[0]), (t["mixed"], w32), 2 * size("mixed")),
        "gdn_in_bwd": (jax.jit(lambda m, w, *g: G._inputs_bwd(c["Hk"], c["dk"], (m, w), g)),
                       (t["mixed"], w32, t["dq"], t["dk"], t["dv"]), 3 * size("mixed")),
        "gdn_out_fwd": (jax.jit(lambda o, z, g: G._output_fwd(o, z, g, c["eps"])[0]), (t["o"], t["z"], g32), 3 * size("o")),
        "gdn_out_bwd": (jax.jit(lambda o, z, g, dy: G._output_bwd(c["eps"], (o, z, g), dy)),
                        (t["o"], t["z"], g32, t["dy"]), 5 * size("o")),
    }


def grads(inputs, output, t):
    """The two sides' gradients, jitted: of the results against fixed probes
    (the operands' cotangents) with respect to every operand."""
    probes = (t["dq"], t["dk"], t["dv"])
    loss_in = lambda m, w: sum(jnp.sum((a * p).astype(F32)) for a, p in zip(inputs(m, w), probes))
    loss_out = lambda o, z, g: jnp.sum((output(o, z, g) * t["dy"]).astype(F32))
    return jax.jit(jax.grad(loss_in, (0, 1))), jax.jit(jax.grad(loss_out, (0, 1, 2)))


def times(c):
    t = operands(c)
    rec = {"part": "times"}
    for name, (fn, args, nbytes) in kernels(c, t).items():
        ms = timed(fn, *args)
        rec[name] = {"ms": ms, "bytes_ms": nbytes / HBM * 1e3, "of_bandwidth": nbytes / HBM * 1e3 / ms}
    for way, inputs, output in (("fused", fused_inputs(c), fused_output(c)), ("xla", xla_inputs(c), xla_output(c))):
        grad_in, grad_out = grads(inputs, output, t)
        rec[f"inputs_fwd_ms.{way}"] = timed(jax.jit(inputs), t["mixed"], t["conv_w"])
        rec[f"inputs_grad_ms.{way}"] = timed(grad_in, t["mixed"], t["conv_w"])
        rec[f"output_fwd_ms.{way}"] = timed(jax.jit(output), t["o"], t["z"], t["gain"])
        rec[f"output_grad_ms.{way}"] = timed(grad_out, t["o"], t["z"], t["gain"])
    return rec


def sweep(c, blocks=(1024, 2048, 4096, 8192), walks=(128, 256, 512, 1024)):
    t = operands(c)
    rec = {"part": "sweep", "rows": ["block", "walk", "gdn_in_fwd", "gdn_in_bwd", "gdn_out_fwd", "gdn_out_bwd"], "ms": []}
    was = G.BLOCK_ELEMENTS, G.IN_WALK_ELEMENTS, G.OUT_WALK_ELEMENTS
    try:
        for block in blocks:
            for walk in (w for w in walks if w <= block):
                G.BLOCK_ELEMENTS = block * G.LANES
                G.IN_WALK_ELEMENTS = G.OUT_WALK_ELEMENTS = walk * G.LANES
                row = [block, walk]
                for name, (fn, args, _) in kernels(c, t).items():
                    try:
                        row.append(round(timed(fn, *args, n=5), 4))
                    except Exception as e:  # a block that outgrows VMEM
                        row.append(str(e).splitlines()[0][:80])
                rec["ms"].append(row)
                print(json.dumps(row), flush=True)
    finally:
        G.BLOCK_ELEMENTS, G.IN_WALK_ELEMENTS, G.OUT_WALK_ELEMENTS = was
    return rec


def steps(a, b, lanes=128):
    """Largest |a - b| in steps of bfloat16 at the largest magnitude of the
    row of ``lanes`` lanes it lies in."""
    a, b = (x.astype(F32).reshape(-1, min(lanes, x.shape[-1])) for x in (a, b))
    scale = jnp.maximum(jnp.max(jnp.abs(b), axis=-1, keepdims=True), 1e-30)
    return float(jnp.max(jnp.abs(a - b) / jnp.exp2(jnp.floor(jnp.log2(scale)) - 7)))


def results(c, inputs, output, t):
    """(q, k, v, d mixed, d conv_w, gated o, d o, d z, d gain)."""
    grad_in, grad_out = grads(inputs, output, t)
    return (*jax.jit(inputs)(t["mixed"], t["conv_w"]), *grad_in(t["mixed"], t["conv_w"]),
            jax.jit(output)(t["o"], t["z"], t["gain"]), *grad_out(t["o"], t["z"], t["gain"]))


NAMES = ("q", "k", "v", "d_mixed", "d_conv_w", "gated_o", "d_o", "d_z", "d_gain")


def gaps(c):
    """One row of the cell's length: the kernels against the XLA functions in
    bf16 and against them over the same operands in float32 (what both round
    from; the sums over 16,384 rows, d conv_w and d gain, by their own largest
    magnitude)."""
    t = operands(c, batch=1)
    exact = {k: v.astype(F32) for k, v in t.items()}
    got = results(c, fused_inputs(c), fused_output(c), t)
    rec = {"part": "gaps"}
    for ref, want in (("xla_bf16", results(c, xla_inputs(c), xla_output(c), t)),
                      ("xla_float32", results(c, xla_inputs(c), xla_output(c), exact))):
        rec[ref] = {n: steps(a, b, 128 if n not in ("d_conv_w", "d_gain") else b.size)
                    for n, a, b in zip(NAMES, got, want)}
    return rec


def mixer(c):
    from deepspeed_tpu.ops import transformer as T
    b, s, e, qk, vz = c["B"], c["S"], c["E"], c["Hk"] * c["dk"], c["Hv"] * c["dv"]
    ks = jax.random.split(jax.random.PRNGKey(43), 8)
    small = lambda k, *shape: (0.02 * jax.random.normal(k, shape, F32)).astype(BF)
    p = {"in_qkvz": small(ks[0], e, 2 * qk + 2 * vz), "in_ba": small(ks[1], e, 2 * c["Hv"]),
         "conv_w": (0.5 * jax.random.normal(ks[2], (c["K"], 2 * qk + vz))).astype(BF),
         "A_log": jnp.log(jax.random.uniform(ks[3], (c["Hv"],), minval=1.0, maxval=16.0)).astype(BF),
         "dt_bias": jnp.zeros((c["Hv"],), BF), "out_norm": jnp.ones((c["dv"],), BF), "out_proj": small(ks[4], vz, e)}
    h = jax.random.normal(ks[5], (b, s, e), F32).astype(BF)
    policy = T.resolve_remat_policy(c["policy"])
    rec = {"part": "mixer"}
    real = la.gdn_glue_path
    for way in ("fused", "xla"):
        la.gdn_glue_path = lambda *a, **k: (way, "held by tools/gdn_glue_alone.py")
        try:
            run = lambda p, h: la.gated_deltanet_mixer(
                p, h, key_heads=c["Hk"], value_heads=c["Hv"], key_dim=c["dk"], value_dim=c["dv"], chunk=c["chunk"], eps=c["eps"])
            grad = jax.jit(jax.grad(lambda p, h: jnp.sum(jax.checkpoint(run, policy=policy)(p, h).astype(F32)), (0, 1)))
            rec[f"mixer_grad_ms.{way}"] = timed(grad, p, h, n=5)
        finally:
            la.gdn_glue_path = real
    return rec


def main(parts):
    c = TOY if "toy" in parts else CELL
    for part in [p for p in parts if p != "toy"] or ["times", "gaps"]:
        rec = {"times": times, "sweep": sweep, "gaps": gaps, "mixer": mixer}[part](c)
        print(json.dumps(rec), flush=True)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", f"gdn_glue_alone_{part}.json"), "w") as f:
            json.dump(rec, f)


if __name__ == "__main__":
    main(sys.argv[1:])
