"""The q/k norm-and-rotary pass alone and the attention mixers around it at the
three cells' shapes, through the kernels ``qk_prep_fwd`` / ``qk_prep_bwd``
(ops/qk_prep.py) and through ``apply_rotary(rms_norm(..))``:

    chiprun -- python3 tools/qk_prep_alone.py [sdar] [ouro] [qwen]

One JSON line a cell (ms a call: the pass forward, forward + backward, and
the whole mixer forward + backward under the cell's remat policy, each way),
also written under ``chiprun_out/``. Outside a model XLA lays arrays out
differently than inside a cell's window: trust the window's trace
(``tools/window_ops.py``)."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from deepspeed_tpu.models.hybrid import HybridLMConfig, attention_spec
from deepspeed_tpu.ops import transformer as T

BF = jnp.bfloat16
cells = {
    "sdar": dict(mixer="A", B=2, S=16384, E=2048, H=32, KV=4, D=128, R=128, theta=1e6, zc=False, bd=4,
                 policy="nothing_saveable+flash_out+flash_lse+moe_plan"),
    "ouro": dict(mixer="R", B=1, S=8192, E=2048, H=16, KV=16, D=128, R=128, theta=1e6, zc=False, bd=0,
                 policy="nothing_saveable+flash_out+flash_lse"),
    "qwen": dict(mixer="G", B=2, S=16384, E=2048, H=16, KV=2, D=256, R=64, theta=1e7, zc=True, bd=0,
                 policy="nothing_saveable+flash_out+flash_lse+moe_plan+gdn_segments"),
    "toy": dict(mixer="A", B=1, S=256, E=256, H=2, KV=1, D=128, R=128, theta=1e6, zc=False, bd=4,
                policy="nothing_saveable+flash_out+flash_lse"),
}


def timed(fn, *args, n=10):
    out = fn(*args); jax.block_until_ready(out)
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def mixer_of(c, path):
    """The cell's mixer with the chooser held to ``path`` ("fused" | "xla")."""
    spec = attention_spec(HybridLMConfig(
        pattern=c["mixer"], hidden_size=c["E"], attn_heads=c["H"], kv_heads=c["KV"], head_dim=c["D"],
        rotary_lanes=c["R"], rope_theta=c["theta"], norm_eps=1e-6, norm_zero_centered=c["zc"],
        objective="block_diffusion" if c["bd"] else "next_token", diffusion_block=c["bd"] or 4), c["mixer"])
    pos = jnp.concatenate([jnp.arange(c["S"] // 2)] * 2) if c["bd"] else None

    def run(p, x):
        real = T.qk_prep_path
        T.qk_prep_path = lambda *a, **k: (path, "held by tools/qk_prep_alone.py")
        try:
            return T.attention_mixer(p, x, spec, positions=pos)
        finally:
            T.qk_prep_path = real
    return run


def pass_of(c, path):
    """q's norm and rotation alone: projection result in, [B, H, S, D] (or the
    packed buffer) out."""
    m, H, D, R, S = c["mixer"], c["H"], c["D"], c["R"], c["S"]
    pos = jnp.concatenate([jnp.arange(S // 2)] * 2) if c["bd"] else None

    def run(x, gain):
        b = x.shape[0]
        angle = T.rotary_angles(S, R, c["theta"], pos)
        if m == "R":
            if path == "fused":
                return T.qk_prep_in_place(x, angle, heads=2 * H, head_dim=D)
            t = x.reshape(b, S, 3 * H, D)
            qk = T.apply_rotary(t[:, :, :2 * H], D, c["theta"], seq_axis=1)
            return jnp.concatenate([qk, t[:, :, 2 * H:]], axis=2).reshape(x.shape)
        if path == "fused":
            return T.qk_prep(x, gain, angle, head_dim=D, eps=1e-6, zero_centered=c["zc"])
        t = T.rms_norm(x.reshape(b, S, H, D), gain, 1e-6, c["zc"])
        return T.apply_rotary(t, R, c["theta"], seq_axis=1, positions=pos).transpose(0, 2, 1, 3)
    return run


def steps(a, b):
    """Largest |a - b| in steps of bfloat16 at the largest magnitude of the
    head's row it lies in (a rotated lane is a difference of two products, so
    its own magnitude can be far under what was rounded)."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(b), axis=-1, keepdims=True), 1e-30)
    return float(jnp.max(jnp.abs(a - b) / jnp.exp2(jnp.floor(jnp.log2(scale)) - 7)))


def main(names):
    for name in names:
        c = cells[name]
        B, S, E, H, KV, D = (c[k] for k in "B S E H KV D".split())
        ks = jax.random.split(jax.random.PRNGKey(39), 8)
        width = (3 if c["mixer"] == "R" else 1) * H * D
        x = jax.random.normal(ks[0], (B, S, width), jnp.float32).astype(BF)
        gain = (1.0 - c["zc"] + 0.1 * jax.random.normal(ks[1], (D,))).astype(BF)
        rec = {"cell": name}
        outs = {}
        for path in ("fused", "xla"):
            f = pass_of(c, path)
            probe = jax.random.normal(ks[2], jax.eval_shape(f, x, gain).shape, jnp.float32).astype(BF)
            fwd = jax.jit(f)
            grad = jax.jit(jax.grad(lambda x, g: jnp.sum((f(x, g) * probe).astype(jnp.float32)), (0, 1)))
            rec[f"pass_fwd_ms.{path}"] = timed(fwd, x, gain)
            rec[f"pass_grad_ms.{path}"] = timed(grad, x, gain)
            outs[path] = (fwd(x, gain), *grad(x, gain))
        rec["pass_gap_bf16_steps"] = {
            k: steps(a, b) for k, a, b in zip(("out", "dx", "dgain"), outs["fused"], outs["xla"])}
        del outs
        p = {"wq": (E, (2 if c["mixer"] == "G" else 1) * H * D), "wk": (E, KV * D), "wv": (E, KV * D), "wo": (H * D, E)}
        p = {k: (0.02 * jax.random.normal(ks[3 + i], s, jnp.float32)).astype(BF) for i, (k, s) in enumerate(p.items())}
        if c["mixer"] != "R":
            p["q_norm"], p["k_norm"] = gain, gain
        h = jax.random.normal(ks[7], (B, S, E), jnp.float32).astype(BF)
        policy = T.resolve_remat_policy(c["policy"])
        for path in ("fused", "xla"):
            mixer = mixer_of(c, path)
            grad = jax.jit(jax.grad(
                lambda p, h: jnp.sum(jax.checkpoint(mixer, policy=policy)(p, h).astype(jnp.float32)), (0, 1)))
            rec[f"mixer_grad_ms.{path}"] = timed(grad, p, h, n=5)
        print(json.dumps(rec), flush=True)
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(ROOT, "chiprun_out", f"qk_prep_alone_{name}.json"), "w") as f:
            json.dump(rec, f)


if __name__ == "__main__":
    main(sys.argv[1:] or ["sdar", "ouro", "qwen"])
