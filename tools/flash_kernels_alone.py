"""The flash kernels alone, on the chip: device time of each kernel's events
in a profiler trace of a jitted forward + backward (mean over the traced
calls, ms), at the shapes the benchmark's cells run, with the backward that
``backward_plan`` picks (``fused``) and with the pair forced (budget 0).

    chiprun -- python3 tools/flash_kernels_alone.py [shape ...] [--sub QxK ...]

``joyai_latent`` is ``joyai`` in the LATENT layout (no pair exists for it: pass
``--no-pair``). ``--sub 256x128`` also times the key-major kernels at those sub-tiles (query x
key) instead of the ones ``pick_subtiles`` gives, and ``--fwd-sub 256x256`` the
FORWARD alone (variant ``forward``: only it is jitted). A line also says how the
forward and the backward walk the grid (``flash_tiling``'s ``walk``, ``bodies``
and ``steps``; the forward's ``order`` and ``chains`` too). Writes one JSON line
a (shape, variant) to stdout and to ``chiprun_out/flash_kernels_alone.jsonl``.
docs/TESTING.md holds the tables this produced."""

import argparse
import glob
import importlib
import json
import os
import re
import sys
import tempfile

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

att = importlib.import_module("deepspeed_tpu.ops.attention")
from benchmark.trace import PS, Trace  # noqa: E402

# name: (entry, batch, heads, seq, head width, causal, key mask, bias[, the
# mask form's keywords]); a head width (q and k's, v's) where they differ (a
# latent mixer's); seq is the row the kernels see (SDAR's [noisy ; clean] 2 L)
SHAPES = {
    "gpt2": ("packed", 8, 20, 1024, 64, True, False, True),
    "bert512": ("packed", 8, 16, 512, 64, False, True, True),
    "bert384": ("packed", 8, 16, 384, 64, False, True, True),
    "ouro": ("packed", 1, 16, 8192, 128, True, False, False),
    "nemotron": ("split", 2, 4, 8192, 128, True, False, False),
    "qwen3next": ("split", 2, 16, 16384, 256, True, False, False),
    "joyai": ("split", 2, 32, 8192, (192, 128), True, False, False),
    "joyai_v192": ("split", 2, 32, 8192, 192, True, False, False),
    # the same numbers where the latent mixer's projections write them (PR 47)
    "joyai_latent": ("latent", 2, 32, 8192, (192, 128), True, False, False),
    "sdar": ("split", 2, 32, 16384, 128, False, False, False,
             {"block_diffusion": 4}),
    "laguna_band": ("split", 2, 72, 8192, 128, True, False, False,
                    {"window": 512}),
    "laguna_full": ("split", 2, 48, 8192, 128, True, False, False),
}
CALLS = 5


def build(shape):
    entry, b, h, s, d, causal, masked, biased, *form = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(33), 4)
    kv_mask = None
    if masked:
        kv_mask = (jnp.arange(s)[None, :] < s - 37 * jnp.arange(b)[:, None])
        kv_mask = kv_mask.astype(jnp.int32)
    if entry == "packed":
        qkv = jax.random.normal(keys[0], (b, s, 3 * h * d), jnp.bfloat16)
        bias = jax.random.normal(keys[1], (3 * h * d,), jnp.bfloat16) if biased else None
        w = jax.random.normal(keys[2], (b, s, h * d), jnp.bfloat16)

        def loss(qkv, bias):
            out = att.flash_attention_packed(
                qkv, h, bias=bias, kv_mask=kv_mask, causal=causal)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

        return loss, (qkv, bias), (0, 1) if biased else (0,)
    dqk, dv = d if isinstance(d, tuple) else (d, d)
    if entry == "latent":
        rope = dqk - dv
        q_nope, q_r, kv, k_r, w = (
            jax.random.normal(kk, (b, s, width), jnp.bfloat16)
            for kk, width in zip(
                jax.random.split(keys[0], 5),
                (h * dv, h * rope, h * 2 * dv, rope, h * dv)))

        def loss(q_nope, q_r, kv, k_r):
            out = att.flash_attention_latent(q_nope, q_r, kv, k_r, h)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

        return loss, (q_nope, q_r, kv, k_r), (0, 1, 2, 3)
    q, k, v, w = (jax.random.normal(kk, (b, h, s, width), jnp.bfloat16)
                  for kk, width in zip(keys, (dqk, dqk, dv, dv)))

    def loss(q, k, v):
        out = att.flash_attention(
            q, k, v, kv_mask=kv_mask, causal=causal, **(form[0] if form else {}))
        return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32))

    return loss, (q, k, v), (0, 1, 2)


def measure(shape, variant, sub):
    """Mean ms a call of each kernel, and the gradients (for the check
    between variants)."""
    real_budget, real_pick = att.FUSED_DQ_VMEM_BUDGET, att.pick_subtiles
    if variant == "pair":
        att.FUSED_DQ_VMEM_BUDGET = 0
    if sub:
        # the kernels of the variant's side at ``sub``, the other side's as picked
        att.pick_subtiles = lambda bq, bk, nq, nk, key_major, *more, **kw: (
            (min(sub[0], bq), min(sub[1], bk))
            if key_major == (variant != "forward")
            else real_pick(bq, bk, nq, nk, key_major, *more, **kw))
    try:
        loss, args, argnums = build(shape)
        step = jax.jit(loss if variant == "forward" else jax.grad(
            lambda *a: loss(*a), argnums=argnums))
        grads = jax.block_until_ready(step(*args))
        with tempfile.TemporaryDirectory(dir=os.path.join(ROOT, "chiprun_out")) as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(CALLS):
                jax.block_until_ready(step(*args))
            jax.profiler.stop_trace()
            path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
            ms = {}
            # alone, XLA names a kernel's operation after the whole path
            # of transformations (``transpose_jvp_flash_bwd_dkv__.1``)
            ops = Trace(None, path=path, whole=True).devices[0].ops
            for e in ops:
                kernel = re.search(r"flash_(?:fwd|bwd_dq|bwd_dkv)(?![a-z])", e.name)
                if kernel:
                    ms[kernel.group(0)] = ms.get(kernel.group(0), 0.0) + (
                        1e3 * PS * e.duration_ps / CALLS)
            if not ms:
                raise SystemExit(
                    f"no flash kernel among {sorted({e.name for e in ops})[:40]}")
    finally:
        att.FUSED_DQ_VMEM_BUDGET, att.pick_subtiles = real_budget, real_pick
    return ms, grads


def walks(shape, sub, fwd_sub=None):
    """How the forward and the key-major backward walk the shape's grid
    (``flash_tiling``: ``walk``, ``bodies``, ``steps``; the forward's ``order``
    and ``chains``), for the line."""
    entry, _, _, s, d, causal, _, _, *form = SHAPES[shape]
    form = dict(form[0] if form else {})
    lanes = max(d) if isinstance(d, tuple) else d
    form["heads_a_block"] = {"packed": 128 // lanes, "latent": 2}.get(entry, 1)
    block = att._pick_blocks(
        s, s, att.DEFAULT_BLOCK_Q, att.DEFAULT_BLOCK_K,
        form.get("block_diffusion", 0))
    fwd_sub = fwd_sub or (None, None)
    t = att.flash_tiling(
        s, s, *block, causal, lanes=lanes, sub_q=fwd_sub[0], sub_k=fwd_sub[1],
        **form)
    if sub:
        t["backward"] = att.flash_tiling(
            s, s, *block, causal, key_major=True, sub_q=sub[0], sub_k=sub[1],
            **form)
    keys = ("sub_q", "sub_k", "walk", "bodies", "steps")
    return {"forward": {k: t[k] for k in keys + ("order", "chains")},
            "backward": {k: t["backward"][k] for k in keys}}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("shapes", nargs="*", default=list(SHAPES))
    parser.add_argument("--sub", action="append", default=[])
    parser.add_argument("--fwd-sub", action="append", default=[])
    parser.add_argument("--no-pair", action="store_true")
    opts = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("flash_kernels_alone: no TPU; a CPU gives no time")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    def sizes(given):
        return [tuple(int(x) for x in s.split("x")) for s in given]

    subs, fwd_subs = [None] + sizes(opts.sub), sizes(opts.fwd_sub)
    with open(os.path.join(ROOT, "chiprun_out", "flash_kernels_alone.jsonl"), "a") as out:
        for shape in opts.shapes:
            pair = None
            block = min(SHAPES[shape][3], 1024)
            def clamped(subs):
                return dict.fromkeys(
                    s and (min(s[0], block), min(s[1], block)) for s in subs)

            for variant, sub in [("pair", None)][opts.no_pair:] + [
                    ("fused", s) for s in clamped(subs)] + [
                    ("forward", s) for s in clamped(fwd_subs)]:
                line = {"shape": shape, "dims": SHAPES[shape], "variant": variant,
                        "sub_q_x_k": sub, "device": jax.devices()[0].device_kind,
                        **walks(shape, sub if variant == "fused" else None,
                                sub if variant == "forward" else None)}
                try:
                    ms, grads = measure(shape, variant, sub)
                except Exception as e:  # a variant Mosaic refuses: say so, go on
                    line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
                else:
                    line["ms"] = {k: round(v, 4) for k, v in ms.items()}
                    line["backward_ms"] = round(
                        sum(v for k, v in ms.items() if k != "flash_fwd"), 4)
                    if variant == "pair":
                        pair = grads
                    elif pair is not None and variant == "fused":
                        # largest difference from the pair's gradient, as a
                        # share of its largest entry
                        line["vs_pair"] = max(
                            float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
                                  / jnp.max(jnp.abs(b.astype(jnp.float32))))
                            for a, b in zip(jax.tree_util.tree_leaves(grads),
                                            jax.tree_util.tree_leaves(pair)))
                text = json.dumps(line)
                print(text, flush=True)
                out.write(text + "\n")


if __name__ == "__main__":
    main()
