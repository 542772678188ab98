"""The held experts' grouped products alone at the two expert cells' shapes
under the level selection: the whole layer (``grouped_expert_ffn`` forward,
and its gradient), and inside one chunk its pieces: the row indices, the
gathers, each kernel, the scatter-add.

    chiprun -- python3 tools/moe_kernels_alone.py [nemotron] [qwen]

One JSON line a cell (ms a pass, microseconds a tile), also written under
``chiprun_out/``. Outside a model the backward kernel reads slower than in
a cell's window (PERF.md section 6, PR 37): trust the window's trace."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
import numpy as np
from deepspeed_tpu.ops import moe

cells = {"nemotron": dict(T=16384, seq=8192, k=22, held=16, routed=512, L=1024, F=2688, tile=384, form="relu2"),
         "toy": dict(T=256, seq=128, k=4, held=4, routed=16, L=128, F=256, tile=16, form="swiglu"),
         "qwen": dict(T=32768, seq=16384, k=10, held=32, routed=512, L=2048, F=512, tile=352, form="swiglu")}


def timed(fn, *args, n=10):
    out = fn(*args); jax.block_until_ready(out)
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


for name in sys.argv[1:] or list(cells):
    c = cells[name]
    T, k, held, routed, L, F, tile, form = (c[x] for x in ("T", "k", "held", "routed", "L", "F", "tile", "form"))
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 8)
    n_in = 1 if form == "relu2" else 2
    mats = tuple([0.02 * jax.random.normal(ks[i], (held, L, F), jnp.float32).astype(jnp.bfloat16) for i in range(n_in)]
                 + [0.02 * jax.random.normal(ks[3], (held, F, L), jnp.float32).astype(jnp.bfloat16)])
    u = jax.random.normal(ks[4], (T, L), jnp.float32).astype(jnp.bfloat16)
    probe = jax.random.normal(ks[5], (T, L), jnp.float32)
    sel = moe.level_selection_scores(jnp.arange(T) % c["seq"], routed)
    scores = jax.nn.sigmoid(jax.random.normal(ks[6], (T, routed), jnp.float32))
    chosen, picked = moe._top_k_of(sel, scores, k)
    weights_t = (picked / picked.sum(-1, keepdims=True))[:, :held].T
    chunk_tiles = held + -(-T * k * held // (routed * tile))

    def ffn(u, mats, wt, plan):
        return moe.grouped_expert_ffn(u, mats, wt, plan, tile, form, chunk_tiles)

    plan_fn = jax.jit(lambda ch: moe.plan_held_rows(ch, held, 0, tile))
    (plan, sizes) = plan_fn(chosen)
    fwd = jax.jit(ffn)
    grad = jax.jit(jax.grad(lambda u, mats, wt, plan: jnp.sum(ffn(u, mats, wt, plan) * probe), (0, 1, 2)))
    t_plan, _ = timed(plan_fn, chosen)
    t_fwd, out = timed(fwd, u, mats, weights_t, plan)
    t_grad, grads = timed(grad, u, mats, weights_t, plan)
    rec = {"cell": name, "n_tiles": int(plan["n_tiles"]), "rows": int(sizes.sum()),
           "plan_ms": t_plan, "fwd_ms": t_fwd, "grad_ms": t_grad,
           "out_norm": float(jnp.linalg.norm(out)),
           "grad_norms": [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads)]}
    stride = moe._row_stride(tile, u.dtype)
    rows = jax.jit(lambda plan, wt: moe._chunk_of_tiles(0, chunk_tiles, tile, stride, plan, wt))
    expert, meta, keyr, tok, wt = rows(plan, weights_t)
    gather = jax.jit(moe._rows_of)
    t_gather, x = timed(gather, u, tok)
    t_gather32, gy = timed(gather, probe, tok)
    kf = jax.jit(lambda e, m, x, wt, mats: moe._ffn_call(
        moe._ffn_fwd_kernel, "moe_ffn_fwd", form, e, m, (x, wt), mats, [(L, jnp.float32)])[0])
    t_kf, y = timed(kf, expert, meta, x, wt, mats)
    scatter = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok].add(y, mode="drop"))
    t_scatter, _ = timed(scatter, tok, y)
    zeros = tuple(jnp.zeros_like(m) for m in mats)
    left = tuple(jnp.zeros((1,) + m.shape[1:], jnp.float32) for m in mats)
    kb = jax.jit(lambda e, m, x, gy, wt, mats, z, l: moe._ffn_call(
        moe._ffn_bwd_kernel, "moe_ffn_bwd", form, e, m, (x, gy, wt), mats,
        [(L, jnp.float32), (1, jnp.float32)], z, l))
    t_kb, _ = timed(kb, expert, meta, x, gy, wt, mats, zeros, left)
    cast = jax.jit(lambda: tuple(jnp.zeros_like(m) for m in mats))
    t_cast, _ = timed(cast)
    t_rows, _ = timed(rows, plan, weights_t)
    rec.update(chunk_tiles=chunk_tiles, stride=stride, rows_ms=t_rows, gather_bf16_ms=t_gather,
               gather_f32_ms=t_gather32, kernel_fwd_ms=t_kf, scatter_ms=t_scatter,
               kernel_bwd_ms=t_kb, zero_fill_ms=t_cast,
               fwd_us_a_tile=1e3 * t_kf / rec["n_tiles"], bwd_us_a_tile=1e3 * t_kb / rec["n_tiles"])
    print(json.dumps(rec), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"moe_kernels_alone_{name}.json"), "w") as fd:
        json.dump(rec, fd)
