"""XLA's row gather and scatter-add at the two expert cells' shapes, by
variant: what stands around the kernels ``moe_ffn_fwd`` / ``moe_ffn_bwd``
(``deepspeed_tpu/ops/moe.py``) in a pass of the held experts.

    chiprun -- python3 tools/moe_rows_probe.py     (~2 chip-minutes)

One JSON line a cell (ms for one chunk's rows), also written under
``chiprun_out/``. PERF.md section 7 "Open after PR 37" has PR 37's readings.
Off the chip it runs a toy shape and times nothing worth reading."""
import json, os, time
import jax, jax.numpy as jnp
import numpy as np


def timed(fn, *args, n=10):
    out = fn(*args); jax.block_until_ready(out)
    out = fn(*args); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e3, 4), out


cells = {"nemotron": (16384, 1024, 384, 46, 32), "qwen": (32768, 2048, 352, 91, 64)}
if jax.devices()[0].platform != "tpu":
    cells = {"toy": (256, 128, 16, 6, 4)}
for name, (T, L, tile, tiles, used) in cells.items():
    rng = np.random.default_rng(0)
    real = int(0.9 * tile)
    tok = np.full((tiles, tile), T, np.int32)
    for i in range(used):
        tok[i, :real] = np.sort(rng.choice(T, real, replace=False))
    tok = jnp.asarray(tok.reshape(-1))
    tok_in = jnp.minimum(tok, T - 1)
    u = jax.random.normal(jax.random.PRNGKey(0), (T, L), jnp.float32)
    ub = u.astype(jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(1), (tiles * tile, L), jnp.float32)
    rec = {"cell": name, "rows": tiles * tile, "real_rows": used * real}
    for mode in ("fill", "clip", "promise_in_bounds"):
        idx = tok if mode == "fill" else tok_in
        g = jax.jit(lambda u, i, mode=mode: u.at[i].get(mode=mode, **({"fill_value": 0} if mode == "fill" else {})))
        rec[f"gather_bf16_{mode}_ms"], _ = timed(g, ub, idx)
        rec[f"gather_f32_{mode}_ms"], _ = timed(g, u, idx)
    g = jax.jit(lambda u, i: u.at[i].get(mode="promise_in_bounds", indices_are_sorted=False, unique_indices=True))
    rec["gather_bf16_unique_flag_ms"], _ = timed(g, ub, tok_in)
    # only the rows in use
    g = jax.jit(lambda u, i: jnp.take(u, i[:used * tile], axis=0, mode="clip"))
    rec["gather_bf16_used_tiles_only_ms"], _ = timed(g, ub, tok_in)
    base = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok].add(y, mode="drop"))
    rec["scatter_drop_ms"], want = timed(base, tok, y)
    v = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok].add(y, mode="promise_in_bounds"))
    rec["scatter_in_bounds_ms"], _ = timed(v, tok_in, y)
    v = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok].add(y, mode="drop", unique_indices=True))
    rec["scatter_unique_flag_ms"], _ = timed(v, tok, y)
    v = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok[:used * tile]].add(y[:used * tile], mode="drop"))
    rec["scatter_used_tiles_only_ms"], _ = timed(v, tok, y)
    yb = y.astype(jnp.bfloat16)
    v = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.bfloat16).at[tok].add(y, mode="drop"))
    rec["scatter_bf16_ms"], _ = timed(v, tok, yb)

    def per_tile_loop(tok, y):
        def body(i, out):
            t = jax.lax.dynamic_slice(tok, (i * tile,), (tile,))
            rows = jax.lax.dynamic_slice(y, (i * tile, 0), (tile, L))
            return out.at[t].add(rows, mode="drop", unique_indices=True, indices_are_sorted=True)
        return jax.lax.fori_loop(0, used, body, jnp.zeros((T, L), jnp.float32))
    rec["scatter_per_tile_loop_unique_sorted_ms"], got = timed(jax.jit(per_tile_loop), tok, y)
    rec["per_tile_loop_err"] = float(jnp.max(jnp.abs(got - want)))

    def per_tile_loop_plain(tok, y):
        def body(i, out):
            t = jax.lax.dynamic_slice(tok, (i * tile,), (tile,))
            rows = jax.lax.dynamic_slice(y, (i * tile, 0), (tile, L))
            return out.at[t].add(rows, mode="drop")
        return jax.lax.fori_loop(0, used, body, jnp.zeros((T, L), jnp.float32))
    rec["scatter_per_tile_loop_plain_ms"], _ = timed(jax.jit(per_tile_loop_plain), tok, y)
    # two halves of the width
    v = jax.jit(lambda tok, y: jnp.concatenate([
        jnp.zeros((T, L // 2), jnp.float32).at[tok].add(y[:, :L // 2], mode="drop"),
        jnp.zeros((T, L // 2), jnp.float32).at[tok].add(y[:, L // 2:], mode="drop")], axis=1))
    rec["scatter_two_halves_ms"], _ = timed(v, tok, y)
    # segment_sum over rows sorted by token
    order = jnp.argsort(tok)
    v = jax.jit(lambda tok, y, order: jnp.zeros((T, L), jnp.float32).at[tok[order]].add(
        y[order], mode="drop", indices_are_sorted=True))
    rec["scatter_sorted_by_token_incl_permute_ms"], _ = timed(v, tok, y, order)
    ys, toks = y[order], tok[order]
    v = jax.jit(lambda tok, y: jnp.zeros((T, L), jnp.float32).at[tok].add(y, mode="drop", indices_are_sorted=True))
    rec["scatter_sorted_by_token_ms"], _ = timed(v, toks, ys)
    # element gathers of the plan's arrays
    keys = jnp.arange(T * 10, dtype=jnp.int32)
    first = jnp.arange(tiles, dtype=jnp.int32) * (tile - 7)
    e1 = jax.jit(lambda keys, first: jnp.take(keys, first[:, None] + jnp.arange(tile), mode="clip"))
    rec["keys_element_gather_ms"], a = timed(e1, keys, first)
    e2 = jax.jit(lambda keys, first: jax.vmap(lambda f: jax.lax.dynamic_slice(keys, (f,), (tile,)))(first))
    rec["keys_slice_gather_ms"], b = timed(e2, keys, first)
    assert bool(jnp.all(a == b))
    w = jax.random.normal(jax.random.PRNGKey(2), (16 * T,), jnp.float32)
    e3 = jax.jit(lambda w, i: jnp.take(w, i, mode="fill", fill_value=0))
    rec["weights_element_gather_ms"], _ = timed(e3, w, a.reshape(-1))
    print(json.dumps(rec), flush=True)
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"moe_rows_probe_{name}.json"), "w") as fd:
        json.dump(rec, fd)
