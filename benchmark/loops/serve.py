"""The serving loop: ``deepspeed_tpu.init_inference()``, then open-loop
load for ``--seconds``.

One thread: the benchmark submits what has fallen due through
``engine.submit()``, calls ``engine.scheduler.step()``, and stamps every new
token with the time the step returned. A request's first token is timed
from when the request was DUE, not from when the loop got to it. After the
window the loop drains what is in flight (no new arrivals), frees the
engine, and the plain reference reads the served tokens back, teacher-forced
(PERF.md, "How correct is decided").
"""

import gc
import time

import numpy as np

from .. import harness, metrics, program


def served_gaps(ref, size, seed, sample, length, precision="float32",
                chosen=None):
    """For each request of ``sample`` (prompt, served tokens): the gap by
    which each served token's logit lies below the reference's best, from
    one float32 forward pass over prompt + served tokens. With
    ``precision`` lower, the pass is made in that precision and the gap read
    is that of the token IT puts first, under the float32 logits given in
    ``chosen``: the control."""
    import jax
    import jax.numpy as jnp

    from ..reference import ops

    dot = ops.make_dot(precision)
    params = ops.initializer(ref, size)(ops.seed_key(seed))
    vocab = size["vocab_size"]

    @jax.jit
    def logits(p, tokens):
        return ref.logits(p, tokens, size, dot)[0, :, :vocab]

    out = []
    for n, (prompt, served) in enumerate(sample):
        seq = np.zeros((1, length), np.int32)
        seq[0, :len(prompt) + len(served)] = list(prompt) + list(served)
        lg = np.asarray(logits(params, jnp.asarray(seq)), np.float64)
        rows = lg[len(prompt) - 1:len(prompt) + len(served) - 1]
        if chosen is None:
            picked = np.asarray(served)
            base = rows
        else:
            picked = rows.argmax(-1)
            base = chosen[n]
        gaps = base.max(-1) - base[np.arange(len(picked)), picked]
        out.append({"gaps": gaps, "rows": rows})
    return out


def pick_sample(seed, finished, count):
    """``count`` finished requests drawn from the seed, the longest among
    them: (prompt, served tokens) each."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 4])
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    others = [i for i in range(len(finished)) if i != longest]
    take = list(rng.permutation(others)[:count - 1]) + [longest]
    return [finished[i] for i in take]


class Live:
    """One request in flight: its handle and the time of each token."""

    def __init__(self, request, handle, submitted):
        self.request, self.handle, self.submitted = request, handle, submitted
        self.times = []
        self.admitted = None


def drive(engine, reqs, seconds, drain_seconds, spans, tracer, trace_seconds):
    """Offer ``reqs`` on their schedule for ``seconds``, then drain. Times
    are seconds after the window opened."""
    sched = engine.scheduler
    live, records, refused = [], [], 0
    peak = {"slots": 0, "pages": 0}   # slots decoded in, pages held after, a step
    nxt = 0
    t0 = time.perf_counter()
    trace_from = max(0.0, seconds - trace_seconds)
    while True:
        now = time.perf_counter() - t0
        # the LAST trace_seconds of the window: the slots are filled by then,
        # and the seconds that stopping the profiler takes fall into the drain
        if tracer.enabled and not tracer.path:
            if not tracer.running and now >= trace_from:
                tracer.start()
            elif tracer.running and now >= seconds:
                tracer.stop()
        if now >= seconds + drain_seconds or (nxt >= len(reqs) and not live):
            break
        with spans.span("bench.stage"):
            while nxt < len(reqs) and reqs[nxt].due <= now and now < seconds:
                r = reqs[nxt]
                nxt += 1
                try:
                    handle = engine.submit(
                        r.prompt, max_new_tokens=r.max_new_tokens)
                except Exception as exc:   # refused at the door: it missed
                    harness.say("refused", error=repr(exc)[:200])
                    refused += 1
                    continue
                rec = Live(r, handle, time.perf_counter() - t0)
                live.append(rec)
                records.append(rec)
            if now >= seconds:
                nxt = len(reqs)
        before = time.perf_counter() - t0
        with spans.span("bench.step") as attrs:
            active = sched.step()
        after = time.perf_counter() - t0
        with spans.span("bench.readback"):
            firsts = 0
            for rec in live:
                new = len(rec.handle.tokens) - len(rec.times)
                if new:
                    if not rec.times:
                        firsts += 1
                        rec.admitted = before   # its prefill ran in this step
                    rec.times.extend([after] * new)
            live = [rec for rec in live if not rec.handle.done]
        attrs.update(active=active, admitted=firsts, ended=after,
                     backlog=len(live))
        peak["slots"] = max(peak["slots"], active)
        peak["pages"] = max(peak["pages"], program.pages_in_use(engine))
        if not active and not live:
            wait = reqs[nxt].due - after if nxt < len(reqs) else 0.0
            if wait > 0:
                time.sleep(min(wait, 0.002))
    tracer.stop()
    return records, refused, time.perf_counter() - t0, peak


def warm(engine, cell, size, seed):
    """Run the two programs this traffic uses (one padded prefill shape, one
    decode shape) with every slot taken and a queue behind them."""
    rng = np.random.default_rng([seed, 5])
    lens = cell["traffic"]["prompt_tokens"]
    for _ in range(cell["engine"]["max_batch_slots"] + 2):
        n = int(rng.integers(lens["min"], lens["max"] + 1))
        engine.submit([int(x) for x in rng.integers(0, size["vocab_size"], n)],
                      max_new_tokens=4)
    engine.scheduler.run_until_idle()


def run(ctx):
    import jax

    cell, config, size = ctx["cell"], ctx["config"], ctx["size"]
    spans, counter, devices = ctx["spans"], ctx["compiles"], ctx["devices"]
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    check, seed, seconds = cell["check"], ctx["seed"], ctx["seconds"]

    # ---- set-up ---------------------------------------------------------
    from ..reference import ops

    with spans.span("bench.weights"):
        params0 = jax.block_until_ready(
            ops.initializer(ref, size)(ops.seed_key(seed)))
    with spans.span("bench.engine_build"):
        engine = program.build_serve(config, cell, size, params0, devices)
    del params0
    with spans.span("bench.warm"):
        warm(engine, cell, size, seed)
    reqs = gen.requests(seed, cell["traffic"], size, seconds)
    compiles_before = counter.total()
    setup_s = time.perf_counter() - ctx["t_loop"]
    harness.say("setup", **harness.setup_account(ctx, setup_s))

    # ---- the measured window, then the drain ------------------------------
    records, refused, total_s, peak = drive(
        engine, reqs, seconds, cell["drain_seconds"], spans, ctx["tracer"],
        cell["trace_seconds"])
    compiled_inside = counter.total() - compiles_before

    good = ("eos", "max_new_tokens", "length")
    finished = [r for r in records
                if r.handle.done and r.handle.finish_reason in good]
    failed = len(reqs) - len(finished)
    end = seconds + cell["drain_seconds"]
    ttft = [(r.times[0] if r.times else end) - r.request.due for r in records]
    ttft += [end] * (len(reqs) - len(records))
    gaps = [b - a for r in records for a, b in zip(r.times, r.times[1:])]
    in_window = sum(t <= seconds for r in records for t in r.times)
    late = [r.submitted - r.request.due for r in records]
    device = harness.device_report(devices)
    steps = spans.named("bench.step")
    # what a user of the engine feels; BENCHMARK.json says which of them a
    # cell is judged on (run.py reports those and no others)
    felt = {"serve_tokens_per_s": metrics.rate(in_window, seconds)}
    for q in (50, 90):
        felt[f"ttft_p{q}_ms"] = 1e3 * metrics.percentile(ttft, q)
    for q in (50, 90, 95, 99):
        felt[f"itl_p{q}_ms"] = 1e3 * (
            metrics.percentile(gaps, q) if gaps else end)
    harness.say(
        "serve_window", requests=len(reqs), finished=len(finished),
        refused=refused, tokens_in_window=in_window,
        tokens_total=sum(len(r.times) for r in records),
        drained_after_s=total_s - seconds, steps=len(steps),
        generator_late_ms_p50=1e3 * metrics.median(late) if late else None,
        generator_late_ms_max=1e3 * max(late) if late else None,
        gaps=len(gaps), compiled_inside_window=compiled_inside,
        slots_in_use_peak=peak["slots"],
        slots=cell["engine"]["max_batch_slots"],
        pages_in_use_peak=peak["pages"],
        pages=cell["engine"]["kv_pool_blocks"], **felt,
    )

    # ---- correct: after the window, with the engine freed -----------------
    sample = pick_sample(
        seed, [(r.request.prompt, list(r.handle.tokens)) for r in finished],
        check["sample"])
    engine.close()
    del engine
    jax.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    length = cell["engine"]["prefill_len"] + cell["traffic"]["output_tokens"]["max"]
    read = served_gaps(ref, size, seed, sample, length)
    widest = max((float(r["gaps"].max()) for r in read), default=float("inf"))
    n_tokens = sum(len(r["gaps"]) for r in read)
    off = sum(int((r["gaps"] > 0).sum()) for r in read)
    harness.say("reference", seconds=time.perf_counter() - t0,
                requests_compared=len(read), tokens_compared=n_tokens,
                tokens_off_reference_argmax=off)
    lim = check["limits"]
    checks = [
        {"name": "widest_gap_below_best", "value": widest,
         "limit": lim["widest_gap_below_best"],
         "ok": bool(widest <= lim["widest_gap_below_best"])},
        {"name": "tokens_compared", "value": n_tokens,
         "limit": lim["min_tokens_compared"],
         "ok": n_tokens >= lim["min_tokens_compared"]},
        {"name": "compiled_inside_window", "value": compiled_inside, "limit": 0,
         "ok": compiled_inside == 0},
        {"name": "requests_failed", "value": failed, "limit": 0,
         "ok": failed == 0},
    ]
    return {
        "attempted": len(reqs),
        "failed": failed,
        "checks": checks,
        "end_to_end": {"setup_s": setup_s, **felt},
        "device": device,
        "sample": sample, "read": read, "length": length,
        "facts": {"queue_wait_s": [r.admitted - r.request.due for r in records
                                   if r.admitted is not None]},
    }
