"""The training loop: ``deepspeed_tpu.initialize()`` and fused
``train_batch()`` windows for ``--seconds``.

Set-up builds ONE engine, drives it through its first ``check.steps``
windows on the cell's own feed (rows from the seed, all different), reads
the first gradient's norms out of the optimizer state after the first, the
parameters' change after the last (and after the first, where the cell's
limits name it), and hands that same engine to the measured window. After
the window has closed and the engine is freed, the plain reference follows
the same steps on the same rows and the two are compared (PERF.md, "How
correct is decided").
"""

import gc
import math
import time

from .. import harness, metrics, program


def reference_readings(ref, size, config, cell, seed, kept, precision=None):
    """Losses, first-gradient norms and parameter-change norms (after the
    first step and after the last) of the reference over the kept
    micro-batches. The inputs of every product are rounded to ``precision``
    (the control's), or as the configuration's recipe states the program
    multiplies (``reference_inputs``; float32, exact, where it says
    nothing); everything else is float32."""
    from ..reference import ops
    from ..reference import train as follower

    init, key = ops.initializer(ref, size), ops.seed_key(seed)
    accum = cell["accum"]
    steps = [kept[i:i + accum] for i in range(0, len(kept), accum)]
    losses, grad, first_change, change = follower.follow(
        ref, size, lambda: init(key), steps, config["train"]["optimizer"],
        ops.make_dot(
            precision or config["train"].get("reference_inputs", "float32")),
        cell["check"]["block_rows"])
    return {"losses": losses, "grad_norms": grad,
            "first_change_norms": first_change, "change_norms": change}


def compare(ours, theirs):
    """The numbers compared, each with where it was worst. The first step's
    loss is read before any update and shows the forward pass alone; the
    later steps' losses also carry what the update amplifies (an Adam or
    LAMB step moves every element by about the same amount whatever its
    gradient's size, so rounding in the smallest gradients moves the next
    loss), and have a limit of their own. The parameters' change is read
    after the last step and, where the program's side was read then, after
    the first: one update moves each leaf by a length that the rule and its
    rates fix (LAMB: lr x the leaf's own norm; Adam: lr x the root of its
    size) whatever the gradient's rounding, so that number holds the SIZE
    of the update tightly, before later steps amplify anything."""
    gaps = [abs(a - b) for a, b in zip(ours["losses"], theirs["losses"])]
    later = max(range(1, len(gaps)), key=lambda i: gaps[i])
    out = {
        "first_loss_gap": (gaps[0], "step 1"),
        "later_loss_gap": (gaps[later], f"step {later + 1}"),
    }
    for name in ("grad_norm", "first_change_norm", "change_norm"):
        if name + "s" in ours:
            out[name + "_gap"] = metrics.worst_gap(
                ours[name + "s"], theirs[name + "s"])
    return out


def run(ctx):
    import jax

    cell, config, size = ctx["cell"], ctx["config"], ctx["size"]
    spans, counter, devices = ctx["spans"], ctx["compiles"], ctx["devices"]
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    check, seed = cell["check"], ctx["seed"]
    accum = cell["accum"]

    # ---- set-up ---------------------------------------------------------
    from ..reference import ops

    init, key = ops.initializer(ref, size), ops.seed_key(seed)
    with spans.span("bench.weights"):
        params0 = jax.block_until_ready(init(key))
    n_params = sum(int(x.size) for x in params0.values())
    with spans.span("bench.engine_build"):
        engine = program.build_train(config, cell, size, params0, devices)
    del params0

    kept = []   # the first steps' micro-batches, for the reference

    def stream():
        for batch in gen.micro_batches(seed, cell, size):
            if len(kept) < check["steps"] * accum:
                kept.append(batch)
            yield program.feed(config, batch)

    feed = stream()
    ours = {"losses": []}
    b1 = config["train"]["optimizer"]["b1"]
    window_s = None   # the last first step's length: the stop rule's first guess
    for step in range(check["steps"]):
        jax.block_until_ready(engine.params)
        t0 = time.perf_counter()
        with spans.span("bench.first_steps"):
            loss = engine.train_batch(feed)
            ours["losses"].append(float(loss))
        window_s = time.perf_counter() - t0
        if step == 0:
            with spans.span("bench.read_state"):
                ours["grad_norms"] = program.first_moment_norms(
                    config, ref, engine, b1)
                if "first_change_norm_gap" in check["limits"]:
                    ours["first_change_norms"] = program.change_norms(
                        config, ref, engine, init, key)
    with spans.span("bench.read_state"):
        ours["change_norms"] = program.change_norms(
            config, ref, engine, init, key)
    jax.block_until_ready(engine.params)
    warm_windows = check["steps"]
    compiles_before = counter.total()
    setup_s = time.perf_counter() - ctx["t_loop"]
    harness.say("setup", **harness.setup_account(ctx, setup_s))

    # ---- the measured window ---------------------------------------------
    tokens_per_window = gen.tokens_per_micro_batch(cell) * accum
    tracer = ctx["tracer"]
    tracer.start()
    losses, pending, read_at = [], [], []
    t0 = time.perf_counter()
    while True:
        elapsed = time.perf_counter() - t0
        if losses or pending:
            if elapsed + window_s * (len(pending) + 1) > ctx["seconds"]:
                break
        with spans.span("bench.submit"):
            pending.append(engine.train_batch(feed))
        if len(pending) > 1:
            with spans.span("bench.readback"):
                losses.append(float(pending.pop(0)))
            read_at.append(time.perf_counter())
            window_s = (time.perf_counter() - t0) / len(losses)
        if tracer.running and time.perf_counter() - t0 >= cell["trace_seconds"]:
            with spans.span("bench.readback"):
                losses.extend(float(x) for x in pending)
                pending.clear()
                jax.block_until_ready(engine.params)
            tracer.stop()
    with spans.span("bench.readback"):
        losses.extend(float(x) for x in pending)
        jax.block_until_ready(engine.params)   # the window ends in a device sync
    wall = time.perf_counter() - t0
    tracer.stop()
    compiled_inside = counter.total() - compiles_before

    windows = len(losses)
    between = [b - a for a, b in zip(read_at, read_at[1:])]
    rate = metrics.rate(windows * tokens_per_window, wall) / cell["chips"]
    bad = sum(not math.isfinite(x) for x in losses)
    skipped = int(engine.skipped_steps)
    steps_taken = int(engine.global_steps)
    device = harness.device_report(devices)
    peak = ctx["peaks"]
    harness.say(
        "train_window", windows=windows, wall_s=wall, window_s=wall / windows,
        tokens_per_window=tokens_per_window, losses=losses[:4] + losses[-2:],
        n_params=n_params, skipped_steps=skipped, steps_taken=steps_taken,
        compiled_inside_window=compiled_inside,
        seconds_between_readbacks_min_median_max=[
            min(between), metrics.median(between), max(between)]
        if between else None,
        # the two ends, which no time between readbacks covers: a far-off
        # run says here where its seconds went (PERF.md, section 7)
        first_readback_s=read_at[0] - t0 if read_at else None,
        after_last_readback_s=t0 + wall - read_at[-1] if read_at else None,
        model_flops_utilization=metrics.model_flops_utilization(
            rate, n_params, peak["bf16_flops_per_s"]) if peak else None,
    )

    # ---- correct: after the window, with the engine freed -----------------
    program.close_train(engine)
    del engine, feed
    jax.clear_caches()
    gc.collect()
    t0 = time.perf_counter()
    theirs = reference_readings(ref, size, config, cell, seed, kept)
    numbers = compare(ours, theirs)
    harness.say("reference", seconds=time.perf_counter() - t0,
                reference_losses=theirs["losses"], program_losses=ours["losses"])
    checks = [
        {"name": name, "value": value, "limit": check["limits"][name],
         "at": where, "ok": bool(value <= check["limits"][name])}
        for name, (value, where) in numbers.items()
    ]
    checks += [
        {"name": "compiled_inside_window", "value": compiled_inside, "limit": 0,
         "ok": compiled_inside == 0},
        {"name": "nonfinite_or_skipped_windows", "value": bad + skipped,
         "limit": 0, "ok": bad + skipped == 0},
        {"name": "steps_taken", "value": steps_taken,
         "limit": warm_windows + windows,
         "ok": steps_taken == warm_windows + windows},
    ]
    return {
        "attempted": windows,
        "failed": min(windows, bad + skipped),
        "checks": checks,
        "end_to_end": {
            "setup_s": setup_s,
            "train_tokens_per_s_per_chip": rate,
        },
        "device": device,
        "facts": {"windows_in_trace": None, "tokens_per_window": tokens_per_window},
    }
