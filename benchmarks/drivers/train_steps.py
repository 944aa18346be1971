"""Training steps: set-up builds ONE compiled step with its state,
drives it through its first three steps (the ones the reference
follows) by the window's own call and feed, and hands the same object
to the window. The window runs from a step boundary to a step boundary
(`block_until_ready` at both); tokens per second are all tokens of all
steps between them over the time between them."""
import time

import numpy as np

from harness import traffic
from harness.trace_reduce import WINDOW_SPAN

CHECK_STEPS = 3
COMPARES = "trained"   # which comparison decides `correct`


def run(ctx):
    import jax

    trained, mix, log = ctx["handle"], ctx["mix"], ctx["log"]
    seconds = float(ctx["seconds"])
    batch, seq = int(mix["batch"]), int(mix["seq"])
    every = int(mix["loss_every"])
    pool = traffic.train_batches(mix, ctx["seed"],
                                 trained.cfg["vocab_size"],
                                 int(mix.get("distinct_batches", 64)))
    step = trained.step

    def one(i):
        return step(trained.feed(pool[i % len(pool)]))

    # ---- set-up: the first steps, read for the comparison ----------
    losses, t0 = [], time.perf_counter()
    for i in range(CHECK_STEPS):
        losses.append(float(one(i).numpy()))
        if i == 0:
            log(f"compile + step 1: {time.perf_counter() - t0:.1f}s")
            moment1 = trained.first_moment_norms()
    change = trained.change_norms()
    stats0 = dict(trained.compile_stats())
    log("first losses " + " ".join(f"{x:.5f}" for x in losses))

    # ---- the window -----------------------------------------------
    n = CHECK_STEPS
    loss = one(n)
    n += 1
    jax.block_until_ready(loss._value)
    t_open = time.perf_counter()
    ctx["window_opened"](t_open)
    first = n
    traced = None
    trace_at = first + 12 if ctx["trace_dir"] else None
    fetched = []
    while True:
        if n == trace_at:
            jax.block_until_ready(loss._value)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(ctx["trace_dir"],
                                     profiler_options=opts)
            t_a = time.perf_counter()
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                for _ in range(6):
                    loss = one(n)
                    n += 1
                jax.block_until_ready(loss._value)
            t_b = time.perf_counter()
            jax.profiler.stop_trace()
            traced = {"steps": 6, "seconds": t_b - t_a}
            continue
        loss = one(n)
        n += 1
        if (n - first) % every == 0:
            fetched.append(float(loss.numpy()))
        if time.perf_counter() - t_open >= seconds:
            break
    jax.block_until_ready(loss._value)
    t_close = time.perf_counter()
    steps = n - first
    stats1 = dict(trained.compile_stats())
    finite = bool(np.all(np.isfinite(fetched))) if fetched else True
    log(f"window {t_close - t_open:.3f}s, {steps} steps of {batch}x{seq}"
        f", {len(fetched)} losses fetched, last "
        f"{fetched[-1] if fetched else float('nan'):.4f}")
    obs = {
        "cfg": trained.cfg, "kind": "train", "batch": batch, "seq": seq,
        "window": {"steps": steps, "seconds": t_close - t_open},
        "compiles_in_window": sum(stats1[k] - stats0[k] for k in stats1),
    }
    if traced:
        obs["traced"] = traced
        obs["trace_dir"] = ctx["trace_dir"]
    return {
        "attempted": steps, "failed": 0 if finite else steps,
        "end_to_end": {"train_tok_s":
                       steps * batch * seq / (t_close - t_open)},
        "obs": obs,
        "check": {"kind": COMPARES, "losses": losses,
                  "moment1": moment1, "change": change,
                  "batches": [pool[i] for i in range(CHECK_STEPS)],
                  "custom_calls_batch": pool[0]},
    }
