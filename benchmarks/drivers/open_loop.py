"""Open loop: N = round(rate × seconds) requests, due on a fixed
schedule whatever the server does, each timed from when it was DUE.
The run then drains; the tails are over every request that was due,
and one that does not finish counts as failed."""
import time

import numpy as np

from harness import traffic

from . import _serving as sv

COMPARES = "served"    # which comparison decides `correct`


def percentile(values, q):
    """Linear interpolation between order statistics (numpy's default),
    stated here so that the yardstick does not move with a library."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def run(ctx):
    served, mix, log = ctx["handle"], ctx["mix"], ctx["log"]
    seconds = float(ctx["seconds"])
    vocab = served.cfg["vocab_size"]
    sched = traffic.open_schedule(mix, ctx["seed"], seconds)
    rng = traffic.rng_for(ctx["seed"], 2)
    recs = [sv.Record(i, sv.make_prompt(rng, p, vocab), o, due=t)
            for i, (t, p, o) in enumerate(sched)]
    tracker = sv.Tracker(served)
    lag = []
    with served.server:
        sv.warm_up(served, log)
        tracker.install()
        open_snap = tracker.snapshot()
        t_open = open_snap["t"]
        ctx["window_opened"](t_open)
        tw = None
        if ctx["trace_dir"]:
            import threading

            tw = threading.Thread(
                target=sv.TraceWindow(tracker, ctx["trace_dir"], log).run,
                args=(t_open + 0.5 * seconds, min(4.0, 0.3 * seconds)))
            tw.start()
        for rec in recs:
            target = t_open + rec.due
            wait = target - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            tracker.submit(rec)
            lag.append(rec.sent - target)
        deadline = time.perf_counter() + float(
            mix.get("drain_timeout_s", 60))
        for rec in recs:
            try:
                rec.fut.result(timeout=max(0.0, deadline
                                           - time.perf_counter()))
            except Exception:  # noqa: BLE001 - counted below
                pass
        if tw is not None:
            tw.join()
        time.sleep(0.05)                # let the last callback land
        undone = tracker.abort_inflight()
        close_snap = tracker.snapshot()
    done = [r for r in recs if r.tokens is not None]
    # the tails are over EVERY request that was due: one that never got
    # its first token has waited at least until now
    ttft = [(r.first if r.first is not None else close_snap["t"])
            - (t_open + r.due) for r in recs]
    tpot = [(r.done - r.first) / (r.n_out - 1) for r in done
            if r.n_out > 1]
    waits = []
    for r in done:
        ph = r.fut.pt_request.trace.phases
        if "queued" in ph and "prefill_start" in ph:
            waits.append(ph["prefill_start"] - ph["queued"])
    marks = [t_open + f * seconds for f in (0.25, 0.5, 0.75, 1.0)]
    inflight = [sum(1 for r in recs if r.sent <= t and (
        r.done is None or r.done > t)) for t in marks]
    log(f"in flight at 25/50/75/100% of the window: {inflight}; tpot p50 "
        f"{percentile(tpot, 50):.4f}s p90 {percentile(tpot, 90):.4f}s; "
        f"ttft p90 {percentile(ttft, 90):.3f}s max {max(ttft):.3f}s")
    log(f"open loop: {len(recs)} due at {mix['rate_per_s']}/s, "
        f"{len(done)} finished, {undone} unfinished at the drain limit; "
        f"generator lag max {max(lag):.4f}s; drained "
        f"{close_snap['t'] - t_open - seconds:.2f}s past the window; "
        f"ttft p50 {percentile(ttft, 50):.3f}s")
    sample, malformed = sv.check_sample(done, mix, ctx["seed"])
    obs = sv.serve_observations(tracker, served, open_snap, close_snap,
                                ctx["trace_dir"])
    obs.update(gen_lag_max_s=float(max(lag)),
               queue_wait_p50_s=(float(np.median(waits)) if waits
                                 else None),
               backlog_s=close_snap["t"] - t_open - seconds)
    return {
        "attempted": len(recs),
        "failed": len(recs) - len(done) + malformed,
        "end_to_end": {"ttft_p90_s": percentile(ttft, 90),
                       "tpot_p90_s": percentile(tpot, 90)},
        "obs": obs,
        "check": {"kind": COMPARES, "sample": sample,
                  "malformed": malformed,
                  "rows_to": int(mix["output_len"]["max"])},
    }
