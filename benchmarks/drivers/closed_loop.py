"""Closed loop: as many clients as the engine has slots, each sending
its next request when the last completes, walking the mix's grid in a
seeded order. The window opens at a step boundary once every slot is
occupied and the first wave's prefill is behind it, runs at least
`--seconds`, and closes at the next step boundary; tokens per second
are all tokens served between the two boundaries over the time between
them."""
import time

from harness import traffic

from . import _serving as sv

COMPARES = "served"    # which comparison decides `correct`


def run(ctx):
    served, mix, log = ctx["handle"], ctx["mix"], ctx["log"]
    seconds = float(ctx["seconds"])
    vocab = served.cfg["vocab_size"]
    n_clients = int(served.cfg["engine"]["num_slots"])
    walk = traffic.closed_walk(mix, ctx["seed"])
    rng = traffic.rng_for(ctx["seed"], 2)
    tracker = sv.Tracker(served)
    state = {"n": 0, "stop": False}

    def next_record(client):
        p, o = next(walk)
        rec = sv.Record(state["n"], sv.make_prompt(rng, p, vocab), o,
                        client=client)
        state["n"] += 1
        return rec

    def on_done(rec):
        if not state["stop"]:
            tracker.submit(next_record(rec.client))

    with served.server:
        sv.warm_up(served, log)
        tracker.install()
        tracker.on_done = on_done
        first_wave = [tracker.submit(next_record(c))
                      for c in range(n_clients)]
        while not all(r.first is not None or r.error for r in first_wave):
            time.sleep(0.005)
        tracker.at_boundary("open", time.perf_counter()).wait(120)
        t_open = tracker.snaps["open"]["t"]
        ctx["window_opened"](t_open)
        closing = tracker.at_boundary("close", t_open + seconds)
        if ctx["trace_dir"]:
            sv.TraceWindow(tracker, ctx["trace_dir"], log).run(
                t_open + 0.3 * seconds, min(4.0, 0.4 * seconds))
        if not closing.wait(seconds + 120):
            raise RuntimeError("the window never reached a closing step "
                               "boundary")
        state["stop"] = True
        aborted = tracker.abort_inflight()
    o, c = tracker.snaps["open"], tracker.snaps["close"]
    elapsed = c["t"] - o["t"]
    finished = [r for r in tracker.records if r.tokens is not None]
    failed = [r for r in tracker.records
              if r.error is not None and r.done <= c["t"]]
    log(f"closed loop: {len(tracker.records)} sent, {len(finished)} "
        f"finished, {aborted} cut off at the close, window {elapsed:.3f}s "
        f"over {c['boundaries'] - o['boundaries']} step boundaries, "
        f"{c['generated'] - o['generated']} tokens served")
    sample, malformed = sv.check_sample(finished, mix, ctx["seed"])
    return {
        "attempted": len(finished) + len(failed),
        "failed": len(failed) + malformed,
        "end_to_end": {"decode_tok_s":
                       (c["generated"] - o["generated"]) / elapsed},
        "obs": sv.serve_observations(tracker, served, o, c,
                                     ctx["trace_dir"]),
        "check": {"kind": COMPARES, "sample": sample,
                  "malformed": malformed,
                  "rows_to": int(mix["output_len"]["max"])},
    }
