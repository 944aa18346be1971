"""What the two serving drivers share: a tracker that stamps requests
with the benchmark's own clock at the engine's step boundaries.

The engine has no streaming hook, so a client sees a token when the
step that produced it returns. The tracker stands around
`engine.step` (observation only: the call and its result pass through
untouched) and, after each step, stamps the first token of every
request that just got one, polls the page pool, and takes the
snapshots the driver asked for — all at a step boundary, on the
engine's thread.
"""
import threading
import time

import numpy as np

from harness import traffic
from harness.trace_reduce import WINDOW_SPAN


class Record:
    __slots__ = ("idx", "prompt", "n_out", "due", "sent", "fut", "first",
                 "done", "tokens", "error", "client")

    def __init__(self, idx, prompt, n_out, due=None, client=None):
        self.idx, self.prompt, self.n_out = idx, prompt, int(n_out)
        self.due, self.client = due, client
        self.sent = self.fut = self.first = self.done = None
        self.tokens = self.error = None

    def generated(self):
        """Tokens served so far (the benchmark's own count)."""
        if self.tokens is not None:
            return len(self.tokens) - len(self.prompt)
        req = getattr(self.fut, "pt_request", None)
        return 0 if req is None else req.num_generated

    def processed(self):
        """Positions this request has had through the model so far."""
        if self.tokens is not None:
            return len(self.tokens) - 1
        req = getattr(self.fut, "pt_request", None)
        return 0 if req is None else int(req.n_prefilled)


class Tracker:
    def __init__(self, served):
        self.engine, self.server = served.engine, served.server
        self.page_occupancy = served.page_occupancy
        self.records = []
        self.inflight = {}
        self.lock = threading.Lock()
        self.wanted = []              # [(name, not_before, hook)]
        self.snaps = {}
        self.events = {}
        self.page_occ_peak = 0.0
        self.boundaries = 0
        self.on_done = None           # closed loop: next request
        self._inner = None

    # ---- around engine.step -------------------------------------
    def install(self):
        self._inner = self.engine.step
        self.engine.step = self._step

    def _step(self):
        out = self._inner()
        now = time.perf_counter()
        self.boundaries += 1
        with self.lock:
            live = list(self.inflight.values())
            wanted = [w for w in self.wanted if now >= w[1]]
            self.wanted = [w for w in self.wanted if now < w[1]]
        for rec in live:
            if rec.first is None and rec.generated() > 0:
                rec.first = now
        self.page_occ_peak = max(self.page_occ_peak,
                                 self.page_occupancy())
        for name, _t, hook in wanted:
            if hook is not None:
                hook()
            self.snaps[name] = self.snapshot(now)
            self.events[name].set()
        return out

    def snapshot(self, now=None):
        """Counts at a step boundary (or while the engine idles):
        the benchmark's own (`generated`, `processed`, each record's
        processed count under `per_record`), the counters today's
        readers know by their plain names, and EVERY numeric entry of
        `server.metrics()` and `engine.stats` under `metrics` and
        `stats`, so that a counter a later PR adds reaches a reader a
        later PR adds with no edit here."""
        st = self.engine.stats
        m = self.server.metrics()
        per_record = {r.idx: r.processed() for r in self.records}
        return {
            "t": time.perf_counter() if now is None else now,
            "generated": sum(r.generated() for r in self.records),
            "processed": sum(per_record.values()),
            "per_record": per_record,
            "steps": st["steps"], "fused_steps": st["fused_steps"],
            "occupancy_sum": st["occupancy_sum"],
            "prefill_tokens": m["prefill_tokens"],
            "decode_tokens": m["decode_tokens"],
            "dispatches": m["dispatches"],
            "preemptions": m["preemptions"],
            "metrics": numeric(m), "stats": numeric(st),
            "compile_stats": dict(self.engine.compile_stats()),
            "boundaries": self.boundaries,
        }

    def at_boundary(self, name, not_before, hook=None):
        """Ask for snapshot `name` at the first step boundary at or
        after `not_before`; returns the Event that it sets."""
        ev = self.events[name] = threading.Event()
        with self.lock:
            self.wanted.append((name, not_before, hook))
        return ev

    # ---- requests -----------------------------------------------
    def submit(self, rec):
        rec.sent = time.perf_counter()
        with self.lock:
            self.records.append(rec)
            self.inflight[rec.idx] = rec
        rec.fut = self.server.submit(rec.prompt,
                                     max_new_tokens=rec.n_out)
        rec.fut.add_done_callback(lambda f, rec=rec: self._done(rec, f))
        return rec

    def _done(self, rec, fut):
        now = time.perf_counter()
        try:
            rec.tokens = np.asarray(fut.result())
        except Exception as e:  # noqa: BLE001 - counted as failed
            rec.error = repr(e)
        rec.done = now
        if rec.first is None and rec.tokens is not None:
            rec.first = now
        with self.lock:
            self.inflight.pop(rec.idx, None)
        if self.on_done is not None:
            self.on_done(rec)

    def abort_inflight(self):
        with self.lock:
            live = list(self.inflight.values())
        for rec in live:
            req = getattr(rec.fut, "pt_request", None)
            if req is not None:
                self.server.abort(req.rid, reason="window closed")
            else:
                rec.fut.cancel()
        return len(live)


def warm_up(served, log):
    """Every executable the traffic can reach: chunked-prefill single
    ticks (a prompt of two token budgets) and one whole fused window."""
    e = served.cfg["engine"]
    t0 = time.perf_counter()
    served.server.submit(
        np.zeros((2 * int(e["token_budget"]),), np.int32),
        max_new_tokens=max(2, int(e["decode_k"]) + 1)).result(timeout=1500)
    log(f"warm-up request {time.perf_counter() - t0:.1f}s; executables "
        f"{served.engine.compile_stats()}")


def make_prompt(rng, length, vocab):
    return rng.integers(0, int(vocab), (int(length),)).astype(np.int32)


class TraceWindow:
    """A short traced part of the window, bounded by step boundaries.
    The profiler is started and stopped from the driver's thread; the
    span that marks the window is entered and left on the engine's."""

    def __init__(self, tracker, trace_dir, log):
        self.tracker, self.dir, self.log = tracker, trace_dir, log
        self._span = None

    def _enter(self):
        import jax

        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def _exit(self):
        self._span.__exit__(None, None, None)

    def run(self, start_at, length):
        import jax

        time.sleep(max(0.0, start_at - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        on = self.tracker.at_boundary("trace_on", time.perf_counter(),
                                      self._enter)
        if not on.wait(60):
            raise RuntimeError("no step boundary to start the trace at")
        off = self.tracker.at_boundary(
            "trace_off", self.tracker.snaps["trace_on"]["t"] + length,
            self._exit)
        if not off.wait(120):
            raise RuntimeError("no step boundary to stop the trace at")
        jax.profiler.stop_trace()
        self.log("traced %.2fs between step boundaries" % (
            self.tracker.snaps["trace_off"]["t"]
            - self.tracker.snaps["trace_on"]["t"]))


def numeric(d):
    return {k: v for k, v in d.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def work_between(a, b, decode_k):
    """The `work` between two snapshots (`harness/arith.py` describes
    it): counter deltas, `segments` [(start, n)] of the positions each
    request had through the model, and the model's `iterations` — a
    fused dispatch scans `decode_k` of them, a single tick is one. A
    request that was preempted inside restarts its prefill at 0 and
    reads a negative `n` here; no cell preempts (`preemptions`)."""
    out = {k: b[k] - v for k, v in numeric(a).items()}
    for group in ("metrics", "stats"):
        out[group] = {k: b[group][k] - v for k, v in a[group].items()
                      if k in b[group]}
    before = a["per_record"]
    moved = ((before.get(i, 0), n) for i, n in b["per_record"].items())
    out["segments"] = [(start, n - start) for start, n in moved
                       if n != start]
    out["iterations"] = out["fused_steps"] * int(decode_k) \
        + (out["steps"] - out["fused_steps"])
    return out


def serve_observations(tracker, served, open_snap, close_snap, trace_dir):
    """What per-layer readers get from a serving run."""
    cfg = served.cfg
    decode_k = int(cfg["engine"]["decode_k"])
    obs = {
        "cfg": cfg, "kind": "serve",
        "kv_dtype": cfg["engine"]["kv_dtype"],
        "weight_dtype": cfg["serve"]["weight_dtype"],
        "decode_k": decode_k,
        "num_slots": int(cfg["engine"]["num_slots"]),
        "window": work_between(open_snap, close_snap, decode_k),
        "page_occ_peak": tracker.page_occ_peak,
        "compiles_in_window": sum(
            close_snap["compile_stats"][k] - open_snap["compile_stats"]
            .get(k, 0) for k in close_snap["compile_stats"]),
    }
    if "trace_off" in tracker.snaps:
        obs["traced"] = work_between(tracker.snaps["trace_on"],
                                     tracker.snaps["trace_off"], decode_k)
        obs["trace_dir"] = trace_dir
    return obs


def check_sample(records, mix, seed):
    """Finished, well-formed requests to compare: the longest and a few
    more drawn from the seed. Returns (sample, malformed count)."""
    good, bad = [], 0
    for r in records:
        if r.tokens is None:
            continue
        p = len(r.prompt)
        if len(r.tokens) != p + r.n_out or not np.array_equal(
                r.tokens[:p], r.prompt):
            bad += 1
            continue
        good.append(r)
    if not good:
        return [], bad
    good.sort(key=lambda r: (-len(r.tokens), r.idx))
    n = max(1, int(mix.get("check_sample", 4)))
    rest = good[1:]
    pick = traffic.rng_for(seed, 3).permutation(len(rest))[:n - 1]
    sample = [good[0]] + [rest[i] for i in sorted(pick)]
    return [(r.tokens.astype(np.int32), len(r.prompt)) for r in sample], bad
