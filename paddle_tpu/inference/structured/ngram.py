"""Draft-model-free speculation: n-gram / prompt-lookup proposals.

`SpeculativeDecoder` (speculative.py) pays for its proposals with a
resident draft model — per-replica weight + KV memory the Gemma
serving paper (PAPERS.md) frames as THE fleet-scale cost. For the
workloads where speculation pays most (templated JSON, code repair,
retrieval-augmented answers that quote their context), the cheapest
draft is the request itself: when the last n tokens of the sequence
also occur earlier in prompt+generated text, the tokens that followed
that earlier occurrence are a strong guess for what follows now.

`NgramSpeculator` mines exactly that — longest-suffix match (n down
to 1) against the request's own token history, most recent occurrence
wins, the k tokens after the match are the proposal — and feeds it to
the SAME `_CompiledVerifyStep` ragged verify the draft path uses.
Selected via ``LLMEngineConfig(spec_mode="ngram")``; no second model,
no draft pool, no catch-up ticks: the window is [host proposal scan]
+ 1 verify dispatch. Slots with no match run verify-only (width 0 —
a plain decode row inside the same executable), so the engine never
falls off the one-executable path.

Losslessness is inherited wholesale: acceptance is exact-match against
`sample_tokens`' (seed, stream, position)-keyed pick, so output is
token-identical to the non-speculative engine for greedy AND sampled
rows regardless of proposal quality — bad proposals cost width, never
correctness. Grammar constraints compose the same way they do in the
draft path: the verify chains arena DFA states across each row's
proposal positions, and a proposal token the grammar masks simply
fails exact-match and truncates acceptance there.

Duck-typed to the `SpeculativeDecoder` surface the engine drives
(`try_window` / `release_pools` / `reset_pools` / `pool_bytes` /
`.k`), reporting 0 pool bytes — brownout L2 has
nothing to release and preemption owes no draft replay
(`draft_prefilled` is dead weight here).
"""
import time as _time

import numpy as np

from ...observability import metrics as _obs
from ...observability.tracing import trace_span as _trace_span

__all__ = ["NgramSpeculator"]

_NGRAM_WINDOWS = _obs.counter(
    "pt_ngram_spec_windows_total",
    "n-gram speculative windows dispatched (one verify executable "
    "call each)")
_NGRAM_PROPOSED = _obs.counter(
    "pt_ngram_spec_proposed_total",
    "prompt-lookup tokens proposed to the verify step (window widths "
    "summed; match-less slots propose 0 and run verify-only)")
_NGRAM_ACCEPTED = _obs.counter(
    "pt_ngram_spec_accepted_total",
    "accepted prompt-lookup tokens that entered the output")
_NGRAM_ACC_RATE = _obs.gauge(
    "pt_ngram_spec_acceptance_rate",
    "accepted / proposed for the n-gram proposer, process-cumulative "
    "(prompt-lookup-favorable workloads sit near 1.0; adversarial "
    "ones near 0 — and still lose nothing but the window width)")


class NgramSpeculator:
    mode = "ngram"

    def __init__(self, engine, spec_k, max_match=3, scan_window=512):
        from ..speculative import _CompiledVerifyStep

        self.engine = engine
        self.k = int(spec_k)
        if self.k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.k}")
        self.max_match = int(max_match)
        self.scan_window = int(scan_window)
        self._verify_fn = _CompiledVerifyStep(
            engine.model, self.k, engine.page_size)
        self._stats = engine.stats
        for key in ("ngram_windows", "ngram_proposed",
                    "ngram_accepted"):
            self._stats.setdefault(key, 0)

    # ---- SpeculativeDecoder duck-type surface ----

    def pool_bytes(self):
        return 0

    def reset_pools(self):
        pass                      # no draft pool to re-zero

    def release_pools(self):
        pass                      # brownout L2: nothing resident

    # ---- proposal mining ----

    def _propose(self, req):
        """Longest-suffix prompt lookup over the request's own token
        history: match the last n tokens (n = max_match..1) against an
        earlier occurrence (most recent wins, bounded to the trailing
        `scan_window` positions) and propose the ≤ k tokens that
        followed it. Empty list = no match = verify-only row."""
        toks = req.tokens
        n_max = min(self.max_match, len(toks) - 1)
        for n in range(n_max, 0, -1):
            tail = toks[-n:]
            hi = len(toks) - n - 1   # latest start with a continuation
            lo = max(0, hi - self.scan_window)
            for j in range(hi, lo - 1, -1):
                if toks[j:j + n] == tail:
                    cont = toks[j + n:j + n + self.k]
                    if cont:
                        return cont
        return []

    # ---- the speculative window ----

    def try_window(self, frontier):
        """One n-gram speculative window over the frontier rows, or
        None when even the frontier token's page cannot be covered —
        same contract, page reservation, and consumption accounting as
        `SpeculativeDecoder.try_window`, minus every draft-model leg
        (no catch-up, no propose dispatch, no device gather)."""
        from ..llm_engine import (
            _DISPATCHES, _FUSED_STEPS, _QUEUE_DEPTH, _STEPS_TOTAL,
            _TOK_PER_DISPATCH, _TOKENS_TOTAL, _TTFT_SECONDS,
        )

        eng = self.engine
        k = self.k
        S = eng.num_slots

        cap = eng._brownout.get("spec_k_cap")
        k_eff = k if cap is None else max(0, min(k, int(cap)))

        proposals = {}
        width = {}
        for slot, req in frontier:
            props = ([] if req.spec_off or not k_eff
                     else self._propose(req))
            w = min(len(props), k_eff, req.target - len(req.tokens))
            w = eng._grow(slot, req, w + 1) - 1
            if w < 0:
                return None   # frontier write itself has no page
            width[slot] = w
            proposals[slot] = props[:w]

        tok0 = np.zeros((S,), np.int32)
        pos0 = np.zeros((S,), np.int32)
        drafts = np.zeros((S, k), np.int32)
        wid = np.zeros((S,), np.int32)
        rem = np.zeros((S,), np.int32)
        fin_v = np.ones((S,), bool)
        eos = np.full((S,), -1, np.int32)
        temps = np.zeros((S,), np.float32)
        tops = np.ones((S,), np.float32)
        streams = np.zeros((S,), np.int32)
        gen_before = {}
        for slot, req in frontier:
            tok0[slot] = req.tokens[-1]
            pos0[slot] = req.n_prefilled
            wid[slot] = width[slot]
            for j, t in enumerate(proposals[slot]):
                drafts[slot, j] = t
            rem[slot] = req.target - len(req.tokens)
            fin_v[slot] = False
            if req.eos is not None:
                eos[slot] = int(req.eos)
            temps[slot] = req.temperature
            tops[slot] = req.top_p
            streams[slot] = req.sample_stream
            gen_before[slot] = req.num_generated

        gst, gtrans, gmask = eng._grammar_args(frontier)

        t0 = _time.perf_counter()
        try:
            with _trace_span("llm_engine.ngram_window", k=k,
                             live=len(frontier)):
                emits, (eng._kv, eng._kv_scales, eng._key) = \
                    self._verify_fn(
                        tok0, pos0, drafts, wid, rem, fin_v, eos,
                        temps, tops, streams, gst, gtrans, gmask,
                        eng._step_tables(),
                        (eng._kv, eng._kv_scales, eng._key))
                emits = np.asarray(emits)  # [k+1, S]: the host sync
        except Exception as e:
            eng.abort_all(e)
            raise
        eng.sched.note_boundary(_time.perf_counter() - t0)

        self._stats["steps"] += 1
        self._stats["ngram_windows"] += 1
        eng._note_launches(self._verify_fn)
        self._stats["occupancy_sum"] += len(frontier) / S
        _STEPS_TOTAL.inc()
        _FUSED_STEPS.inc()
        _DISPATCHES.inc()
        _NGRAM_WINDOWS.inc()

        finished = []
        now = _time.perf_counter()
        total = 0
        proposed = 0
        accepted = 0
        for slot, req in frontier:
            emitted, done, from_draft = 0, False, 0
            for j in range(k + 1):
                t = int(emits[j, slot])
                if t < 0:
                    break
                req.tokens.append(t)
                if req.grammar is not None:
                    req.gstate = req.grammar.advance(req.gstate, t)
                if j < k and t == int(drafts[slot, j]):
                    from_draft += 1
                emitted += 1
                if ((req.eos is not None and t == req.eos)
                        or len(req.tokens) >= req.target):
                    done = True
            req.n_prefilled += emitted
            total += emitted
            proposed += width[slot]
            accepted += from_draft
            self._stats["generated"] += emitted
            eng.sched.note_tokens(req.tenant, emitted)
            if gen_before[slot] == 0 and emitted > 0:
                ttft = now - req.t_submit
                req.t_first_token = now
                req.trace.stamp("first_token")
                eng._note_timeline(req)
                _TTFT_SECONDS.observe(ttft)
                eng.sched.note_first_token(req, ttft)
            if done:
                eng._finish(slot, req)
                finished.append(req)
        self._stats["tokens_in"] += total
        self._stats["ngram_proposed"] += proposed
        self._stats["ngram_accepted"] += accepted
        eng.sched.note_spec_window(proposed, accepted)
        _NGRAM_PROPOSED.inc(proposed)
        _NGRAM_ACCEPTED.inc(accepted)
        n_prop = _NGRAM_PROPOSED.value
        if n_prop:
            _NGRAM_ACC_RATE.set(_NGRAM_ACCEPTED.value / n_prop)
        _TOKENS_TOTAL.labels(phase="decode").inc(total)
        _TOK_PER_DISPATCH.set(total)
        _QUEUE_DEPTH.set(len(eng.waiting))
        eng._publish_load()
        return finished
