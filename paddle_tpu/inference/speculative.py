"""Speculative decoding — draft-model propose, one-dispatch ragged verify.

PR-8's fused window killed the per-token host loop, but every accepted
token still costs one full-model forward: k tokens = k sequential big
matmul stacks inside the scan. Speculative decoding breaks that bound
(ROADMAP item 2(b); "Fine-Tuning and Serving Gemma on Cloud TPU" in
PAPERS.md is the serving-economics reference — accepted tokens per
big-model dispatch is the metric that pays for TPU serving):

* a small **draft model** (same GPT family, tied tokenizer) proposes k
  tokens per live sequence through its own paged KV pool — the cheap
  sequential part;
* the big model **verifies all k+1 positions of every slot in ONE
  ragged batched step** (`_CompiledVerifyStep` over
  `GPTGenerationMixin._paged_verify_fused`): the flat-token [1, T, d]
  layout and `F.paged_attention`'s per-token kv_lens already express
  "slot s, query j attends prefix pos0+j" with zero padding, so the k+1
  sequential big-model steps collapse into one batched matmul stack.

**Losslessness.** `sample_tokens` keys every draw on (engine seed,
stream, position) ONLY, so the target pick at a position is a
deterministic function of the accepted prefix. Acceptance is exact
match against that pick: for greedy rows this is longest-prefix argmax
match; for sampled rows the standard accept/reject test degenerates to
equality because the keyed categorical draw IS the target sample.
Greedy AND sampled outputs are therefore token-identical to the
non-speculative engine and invariant to spec_k (tests pin both). The
draft is *coupled* to the same key: `jax.random.categorical` is a
Gumbel argmax, so identical keys add identical noise to draft and
target logits — agreement is high whenever the distributions are
close, degrading gracefully (not catastrophically) at temperature.

**Pool mirroring.** The draft pool shares the engine's page tables and
page ids: same num_pages × page_size geometry, its own [N, P, h', d']
buffers sized by the draft config. One page allocation covers both
pools, so the PagePool/prefix-cache/preemption accounting is unchanged
— a page simply costs big-bytes + draft-bytes (`pool_bytes` reports
both; docs/SERVING.md "Speculative decoding" has the sizing table).

**Rollback is positional.** Rejected draft KV rows — in BOTH pools —
stay in place as stale garbage past the accepted frontier: kv_lens
masks them out of every later attention, and the rows are overwritten
(by position) when the real tokens arrive. No cleanup dispatch. The
draft's valid prefix is tracked per request (`draft_prefilled`) and
caught up through the draft's own flat-token prefill step — the same
chunked mechanism that replays the prompt into the draft pool after
admission or preemption.

Per window: [0-or-more draft catch-up ticks] + 1 draft propose scan +
1 big verify dispatch, emitting 1..k+1 tokens per live slot with ONE
host sync (the verify emits). All three executables follow the
TrainStep pattern — weights as jit arguments, (pools, scale planes,
PRNG key) one donated pytree; the key threads sequentially through
draft and big dispatches, so `reseed()` never recompiles any of them.
"""
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..observability import metrics as _obs
from ..observability.tracing import trace_span as _trace_span
from .llm_engine import (
    _DISPATCHES, _FUSED_STEPS, _QUEUE_DEPTH, _STEPS_TOTAL,
    _TOK_PER_DISPATCH, _TOKENS_TOTAL, _TTFT_SECONDS,
    _CompiledPagedStep, _CompiledStepBase,
)

__all__ = ["SpeculativeDecoder"]

# speculative-decoding telemetry (docs/OBSERVABILITY.md). Counters are
# process-global; the acceptance-rate gauge is derived from the global
# counters so several engines in one process don't stomp each other
# (same contract as pt_sched_ttft_slo_attainment).
_SPEC_PROPOSED = _obs.counter(
    "pt_spec_proposed_total",
    "draft tokens proposed to the verify step (window widths summed)")
_SPEC_ACCEPTED = _obs.counter(
    "pt_spec_accepted_total",
    "accepted draft tokens that entered the output (each window also "
    "emits one non-draft token: the target's own pick)")
_SPEC_ACC_RATE = _obs.gauge(
    "pt_spec_acceptance_rate",
    "accepted / proposed, process-cumulative (the multiplier that "
    "decides whether speculation pays)")
_SPEC_DRAFT_SECONDS = _obs.counter(
    "pt_spec_draft_seconds",
    "wall seconds spent in draft-model dispatches (catch-up prefill + "
    "propose scan) — the overhead side of the acceptance trade")


class _CompiledProposeStep(_CompiledStepBase):
    """The draft model's propose executable: the PR-8 fused scan
    (`_paged_decode_fused`) in PROPOSE mode — scan length k+1, per-row
    `lag`/`frontier` so the 1-token draft-KV lag a fully-accepted
    window leaves is replayed INSIDE this dispatch (iteration 0)
    instead of costing a separate catch-up tick on the steady-state
    hot path. Same compilation contract as every decode executable
    (`_CompiledStepBase`): weights as jit arguments, (pools, scales,
    key) donated."""

    def __init__(self, model, k, page_size):
        self._params = list(model.state_dict().values())
        self.k = int(k)
        ps = int(page_size)

        def pure(param_vals, tok0, pos0, rem, fin0, eos, temps, top_ps,
                 streams, lag, frontier, pt, kv_state):
            from ..autograd import engine as eng

            kv_vals, kv_scales, key = kv_state
            originals = [p._value for p in self._params]
            for p, v in zip(self._params, param_vals):
                p._value = v
            try:
                with eng.no_grad_guard(), \
                        self._counting_launches(self.k + 1):
                    emits, new_kv, new_scales = model._paged_decode_fused(
                        self.k + 1, ps, tok0, pos0, rem, fin0, eos,
                        temps, top_ps, streams, pt, list(kv_vals),
                        list(kv_scales) if kv_scales else None, key,
                        lag=lag, frontier=frontier)
            finally:
                for p, v in zip(self._params, originals):
                    p._value = v
            return emits, (new_kv, new_scales, key)

        self._jit = jax.jit(pure, donate_argnums=(12,))

    def __call__(self, tok0, pos0, rem, fin0, eos, temps, top_ps,
                 streams, lag, frontier, pt, kv_state):
        return self._jit([p._value for p in self._params], tok0, pos0,
                         rem, fin0, eos, temps, top_ps, streams, lag,
                         frontier, pt, kv_state)


class _CompiledVerifyStep(_CompiledStepBase):
    """The big model's speculative-verify executable: ONE ragged
    batched step over all S·(k+1) positions
    (`GPTGenerationMixin._paged_verify_fused`) with exact-match
    acceptance, EOS and budget masking in-executable. Built exactly
    like `_CompiledFusedStep` (weights as jit ARGUMENTS, the kv pytree
    — pools + scale planes + PRNG key — DONATED). k is baked into the
    flat geometry, so one engine holds ONE verify executable per (k,
    geometry); narrow
    windows (pool pressure / short budgets) ride the width/rem
    arguments instead of re-tracing."""

    def __init__(self, model, k, page_size):
        self._params = list(model.state_dict().values())
        self.k = int(k)
        ps = int(page_size)

        def pure(param_vals, tok0, pos0, drafts, width, rem, fin0, eos,
                 temps, top_ps, streams, gstate0, gtrans, gmask, pt,
                 kv_state):
            from ..autograd import engine as eng

            kv_vals, kv_scales, key = kv_state
            originals = [p._value for p in self._params]
            for p, v in zip(self._params, param_vals):
                p._value = v
            try:
                with eng.no_grad_guard(), self._counting_launches():
                    emits, new_kv, new_scales = model._paged_verify_fused(
                        self.k, ps, tok0, pos0, drafts, width, rem,
                        fin0, eos, temps, top_ps, streams, pt,
                        list(kv_vals),
                        list(kv_scales) if kv_scales else None, key,
                        gstate0=gstate0, gtrans=gtrans, gmask=gmask)
            finally:
                for p, v in zip(self._params, originals):
                    p._value = v
            return emits, (new_kv, new_scales, key)

        self._jit = jax.jit(pure, donate_argnums=(15,))

    def __call__(self, tok0, pos0, drafts, width, rem, fin0, eos, temps,
                 top_ps, streams, gstate0, gtrans, gmask, pt, kv_state):
        return self._jit([p._value for p in self._params], tok0, pos0,
                         drafts, width, rem, fin0, eos, temps, top_ps,
                         streams, gstate0, gtrans, gmask, pt, kv_state)


class SpeculativeDecoder:
    """The engine's speculative-decoding state and window orchestration
    (module docstring has the design). Owned by `LLMEngine` when
    `LLMEngineConfig(draft_model=...)` is set; `try_window(frontier)`
    is the spec sibling of `_try_step_fused`."""

    mode = "draft"   # vs the n-gram speculator's "ngram" (metrics split)

    def __init__(self, engine, draft_model, spec_k):
        from ..distributed import mesh as mesh_mod
        from ..quantization import runtime as _qrt

        draft_model.eval()
        big_cfg = engine.model.config
        dcfg = draft_model.config
        if dcfg.vocab_size != big_cfg.vocab_size:
            raise ValueError(
                f"draft_model vocab_size {dcfg.vocab_size} != target "
                f"{big_cfg.vocab_size}: speculative decoding needs a "
                "tied tokenizer (proposals are target token ids)")
        if dcfg.max_seq_len < engine.max_model_len:
            raise ValueError(
                f"draft_model max_seq_len {dcfg.max_seq_len} < engine "
                f"max_model_len {engine.max_model_len}: the draft must "
                "reach every position it proposes at")
        self.engine = engine
        self.draft = draft_model
        self.k = int(spec_k)
        if self.k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.k}")
        ps = engine.page_size
        num_pages = engine.pool.num_pages
        nh = dcfg.num_heads
        hd = dcfg.hidden_size // nh
        # draft pool mirrors the engine pool's geometry — SAME page ids
        # and page tables, its own buffers in the engine's kv dtype
        draft_dt, self._quantized = _qrt.resolve_kv_dtype(
            engine.kv_dtype, draft_model.compute_dtype())
        # packed int4 pools halve the stored head_dim (same shape
        # discriminator the engine pool uses)
        hd_store = hd // 2 if self._quantized == 4 else hd
        if self._quantized == 4 and hd % 2:
            raise ValueError(
                f"kv_dtype='int4': draft head_dim {hd} is odd — nibble "
                "packing pairs head_dim elements")
        sharding = mesh_mod.named_sharding()

        def _fresh_pools():
            pools = [
                jax.device_put(jnp.zeros((num_pages, ps, nh, hd_store),
                                         draft_dt), sharding)
                for _ in range(2 * dcfg.num_layers)]
            scales = []
            if self._quantized:
                sshape = _qrt.kv_scale_shape(num_pages, ps, nh)
                scales = [
                    jax.device_put(jnp.zeros(sshape, jnp.float32),
                                   sharding)
                    for _ in range(2 * dcfg.num_layers)]
            return pools, scales

        self._fresh_pools = _fresh_pools
        self._kv, self._kv_scales = _fresh_pools()
        # three executables, one each per (k, geometry): draft catch-up
        # prefill (flat tokens), draft propose (the PR-8 fused scan on
        # the draft model — the engine key couples its draws to the
        # target's), big verify (all k+1 positions, one dispatch)
        self._prefill_fn = _CompiledPagedStep(draft_model)
        self._propose_fn = _CompiledProposeStep(draft_model, self.k, ps)
        self._verify_fn = _CompiledVerifyStep(engine.model, self.k, ps)
        # catch-up geometry: its own flat budget (one executable) —
        # wide enough that a typical post-acceptance 1-token lag per
        # slot clears in one tick
        self._draft_T = max(engine.token_budget, engine.num_slots)
        self._stats = engine.stats
        for key in ("spec_windows", "spec_proposed", "spec_accepted"):
            self._stats.setdefault(key, 0)

    # ---- pool accounting ----

    def pool_bytes(self):
        """Draft-pool resident bytes (scale planes included) — added to
        the engine's `pool_bytes()`: a shared page costs big + draft."""
        return int(sum(int(a.nbytes) for a in self._kv)
                   + sum(int(s.nbytes) for s in self._kv_scales))

    def reset_pools(self):
        """abort_all path: the donated draft pytree may be consumed by
        a dispatch that died — re-zero (the engine re-creates the
        shared PRNG key via its own reseed)."""
        self._kv, self._kv_scales = self._fresh_pools()

    def release_pools(self):
        """Brownout L2 (fleet_serving.overload): drop the draft pool
        arrays — the HBM returns to the fleet NOW. pool_bytes() reads
        0 until `reset_pools` rebuilds; the engine parks this decoder
        while released, so no window can touch the empty lists."""
        self._kv, self._kv_scales = [], []

    # ---- draft catch-up ----

    def _catch_up(self, rows):
        """Replay tokens the draft pool is missing — down to a lag of
        at most ONE position per request — through the draft prefill
        executable, chunked to the flat budget. Covers initial prompt
        catch-up after admission and replay after preemption; the
        FINAL lagging position is deliberately left: the propose scan
        replays it in-dispatch (its lag/frontier mode), so the
        steady-state 1-token lag a fully-accepted window leaves never
        costs a catch-up tick here."""
        from ..distributed import mesh as mesh_mod

        eng = self.engine
        T = self._draft_T
        sharding = mesh_mod.named_sharding()
        while True:
            todo = [(slot, req) for slot, req in rows
                    if req.draft_prefilled < req.n_prefilled - 1]
            if not todo:
                return
            tok = np.zeros((T,), np.int32)
            pos = np.zeros((T,), np.int32)
            sid = np.zeros((T,), np.int32)
            klen = np.zeros((T,), np.int32)
            i = 0
            took = {}
            for slot, req in todo:
                take = min(req.n_prefilled - 1 - req.draft_prefilled,
                           T - i)
                for d in range(take):
                    p = req.draft_prefilled + d
                    tok[i] = req.tokens[p]
                    pos[i] = p
                    sid[i] = slot
                    klen[i] = p + 1
                    i += 1
                took[slot] = take
                if i == T:
                    break
            _, (self._kv, self._kv_scales, eng._key) = self._prefill_fn(
                tok, pos, jax.device_put(sid, sharding),
                eng._write_index(sid, pos, klen), eng._step_tables(),
                klen,
                jax.device_put(np.zeros((1,), np.int32), sharding),
                (self._kv, self._kv_scales, eng._key))
            eng._note_launches(self._prefill_fn)
            for slot, req in todo:
                req.draft_prefilled += took.get(slot, 0)

    # ---- the speculative window ----

    def try_window(self, frontier):
        """One speculative decode window over the frontier rows, or
        None when even the frontier token's page cannot be covered (the
        single-tick path takes the tick and owns preemption — same
        contract as `_try_step_fused`). Page capacity for positions
        pos0..pos0+width is reserved UP FRONT per row; pool pressure
        narrows a row's width (down to 0: verify-only plain decode for
        that row) instead of re-tracing anything."""
        eng = self.engine
        k = self.k
        S = eng.num_slots

        # reserve pages: verify writes positions pos0..pos0+width (the
        # propose scan writes a prefix of the same range in the
        # mirrored draft pool — one reservation covers both)
        # brownout spec_k cap: a narrower proposal rides the `wid`/`rem`
        # runtime arguments of the SAME k-scan — degrading never
        # recompiles (fleet_serving.overload, ladder L1)
        cap = eng._brownout.get("spec_k_cap")
        k_eff = k if cap is None else max(0, min(k, int(cap)))

        width = {}
        for slot, req in frontier:
            w = min(0 if req.spec_off else k_eff,
                    req.target - len(req.tokens))
            w = eng._grow(slot, req, w + 1) - 1
            if w < 0:
                return None   # frontier write itself has no page
            width[slot] = w

        # draft catch-up (prompt replay / post-acceptance lag)
        t_draft = _time.perf_counter()
        self._catch_up(frontier)

        tok0 = np.zeros((S,), np.int32)   # verify: the frontier token
        tok_p = np.zeros((S,), np.int32)  # propose: first scanned token
        pos0 = np.zeros((S,), np.int32)
        wid = np.zeros((S,), np.int32)
        rem = np.zeros((S,), np.int32)
        rem_p = np.zeros((S,), np.int32)
        lag = np.zeros((S,), np.int32)
        fin_v = np.ones((S,), bool)       # verify: dead slots
        fin_p = np.ones((S,), bool)       # propose: also width-0 rows
        eos = np.full((S,), -1, np.int32)
        temps = np.zeros((S,), np.float32)
        tops = np.ones((S,), np.float32)
        streams = np.zeros((S,), np.int32)
        gen_before = {}
        for slot, req in frontier:
            tok0[slot] = req.tokens[-1]
            pos0[slot] = req.n_prefilled
            wid[slot] = width[slot]
            rem[slot] = req.target - len(req.tokens)
            fin_v[slot] = False
            fin_p[slot] = width[slot] < 1
            if not fin_p[slot]:
                # after catch-up the draft lags by at most ONE row —
                # the propose scan replays it at iteration 0 (lag
                # mode), starting from the token BEFORE the frontier
                lag[slot] = req.n_prefilled - req.draft_prefilled
                tok_p[slot] = req.tokens[-1 - lag[slot]]
                rem_p[slot] = width[slot] + lag[slot]
            if req.eos is not None:
                eos[slot] = int(req.eos)
            temps[slot] = req.temperature
            tops[slot] = req.top_p
            streams[slot] = req.sample_stream
            gen_before[slot] = req.num_generated

        # structured decoding: arena DFA states + tables for the
        # verify's in-executable masking (the draft propose scan stays
        # unmasked — a grammar-illegal proposal simply fails
        # exact-match and truncates acceptance, losslessly)
        gst, gtrans, gmask = eng._grammar_args(frontier)

        t0 = _time.perf_counter()
        try:
            with _trace_span("llm_engine.spec_window", k=k,
                             live=len(frontier)):
                # draft propose: the PR-8 fused scan on the draft
                # model in propose mode, coupled to the engine key.
                # Proposals stay ON DEVICE into the verify call — the
                # window's single host sync is the verify emits below.
                d_emits, (self._kv, self._kv_scales, eng._key) = \
                    self._propose_fn(
                        tok_p, pos0, rem_p, fin_p, eos, temps, tops,
                        streams, lag, tok0, eng._step_tables(),
                        (self._kv, self._kv_scales, eng._key))
                # row s's proposals start after its lag replay:
                # drafts[s, j] = emits[lag_s + j, s] (device gather —
                # no host sync)
                idx = (jnp.asarray(lag)[None, :]
                       + jnp.arange(k, dtype=jnp.int32)[:, None])
                drafts = jnp.swapaxes(
                    jnp.take_along_axis(d_emits, idx, axis=0), 0, 1)
                # block on the proposals before stamping: dispatch is
                # ASYNC, so the enqueue time alone would report the
                # draft as nearly free while its real cost hid inside
                # the verify's host sync. The verify consumes `drafts`
                # anyway, so the wait moves, it isn't added.
                jax.block_until_ready(drafts)
                _SPEC_DRAFT_SECONDS.inc(
                    _time.perf_counter() - t_draft)
                emits, (eng._kv, eng._kv_scales, eng._key) = \
                    self._verify_fn(
                        tok0, pos0, drafts, wid, rem, fin_v, eos,
                        temps, tops, streams, gst, gtrans, gmask,
                        eng._step_tables(),
                        (eng._kv, eng._kv_scales, eng._key))
                emits = np.asarray(emits)  # [k+1, S]: the host sync
                # already materialized by the sync above — the host
                # copy feeds the exact accepted-token count below
                drafts_h = np.asarray(drafts)             # [S, k]
        except Exception as e:
            # the donated pytrees may be consumed mid-dispatch — same
            # recovery contract as the single tick and fused window
            eng.abort_all(e)
            raise
        eng.sched.note_boundary(_time.perf_counter() - t0)

        self._stats["steps"] += 1
        self._stats["spec_windows"] += 1
        eng._note_launches(self._propose_fn)
        eng._note_launches(self._verify_fn)
        self._stats["occupancy_sum"] += len(frontier) / S
        _STEPS_TOTAL.inc()
        _FUSED_STEPS.inc()
        _DISPATCHES.inc()

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
                    # host replay of the DFA advance (llm_engine keeps
                    # gstate a pure function of the emitted tokens)
                    req.gstate = req.grammar.advance(req.gstate, t)
                # exact accepted count: an emitted pick equals the
                # draft at its position IFF that draft was accepted
                # (a rejected position's pick differs by definition),
                # so this also counts rem-clamped windows and an
                # accepted draft EOS correctly — emitted-1 would not
                if j < k and t == int(drafts_h[slot, j]):
                    from_draft += 1
                emitted += 1
                if ((req.eos is not None and t == req.eos)
                        or len(req.tokens) >= req.target):
                    done = True
            # positional rollback: n_prefilled advances over exactly
            # the verified-correct rows; stale draft/verify rows past
            # it are masked by kv_len and overwritten later
            req.n_prefilled += emitted
            # draft validity: the propose scan wrote width rows
            # starting at pos0 — correct up to the accepted prefix
            if width[slot] >= 1:
                req.draft_prefilled = min(
                    pos0[slot] + width[slot], req.n_prefilled)
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
        self._stats["spec_proposed"] += proposed
        self._stats["spec_accepted"] += accepted
        eng.sched.note_spec_window(proposed, accepted)
        _SPEC_PROPOSED.inc(proposed)
        _SPEC_ACCEPTED.inc(accepted)
        n_prop = _SPEC_PROPOSED.value
        if n_prop:
            _SPEC_ACC_RATE.set(_SPEC_ACCEPTED.value / n_prop)
        _TOKENS_TOTAL.labels(phase="decode").inc(total)
        _TOK_PER_DISPATCH.set(total)
        _QUEUE_DEPTH.set(len(eng.waiting))
        eng._publish_load()
        return finished
