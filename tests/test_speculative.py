"""Speculative decoding (ISSUE 10): draft-model propose, one-dispatch
ragged verify in the fused decode executable.

The acceptance suite: LOSSLESS guarantees — greedy outputs
token-identical to the non-speculative engine at every spec_k (incl.
EOS mid-window, preemption at a boundary, prefix-cache on, int8 KV,
and a maximally-adversarial random draft that gets ~everything
rejected), sampled-path invariance to spec_k via the shared
(seed, stream, position) PRNG keying, draft-KV rollback correctness
after rejection — plus the CI probe: `{"executables": 1,
"verify_executables": 1}` zero-recompile after warmup, zero host
callbacks (PTL513) in the verify executable, and full donation of the
big kv pytree (`pt_step_donation_held{step="spec_verify"}`). The
PR-8-leftover ragged-window fallback (a straggler prefill row no
longer forces the whole engine onto single ticks) is pinned here for
BOTH the speculative and the fused engines.

Budget note: every spec engine compiles FOUR executables (big
single-tick, draft prefill, draft propose scan, big verify), so fast
cases share one tiny geometry and the widest sweeps carry `slow`.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.text.models import GPTForCausalLM
from paddle_tpu.text.models.gpt import GPTConfig, gpt_tiny

pytestmark = pytest.mark.serving


@pytest.fixture(autouse=True)
def _serial_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield


def _make_pair(seed=30, layers=4, draft_layers=1, damp=0.05):
    """A draft-FAVORABLE (target, draft) pair without training:
    the target's deep layers get their residual contributions damped,
    and the draft is the target's first `draft_layers` layers plus its
    embeddings/final-LN/head, copied weight-for-weight — an emulated
    distilled draft whose logits track the target's, so acceptance is
    a real measured quantity (the same construction the llm_serve spec
    bench arm uses)."""
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=2048, hidden_size=128, num_layers=layers,
                    num_heads=4, max_seq_len=256)
    big = GPTForCausalLM(cfg)
    big.eval()
    for layer in big.gpt.layers[draft_layers:]:
        for lin in (layer.proj, layer.fc2):
            lin.weight._value = lin.weight._value * damp
            if lin.bias is not None:
                lin.bias._value = lin.bias._value * damp
    dcfg = GPTConfig(vocab_size=2048, hidden_size=128,
                     num_layers=draft_layers, num_heads=4,
                     max_seq_len=256)
    draft = GPTForCausalLM(dcfg)
    draft.eval()
    bsd = big.state_dict()
    for k, p in draft.state_dict().items():
        p._value = bsd[k]._value
    return cfg, big, draft


@pytest.fixture(scope="module")
def pair():
    return _make_pair()


@pytest.fixture(scope="module")
def rand_draft():
    """An UNRELATED random draft — the adversarial case: near-zero
    acceptance, so every window exercises rejection + rollback, and
    the lossless contract must carry the whole load."""
    paddle.seed(99)
    draft = GPTForCausalLM(gpt_tiny())
    draft.eval()
    return draft


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, 2048, (L,)) for L in (5, 13, 8)]


MAX_NEW = 24


def _drain(eng, cap=800):
    steps = 0
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
        steps += 1
        assert steps < cap, "engine failed to drain (livelock?)"


def _serve(model, prompts, *, max_new=MAX_NEW, temperature=0.0,
           eos=None, **cfg_kw):
    cfg_kw.setdefault("num_slots", 3)
    cfg_kw.setdefault("page_size", 16)
    cfg_kw.setdefault("token_budget", 8)
    cfg_kw.setdefault("max_model_len", 64)
    eng = LLMEngine(model, LLMEngineConfig(**cfg_kw))
    reqs = [eng.add_request(p, max_new_tokens=max_new, eos_token_id=eos,
                            temperature=temperature) for p in prompts]
    _drain(eng)
    if eng.prefix_cache is None:
        assert eng.pool.num_live == 0
    return [r.future.result(timeout=0) for r in reqs], eng


@pytest.fixture(scope="module")
def k1_greedy(pair, prompts):
    """The non-speculative engine's outputs — the identity baseline
    (itself pinned against generate() in test_llm_engine)."""
    _, big, _ = pair
    outs, _ = _serve(big, prompts, decode_k=1)
    return outs


# --------------------------------------------------------------------
# lossless greedy identity
# --------------------------------------------------------------------

@pytest.mark.parametrize("k", [2, 4])
def test_spec_greedy_token_identical(pair, prompts, k1_greedy, k):
    _, big, draft = pair
    outs, eng = _serve(big, prompts, draft_model=draft, spec_k=k)
    for ref, got in zip(k1_greedy, outs):
        np.testing.assert_array_equal(got, ref)
    # the windows actually ran speculative — and the favorable pair
    # actually accepted drafts (this test must not pass by rejecting
    # everything into de-facto 1-token decode)
    assert eng.stats["spec_windows"] > 0
    assert eng.stats["spec_accepted"] > 0
    assert eng.stats["steps"] > eng.stats["spec_windows"]  # prefill ticks


def test_spec_greedy_identical_random_draft(pair, prompts, k1_greedy,
                                            rand_draft):
    """Adversarial draft: a random unrelated model proposes garbage,
    ~every draft is rejected, every window rolls back — outputs must
    STILL be token-identical (the lossless guarantee does all the
    work) and every window must still emit its one target pick."""
    _, big, _ = pair
    outs, eng = _serve(big, prompts, draft_model=rand_draft, spec_k=4)
    for ref, got in zip(k1_greedy, outs):
        np.testing.assert_array_equal(got, ref)
    assert eng.stats["spec_windows"] > 0
    assert eng.stats["spec_proposed"] > 0
    # near-total rejection (random 2048-vocab argmax agreement)
    assert eng.stats["spec_accepted"] < eng.stats["spec_proposed"] / 4


def test_spec_eos_mid_window(pair, prompts, k1_greedy):
    """A row whose eos lands mid-window must stop exactly where the
    non-speculative engine stops: in-executable masking keeps the eos
    and suppresses every later pick of the window."""
    _, big, draft = pair
    ref0 = k1_greedy[0]
    plen = len(prompts[0])
    eos = int(ref0[plen + 1])   # generated index 1: mid-window at k=4
    ref_outs, _ = _serve(big, prompts, decode_k=1, eos=eos)
    outs, eng = _serve(big, prompts, draft_model=draft, spec_k=4,
                       eos=eos)
    assert eng.stats["spec_windows"] > 0
    for ref, got in zip(ref_outs, outs):
        np.testing.assert_array_equal(got, ref)
    assert len(outs[0]) == plen + 2 and outs[0][-1] == eos


def test_spec_preemption_at_boundary(pair):
    """Tight pool: window reservations spill, and when even the
    frontier write has no page the single-tick path takes the tick and
    preempts at the BOUNDARY — greedy outputs must not notice."""
    cfg, big, draft = pair
    rng = np.random.default_rng(7)
    prompts4 = [rng.integers(0, cfg.vocab_size, (20,)) for _ in range(4)]
    ref, _ = _serve(big, prompts4, max_new=20, decode_k=1,
                    num_slots=3, num_pages=6, max_model_len=48)
    outs, eng = _serve(big, prompts4, max_new=20, draft_model=draft,
                       spec_k=2, num_slots=3, num_pages=6,
                       max_model_len=48)
    assert eng.stats["preemptions"] > 0, "pool was not tight enough"
    assert eng.stats["spec_windows"] > 0
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(b, a)


def test_spec_with_prefix_cache(pair):
    """Radix prefix cache + speculative windows: wave 2 maps the
    shared system prefix read-only (a real trie hit) — and because the
    draft pool mirrors page ids, the publisher's own catch-up already
    wrote the shared pages' draft rows. Greedy outputs identical to
    the uncached non-speculative engine."""
    cfg, big, draft = pair
    rng = np.random.default_rng(11)
    sys_prompt = rng.integers(0, cfg.vocab_size, (16,))
    shared = [np.concatenate([sys_prompt,
                              rng.integers(0, cfg.vocab_size, (L,))])
              for L in (4, 9, 6)]
    ref, _ = _serve(big, shared[:1], max_new=8, decode_k=1)
    ref2, _ = _serve(big, shared[1:], max_new=8, decode_k=1)
    eng = LLMEngine(big, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64,
        draft_model=draft, spec_k=4, prefix_cache=True))
    r0 = eng.add_request(shared[0], max_new_tokens=8)
    _drain(eng)   # wave 1 publishes the 16-token system prefix
    wave2 = [eng.add_request(p, max_new_tokens=8) for p in shared[1:]]
    _drain(eng)
    assert eng.stats["spec_windows"] > 0
    assert eng.prefix_cache.snapshot()["hits"] > 0
    np.testing.assert_array_equal(r0.future.result(timeout=0), ref[0])
    for a, r in zip(ref2, wave2):
        np.testing.assert_array_equal(r.future.result(timeout=0), a)
    eng.close()
    assert eng.pool.num_live == 0


@pytest.mark.slow
@pytest.mark.quant
def test_spec_int8_kv(pair, prompts):
    """int8 KV pools under speculation: BOTH pools (big + mirrored
    draft) quantize with per-row scale planes in their donated
    pytrees; greedy outputs identical to the int8 non-speculative
    engine (int8-vs-fp32 drift is the quant suite's contract)."""
    _, big, draft = pair
    ref, _ = _serve(big, prompts, decode_k=1, kv_dtype="int8")
    outs, eng = _serve(big, prompts, draft_model=draft, spec_k=4,
                       kv_dtype="int8")
    assert eng.stats["spec_windows"] > 0
    assert eng._spec._quantized
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(b, a)


# --------------------------------------------------------------------
# sampled-path invariance
# --------------------------------------------------------------------

def test_spec_sampled_invariant_to_k(pair, prompts):
    """Sampled draws key on (engine seed, stream, position) only, so
    the verify's exact-match acceptance reproduces the k=1 host-path
    continuation at EVERY spec_k — and the draft, coupled to the same
    key, agrees far more often than argmax would (the Gumbel noise is
    shared). A different engine seed must change the outputs."""
    _, big, draft = pair

    def sample(seed, **kw):
        outs, eng = _serve(big, prompts, temperature=0.8, seed=seed,
                           **kw)
        return outs, eng

    base, _ = sample(7, decode_k=1)     # host sample_tokens path
    s2, _ = sample(7, draft_model=draft, spec_k=2)
    s4, e4 = sample(7, draft_model=draft, spec_k=4)
    for a, b, c in zip(base, s2, s4):
        np.testing.assert_array_equal(b, a)
        np.testing.assert_array_equal(c, a)
    # coupled sampling really accepted (shared Gumbel noise)
    assert e4.stats["spec_accepted"] > 0
    # sampling actually happened, and the seed matters
    greedy, _ = _serve(big, prompts, decode_k=1)
    assert any(not np.array_equal(a, g) for a, g in zip(base, greedy))
    other, _ = sample(8, draft_model=draft, spec_k=4)
    assert any(not np.array_equal(a, b) for a, b in zip(s4, other))


# --------------------------------------------------------------------
# draft-KV rollback
# --------------------------------------------------------------------

def test_spec_draft_rollback_after_rejection(pair, prompts, rand_draft,
                                             k1_greedy):
    """Rollback is positional: after a rejection the draft pool's
    valid prefix must never claim rows past the verified frontier, and
    the next window's catch-up must re-write from there. Driven with
    the random draft (maximal rejection) and checked invariant-by-step;
    the greedy output staying identical proves the rewritten rows are
    the right ones."""
    _, big, _ = pair
    eng = LLMEngine(big, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64,
        draft_model=rand_draft, spec_k=4))
    reqs = [eng.add_request(p, max_new_tokens=MAX_NEW) for p in prompts]
    steps = 0
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
        for r in eng._slots:
            if r is None:
                continue
            # the draft prefix may lag (catch-up pending) but may
            # NEVER run ahead of the big pool's verified rows
            assert 0 <= r.draft_prefilled <= r.n_prefilled, (
                r.draft_prefilled, r.n_prefilled)
        steps += 1
        assert steps < 800
    assert eng.stats["spec_accepted"] < eng.stats["spec_proposed"]
    for ref, r in zip(k1_greedy, reqs):
        np.testing.assert_array_equal(r.future.result(timeout=0), ref)


def test_spec_abort_recovery(pair, prompts):
    """abort_all() re-zeros BOTH donated pool pytrees (big + draft)
    and recreates the shared PRNG key — a recovered engine must serve
    identically to a fresh-history engine."""
    _, big, draft = pair
    eng = LLMEngine(big, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64,
        draft_model=draft, spec_k=2, seed=7))
    doomed = eng.add_request(prompts[0], max_new_tokens=8)
    eng.step()
    eng.abort_all(RuntimeError("injected device error"))
    with pytest.raises(RuntimeError, match="injected"):
        doomed.future.result(timeout=0)
    reqs = [eng.add_request(p, max_new_tokens=12) for p in prompts]
    _drain(eng)
    ref, _ = _serve(big, prompts, max_new=12, decode_k=1)
    for a, r in zip(ref, reqs):
        np.testing.assert_array_equal(r.future.result(timeout=0), a)


# --------------------------------------------------------------------
# ragged windows (the PR-8 leftover): stragglers don't stall decode
# --------------------------------------------------------------------

def _serve_with_straggler(model, prompts, long_prompt, **cfg_kw):
    """Two short requests decode; a long prompt is admitted mid-run and
    needs several chunked-prefill ticks at token_budget 6. Counts the
    multi-token windows that ran while the straggler was still
    prefilling."""
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=6, max_model_len=64,
        **cfg_kw))
    reqs = [eng.add_request(p, max_new_tokens=20) for p in prompts[:2]]
    for _ in range(6):   # let the two reach their decode frontier
        eng.step()
    reqs.append(eng.add_request(long_prompt, max_new_tokens=10))
    ragged = 0
    steps = 0
    while eng.has_work():
        w0 = (eng.stats.get("spec_windows", 0)
              + eng.stats["fused_steps"])
        eng.step()
        w1 = (eng.stats.get("spec_windows", 0)
              + eng.stats["fused_steps"])
        still_prefilling = any(
            r is not None and r.n_prefilled < len(r.tokens) - 1
            for r in eng._slots)
        if w1 > w0 and still_prefilling:
            ragged += 1
        steps += 1
        assert steps < 800
    return [r.future.result(timeout=0) for r in reqs], eng, ragged


@pytest.mark.parametrize("mode", ["spec", "fused"])
def test_ragged_window_straggler(pair, prompts, mode):
    cfg, big, draft = pair
    rng = np.random.default_rng(17)
    long_prompt = rng.integers(0, cfg.vocab_size, (40,))
    ref, _, _ = _serve_with_straggler(big, prompts, long_prompt,
                                      decode_k=1)
    kw = ({"draft_model": draft, "spec_k": 4} if mode == "spec"
          else {"decode_k": 4})
    outs, eng, ragged = _serve_with_straggler(big, prompts, long_prompt,
                                              **kw)
    # windows kept running WHILE the straggler chunk-prefilled — the
    # pre-fix engine forced every one of those ticks to single steps
    assert ragged > 0, "no ragged window ran"
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(b, a)


# --------------------------------------------------------------------
# CI contract: zero host callbacks, donation, zero recompiles
# --------------------------------------------------------------------

def test_spec_zero_host_callbacks_donation_and_recompile_probe(
        pair, prompts):
    """The ISSUE-10 CI assertion, one engine end-to-end: (1) the
    verify executable has ZERO host callbacks (PTL513) and every leaf
    of the big kv pytree — pools AND the PRNG key — donated
    (pt_step_donation_held{step="spec_verify"}); (2) reseed() swaps
    the key without recompiling ANY of the four executables; (3)
    steady-state speculative serving holds exactly
    {"executables": 1, "verify_executables": 1}."""
    from paddle_tpu import analysis
    from paddle_tpu.jit import _DONATION_HELD

    _, big, draft = pair
    outs, eng = _serve(big, prompts, draft_model=draft, spec_k=4)
    stats = eng.compile_stats(check_donation=True)
    assert stats["executables"] == 1
    assert stats["verify_executables"] == 1
    assert stats["donation"]["held"], stats["donation"]
    assert stats["verify"]["donation"]["held"], stats["verify"]
    assert stats["verify"]["host_calls"] == {}, stats["verify"]
    # BOTH kv pytrees of the speculative contract: the draft propose
    # scan's pools + shared key alias too (a silent drop there would
    # copy the whole draft pool every window)
    assert stats["propose"]["donation"]["held"], stats["propose"]
    assert stats["propose"]["host_calls"] == {}, stats["propose"]
    assert _DONATION_HELD.labels(step="spec_verify").value == 1.0
    assert _DONATION_HELD.labels(step="spec_propose").value == 1.0
    rep = analysis.analyze_step(eng, which="verify")
    assert rep.kind == "SpecVerify"
    assert rep.host_calls == {}
    assert rep.donation["aliased"] == rep.donation["expected"] > 0
    prep = analysis.analyze_step(eng, which="propose")
    assert prep.kind == "SpecPropose"
    assert prep.donation["aliased"] == prep.donation["expected"] > 0
    # reseed + sampled traffic: same executables — the key is a step
    # ARGUMENT of every dispatch in the speculative pipeline
    eng.reseed(123)
    rng = np.random.default_rng(13)
    for L in (3, 17, 9):
        eng.add_request(rng.integers(0, 2048, (L,)), max_new_tokens=6,
                        temperature=0.5)
    _drain(eng)
    after = eng.compile_stats()
    assert after == {"executables": 1, "verify_executables": 1}, after
    # the draft-side executables are zero-recompile too
    assert eng._spec._prefill_fn.cache_size() in (1, -1)
    assert eng._spec._propose_fn.cache_size() in (1, -1)


def test_spec_config_validation(pair, rand_draft):
    _, big, draft = pair
    with pytest.raises(ValueError, match="spec_k"):
        LLMEngineConfig(spec_k=0)
    # vocab mismatch: speculative decoding needs a tied tokenizer
    paddle.seed(1)
    other = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=64, num_layers=1, num_heads=2,
        max_seq_len=256))
    with pytest.raises(ValueError, match="vocab"):
        LLMEngine(big, LLMEngineConfig(
            num_slots=2, page_size=16, max_model_len=64,
            draft_model=other))
    # draft must reach every position it proposes at
    paddle.seed(2)
    short = GPTForCausalLM(GPTConfig(
        vocab_size=2048, hidden_size=64, num_layers=1, num_heads=2,
        max_seq_len=32))
    with pytest.raises(ValueError, match="max_seq_len"):
        LLMEngine(big, LLMEngineConfig(
            num_slots=2, page_size=16, max_model_len=64,
            draft_model=short))


def test_spec_k_env_default(monkeypatch):
    monkeypatch.setenv("PT_SPEC_K", "6")
    assert LLMEngineConfig().spec_k == 6
    monkeypatch.delenv("PT_SPEC_K")
    assert LLMEngineConfig().spec_k == 4


def test_spec_metrics_surface(pair, prompts):
    _, big, draft = pair
    outs, eng = _serve(big, prompts, draft_model=draft, spec_k=2)
    m = eng.metrics()
    spec = m["spec"]
    assert spec["spec_k"] == 2
    assert spec["windows"] == eng.stats["spec_windows"] > 0
    assert spec["proposed"] >= spec["accepted"] >= 0
    assert spec["draft_pool_bytes"] > 0
    # the draft pool is part of the engine's true KV footprint
    assert m["kv_pool_bytes"] > spec["draft_pool_bytes"]
    # scheduler snapshot carries the window accounting
    assert eng.sched.snapshot()["spec_proposed"] == \
        eng.stats["spec_proposed"]
    # non-speculative engines report None
    m1 = LLMEngine(big, LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=64)).metrics()
    assert m1["spec"] is None


# --------------------------------------------------------------------
# kernels: blocked-verify Pallas parity + jnp grid hint
# --------------------------------------------------------------------

# the page grid's geometry (head_dim 64), and the decode cell's: 16
# heads × 128, where the float pool walks its pages in the kernel and
# multiplies on the MXU, the int8 pool keeps the page grid
@pytest.mark.parametrize("P,H,D", [(8, 4, 64), (16, 16, 128)])
def test_qblock_pallas_parity_interpret(P, H, D):
    """The query-blocked Pallas kernel (one DMA of each page per slot
    BLOCK instead of per row) must match the per-token kernel on
    verify-shaped ragged inputs — float and int8, with and without the
    frontier offset — including the all-masked-row edge (a row whose
    pages run only because a longer sibling row needs them)."""
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        ragged_paged_attention)

    rng = np.random.default_rng(0)
    S, MP, N = 3, 4, 13
    k = 3
    Q = k + 1
    T = S * Q
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    kp = rng.standard_normal((N, P, H, D)).astype(np.float32)
    vp = rng.standard_normal((N, P, H, D)).astype(np.float32)
    pt = rng.integers(1, N, (S, MP)).astype(np.int32)
    sid = np.repeat(np.arange(S, dtype=np.int32), Q)
    lens = np.zeros((T,), np.int32)
    pos0, width = [5, 11, 0], [3, 2, -1]   # slot 2 dead, slot 1 narrow
    for s in range(S):
        for j in range(Q):
            if width[s] >= 0 and j <= width[s]:
                lens[s * Q + j] = pos0[s] + j + 1
    ref = ragged_paged_attention(q, kp, vp, pt, sid, lens,
                                 interpret=True)
    blk = ragged_paged_attention(q, kp, vp, pt, sid, lens,
                                 q_per_slot=Q, interpret=True)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    ks = rng.uniform(0.01, 0.1, (N, P, H)).astype(np.float32)
    vs = rng.uniform(0.01, 0.1, (N, P, H)).astype(np.float32)
    kq = rng.integers(-127, 127, (N, P, H, D)).astype(np.int8)
    vq = rng.integers(-127, 127, (N, P, H, D)).astype(np.int8)
    r8 = ragged_paged_attention(q, kq, vq, pt, sid, lens, k_scales=ks,
                                v_scales=vs, interpret=True)
    b8 = ragged_paged_attention(q, kq, vq, pt, sid, lens, k_scales=ks,
                                v_scales=vs, q_per_slot=Q,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(b8), np.asarray(r8),
                               rtol=2e-5, atol=2e-5)
    base = np.maximum(lens - 2, 0)
    ro = ragged_paged_attention(q, kp, vp, pt, sid, base,
                                frontier_offset=2, interpret=True)
    bo = ragged_paged_attention(q, kp, vp, pt, sid, base,
                                frontier_offset=2, q_per_slot=Q,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(bo), np.asarray(ro),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_grid_hint_parity():
    """The jnp path's max_tokens_per_slot hint shrinks the slot grid
    [S, C]; outputs must be bitwise-identical to the unhinted call on
    the verify layout."""
    import paddle_tpu  # noqa: F401  (Tensor registry)
    from paddle_tpu import to_tensor
    from paddle_tpu.nn import functional as F

    rng = np.random.default_rng(3)
    S, MP, N, P, H, D = 3, 4, 9, 8, 2, 16
    Q = 4
    T = S * Q
    q = to_tensor(rng.standard_normal((T, H, D)).astype(np.float32))
    kp = to_tensor(rng.standard_normal((N, P, H, D)).astype(np.float32))
    vp = to_tensor(rng.standard_normal((N, P, H, D)).astype(np.float32))
    pt = to_tensor(rng.integers(1, N, (S, MP)).astype(np.int32))
    sid = to_tensor(np.repeat(np.arange(S, dtype=np.int32), Q))
    lens = np.zeros((T,), np.int32)
    for s in range(S):
        for j in range(Q):
            lens[s * Q + j] = 3 + 2 * s + j + 1
    lens = to_tensor(lens)
    ref = F.paged_attention(q, kp, vp, pt, sid, lens)
    hinted = F.paged_attention(q, kp, vp, pt, sid, lens,
                               max_tokens_per_slot=Q)
    np.testing.assert_array_equal(np.asarray(hinted.numpy()),
                                  np.asarray(ref.numpy()))
