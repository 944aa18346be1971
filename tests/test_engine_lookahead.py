"""One step ahead: `LLMEngine.step` dispatches step n + 1 before it
reads step n (docs/SERVING.md "One step ahead"). What a client gets must
not change: the same tokens as the serial order (every step read before
the next is planned, which the engine keeps where a plan needs the
values), greedy, sampled, and stopped by a stop token inside a window;
the pools consistent after every step and empty at the end; and a drain
wherever a plan needs the values of the step before.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.llm_engine import (_IN_FLIGHT, LLMEngine,
                                          LLMEngineConfig)
from paddle_tpu.text.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.text.models.gpt import GPTConfig
from paddle_tpu.text.models.laguna import LagunaForCausalLM, laguna_tiny
from paddle_tpu.text.models.ling_hybrid import (LingHybridForCausalLM,
                                                ling_hybrid_tiny)
from paddle_tpu.text.models.sarvam_mla import (SarvamMLAForCausalLM,
                                               sarvam_mla_tiny)

pytestmark = pytest.mark.serving

K = 4


@pytest.fixture(autouse=True)
def _serial_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield


_FAMILIES = {
    "gpt": lambda: GPTForCausalLM(gpt_tiny()),
    "laguna": lambda: LagunaForCausalLM(laguna_tiny()),
    "sarvam_mla": lambda: SarvamMLAForCausalLM(sarvam_mla_tiny()),
    "ling_hybrid": lambda: LingHybridForCausalLM(ling_hybrid_tiny()),
}


def _model(family):
    paddle.seed(43)
    model = _FAMILIES[family]()
    model.eval()
    return model


def _serve(model, requests, serial=False, **kw):
    """Serve `requests` [(prompt, add_request keywords)] to the end;
    every pool consistent after each step, and a request's count of
    generated tokens holds only tokens the host has read. `serial=True`
    reads every step before the next is planned (the order a plan that
    needs the values keeps). Returns (engine, each request's tokens)."""
    args = dict(num_slots=3, page_size=16, max_model_len=128,
                token_budget=16, kv_dtype="float32", decode_k=K)
    args.update(kw)
    eng = LLMEngine(model, LLMEngineConfig(**args))
    if serial:
        eng._needs_values = lambda: True
    reqs = [eng.add_request(p, **r) for p, r in requests]
    steps = 0
    while eng.has_work():
        eng.step()
        for c in eng._caches:
            c.pool.assert_consistent()
        for r in reqs:
            assert _IN_FLIGHT not in r.tokens[:r.prompt_len
                                              + r.num_generated]
        steps += 1
        assert steps < 500
    for c in eng._caches:
        assert c.pool.num_live == 0
    return eng, [[int(t) for t in r.future.result(timeout=0)]
                 for r in reqs]


def _assert_counted(eng, overlapped):
    st = eng.stats
    assert st["steps_overlapped"] + st["steps_drained"] == st["step_calls"]
    assert (st["steps_overlapped"] > 0) == overlapped, dict(st)


def _stop_inside_a_window(tokens, prompt_len):
    """A token that first appears at a generated index that a window
    carries and that is not its window's last (the first generated
    token comes from the tick, windows of K after it)."""
    gen = tokens[prompt_len:]
    for j in range(2, len(gen)):
        if j % K and gen[j] not in gen[:j]:
            return gen[j], j
    raise AssertionError(f"no stop token inside a window in {gen}")


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_one_step_ahead_serves_the_serial_orders_tokens(family):
    """Five requests through three slots (slots refilled): greedy rows,
    sampled rows, and a row that its stop token ends inside a window,
    one step ahead against the serial order without the stop token."""
    model = _model(family)
    rng = np.random.default_rng(7)
    vocab = model.config.vocab_size
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in (9, 21, 5, 30, 14)]
    sampled = dict(temperature=0.8, top_p=0.9)
    kws = [dict(max_new_tokens=11), dict(max_new_tokens=10, **sampled),
           dict(max_new_tokens=14), dict(max_new_tokens=7),
           dict(max_new_tokens=9, **sampled)]
    requests = list(zip(prompts, kws))
    serial, want = _serve(model, requests, serial=True)
    _assert_counted(serial, overlapped=False)
    eos, j = _stop_inside_a_window(want[2], len(prompts[2]))
    requests[2] = (prompts[2], dict(kws[2], eos_token_id=eos))
    eng, got = _serve(model, requests)
    want[2] = want[2][:len(prompts[2]) + j + 1]
    assert got == want
    _assert_counted(eng, overlapped=True)
    assert eng.stats["finished"] == len(requests)


class _LateWindow:
    """Stands in for the engine's window program: each window first
    waits for a slow computation on the device, so it runs well after
    its dispatch has returned and the host has moved on."""

    def __init__(self, fn):
        import jax
        import jax.numpy as jnp

        big = jnp.asarray(np.random.default_rng(0).random((768, 768)),
                          jnp.float32) / 768

        def slow(tok0):
            x = big
            for _ in range(24):
                x = jnp.tanh(x @ big)
            return tok0 + (x.sum() * 0).astype(tok0.dtype)

        self.fn, self.slow = fn, jax.jit(slow)

    def __getattr__(self, name):
        return getattr(self.fn, name)

    def __call__(self, tok0, *args):
        return self.fn(self.slow(tok0), *args)


def test_page_tables_that_grow_in_flight_serve_generates_tokens():
    """Pages of four tokens, so a window's rows take new pages while
    the window before is still on the device, prompts chunked beside
    them, and requests that leave their slots (their tables zeroed)
    while their last window is yet to run: each program must run on the
    tables it was dispatched with, whatever the host did to them since.
    Greedy tokens against `generate()`; the small programs that carry
    tokens on the device compile once."""
    model = _model("gpt")
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 2048, (n,)) for n in (23, 3, 17, 9, 12)]
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=3, page_size=4, token_budget=8, max_model_len=64,
        decode_k=K))
    eng._fused_fn = _LateWindow(eng._ensure_fused())
    warm = eng.add_request(prompts[0], max_new_tokens=9)
    while eng.has_work():
        eng.step()
    compiled = (eng._carry_tokens._cache_size(),
                eng._merge_tokens._cache_size())
    reqs = [eng.add_request(p, max_new_tokens=9) for p in prompts]
    while eng.has_work():
        eng.step()
        eng.pool.assert_consistent()
    assert eng.pool.num_live == 0
    assert (eng._carry_tokens._cache_size(),
            eng._merge_tokens._cache_size()) == compiled
    for p, r in zip([prompts[0]] + prompts, [warm] + reqs):
        ref = model.generate(paddle.to_tensor(p[None].astype(np.int64)),
                             max_new_tokens=9).numpy()[0]
        np.testing.assert_array_equal(r.future.result(timeout=0), ref)
    _assert_counted(eng, overlapped=True)


def _gpt_requests(vocab, n=4, new=10):
    rng = np.random.default_rng(5)
    return [(rng.integers(1, vocab, (L,)), dict(max_new_tokens=new))
            for L in (7, 19, 4, 12)[:n]]


def test_a_pool_that_preempts_drains_and_serves_the_same_tokens():
    model = _model("gpt")
    requests = _gpt_requests(2048, new=24)
    roomy, want = _serve(model, requests, num_pages=40)
    tight, got = _serve(model, requests, num_pages=6)
    assert roomy.stats["preemptions"] == 0 < tight.stats["preemptions"]
    assert got == want
    _assert_counted(tight, overlapped=True)
    # beyond the first step: a window the pool cannot cover, and a
    # preempted request whose tokens were on the device, read first
    assert tight.stats["steps_drained"] > 1


def test_a_draft_model_drains_every_step_and_serves_the_same_tokens():
    from paddle_tpu.text.models.gpt import GPTConfig as _Cfg

    model = _model("gpt")
    draft = GPTForCausalLM(_Cfg(vocab_size=2048, hidden_size=128,
                                num_layers=1, num_heads=4, max_seq_len=256))
    src = model.state_dict()
    for k, p in draft.state_dict().items():
        p._value = src[k]._value
    draft.eval()
    requests = _gpt_requests(2048)
    plain, want = _serve(model, requests)
    spec, got = _serve(model, requests, draft_model=draft, spec_k=3)
    assert got == want
    _assert_counted(plain, overlapped=True)
    _assert_counted(spec, overlapped=False)


# token i > 0 is chr(31 + i); token 0 the stop token
_TOKS = [""] + [chr(c) for c in range(32, 127)]


def test_a_grammar_row_drains_while_it_runs():
    paddle.seed(30)
    model = GPTForCausalLM(GPTConfig(vocab_size=len(_TOKS), hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     max_seq_len=128))
    model.eval()
    plain_rows = _gpt_requests(len(_TOKS), n=2, new=6)
    _eng, want = _serve(model, plain_rows, token_strs=_TOKS)
    grammar = (np.arange(1, 6), dict(max_new_tokens=20, eos_token_id=0,
                                     grammar="[a-z]{12}"))
    eng, got = _serve(model, [grammar] + plain_rows, token_strs=_TOKS)
    text = "".join(_TOKS[t] for t in got[0][5:] if t)
    assert len(text) == 12 and text.isalpha() and text.islower()
    assert got[1:] == want
    # the grammar row outlives the others: no step ran ahead
    _assert_counted(eng, overlapped=False)


class _Unreadable:
    """A device result whose read raises: JAX raises a device error at
    the read, not at the call."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device lost at the read")


def test_a_read_that_raises_fails_every_future_and_the_engine_serves_on():
    """Direct drive: the window in flight raises at its read. The error
    leaves step(), every request fails (those that the step dispatched
    just before took out of their slots at their lengths too), the
    pools are empty, and the engine serves the next request as
    `generate()` does."""
    model = _model("gpt")
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=4, page_size=16, max_model_len=128, token_budget=16,
        decode_k=K))
    rng = np.random.default_rng(3)
    reqs = [eng.add_request(rng.integers(1, 2048, (L,)), max_new_tokens=n)
            for L, n in ((5, 1 + 2 * K), (6, 16), (4, 16))]
    eng.step()                       # the tick: prompts and first tokens
    eng.step()                       # a window, the tick read
    (window,) = eng._flight
    window.out = _Unreadable()
    # its only token comes from the straggler tick beside the next window
    reqs.append(eng.add_request(rng.integers(1, 2048, (3,)),
                                max_new_tokens=1))
    with pytest.raises(RuntimeError, match="lost at the read"):
        eng.step()                   # the first request's last window
    for r in reqs:
        assert isinstance(r.future.exception(timeout=0), RuntimeError)
    assert not eng.has_work()
    eng.pool.assert_consistent()
    assert eng.pool.num_live == 0
    prompt = rng.integers(1, 2048, (7,))
    req = eng.add_request(prompt, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    ref = model.generate(paddle.to_tensor(prompt[None].astype(np.int64)),
                         max_new_tokens=6).numpy()[0]
    np.testing.assert_array_equal(req.future.result(timeout=0), ref)
