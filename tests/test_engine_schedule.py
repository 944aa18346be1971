"""The engine's schedule against a record: which request sits in which
slot, how far it is prefilled and how many pages of every cache kind it
holds, step boundary by step boundary, for a tiny GPT (one kind) and a
tiny Laguna (a full and a window pool).

`tests/golden/engine_schedule.json` was recorded at commit ed35358 (PR
29), BEFORE the page bookkeeping of the first cache kind moved into
`_CacheKindState`, by

    JAX_PLATFORMS=cpu python tests/test_engine_schedule.py --record

which runs this module's one test under pytest (so under `conftest.py`'s
devices and precision) and writes what it would otherwise compare. A PR
that means to move the schedule records again and says so; a PR that
does not (a refactoring of the bookkeeping, a kernel, a step program)
passes unedited. Physical page ids are never compared: only counts, the
first LOGICAL page held, and the tokens served.
"""
import itertools
import json
import os
import sys

import pytest


class _Record:
    """The plugin `--record` hands to pytest: the test writes."""

    def pytest_configure(self, config):
        config.record_engine_schedule = True


if __name__ == "__main__":
    # before anything imports jax: pytest loads `conftest.py` first
    assert sys.argv[1:] == ["--record"], __doc__
    sys.exit(pytest.main([__file__, "-q", "-p", "no:cacheprovider"],
                         plugins=[_Record()]))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.text.models import GPTForCausalLM, gpt_tiny
from paddle_tpu.text.models.gpt import GPTConfig
from paddle_tpu.text.models import laguna
from paddle_tpu.text.models.laguna import LagunaForCausalLM, laguna_tiny

pytestmark = pytest.mark.serving

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "engine_schedule.json")


@pytest.fixture(autouse=True)
def _serial_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    yield


_MODELS = {}


def _model(family):
    """One model a family for the whole module (weights from the seed)."""
    if family not in _MODELS:
        paddle.seed(41)
        if family == "gpt":
            _MODELS[family] = GPTForCausalLM(gpt_tiny())
        elif family == "chain":
            # the blocks damped: the next token follows mostly from the
            # last one, so a run seen before is proposed AND accepted
            model = _MODELS[family] = GPTForCausalLM(gpt_tiny())
            for layer in model.gpt.layers:
                for lin in (layer.proj, layer.fc2):
                    lin.weight._value = lin.weight._value * 0.05
                    lin.bias._value = lin.bias._value * 0.05
        elif family == "draft":
            # the target's first layer with its embeddings, final norm
            # and head: proposals that are sometimes right
            big = _model("gpt")
            draft = GPTForCausalLM(GPTConfig(
                vocab_size=2048, hidden_size=128, num_layers=1,
                num_heads=4, max_seq_len=256))
            src = big.state_dict()
            for k, p in draft.state_dict().items():
                p._value = src[k]._value
            _MODELS[family] = draft
        else:
            # its leaves are keyed by a process-wide counter: count
            # from 1, as in a process that builds this model first
            count, laguna._SEEDS = laguna._SEEDS, itertools.count(1)
            try:
                _MODELS[family] = LagunaForCausalLM(laguna_tiny())
            finally:
                laguna._SEEDS = count
        _MODELS[family].eval()
    return _MODELS[family]


# what the "chain" model says after 389, greedily
_CHAIN = [389, 1632, 1972, 2023, 1113, 1686, 157, 1736, 86, 1764, 252, 100]


def _gpt_requests():
    rng = np.random.default_rng(17)
    body = _CHAIN * 3 + _CHAIN[:3]
    shared = [int(t) for t in rng.integers(0, 2048, (40,))]
    tails = [[int(t) for t in rng.integers(0, 2048, (n,))]
             for n in (9, 3)]
    return [(body, 30),                       # repeats: n-gram food
            (shared + tails[0], 22),          # two prompts that share
            ([int(t) for t in rng.integers(0, 2048, (7,))], 36),
            (shared + tails[1], 18),          # … 40 tokens (the trie)
            ([int(t) for t in rng.integers(0, 2048, (21,))], 27)]


def _laguna_requests():
    return [(list(np.arange(40) % 250), 56),
            (list((np.arange(34) * 7) % 250), 40),
            (list((np.arange(30) * 3) % 250), 44),
            (list(np.arange(9) % 250), 30)]


# name -> (family, engine arguments, what the case must have exercised)
_GPT_CASES = {
    "roomy": (dict(num_slots=3, max_model_len=96, token_budget=12),
              lambda e: e.stats["preemptions"] == 0),
    "tight": (dict(num_slots=3, num_pages=8, max_model_len=96,
                   token_budget=12),
              lambda e: e.stats["preemptions"] > 0),
    "prefix": (dict(num_slots=2, num_pages=10, max_model_len=96,
                    token_budget=12, prefix_cache=True),
               lambda e: e.prefix_cache.snapshot()["tokens_saved"] > 0),
    "draft": (dict(num_slots=3, num_pages=9, max_model_len=96,
                   token_budget=12, spec_k=3),
              lambda e: e.stats["spec_accepted"] > 0),
    "ngram": (dict(num_slots=3, num_pages=9, max_model_len=96,
                   token_budget=12, spec_mode="ngram", spec_k=3),
              lambda e: e.stats["ngram_accepted"] > 0),
}
_LAGUNA_CASES = {
    "roomy": (dict(num_slots=2, max_model_len=128, token_budget=24),
              lambda e: e.stats["window_pages_freed"] > 0
              and e.stats["preemptions"] == 0),
    "tight_window": (dict(num_slots=3, max_model_len=128,
                          token_budget=24,
                          num_pages={"full": 30, "window": 7}),
                     lambda e: e.stats["preemptions"] > 0),
}
CASES = [(fam, name, k)
         for fam, cases in (("gpt", _GPT_CASES), ("laguna", _LAGUNA_CASES))
         for name in cases for k in (1, 4)]


def _pools(eng):
    """Every cache kind's page pool, in the model's order."""
    if hasattr(eng, "_caches"):
        return [c.pool for c in eng._caches]
    # ed35358's spelling, kept so that `--record` still runs there
    return [eng.pool] + [ks.pool for ks in eng._extra]


def _held(eng, req):
    """[pages held, first logical page held] of `req`, a cache kind."""
    if hasattr(eng, "_caches"):
        runs = [req.kind_pages[c.index] for c in eng._caches]
    else:
        runs = [range(len(req.pages))] + [
            sorted(req.kind_pages[ks.index]) for ks in eng._extra]
    return [[len(r), min(r, default=0)] for r in runs]


def _serve(family, name, decode_k):
    cases = _GPT_CASES if family == "gpt" else _LAGUNA_CASES
    kw, exercised = cases[name]
    kw = dict(kw, page_size=16, decode_k=decode_k)
    if name == "draft":
        kw["draft_model"] = _model("draft")
    model = _model("chain" if name == "ngram" else family)
    eng = LLMEngine(model, LLMEngineConfig(**kw))
    requests = _gpt_requests() if family == "gpt" else _laguna_requests()
    reqs = [eng.add_request(p, max_new_tokens=n) for p, n in requests]
    order = {id(r): i for i, r in enumerate(reqs)}
    steps = []
    while eng.has_work():
        eng.step()
        steps.append({
            "live": [p.num_live for p in _pools(eng)],
            "slots": [[slot, order[id(r)], r.n_prefilled, _held(eng, r)]
                      for slot, r in enumerate(eng._slots)
                      if r is not None],
            "preemptions": eng.stats["preemptions"],
            "freed": eng.stats.get("window_pages_freed", 0)})
        for p in _pools(eng):
            p.assert_consistent()
        assert len(steps) < 3000
    assert exercised(eng), (family, name, dict(eng.stats))
    eng.close()        # the trie returns its pages
    for p in _pools(eng):
        p.assert_consistent()
        assert p.num_live == 0
    return {"steps": steps,
            "tokens": [[int(t) for t in r.future.result(timeout=0)]
                       for r in reqs]}


@pytest.mark.parametrize("family,name,decode_k", CASES)
def test_the_schedule_is_the_recorded_one(request, family, name,
                                          decode_k):
    key = f"{family}-{name}-k{decode_k}"
    got = _serve(family, name, decode_k)
    if getattr(request.config, "record_engine_schedule", False):
        golden = {}
        if os.path.exists(GOLDEN):
            with open(GOLDEN) as f:
                golden = json.load(f)
        golden[key] = got
        with open(GOLDEN, "w") as f:
            json.dump(golden, f, separators=(",", ":"), sort_keys=True)
            f.write("\n")
        return
    with open(GOLDEN) as f:
        want = json.load(f)[key]
    assert got["tokens"] == want["tokens"]
    for n, (a, b) in enumerate(zip(got["steps"], want["steps"])):
        assert a == b, f"{key}: step {n} differs"
    assert len(got["steps"]) == len(want["steps"])
