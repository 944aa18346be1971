"""The program's own names on a profile (ISSUE 25): every step span is
a profiler annotation in the default telemetry mode, on the capture's
clock with its args, a step's read comes with the next step and names
the step it reads; and the step programs carry one vocabulary of named
scopes in every operation's `op_name`, backward included, without
changing a single operation."""
import contextlib
import glob
import re

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.analysis import step_analysis
from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
from paddle_tpu.text.models import GPTForCausalLM, GPTPretrainingCriterion
from paddle_tpu.text.models import gpt as gpt_mod
from paddle_tpu.text.models.gpt import gpt_tiny

ENGINE_SPANS = {
    "llm_engine.admit": {"waiting", "admitted"},
    "llm_engine.reserve": {"rows"},
    "llm_engine.fused_step": {"k", "rows", "prefill_tokens",
                              "decode_tokens"},
    "llm_engine.plan": set(),
    "llm_engine.step": {"rows", "prefill_tokens", "decode_tokens"},
    "llm_engine.dispatch": set(),
    "llm_engine.sync": {"step"},
    "llm_engine.emit": set(),
}
TRAIN_SPANS = {"jit.TrainStep.h2d": set(), "jit.TrainStep": {"step"},
               "jit.TrainStep.publish": set()}
VOCABULARY = ("embed", "attn", "mlp", "norm", "lm_head", "loss",
              "optimizer", "sample")


@pytest.fixture(autouse=True)
def _serial_mesh_and_mode():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    prev = obs.mode()
    yield
    obs.set_mode(prev)


def _engine(decode_k=4):
    paddle.seed(30)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    return LLMEngine(model, LLMEngineConfig(
        num_slots=3, page_size=16, token_budget=8, max_model_len=64,
        decode_k=decode_k))


def _train_step():
    paddle.seed(31)
    model = GPTForCausalLM(gpt_tiny())
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters())
    step = paddle.jit.TrainStep(
        model, lambda m, ids: crit(m(ids), ids), opt)
    ids = paddle.to_tensor(np.random.default_rng(3).integers(
        0, model.config.vocab_size, (2, 16)))
    return step, ids


def _program_spans(trace_dir):
    """[(name, start_ns, end_ns, args)] of the program's spans in the
    one capture under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("llm_engine.", "jit.TrainStep")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def _capture(trace_dir, eng, prompt, step, ids):
    """Three engine steps (a single tick that prefills and samples, a
    fused window, then the window of the last token, whose call reads
    every step left) and two train steps under one capture; everything
    compiled beforehand."""
    req = eng.add_request(prompt, max_new_tokens=6)
    jax.profiler.start_trace(str(trace_dir))
    try:
        eng.step()
        eng.step()
        eng.step()
        step(ids)
        step(ids)
    finally:
        jax.profiler.stop_trace()
    while eng.has_work():
        eng.step()
    assert len(req.future.result(timeout=0)) == len(prompt) + 6


def test_default_mode_capture_holds_every_step_span(tmp_path):
    obs.set_mode("metrics")          # the default mode
    eng = _engine()
    prompt = np.random.default_rng(5).integers(0, 2048, (5,))
    warm = eng.add_request(prompt, max_new_tokens=6)
    while eng.has_work():
        eng.step()
    warm.future.result(timeout=0)
    step, ids = _train_step()
    step(ids)

    first = eng.stats["step_calls"] + 1
    _capture(tmp_path / "on", eng, prompt, step, ids)
    spans = _program_spans(tmp_path / "on")
    by_name = {}
    for name, a, b, args in spans:
        by_name.setdefault(name, []).append((a, b, args))
    for name, keys in {**ENGINE_SPANS, **TRAIN_SPANS}.items():
        assert name in by_name, (name, sorted(by_name))
        for _a, _b, args in by_name[name]:
            assert set(args) == keys, (name, args)
    # what the args say: the tick carried the 5-token prompt (4
    # prefill rows and the frontier), the window 4 tokens of one row
    (tick,) = by_name["llm_engine.step"]
    assert tick[2] == {"rows": 1, "prefill_tokens": 4,
                       "decode_tokens": 1}
    assert [w[2] for w in sorted(by_name["llm_engine.fused_step"])] == [
        {"k": 4, "rows": 1, "prefill_tokens": 0, "decode_tokens": n}
        for n in (4, 1)]
    assert by_name["llm_engine.admit"][0][2] == {"waiting": 1,
                                                 "admitted": 1}
    assert len(by_name["jit.TrainStep"]) == 2
    assert [s[2]["step"] for s in by_name["jit.TrainStep"]] == [1, 2]
    # each dispatch span encloses exactly one jitted call
    # (`llm_engine.dispatch`) and no read; one step ahead, each step's
    # read (`llm_engine.sync`, naming its step) comes after the next
    # step's call, and the last step's (nothing left to dispatch) after
    # its own
    dispatches = sorted(by_name["llm_engine.step"]
                        + by_name["llm_engine.fused_step"])
    syncs = sorted(by_name["llm_engine.sync"])
    calls = by_name["llm_engine.dispatch"]
    assert len(calls) == len(dispatches) == len(syncs) == 3
    ends = []
    for a, b, _ in dispatches:
        (call,) = [(ca, cb) for ca, cb, _ in calls if a <= ca and cb <= b]
        assert not [sa for sa, _sb, _ in syncs if a <= sa <= b]
        ends.append(call[1])
    assert [args["step"] for _a, _b, args in syncs] == [
        first, first + 1, first + 2]
    for n, (sync_start, _b, _args) in enumerate(syncs):
        assert ends[min(n + 1, 2)] <= sync_start
    # at step granularity: never per token or per row
    assert len(spans) <= 2 * 12 + 2 * 5

    obs.set_mode("off")              # PT_TELEMETRY=0
    _capture(tmp_path / "off", eng, prompt, step, ids)
    assert _program_spans(tmp_path / "off") == []


def _words(text):
    """Vocabulary words on the name paths of a lowered text's
    locations, forward and backward apart."""
    fwd, bwd = set(), set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        if path.endswith(".py"):
            continue        # a source file, not a name path
        for seg in path.split("/"):
            m = re.fullmatch(r"((?:\w+\()*)(\w+)\)*", seg)
            if m and m.group(2) in VOCABULARY:
                (bwd if "transpose(" in m.group(1) else fwd).add(
                    m.group(2))
    return fwd, bwd


def _lowered_texts(debug_info):
    step, ids = _train_step()
    eng = _engine()
    eng._ensure_fused()
    return {
        "train": step.lower(ids).as_text(debug_info=debug_info),
        "paged": eng._step_fn._jit.lower(
            *step_analysis._paged_step_args(eng)).as_text(
                debug_info=debug_info),
        "fused": eng._fused_fn._jit.lower(
            *step_analysis._fused_step_args(eng)).as_text(
                debug_info=debug_info),
    }


@pytest.fixture(scope="module")
def scoped_texts():
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    return _lowered_texts(debug_info=True)


MODEL_WORDS = {"embed", "attn", "mlp", "norm", "lm_head"}


@pytest.mark.parametrize("program,forward,backward", [
    ("train", MODEL_WORDS | {"loss", "optimizer"},
     MODEL_WORDS | {"loss"}),
    ("paged", MODEL_WORDS, set()),
    ("fused", MODEL_WORDS | {"sample"}, set()),
])
def test_step_program_carries_its_words(scoped_texts, program, forward,
                                        backward):
    """Every vocabulary word that applies to a step program is on its
    operations' name paths; backward operations inherit the word
    through transpose(jvp(<word>))."""
    assert _words(scoped_texts[program]) == (forward, backward)
    if backward:
        assert "transpose(jvp(mlp))" in scoped_texts[program]


@pytest.mark.parametrize("program,module", [
    ("paged", "jit_pure_tick"), ("fused", "jit_pure_window")])
def test_each_step_program_names_itself(scoped_texts, program, module):
    """The module name a device trace shows a step program under: the
    tick and the window apart, and both still found by a search for
    `jit_pure` (what the readers of both programs match)."""
    (name,) = re.findall(r"^module @(\w+)", scoped_texts[program], re.M)
    assert name == module and re.search("jit_pure", name)


def test_host_tick_sampler_carries_sample():
    sampler = jax.jit(gpt_mod.sample_tokens).lower(
        np.zeros((3, 8), np.float32), np.zeros((3,), np.float32),
        np.ones((3,), np.float32), np.zeros((3,), np.int32),
        np.zeros((3,), np.int32), jax.random.PRNGKey(0)).as_text(
            debug_info=True)
    assert _words(sampler) == ({"sample"}, set())


def test_scopes_are_metadata_only(monkeypatch):
    """A build without the scopes lowers to the same operations."""
    scoped = _lowered_texts(debug_info=False)

    def no_scope(_name):
        return contextlib.nullcontext()

    monkeypatch.setattr(gpt_mod, "_scope", no_scope)
    monkeypatch.setattr(jax, "named_scope", no_scope)
    bare = _lowered_texts(debug_info=True)
    assert all(_words(t) == (set(), set()) for t in bare.values())
    assert _lowered_texts(debug_info=False) == scoped
