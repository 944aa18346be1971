"""The span and scope reduction (`harness/span_reduce.py`) and the
readers of spans and scopes, on two trimmed traces recorded on the chip
(data/README.txt: both cells, 0.3 s each, the program's spans and the
operations' `tf_op` kept), on the older fixture that has neither, and
on made-up events."""
import gzip
import importlib.util
import os
import shutil
import sys

import pytest

from harness import span_reduce as sr, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DISPATCH = ("llm_engine.step", "llm_engine.fused_step")
DECODE_READERS = ("step_turnaround_ms_p50.decode",
                  "lm_head_time_share.decode",
                  "unscoped_time_share.decode")
TRAIN_READERS = ("mlp_time_share.train", "norm_time_share.train",
                 "lm_head_loss_time_share.train",
                 "optimizer_time_share.train",
                 "unscoped_time_share.train")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ctx_of(fixture, tmp_path_factory):
    """A reader's context over one recorded fixture, laid out as a
    run's trace directory is."""
    root = tmp_path_factory.mktemp(fixture.split(".")[0])
    d = root / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(HERE, "data", fixture)) as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return {"obs": {"trace_dir": str(root)},
            "trace": tr.reduce_trace(tr.find_xplane(str(root)))}


@pytest.fixture(scope="module")
def decode_ctx(tmp_path_factory):
    return _ctx_of("decode_spans_v5e_300ms.xplane.pb.gz", tmp_path_factory)


@pytest.fixture(scope="module")
def train_ctx(tmp_path_factory):
    return _ctx_of("train_spans_v5e_300ms.xplane.pb.gz", tmp_path_factory)


@pytest.fixture(scope="module")
def old_ctx(tmp_path_factory):
    """PR 24's fixture: recorded before the program had spans or
    scopes, trimmed of every metadata statistic."""
    return _ctx_of("decode_v5e_260ms.xplane.pb.gz", tmp_path_factory)


def test_recorded_decode_window_spans_and_the_programs_they_launched(
        decode_ctx):
    red = sr.reduction(decode_ctx)
    # the same window and busy time as the accepted reduction reads
    assert red["window"] == tuple(decode_ctx["trace"]["window"])
    assert red["busy_s"] == pytest.approx(decode_ctx["trace"]["busy_s"],
                                          rel=1e-6)
    assert red["busy_s"] == pytest.approx(0.287011494, rel=1e-6)
    mine = [s for s in red["spans"] if s[0].startswith("llm_engine.")]
    assert {s[0] for s in mine} == {
        "llm_engine.admit", "llm_engine.reserve", "llm_engine.plan",
        "llm_engine.fused_step", "llm_engine.step", "llm_engine.sync",
        "llm_engine.emit"}
    args = {s[0]: s[3] for s in mine}
    assert args["llm_engine.step"] == {"rows": 1, "prefill_tokens": 98,
                                       "decode_tokens": 1}
    assert args["llm_engine.fused_step"]["k"] == 8
    assert args["llm_engine.admit"] == {"waiting": 0, "admitted": 0}
    # one fused window, one tick (its step program and the six small
    # programs of its frontier read), the start of the next window: the
    # programs and the idle time make up the window
    assert len(red["modules"]) == 9
    window_s = decode_ctx["trace"]["window_s"]
    idle = window_s - red["busy_s"]
    assert sum(m[2] for m in red["modules"]) / 1e9 + idle == \
        pytest.approx(window_s, rel=0.02)
    # the dispatch spans enclose their sync, and the idle gaps carry
    # the program's names
    syncs = [s for s in mine if s[0] == "llm_engine.sync"]
    for s in syncs:
        assert any(d[1] <= s[1] and s[2] <= d[2]
                   for d in mine if d[0] in DISPATCH)
    assert decode_ctx["trace"]["idle_gaps"][0][0] == \
        "llm_engine.fused_step"


def test_decode_readers_on_the_recorded_window(decode_ctx):
    got = {n: _reader(n)(decode_ctx) for n in DECODE_READERS}
    # fused -> tick 3.83 ms, tick -> fused 4.57 ms of idle
    assert got["step_turnaround_ms_p50.decode"] == pytest.approx(
        4.1981795)
    assert got["lm_head_time_share.decode"] == pytest.approx(0.979, abs=1e-3)
    assert got["unscoped_time_share.decode"] == pytest.approx(0.431,
                                                              abs=1e-3)
    scopes = sr.reduction(decode_ctx)["scopes"]
    assert set(scopes) == {"", "embed", "attn", "mlp", "norm", "lm_head",
                           "sample"}
    assert max(scopes, key=scopes.get) == "attn"
    assert sum(scopes.values()) == pytest.approx(0.287011494, rel=1e-6)
    assert all(0 <= v <= 100 for v in got.values())


def test_train_readers_on_the_recorded_window(train_ctx):
    got = {n: _reader(n)(train_ctx) for n in TRAIN_READERS}
    # the first 0.3 s of a 0.595 s step: forward, loss, the start of
    # the backward pass; the optimizer has not run yet
    assert got["mlp_time_share.train"] == pytest.approx(32.082, abs=1e-3)
    assert got["norm_time_share.train"] == pytest.approx(1.030, abs=1e-3)
    assert got["lm_head_loss_time_share.train"] == pytest.approx(
        13.131, abs=1e-3)
    assert got["optimizer_time_share.train"] is None
    assert got["unscoped_time_share.train"] == pytest.approx(2.938,
                                                             abs=1e-3)
    red = sr.reduction(train_ctx)
    mine = [s for s in red["spans"] if s[0].startswith("jit.")]
    assert [s[0] for s in mine[:3]] == [
        "jit.TrainStep.h2d", "jit.TrainStep", "jit.TrainStep.publish"]
    # six steps dispatched ahead of the device: no stamp stalls a step
    steps = [s[3]["step"] for s in mine if s[0] == "jit.TrainStep"]
    assert steps == list(range(16, 22))
    assert mine[-1][2] - red["window"][0] < 0.1e9 < red["modules"][1][2]
    assert train_ctx["trace"]["idle_gaps"][0][0] == "jit.TrainStep"
    # no engine span in a training trace: the decode readers read
    # nothing there (the turnaround needs no span, only step programs)
    assert _reader("step_turnaround_ms_p50.decode")(train_ctx) is None


def test_a_trace_without_spans_or_scopes_reads_nothing(old_ctx):
    """What the parent commit's traces give the new readers."""
    for n in DECODE_READERS + TRAIN_READERS:
        if n != "step_turnaround_ms_p50.decode":
            assert _reader(n)(old_ctx) is None, n
    # step programs are there whatever the program names: one fused
    # window, then one tick
    assert _reader("step_turnaround_ms_p50.decode")(old_ctx) == \
        pytest.approx(3.821206)
    for n in DECODE_READERS + TRAIN_READERS:
        assert _reader(n)({"obs": {}, "trace": None}) is None


# ---- made-up events ---------------------------------------------------

def _span(name, a, b, **args):
    return (name, a, b, args)


def test_a_gap_between_two_step_programs_is_a_turnaround():
    modules = [("jit_pure(1)", 0, 1_000_000),
               ("jit__take(2)", 1_200_000, 100_000),  # not idle: a program
               ("jit_pure(1)", 4_000_000, 1_000_000),
               ("jit_pure(3)", 5_500_000, 1_000_000)]
    assert sr.turnarounds_ms(modules, r"jit_pure") == [2.9, 0.5]
    assert sr.turnarounds_ms(modules[:1], r"jit_pure") == []
    assert sr.turnarounds_ms(modules, r"no_such_program") == []


def test_an_operation_belongs_to_the_innermost_word_on_its_path():
    assert sr.scope_of("jit(step)/jvp(mlp)/norm/mul:") == "norm"
    assert sr.scope_of(
        "jit(step)/transpose(jvp(mlp))/dot_general:") == "mlp"
    assert sr.scope_of("jit(pure)/while/body/closed_call/attn/"
                       "pallas_call:") == "attn"
    assert sr.scope_of("jit(pure)/while/body/sample/sample/argmax") \
        == "sample"
    assert sr.scope_of("jit(step)/optimizer/sub:") == "optimizer"
    # a word is a whole segment: not part of a primitive's or a
    # function's name, not a file
    assert sr.scope_of("jit(step)/jit(normalize)/loss_scale/mul:") == ""
    assert sr.scope_of("jit(_take)/gather:") == ""
    assert sr.scope_of("") == ""
    # self time: a while around its body's operations counts neither
    # twice; an operation with no name at all is unscoped
    events = [(1, 0, 100), (2, 10, 30), (3, 50, 20), (4, 200, 50)]
    names = {1: "jit(pure)/while:", 2: "jit(pure)/while/body/attn/dot:",
             3: "jit(pure)/while/body/mlp/norm/mul:"}
    assert sr.scope_seconds(events, names, (0, 1000)) == {
        "": (50 + 50) / 1e9, "attn": 30 / 1e9, "norm": 20 / 1e9}
    assert sr.scope_seconds(events, names, (0, 60)) == {
        "": 20 / 1e9, "attn": 30 / 1e9, "norm": 10 / 1e9}


def _made_up_space(op_names, spans=()):
    """An XSpace with one device plane (four operations in one step
    program) and the given host spans, as the profiler would write it."""
    space = sr.xspace_class()()
    dev = space.planes.add(id=1, name="/device:TPU:0")
    dev.stat_metadata.add(key=1).value.name = sr.SCOPE_STAT
    ops = dev.lines.add(id=1, name="XLA Ops", timestamp_ns=1000)
    mods = dev.lines.add(id=2, name="XLA Modules", timestamp_ns=1000)
    for i, name in enumerate(op_names, start=1):
        meta = dev.event_metadata.add(key=i).value
        meta.id, meta.name = i, f"%op.{i} = f32[8] fusion(x)"
        if name is not None:
            meta.stats.add(metadata_id=1, str_value=name)
        ops.events.add(metadata_id=i, offset_ps=i * 100_000,
                       duration_ps=50_000)      # 50 ns every 100 ns
    meta = dev.event_metadata.add(key=9).value
    meta.id, meta.name = 9, "jit_pure(7)"
    mods.events.add(metadata_id=9, offset_ps=90_000,
                    duration_ps=400_000)
    host = space.planes.add(id=2, name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "rows"
    line = host.lines.add(id=1, name="python3", timestamp_ns=1000)
    for i, (name, a, b) in enumerate(
            ((tr.WINDOW_SPAN, 0, 1000),) + tuple(spans), start=1):
        meta = host.event_metadata.add(key=i).value
        meta.id, meta.name = i, name
        ev = line.events.add(metadata_id=i, offset_ps=a * 1000,
                             duration_ps=(b - a) * 1000)
        if name in DISPATCH:
            ev.stats.add(metadata_id=1, int64_value=3)
    return space


def _ctx_of_space(space, tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(space.SerializeToString())
    return {"obs": {"trace_dir": str(tmp_path)}, "trace": None}


def test_made_up_trace_through_the_readers(tmp_path):
    space = _made_up_space(
        ["jit(pure)/attn/dot_general:", "jit(pure)/lm_head/dot_general:",
         "jit(pure)/add:", None],
        spans=[("llm_engine.step", 50, 600)])
    ctx = _ctx_of_space(space, tmp_path)
    red = sr.reduction(ctx)
    assert red["window"] == (1000, 2000) and red["scoped"]
    assert red["busy_s"] == pytest.approx(200e-9)
    assert red["spans"] == [("llm_engine.step", 1050, 1600, {"rows": 3})]
    assert _reader("lm_head_time_share.decode")(ctx) == pytest.approx(25.0)
    # an operation with no word and one with no name are unscoped
    assert _reader("unscoped_time_share.decode")(ctx) == pytest.approx(50.0)
    assert _reader("mlp_time_share.train")(ctx) is None


def test_a_build_without_scopes_reads_as_unscoped(tmp_path):
    """What a stale compile cache, or the parent's program, gives: the
    operations have names, none has a word."""
    space = _made_up_space(["jit(pure)/dot_general:", "jit(pure)/add:",
                            "jit(pure)/mul:", None])
    ctx = _ctx_of_space(space, tmp_path)
    assert _reader("unscoped_time_share.train")(ctx) == pytest.approx(100.0)
    assert _reader("lm_head_time_share.decode")(ctx) is None
    assert _reader("optimizer_time_share.train")(ctx) is None


def test_trim_tool_keeps_what_the_reductions_read(tmp_path):
    sys.path.insert(0, os.path.join(ROOT, "benchmarks", "tools"))
    try:
        import trim_trace
    finally:
        sys.path.pop(0)
    space = _made_up_space(
        ["jit(pure)/attn/dot_general:", "jit(pure)/mlp/dot_general:",
         "jit(pure)/add:", None],
        spans=[("llm_engine.step", 50, 600)])
    # the first 250 ns of the window: the module, the span, two ops
    cut = trim_trace.trim(space, seconds=250e-9)
    red = sr.reduce_xspace(cut)
    assert red["window"] == (1000, 1250)
    assert red["spans"] == [("llm_engine.step", 1050, 1600, {"rows": 3})]
    assert [m[0] for m in red["modules"]] == ["jit_pure(7)"]
    assert red["scopes"] == {"attn": 50e-9, "mlp": 50e-9}
    whole = sr.reduce_xspace(space)
    assert whole["scopes"] == {"attn": 50e-9, "mlp": 50e-9, "": 100e-9}
