"""The readers of the step programs by name, of the device's idle time by
the engine's innermost span (`harness/idle_split.py`) and of the
engine's held-slot counters: on made-up reductions, on the recorded
fixtures (data/README*.txt), whose programs predate the names, the
dispatch span and the counters, and on a tiny engine's own counters."""
import gzip
import importlib.util
import os
import shutil

import pytest

from harness import idle_split, span_reduce as sr, trace_reduce as tr
from perfbench_newarch import cells_of, manifest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
DECODE_CELLS = ["cgpt1p3b_decode", "laguna_s21_decode",
                "sarvam105b_longdoc_decode", "ling3flash_manyseq_decode"]
# name -> (unit, better, source, layer)
NEW = {
    "tick_program_time_share.decode": (
        "%", "lower", "device_trace", "compiled steps"),
    "tick_program_ms_p50.decode": (
        "ms", "lower", "device_trace", "compiled steps"),
    "window_program_ms_p50.decode": (
        "ms", "lower", "device_trace", "compiled steps"),
    "idle_in_dispatch_share.decode": (
        "%", "lower", "program_span", "admission and batching"),
    "idle_in_sync_share.decode": (
        "%", "lower", "program_span", "admission and batching"),
    "idle_in_schedule_share.decode": (
        "%", "lower", "program_span", "admission and batching"),
    "idle_unspanned_share.decode": (
        "%", "lower", "program_span", "admission and batching"),
    "slots_held_share.decode": (
        "%", "higher", "program_counter", "admission and batching"),
    "slots_stalled_share.decode": (
        "%", "lower", "program_counter", "admission and batching"),
    "slots_prefilling_share.decode": (
        "%", "lower", "program_counter", "admission and batching"),
}
IDLE = {"dispatch": "idle_in_dispatch_share.decode",
        "sync": "idle_in_sync_share.decode",
        "schedule": "idle_in_schedule_share.decode",
        "unspanned": "idle_unspanned_share.decode"}
FIXTURES = ("decode_v5e_260ms.xplane.pb.gz",
            "decode_spans_v5e_300ms.xplane.pb.gz",
            "train_spans_v5e_300ms.xplane.pb.gz",
            "sarvam_spans_v5e_400ms.xplane.pb.gz")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "tiles_" + name.replace(".", "_"), os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- the manifest -----------------------------------------------------

def _block(per_layer, names):
    """The entries named `names`, checked to stand together in that
    order wherever they are in the list."""
    at = [p["name"] for p in per_layer].index(names[0])
    block = per_layer[at:at + len(names)]
    assert [p["name"] for p in block] == list(names)
    return block


def test_the_new_readers_close_the_list(bench_root):
    """The readers every decode cell shares stand together, in their
    order, and list every cell that reports `decode_tok_s`: a cell added
    later is appended to the workloads and to the readers' lists, and
    its own readers after them."""
    m = manifest(bench_root)
    decode = set(cells_of(m, "decode_tok_s"))
    assert set(DECODE_CELLS) <= decode
    mine = _block(m["per_layer"], list(NEW))
    for p in mine:
        assert (p["unit"], p["better"], p["source"], p["layer"]) == \
            NEW[p["name"]]
        assert p["moves"] == "decode_tok_s"
        assert set(p["workloads"]) >= decode
        assert os.path.exists(os.path.join(
            bench_root, "benchmarks", "layer_metrics", p["name"] + ".py"))
    # what the cells' CPU rehearsals print may hold no such name
    for p in mine:
        if p["source"] == "program_counter":
            assert "time_share" not in p["name"]
            assert not p["name"].endswith("_roofline.decode")
    # every older reader that the four decode cells share lists every
    # decode cell too
    shared = [p for p in m["per_layer"]
              if set(DECODE_CELLS) <= set(p.get("workloads", decode))]
    assert len(shared) > len(NEW)
    assert all(set(p.get("workloads", decode)) >= decode for p in shared)


def test_the_single_cell_block_stays_whole_ahead_of_the_new_readers(
        bench_root):
    """`ling3flash_manyseq_decode`'s own five readers keep their order and
    stand together, wherever the block is; the cell reads every reader
    `sarvam105b_longdoc_decode` reads."""
    m = manifest(bench_root)
    per_layer, cell = m["per_layer"], "ling3flash_manyseq_decode"
    own = [p["name"] for p in per_layer if p.get("workloads") == [cell]]
    assert own == ["kda_time_share.decode", "kda_proj_time_share.decode",
                   "kda_recur_roofline.decode", "kda_chunk_roofline.decode",
                   "kda_chunked_share_of_rows.decode"]
    _block(per_layer, own)
    assert cell in {w["name"] for w in m["workloads"]}

    def reads(name):
        return {p["name"] for p in per_layer
                if name in p.get("workloads", ())}
    assert reads("sarvam105b_longdoc_decode") == reads(cell) - set(own)
    assert set(NEW) <= reads("sarvam105b_longdoc_decode")


# ---- the innermost span and the idle split ----------------------------

SPANS = [("llm_engine.admit", 0, 50),
         ("llm_engine.fused_step", 100, 400),
         ("llm_engine.dispatch", 110, 150),
         ("llm_engine.sync", 150, 390),
         ("llm_engine.emit", 400, 420)]


def test_the_innermost_span_over_each_instant():
    assert idle_split.innermost(SPANS) == [
        (0, 50, "llm_engine.admit"),
        (100, 110, "llm_engine.fused_step"),
        (110, 150, "llm_engine.dispatch"),
        (150, 390, "llm_engine.sync"),
        (390, 400, "llm_engine.fused_step"),
        (400, 420, "llm_engine.emit")]
    assert idle_split.innermost([]) == []
    # a span of its own inside the sync, and two that start together
    assert idle_split.innermost([("a", 0, 100), ("b", 0, 40),
                                 ("c", 50, 60)]) == [
        (0, 40, "b"), (40, 50, "a"), (50, 60, "c"), (60, 100, "a")]


@pytest.mark.parametrize("gap,part", [
    ((120, 20), "dispatch"),        # inside dispatch ⊂ fused_step
    ((200, 50), "sync"),
    ((10, 30), "schedule"),         # admit
    ((392, 6), "schedule"),         # the fused_step outside its children
    ((402, 10), "schedule"),        # emit
    ((60, 30), "unspanned"),        # between two steps
    ((500, 100), "unspanned"),
])
def test_a_gap_goes_to_the_innermost_span_over_it(gap, part):
    got = idle_split.split_ns([gap], SPANS)
    assert got == {p: gap[1] if p == part else 0 for p in idle_split.PARTS}


def test_a_gap_across_spans_is_cut_where_they_meet():
    got = idle_split.split_ns([(90, 40), (380, 30), (45, 10)], SPANS)
    assert got == {"dispatch": 20, "sync": 10,
                   "schedule": 10 + 10 + 10 + 5, "unspanned": 10 + 5}
    assert sum(got.values()) == 40 + 30 + 10


# two steps: the first's read, its emit, the next admission, then the
# next call; device 0 idles from the first program's end to the second's
# start
TWO_STEPS = [("llm_engine.fused_step", 0, 100),
             ("llm_engine.dispatch", 10, 20),
             ("llm_engine.sync", 20, 100),
             ("llm_engine.emit", 100, 110),
             ("llm_engine.admit", 110, 115),
             ("llm_engine.fused_step", 120, 300),
             ("llm_engine.dispatch", 120, 140),
             ("llm_engine.sync", 140, 300)]


@pytest.mark.parametrize("shift", [-3, 0, 3])
def test_an_aligned_gap_moves_between_the_call_and_the_read(shift):
    """The profiler lays the device's line on the host's clock; where it
    lays it a little earlier or later, the gap's time passes between the
    read at one end and the call at the other, and nothing else moves."""
    got = idle_split.split_ns([(95 + shift, 40)], TWO_STEPS)
    assert got["dispatch"] + got["sync"] == 20
    assert got["sync"] == 5 - shift
    assert (got["schedule"], got["unspanned"]) == (15, 5)


def _trace(gaps, window=(0, 1000), modules=()):
    """A made-up `trace_reduce` reduction: device 0 busy wherever it
    has no gap."""
    t0, t1 = window
    idle = sum(d for _s, d in gaps)
    return {"window": window, "window_s": (t1 - t0) / 1e9,
            "busy_s": (t1 - t0 - idle) / 1e9,
            "devices": {0: {"gaps": list(gaps), "modules": list(modules)}}}


def _red(spans=(), modules=(), busy_s=1e-6):
    """A made-up `span_reduce` reduction."""
    return {"window": (0, 1000), "busy_s": busy_s,
            "spans": [(n, a, b, {}) for n, a, b in spans],
            "modules": list(modules), "scopes": {}, "scoped": False}


def test_the_four_shares_add_up_to_the_idle_share(monkeypatch):
    gaps = [(10, 30), (60, 30), (120, 20), (200, 50), (392, 6),
            (402, 10), (500, 100)]
    ctx = {"obs": {}, "trace": _trace(gaps)}
    monkeypatch.setattr(sr, "reduction", lambda _ctx: _red(SPANS))
    got = {part: _reader(name)(ctx) for part, name in IDLE.items()}
    assert got == pytest.approx({"dispatch": 2.0, "sync": 5.0,
                                 "schedule": 4.6, "unspanned": 13.0})
    tr_ = ctx["trace"]
    assert sum(got.values()) == pytest.approx(
        100 * (1 - tr_["busy_s"] / tr_["window_s"]))


def test_without_the_dispatch_span_the_split_reads_nothing(monkeypatch):
    ctx = {"obs": {}, "trace": _trace([(60, 30)])}
    monkeypatch.setattr(sr, "reduction", lambda _ctx: _red(
        [s for s in SPANS if s[0] != "llm_engine.dispatch"]))
    assert all(_reader(n)(ctx) is None for n in IDLE.values())
    monkeypatch.setattr(sr, "reduction", lambda _ctx: None)
    assert all(_reader(n)(ctx) is None for n in IDLE.values())
    assert all(_reader(n)({"obs": {}, "trace": None}) is None
               for n in IDLE.values())


# ---- the programs by name ---------------------------------------------

MS = 1_000_000     # ns


def test_a_tick_that_starts_after_the_next_window_opened_is_a_tick(
        monkeypatch):
    """A tick with only prompt rows has nothing to read, its span closes
    before its program runs, and the next window's span opens first: a
    guess by the span over a program's start would call it a window. By
    name it stays a tick."""
    spans = [("llm_engine.fused_step", 100 * MS, 400 * MS),
             ("llm_engine.step", 500 * MS, 520 * MS),
             ("llm_engine.fused_step", 525 * MS, 900 * MS)]
    modules = [("jit_pure_window(1)", 120 * MS, 130 * MS),
               ("jit_pure_tick(2)", 530 * MS, 100 * MS),
               ("jit_pure_window(1)", 640 * MS, 134 * MS)]
    red = _red(spans, modules, busy_s=364 * MS / 1e9)
    monkeypatch.setattr(sr, "reduction", lambda _ctx: red)
    ctx = {"obs": {}, "trace": _trace([], window=(0, 1000 * MS),
                                      modules=modules)}
    assert _reader("tick_program_time_share.decode")(ctx) == \
        pytest.approx(100 * 100 / 364)
    assert _reader("tick_program_ms_p50.decode")(ctx) == \
        pytest.approx(100.0)
    assert _reader("window_program_ms_p50.decode")(ctx) == \
        pytest.approx(132.0)


def test_programs_named_with_no_tick_in_the_window_read_zero(monkeypatch):
    modules = [("jit_pure_window(1)", 0, 5 * MS)]
    monkeypatch.setattr(sr, "reduction",
                        lambda _ctx: _red(modules=modules, busy_s=5e-3))
    ctx = {"obs": {}, "trace": _trace([], modules=modules)}
    assert _reader("tick_program_time_share.decode")(ctx) == 0.0
    assert _reader("tick_program_ms_p50.decode")(ctx) is None
    # the older single name: nothing says which program ran
    monkeypatch.setattr(sr, "reduction", lambda _ctx: _red(
        modules=[("jit_pure(1)", 0, 5 * MS)], busy_s=5e-3))
    assert _reader("tick_program_time_share.decode")(ctx) is None


# ---- the held-slot counters -------------------------------------------

def _counted(**stats):
    return {"obs": {"num_slots": 32, "window": {"stats": stats}},
            "trace": None}


def test_the_slot_shares_read_the_engines_counters():
    ctx = _counted(steps=20, step_calls=10, slots_held=320,
                   slots_prefilling=150, slots_stalled=30)
    # the window and its straggler tick are ONE step here
    assert _reader("slots_held_share.decode")(ctx) == 100.0
    assert _reader("slots_stalled_share.decode")(ctx) == \
        pytest.approx(100 * 30 / 320)
    assert _reader("slots_prefilling_share.decode")(ctx) == \
        pytest.approx(100 * 150 / 320)


@pytest.mark.parametrize("stats", [
    {}, {"steps": 4, "occupancy_sum": 2.0},
    {"step_calls": 0, "slots_held": 0, "slots_stalled": 0}])
def test_without_the_counters_the_slot_shares_read_nothing(stats):
    for name in ("slots_held_share.decode", "slots_stalled_share.decode",
                 "slots_prefilling_share.decode"):
        assert _reader(name)(_counted(**stats)) is None
        assert _reader(name)({"obs": {}, "trace": None}) is None


def test_a_tiny_engines_counters_reach_the_readers():
    """The names the readers ask for are the ones the engine counts
    under, carried as `work_between` carries them (numeric deltas)."""
    import numpy as np

    import paddle_tpu as paddle
    from drivers._serving import numeric
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.inference.llm_engine import LLMEngine, LLMEngineConfig
    from paddle_tpu.text.models import GPTForCausalLM, gpt_tiny

    mesh_mod.reset_mesh()
    paddle.seed(7)
    model = GPTForCausalLM(gpt_tiny())
    model.eval()
    eng = LLMEngine(model, LLMEngineConfig(
        num_slots=2, page_size=16, token_budget=8, max_model_len=64,
        decode_k=4))
    before = numeric(eng.stats)
    rng = np.random.default_rng(3)
    for n in (13, 5):
        eng.add_request(rng.integers(0, 2048, (n,)), max_new_tokens=9)
    while eng.has_work():
        eng.step()
    after = numeric(eng.stats)
    ctx = {"obs": {"num_slots": 2, "window": {"stats": {
        k: after[k] - v for k, v in before.items()}}}, "trace": None}
    st = ctx["obs"]["window"]["stats"]
    assert st["step_calls"] <= st["steps"]
    assert 50.0 <= _reader("slots_held_share.decode")(ctx) <= 100.0
    assert _reader("slots_stalled_share.decode")(ctx) == 0.0
    # both prompts are longer than their first tick's rows
    assert 0.0 < _reader("slots_prefilling_share.decode")(ctx) < 100.0


# ---- the recorded fixtures --------------------------------------------

_CTX = {}


def _fixture_ctx(fixture, tmp_path_factory):
    """A reader's context over one recorded fixture, laid out as a run's
    trace directory is, with a window's counters that predate the
    held-slot ones."""
    if fixture not in _CTX:
        root = tmp_path_factory.mktemp(fixture.split(".")[0])
        d = root / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        with gzip.open(os.path.join(HERE, "data", fixture)) as src, \
                open(d / "host.xplane.pb", "wb") as dst:
            shutil.copyfileobj(src, dst)
        _CTX[fixture] = {
            "obs": {"trace_dir": str(root), "num_slots": 24,
                    "window": {"steps": 9, "stats": {
                        "steps": 9, "occupancy_sum": 6.5}}},
            "trace": tr.reduce_trace(tr.find_xplane(str(root)))}
    return _CTX[fixture]


@pytest.mark.parametrize("name", list(NEW))
@pytest.mark.parametrize("fixture", FIXTURES)
def test_a_recorded_program_reads_nothing(fixture, name,
                                          tmp_path_factory):
    """What the parent's checkout gives: programs named `jit_pure`
    alike, no `llm_engine.dispatch` span, no held-slot counter."""
    assert _reader(name)(_fixture_ctx(fixture, tmp_path_factory)) is None


@pytest.mark.parametrize("fixture", FIXTURES[1:2] + FIXTURES[3:])
def test_the_sweep_over_a_recorded_window_adds_up(fixture,
                                                 tmp_path_factory):
    """The engine's spans and device 0's gaps of a recorded decode
    window: every idle nanosecond goes to exactly one part, and the
    gaps (those of 2 µs and more) hold the idle share to a tenth of a
    point."""
    ctx = _fixture_ctx(fixture, tmp_path_factory)
    trace = ctx["trace"]
    gaps = trace["devices"][0]["gaps"]
    spans = [(n, a, b) for n, a, b, _args in sr.reduction(ctx)["spans"]
             if n.startswith("llm_engine.")]
    assert spans
    got = idle_split.split_ns(gaps, spans)
    assert sum(got.values()) == sum(d for _s, d in gaps)
    assert got["sync"] > 0 and got["schedule"] > 0
    assert got["dispatch"] == 0          # no such span yet
    t0, t1 = trace["window"]
    assert 100 * sum(got.values()) / (t1 - t0) == pytest.approx(
        100 * (1 - trace["busy_s"] / trace["window_s"]), abs=0.1)
