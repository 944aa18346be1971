"""Unified runtime telemetry (paddle_tpu/observability/).

ISSUE-3 acceptance: registry semantics (labels, cardinality collapse,
histogram quantiles, lock-free concurrent increments, disabled no-op,
<1%-per-step overhead pin), span nesting + chrome-trace export +
trace_merge round trip, the instrumented hot paths (TrainStep with
grad-norm aux, LLMEngine tick, checkpoint save/load), and the
LLMServer /metrics endpoint under concurrent requests.
"""
import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, observability as obs
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import tracing as obs_tracing

pytestmark = pytest.mark.observability


@pytest.fixture
def mode():
    """Restore the telemetry mode (and drop test spans) after each test."""
    prev = obs.mode()
    yield obs
    obs.set_mode(prev)
    obs_tracing.reset()


def _reg():
    return obs_metrics.MetricsRegistry()


# --------------------------------------------------------------- registry

def test_counter_gauge_histogram_basics():
    reg = _reg()
    c = reg.counter("c_total", "help text", labelnames=("op",))
    c.labels(op="a").inc()
    c.labels(op="a").inc(2)
    c.labels("b").inc()
    assert c.labels(op="a").value == 3
    assert c.labels(op="b").value == 1
    g = reg.gauge("g")
    g.set(2.5)
    assert g.value == 2.5
    h = reg.histogram("h", buckets=(1.0, 10.0))
    for x in (0.5, 5.0, 50.0):
        h.observe(x)
    assert h.count == 3
    assert h.sum == 55.5
    snap = reg.snapshot()
    assert snap["c_total"]["type"] == "counter"
    assert {s["labels"]["op"]: s["value"]
            for s in snap["c_total"]["series"]} == {"a": 3, "b": 1}
    assert snap["h"]["series"][0]["count"] == 3


def test_registry_type_and_label_conflicts():
    reg = _reg()
    reg.counter("x_total")
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("op",))
    # same spec is get-or-create
    assert reg.counter("x_total") is reg.counter("x_total")


def test_label_cardinality_collapses_to_overflow():
    reg = _reg()
    c = reg.counter("card_total", labelnames=("k",), max_series=4)
    for i in range(4):
        c.labels(k=f"v{i}").inc()
    with pytest.warns(RuntimeWarning, match="max_series"):
        c.labels(k="v_extra_1").inc()
    c.labels(k="v_extra_2").inc(5)     # same overflow cell, no new series
    assert len(c._children) == 5       # 4 real + 1 __overflow__
    snap = reg.snapshot()["card_total"]["series"]
    over = [s for s in snap if s["labels"]["k"] == "__overflow__"]
    assert over and over[0]["value"] == 6


def test_histogram_quantiles_interpolate():
    reg = _reg()
    h = reg.histogram("q", buckets=(0.01, 0.1, 1.0, 10.0))
    for _ in range(100):
        h.observe(0.05)                # all in the (0.01, 0.1] bucket
    assert 0.01 <= h.quantile(0.5) <= 0.1
    assert 0.01 <= h.quantile(0.99) <= 0.1
    h.observe(100.0)                   # overflow bucket → largest bound
    assert h.quantile(1.0) == 10.0
    empty = reg.histogram("q_empty")
    assert empty.quantile(0.5) == 0.0


def test_concurrent_increments_are_exact():
    """The lock-free fast path (per-thread cells) must not lose updates
    under contention — the failure mode of bare `self._v += 1`."""
    reg = _reg()
    c = reg.counter("thr_total")
    h = reg.histogram("thr_seconds", buckets=(1.0,))
    n_threads, per_thread = 8, 20_000

    def work():
        for _ in range(per_thread):
            c.inc()
            h.observe(0.5)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    assert h.count == n_threads * per_thread


def test_disabled_mode_is_a_noop(mode):
    reg = _reg()
    c = reg.counter("off_total")
    g = reg.gauge("off_g")
    h = reg.histogram("off_h")
    c.inc()
    obs.set_mode("off")
    c.inc(100)
    g.set(42)
    h.observe(1.0)
    with obs.trace_span("off_span"):
        pass
    obs.set_mode("metrics")
    assert c.value == 1
    assert g.value == 0.0
    assert h.count == 0
    assert all(e["name"] != "off_span" for e in obs.chrome_events())


def test_instrumentation_overhead_pinned(mode):
    """Acceptance: with telemetry off, per-step instrumentation costs
    <1% of a step. A generous CPU step is ~2 ms; one step's worth of
    instrumentation is ~10 metric writes + a span, so pin the per-call
    cost well under 2 µs (10 calls × 2 µs = 20 µs = 1% of 2 ms). In
    the default mode the span is a profiler annotation (no capture
    running here): it rides inside the same 40 µs budget."""
    reg = _reg()
    c = reg.counter("ovh_total")
    h = reg.histogram("ovh_seconds")
    g = reg.gauge("ovh_g")

    def bundle(n):
        t0 = time.perf_counter()
        for _ in range(n):
            c.inc()
            h.observe(0.001)
            g.set(1.0)
            with obs.trace_span("ovh"):
                pass
        return (time.perf_counter() - t0) / n

    obs.set_mode("off")
    bundle(1000)                               # warm caches/JIT paths
    per_iter_off = min(bundle(20_000) for _ in range(3))
    obs.set_mode("metrics")
    per_iter_on = min(bundle(20_000) for _ in range(3))
    # 4 instrumentation points per iteration here; budget 2 µs/call off
    assert per_iter_off < 8e-6, f"off-mode bundle {per_iter_off:.2e}s"
    # counting on (the default) must stay far below 1% of a step too
    assert per_iter_on < 40e-6, f"metrics-mode bundle {per_iter_on:.2e}s"


def test_prometheus_and_jsonl_exports_parse():
    reg = _reg()
    reg.counter("e_total", "a counter", labelnames=("op",)).labels(
        op='we"ird\nval').inc(3)
    reg.gauge("e_g", "a gauge").set(1.5)
    reg.histogram("e_h", "a hist", buckets=(0.1, 1.0)).observe(0.5)
    text = reg.to_prometheus()
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+-]+$')
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ",
                            line), line
        else:
            assert line_re.match(line), line
    # histogram series complete: buckets are cumulative + sum + count
    assert 'e_h_bucket{le="+Inf"} 1' in text
    assert "e_h_count 1" in text
    for line in reg.to_jsonl().strip().splitlines():
        rec = json.loads(line)
        assert rec["metric"] and rec["type"]


def test_histogram_percentile_summaries_in_exporters():
    """Satellite (ISSUE 15): p50/p95/p99 ship in the snapshot/compact
    dicts AND as summary-style quantile series in the Prometheus text,
    so consumers stop re-deriving percentiles from bucket counts."""
    reg = _reg()
    h = reg.histogram("q_h", "a hist", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    series = reg.snapshot()["q_h"]["series"][0]
    assert {"p50", "p95", "p99"} <= set(series)
    assert 0.1 <= series["p50"] <= 1.0
    compact = reg.compact()["q_h"]
    assert {"count", "sum", "p50", "p95", "p99"} <= set(compact)
    text = reg.to_prometheus()
    for q in ("0.5", "0.95", "0.99"):
        # a SEPARATE `_quantile` gauge family — quantile samples under
        # the bare name inside a histogram family split the family in
        # spec parsers
        assert f'q_h_quantile{{quantile="{q}"}}' in text, text
    assert "# TYPE q_h_quantile gauge" in text
    # the scrape must stay parseable by the reference parser when the
    # library is available (the format-violation regression fence)
    try:
        from prometheus_client.parser import text_string_to_metric_families
    except ImportError:
        pass
    else:
        fams = {f.name: f.type
                for f in text_string_to_metric_families(text)}
        assert fams.get("q_h") == "histogram", fams
    # the one-call view metrics() consumers use
    s = h.summary()
    assert s["count"] == 4 and {"p50", "p95", "p99"} <= set(s)


# ---------------------------------------------------------------- tracing

def test_span_nesting_and_chrome_roundtrip(mode, tmp_path):
    obs.set_mode("full")
    obs_tracing.reset()
    with obs.trace_span("outer", layer="test"):
        time.sleep(0.002)
        with obs.trace_span("inner"):
            time.sleep(0.001)

    @obs.trace_span("decorated")
    def fn(x):
        return x + 1

    assert fn(1) == 2
    events = obs.chrome_events()
    byname = {e["name"]: e for e in events}
    assert set(byname) >= {"outer", "inner", "decorated"}
    outer, inner = byname["outer"], byname["inner"]
    # chrome "X" events: child span nests inside the parent on one tid
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert outer["args"]["layer"] == "test"

    # export → per-rank JSONL → tools/trace_merge → chrome trace dict
    path = obs_tracing.flush(str(tmp_path))
    assert path and os.path.exists(path)
    assert obs.chrome_events() == []            # buffer drained
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(os.path.dirname(__file__), "..",
                                    "tools", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)
    trace = tm.merge([path])
    names = [e["name"] for e in trace["traceEvents"]]
    assert "outer" in names and "inner" in names
    assert any(e.get("ph") == "M" for e in trace["traceEvents"])
    assert min(e["ts"] for e in trace["traceEvents"]
               if e.get("ph") == "X") == 0      # re-based timeline
    json.dumps(trace)                           # serializable


def test_span_error_annotation(mode):
    obs.set_mode("full")
    obs_tracing.reset()
    with pytest.raises(ValueError):
        with obs.trace_span("boom"):
            raise ValueError("x")
    ev = [e for e in obs.chrome_events() if e["name"] == "boom"][0]
    assert ev["args"]["error"] == "ValueError"


# ----------------------------------------------- instrumented hot paths

def _tiny_train_step():
    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(
        m, lambda mm, x, y: nn.functional.cross_entropy(mm(x), y), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (4,)))
    return step, x, y


def test_trainstep_telemetry_smoke(mode, tmp_path):
    """The tier-1-safe acceptance smoke: one TrainStep under full
    telemetry → step/loss/grad-norm metrics + span, and the exported
    Prometheus text and JSONL parse."""
    obs.set_mode("full")
    obs_tracing.reset()
    reg = obs.registry()

    def val(name):
        m = reg.get(name)
        return 0 if m is None else m.value

    steps0 = val("pt_train_steps_total")
    compiles0 = val("pt_train_compiles_total")
    step, x, y = _tiny_train_step()   # built under full mode → gn aux
    for _ in range(3):
        loss = step(x, y)
    assert np.isfinite(float(loss.numpy()))
    assert val("pt_train_steps_total") - steps0 == 3
    assert val("pt_train_compiles_total") - compiles0 == 1
    assert step.compile_stats() == {"batch_signatures": 1,
                                    "executables": 1}
    # the recompile probe also proves donation held (params/opt-state
    # aliased in the executable) and publishes the gauge
    don = step.compile_stats(check_donation=True)["donation"]
    assert don["held"] and don["expected"] == don["aliased"] > 0, don
    held = reg.get("pt_step_donation_held")
    assert held is not None and \
        held.labels(step="train").value == 1.0
    gn = reg.get("pt_train_grad_norm")
    assert gn is not None and gn.count >= 3 and gn.quantile(0.5) > 0
    assert np.isfinite(reg.get("pt_train_loss").value)
    assert reg.get("pt_train_loss").value == pytest.approx(
        float(loss.numpy()))
    spans = [e for e in obs.chrome_events()
             if e["name"] == "jit.TrainStep"]
    assert len(spans) == 3

    # exported artifacts parse (the acceptance criterion)
    d = obs.export_all(str(tmp_path), journal=True)
    prom = open(os.path.join(d, "metrics.rank0.prom")).read()
    assert "pt_train_steps_total" in prom
    snap = json.load(open(os.path.join(d, "metrics.rank0.json")))
    assert snap["pt_train_steps_total"]["type"] == "counter"
    with open(os.path.join(d, "trace.rank0.jsonl")) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert any(e["name"] == "jit.TrainStep" for e in lines)
    # the journal fold: telemetry and chaos forensics share one stream
    from paddle_tpu.distributed import resilience

    evs = resilience.events("telemetry_snapshot")
    assert evs and "pt_train_steps_total" in evs[-1]["metrics"]


def test_trainstep_mode_flip_does_not_break_running_step(mode):
    """A step BUILT without the grad-norm aux keeps working after the
    mode flips to full (the aux choice is frozen at build time)."""
    obs.set_mode("metrics")
    step, x, y = _tiny_train_step()
    step(x, y)
    obs.set_mode("full")
    loss = step(x, y)
    assert np.isfinite(float(loss.numpy()))


def _tiny_llm_server(**cfg_kw):
    from paddle_tpu import inference
    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import gpt_tiny

    paddle.seed(0)
    model = GPTForCausalLM(gpt_tiny())
    cfg = inference.LLMEngineConfig(
        num_slots=2, page_size=16, max_model_len=64, **cfg_kw)
    return inference.LLMServer(model, cfg)


def test_llm_engine_tick_telemetry(mode):
    """One LLMEngine tick with telemetry on: queue/slot/pool gauges,
    token split, admission/TTFT histograms, span."""
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    obs.set_mode("full")
    obs_tracing.reset()
    reg = obs.registry()
    server = _tiny_llm_server()
    eng = server.engine
    steps0 = reg.get("pt_llm_steps_total").value \
        if reg.get("pt_llm_steps_total") else 0
    rng = np.random.default_rng(0)
    req = eng.add_request(rng.integers(0, 2048, (7,)), max_new_tokens=4)
    while eng.has_work():
        eng.step()
    out = req.future.result(timeout=60)
    assert len(out) == 11
    m = server.metrics()
    assert m["queue_depth"] == 0 and m["live_slots"] == 0
    assert m["finished"] >= 1 and m["executables"] == 1
    assert m["decode_tokens"] >= 4 and m["prefill_tokens"] >= 6
    assert m["ttft_p50_s"] > 0 and m["admission_p50_s"] >= 0
    assert 0.0 <= m["kv_fragmentation"] <= 1.0
    assert reg.get("pt_llm_steps_total").value > steps0
    assert any(e["name"] == "llm_engine.step"
               for e in obs.chrome_events())
    eng.pool.assert_consistent()


def test_llm_server_metrics_http_under_concurrency(mode):
    """LLMServer.metrics() + the stdlib /metrics endpoint stay coherent
    while clients submit concurrently."""
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.reset_mesh()
    obs.set_mode("metrics")
    server = _tiny_llm_server()
    rng = np.random.default_rng(1)
    scrapes, errors = [], []

    def scraper(url):
        try:
            for _ in range(5):
                body = urllib.request.urlopen(url, timeout=30).read()
                scrapes.append(body.decode())
                time.sleep(0.01)
        except Exception as e:     # surfaced below
            errors.append(e)

    with server:
        handle = server.start_metrics_http()
        futs = [server.submit(rng.integers(0, 2048, (int(n),)),
                              max_new_tokens=3)
                for n in rng.integers(4, 20, 6)]
        threads = [threading.Thread(target=scraper, args=(handle.url,))
                   for _ in range(3)]
        for t in threads:
            t.start()
        outs = [f.result(timeout=120) for f in futs]
        for t in threads:
            t.join()
        m = server.metrics()
        j = json.loads(urllib.request.urlopen(
            handle.url + ".json", timeout=30).read())
    assert not errors, errors
    assert len(outs) == 6 and all(len(o) > 0 for o in outs)
    assert m["finished"] >= 6
    assert j["extra"]["num_slots"] == 2
    assert "pt_llm_steps_total" in j["metrics"]
    for body in scrapes:
        assert "pt_llm_steps_total" in body
    # endpoint is down after stop()
    assert server._http is None


def test_checkpoint_metrics_and_torn_fallback(mode, tmp_path):
    from paddle_tpu.distributed import checkpoint as ckpt

    obs.set_mode("metrics")
    reg = obs.registry()

    def val(name, **labels):
        metric = reg.get(name)
        if metric is None:
            return 0
        return (metric.labels(**labels) if labels else metric).value

    saves0 = val("pt_ckpt_ops_total", op="save")
    saved0 = val("pt_ckpt_bytes_total", direction="saved")
    state = {"w": paddle.to_tensor(np.ones((32, 32), np.float32))}
    ckpt.save_state_dict(state, str(tmp_path / "c1"))
    ckpt.load_state_dict(str(tmp_path / "c1"))
    assert val("pt_ckpt_ops_total", op="save") == saves0 + 1
    assert val("pt_ckpt_bytes_total",
               direction="saved") - saved0 == 32 * 32 * 4
    assert val("pt_ckpt_ops_total", op="load") >= 1
    assert reg.get("pt_ckpt_save_seconds").count >= 1

    # torn fallback counter: truncate the newest checkpoint's shard
    torn0 = val("pt_ckpt_torn_fallbacks_total")
    cp = ckpt.Checkpointer(str(tmp_path / "run"))
    ckpt.save_state_dict({"step": 1, "w": state["w"]},
                         os.path.join(str(tmp_path / "run"),
                                      "ckpt-00000001"))
    ckpt.save_state_dict({"step": 2, "w": state["w"]},
                         os.path.join(str(tmp_path / "run"),
                                      "ckpt-00000002"))
    shard_dir = tmp_path / "run" / "ckpt-00000002" / "shards"
    shard = next(shard_dir.iterdir())
    shard.write_bytes(b"torn")
    assert cp.load_latest() == 1
    assert val("pt_ckpt_torn_fallbacks_total") == torn0 + 1


def test_xproc_stats_deprecated_view(mode):
    """The old xproc.stats keys read through to the normalized registry
    counters; writes are deprecated and only offset the view."""
    from paddle_tpu.distributed import xproc

    obs.set_mode("metrics")
    assert set(xproc.stats) == {
        "p2p_bytes", "gather_bytes", "kv_bulk_bytes", "socket_bytes",
        "kv_retries", "connect_retries", "send_retries"}
    base = xproc.stats["p2p_bytes"]
    xproc._BYTES_TOTAL.labels(channel="p2p").inc(100)
    assert xproc.stats["p2p_bytes"] == base + 100
    with pytest.warns(DeprecationWarning):
        xproc.stats["p2p_bytes"] = 0
    assert xproc.stats["p2p_bytes"] == 0
    xproc._BYTES_TOTAL.labels(channel="p2p").inc(7)
    assert xproc.stats["p2p_bytes"] == 7          # offset view, counter
    assert xproc._BYTES_TOTAL.labels(               # itself untouched
        channel="p2p").value >= base + 107
    with pytest.raises(TypeError):
        del xproc.stats["p2p_bytes"]
    with pytest.raises(KeyError):
        xproc.stats["unknown_key"] = 1
    # retry counters share resilience's unified op naming
    r0 = xproc.stats["kv_retries"]
    xproc._count_retry("kv")(1, OSError())
    assert xproc.stats["kv_retries"] == r0 + 1


@pytest.mark.slow
@pytest.mark.chaos
def test_two_proc_telemetry_export(tmp_path):
    """ISSUE-3 acceptance: a 2-proc run (chaos plan active) under
    PT_TELEMETRY=1 produces parseable per-rank metrics snapshots and a
    merged chrome trace covering TrainStep/checkpoint/xproc spans."""
    import subprocess
    import sys as _sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "PYTHONPATH": root + os.pathsep + env.get("PYTHONPATH", ""),
        "PT_TELEMETRY": "1",
        "PT_TELEMETRY_DIR": str(tmp_path / "telemetry"),
        # seeded chaos: transient kv faults ride the same run, proving
        # telemetry and chaos share one event stream
        "PT_CHAOS_PLAN": json.dumps({"seed": 7, "injectors": [
            {"scope": "kv.get", "kind": "error", "p": 0.05}]}),
    })
    r = subprocess.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", f"--log_dir={tmp_path}/log",
         os.path.join(root, "tests", "telemetry_worker.py"),
         str(tmp_path)],
        env=env, cwd=root, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"

    telem = tmp_path / "telemetry"
    for rank in (0, 1):
        with open(tmp_path / f"telemetry_out_{rank}.json") as f:
            out = json.load(f)
        assert out["mode"] == "full"
        # metrics snapshot parses and carries the instrumented families
        snap = json.load(open(telem / f"metrics.rank{rank}.json"))
        assert snap["pt_train_steps_total"]["series"][0]["value"] == 3
        assert "pt_ckpt_ops_total" in snap
        assert "pt_xproc_bytes_total" in snap
        prom = open(telem / f"metrics.rank{rank}.prom").read()
        assert "pt_train_step_seconds_bucket" in prom

    # merged chrome trace covers the span families, both ranks
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(root, "tools", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)
    outfile = tmp_path / "trace.json"
    assert tm.main([str(telem), "-o", str(outfile)]) == 0
    trace = json.load(open(outfile))
    events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in events}
    assert {"jit.TrainStep", "ckpt.save", "ckpt.load",
            "xproc.send", "xproc.recv",
            "xproc.all_reduce"} <= names, names
    assert {e["pid"] for e in events} == {0, 1}

    # the unified stream: journal holds the telemetry snapshot next to
    # any chaos/retry events
    journal_kinds = set()
    for rank in (0, 1):
        jpath = tmp_path / "log" / f"anomalies.rank{rank}.jsonl"
        if jpath.exists():
            for line in open(jpath):
                journal_kinds.add(json.loads(line)["kind"])
    assert "telemetry_snapshot" in journal_kinds


def test_decorated_span_error_does_not_poison_other_calls(mode):
    """The decorator shares one args dict across calls; the error
    annotation must land on a COPY, not retroactively mark successful
    spans as failed."""
    obs.set_mode("full")
    obs_tracing.reset()

    @obs.trace_span("maybe", tag="x")
    def maybe(fail):
        if fail:
            raise ValueError("boom")

    maybe(False)
    with pytest.raises(ValueError):
        maybe(True)
    maybe(False)
    evs = [e for e in obs.chrome_events() if e["name"] == "maybe"]
    assert [("error" in e["args"]) for e in evs] == [False, True, False]
    assert all(e["args"]["tag"] == "x" for e in evs)


def test_xproc_stats_count_even_in_off_mode(mode):
    """xproc.stats consumers predate the telemetry gate — PT_TELEMETRY=0
    must not zero the byte/retry accounting (always_on counters)."""
    from paddle_tpu.distributed import xproc

    obs.set_mode("off")
    before = xproc.stats["socket_bytes"]
    xproc._BYTES_TOTAL.labels(channel="socket").inc(11)
    r_before = xproc.stats["kv_retries"]
    xproc._count_retry("kv")(1, OSError())
    assert xproc.stats["socket_bytes"] == before + 11
    assert xproc.stats["kv_retries"] == r_before + 1


def test_mode_env_parse(monkeypatch):
    """PT_TELEMETRY accepts the documented mode NAMES: 'metrics' must
    not silently enable full mode (grad-norm aux + file exports)."""
    cases = {"0": 0, "off": 0, "": 1, "metrics": 1, "counters": 1,
             "1": 2, "full": 2, "on": 2}
    for env, want in cases.items():
        monkeypatch.setenv("PT_TELEMETRY", env)
        assert obs_metrics._State().mode == want, env


def test_trace_flush_truncates_per_process(mode, tmp_path):
    """A fresh process's first flush truncates trace.rank<r>.jsonl —
    successive runs sharing PT_TELEMETRY_DIR must not concatenate into
    one file (trace_merge would fold distinct runs onto one timeline)."""
    obs.set_mode("full")
    obs_tracing.reset()
    with obs.trace_span("run1"):
        pass
    path = obs_tracing.flush(str(tmp_path))
    with obs.trace_span("run1b"):
        pass
    obs_tracing.flush(str(tmp_path))        # same process: appends
    names = [json.loads(ln)["name"] for ln in open(path)]
    assert names == ["run1", "run1b"]
    obs_tracing._flushed_paths.discard(path)  # simulate a new process
    with obs.trace_span("run2"):
        pass
    obs_tracing.flush(str(tmp_path))
    names = [json.loads(ln)["name"] for ln in open(path)]
    assert names == ["run2"]


def test_elastic_peer_gauges_drop_departed_ranks(mode):
    from paddle_tpu.distributed.fleet.elastic import (
        ElasticManager, _PEER_AGE, _PEERS, _STALE_PEERS)

    obs.set_mode("metrics")
    mgr = ElasticManager()
    mgr.timeout = 30.0
    mgr._gauge_peers([(0, 1.0), (1, 2.0), (2, 99.0)])
    assert _PEERS.value == 3 and _STALE_PEERS.value == 1
    assert _PEER_AGE.labels(rank="2").value == 99.0
    mgr._gauge_peers([(0, 1.5)])            # ranks 1, 2 departed
    assert _PEERS.value == 1 and _STALE_PEERS.value == 0
    assert ("1",) not in _PEER_AGE._children
    assert ("2",) not in _PEER_AGE._children
    assert _PEER_AGE.labels(rank="0").value == 1.5


def test_steptimer_feeds_shared_registry(mode):
    """profiler.benchmark() and hapi's ProgBarLogger source from the
    same meter + registry histograms (identical numbers satellite)."""
    from paddle_tpu import profiler

    obs.set_mode("metrics")
    reg = obs.registry()
    h0 = reg.get("pt_step_batch_cost_seconds")
    n0 = h0.count if h0 else 0
    bm = profiler.benchmark()
    bm.enable()
    try:
        bm.step()
        for _ in range(3):
            time.sleep(0.001)
            bm.auto_step(num_samples=4)
        s = bm.stats()
        assert s["steps"] == 3 and bm.auto_fed
        assert reg.get("pt_step_batch_cost_seconds").count - n0 == 3
        assert reg.get("pt_step_samples_total").value >= 12
    finally:
        bm.disable()
