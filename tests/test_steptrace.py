"""Training goodput plane (observability/steptrace.py) — ISSUE-18.

Pins: the segments-sum-to-wall-clock identity (UNROUNDED) for the
instrumented step families; quiet warm-up exclusion (compile steps stay
out of pt_train_phase_seconds); the ckpt_snapshot carve-out and the
preemption/restore path; the recompile sentinel (counter + flight
postmortem); the analytic FLOPs accountant shared with bench.py and the
continuous MFU/goodput gauges; straggler attribution — straggler_of on
cross-rank views and tools/trace_merge.py --train-report over per-rank
step.<phase> chrome events (chaos-verified in the slow 2-proc test);
collective bytes/s attribution; and the profiler step-timer dt routing
that keeps the shared meter and the phase plane in agreement.
"""
import glob
import importlib.util
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, observability as obs
from paddle_tpu.observability import steptrace
from paddle_tpu.observability import tracing as obs_tracing

pytestmark = pytest.mark.observability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the acceptance bar is 1e-6; the chain identity is exact up to float
# telescoping, so pin much tighter
SUM_TOL = 1e-9

# `device_step` is the block_until_ready delta: full mode only (no
# stamp stalls a step in the default mode)
EMITTING = {"data_wait", "h2d", "dispatch", "opt_publish"}
EMITTING_FULL = EMITTING | {"device_step"}


@pytest.fixture
def mode():
    """Restore mode and drop steptrace/tracing state after each test."""
    prev = obs.mode()
    yield obs
    obs.set_mode(prev)
    obs_tracing.reset()
    steptrace.reset()


def _load_trace_merge():
    spec = importlib.util.spec_from_file_location(
        "trace_merge", os.path.join(ROOT, "tools", "trace_merge.py"))
    tm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tm)
    return tm


def _tiny_step(seed=0):
    paddle.seed(seed)
    m = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(
        m, lambda mm, x, y: nn.functional.cross_entropy(mm(x), y), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal((4, 8)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 4, (4,)))
    return m, opt, step, x, y


def _assert_identity(rec):
    """The exported invariant: unrounded segment durations sum to the
    step's wall time, and every segment is non-negative."""
    dts = [e["dt_s"] for e in rec["timeline"]]
    assert all(dt >= 0.0 for dt in dts)
    assert abs(sum(dts) - rec["total_s"]) < SUM_TOL


# ------------------------------------------------ phase decomposition

@pytest.mark.parametrize("telemetry,emitting", [
    ("metrics", EMITTING), ("full", EMITTING_FULL)])
def test_trainstep_phase_identity_and_quiet_warmup(mode, telemetry,
                                                   emitting):
    """4 calls → 3 ring records (the compile step runs quiet); each
    record's segments sum exactly to its wall time, stamps arrive in
    the canonical order, and the histogram carries every phase the
    mode takes: `device_step` in full mode only."""
    obs.set_mode(telemetry)
    steptrace.reset()
    ps0 = steptrace.phase_summary()
    _, _, step, x, y = _tiny_step()
    for _ in range(4):
        step(x, y)
    recs = steptrace.recent_steps()
    assert [r["step"] for r in recs] == [1, 2, 3]
    assert all(r["family"] == "train" for r in recs)
    order = {p: i for i, p in
             enumerate(("start",) + steptrace.PHASES)}
    for rec in recs:
        _assert_identity(rec)
        names = [e["phase"] for e in rec["timeline"]]
        assert names[0] == "start"
        idx = [order[n] for n in names]
        assert idx == sorted(idx), names
        assert set(names) - {"start"} == emitting
    ps = steptrace.phase_summary()
    for phase in EMITTING_FULL:
        delta = ps.get(phase, {}).get("count", 0) \
            - ps0.get(phase, {}).get("count", 0)
        assert delta == (3 if phase in emitting else 0), (phase, delta)
    # the internal chain anchor is never a histogram label
    assert "start" not in ps


def test_stamp_first_wins_and_replay_noop(mode):
    obs.set_mode("metrics")
    tr = steptrace.begin_step("train", 7, prev_end=100.0,
                              t_entry=100.25)
    assert tr.stamp("h2d", 100.3)
    assert not tr.stamp("h2d", 999.0)     # replay keeps the first truth
    assert tr.phases["h2d"] == 100.3
    tr.stamp("dispatch", 100.4)
    tr.stamp("opt_publish", 100.5)
    total, end_t = steptrace.end_step(tr)
    assert total == pytest.approx(0.5)
    assert end_t == 100.5
    tl = tr.timeline()
    assert [e["phase"] for e in tl] == \
        ["start", "data_wait", "h2d", "dispatch", "opt_publish"]
    assert sum(e["dt_s"] for e in tl) == pytest.approx(total,
                                                       abs=SUM_TOL)
    assert tr.to_dict()["phases"] == tr.phases


def test_ckpt_snapshot_carved_from_data_wait(mode):
    """A pending snapshot interval inside the prev-step→entry gap
    becomes its own segment — and is consumed exactly once."""
    obs.set_mode("metrics")
    steptrace.reset()
    steptrace.note_ckpt_snapshot(100.05, 100.2)
    tr = steptrace.begin_step("train", 3, prev_end=100.0,
                              t_entry=100.25)
    assert [e["phase"] for e in tr.timeline()] == \
        ["start", "ckpt_snapshot", "data_wait"]
    tr2 = steptrace.begin_step("train", 4, prev_end=200.0,
                               t_entry=200.1)
    assert "ckpt_snapshot" not in tr2.phases


def test_preemption_restore_keeps_identity_and_ckpt_phase(mode,
                                                          tmp_path):
    """Checkpointer.save between steps surfaces as the next step's
    ckpt_snapshot segment; after a preempt+restore the identity and
    quiet-warm-up rules hold unchanged on the restored step object."""
    from paddle_tpu.distributed.checkpoint import Checkpointer

    obs.set_mode("metrics")
    steptrace.reset()
    m, _, step, x, y = _tiny_step()
    for _ in range(3):
        step(x, y)
    cp = Checkpointer(str(tmp_path / "run"), model=m, train_step=step)
    cp.save(3)
    step(x, y)     # the step AFTER the save carries the snapshot time
    rec = steptrace.recent_steps()[-1]
    assert "ckpt_snapshot" in {e["phase"] for e in rec["timeline"]}
    _assert_identity(rec)

    # preempt: fresh objects (different init — must be overwritten)
    m2, opt2, step2, _, _ = _tiny_step(seed=123)
    cp2 = Checkpointer(str(tmp_path / "run"), model=m2,
                       train_step=step2)
    assert cp2.load_latest() == 3
    steptrace.reset()
    for _ in range(3):
        step2(x, y)
    recs = steptrace.recent_steps()
    # restored step compiles (fresh signature set) → quiet, excluded
    assert [r["step"] for r in recs] == [4, 5]
    for rec in recs:
        _assert_identity(rec)
        assert EMITTING <= {e["phase"] for e in rec["timeline"]}


@pytest.mark.slow
def test_quiet_warmup_distributed_and_hybrid_families(mode):
    """All three step classes run their compile step quiet: two calls
    on one batch → exactly ONE ring record, correctly family-labeled,
    with the sum identity intact."""
    from paddle_tpu.distributed import hybrid3d
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.parallel_step import DistributedTrainStep
    from paddle_tpu.text.models.gpt import GPTConfig

    obs.set_mode("metrics")
    try:
        steptrace.reset()
        mesh_mod.reset_mesh()
        mesh_mod.init_mesh(dp=8)
        paddle.seed(0)
        net = nn.Linear(16, 4)
        opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
        dstep = DistributedTrainStep(
            net, lambda mm, a, b: nn.functional.mse_loss(mm(a), b), opt)
        rng = np.random.default_rng(1)
        dx = paddle.to_tensor(
            rng.standard_normal((16, 16)).astype(np.float32))
        dy = paddle.to_tensor(
            rng.standard_normal((16, 4)).astype(np.float32))
        dstep(dx, dy)
        dstep(dx, dy)
        recs = steptrace.recent_steps()
        assert [(r["family"], r["step"]) for r in recs] == [("dist", 1)]
        _assert_identity(recs[0])

        steptrace.reset()
        mesh_mod.reset_mesh()
        cfg3d = hybrid3d.Hybrid3DConfig(dp=2, tp=2, pp=2)
        hybrid3d.init_hybrid_mesh(cfg3d)
        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=16)
        paddle.seed(0)
        hm = hybrid3d.build_gpt3d(cfg, cfg3d)
        hopt = paddle.optimizer.AdamW(1e-3,
                                      parameters=hm.parameters())
        hstep = hybrid3d.HybridTrainStep(hm, lambda mm, i: mm.loss(i),
                                         hopt, config=cfg3d)
        ids = paddle.to_tensor(
            np.random.default_rng(2).integers(0, 64, (8, 16)))
        hstep(ids)
        hstep(ids)
        recs = steptrace.recent_steps()
        assert [(r["family"], r["step"])
                for r in recs] == [("hybrid3d", 1)]
        _assert_identity(recs[0])
    finally:
        mesh_mod.reset_mesh()


def test_off_mode_emits_nothing(mode):
    obs.set_mode("off")
    steptrace.reset()
    _, _, step, x, y = _tiny_step()
    for _ in range(3):
        step(x, y)
    assert steptrace.recent_steps() == []
    assert not steptrace.active()


# --------------------------------------------------- recompile sentinel

def test_recompile_sentinel_counts_and_dumps(mode, tmp_path,
                                             monkeypatch):
    """Post-warm-up batch-signature growth increments
    pt_step_recompiles_total{step}, runs the recompiling step quiet,
    and dumps a flight-recorder postmortem carrying recent timelines."""
    monkeypatch.setenv("PT_FLIGHT_DIR", str(tmp_path))
    obs.set_mode("metrics")
    steptrace.reset()
    reg = obs.registry()

    def n_rec():
        c = reg.get("pt_step_recompiles_total")
        return 0 if c is None else c.labels(step="train").value

    base = n_rec()
    _, _, step, x, y = _tiny_step()
    step(x, y)                    # warm-up compile — NOT a recompile
    step(x, y)
    assert n_rec() == base
    n_ring = len(steptrace.recent_steps())
    x2 = paddle.to_tensor(np.zeros((6, 8), np.float32))
    y2 = paddle.to_tensor(np.zeros((6,), np.int64))
    step(x2, y2)                  # post-warm-up signature growth
    assert n_rec() == base + 1
    # the recompiling step itself ran quiet (no ring record)
    assert len(steptrace.recent_steps()) == n_ring
    dumps = sorted(tmp_path.glob("postmortem.*.step_recompile.json"))
    assert dumps, list(tmp_path.iterdir())
    post = json.loads(dumps[-1].read_text())
    assert post["context"]["signatures"] == 2
    assert post["context"]["family"] == "train"
    assert "recent_steps" in post["states"]
    assert any(e["kind"] == "step_recompile" for e in post["events"])


# ------------------------------------------------------ goodput gauges

def test_goodput_gauges_continuous(mode):
    obs.set_mode("metrics")
    steptrace.reset()
    steptrace.arm_goodput(flops_per_step=1e12, tokens_per_step=4096,
                          peak_flops=2e14)
    assert steptrace.goodput_armed()
    tr = steptrace.begin_step("train", 1, prev_end=1000.0,
                              t_entry=1000.1)
    tr.stamp("h2d", 1000.2)
    tr.stamp("opt_publish", 1000.5)
    total, _ = steptrace.end_step(tr)
    assert total == pytest.approx(0.5)
    reg = obs.registry()
    assert reg.get("pt_train_mfu").value == \
        pytest.approx(1e12 / 0.5 / 2e14)
    assert reg.get("pt_train_tokens_per_second").value == \
        pytest.approx(4096 / 0.5)
    # quiet steps never move the gauges
    mfu = reg.get("pt_train_mfu").value
    trq = steptrace.begin_step("train", 2, prev_end=2000.0,
                               quiet=True, t_entry=2000.1)
    trq.stamp("opt_publish", 2000.9)
    steptrace.end_step(trq)
    assert reg.get("pt_train_mfu").value == mfu
    steptrace.arm_goodput()       # no args = disarm
    assert not steptrace.goodput_armed()


def test_goodput_gauges_read_step_intervals_without_a_sync(
        mode, monkeypatch):
    """Default mode: no train step waits for the device (the calls to
    jax.block_until_ready are counted), a step's chain runs from the
    previous step's last stamp to its own, and the goodput gauge is
    tokens over that interval. Full mode syncs once a step and stamps
    `device_step`."""
    import jax

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    obs.set_mode("metrics")
    steptrace.reset()
    steptrace.arm_goodput(tokens_per_step=4096)
    _, _, step, x, y = _tiny_step()
    for _ in range(4):
        step(x, y)
    assert calls == []
    recs = steptrace.recent_steps()
    assert [r["step"] for r in recs] == [1, 2, 3]
    for prev, rec in zip(recs, recs[1:]):
        assert rec["timeline"][0]["phase"] == "start"
        assert rec["timeline"][0]["t"] == prev["timeline"][-1]["t"]
        assert "device_step" not in {e["phase"] for e in rec["timeline"]}
    assert obs.registry().get("pt_train_tokens_per_second").value == \
        pytest.approx(4096 / recs[-1]["total_s"])
    steptrace.arm_goodput()

    obs.set_mode("full")
    steptrace.reset()
    _, _, step, x, y = _tiny_step()
    for _ in range(3):
        step(x, y)
    assert len(calls) == 3
    assert all("device_step" in {e["phase"] for e in r["timeline"]}
               for r in steptrace.recent_steps())


def test_model_flops_accountant():
    """The analytic accountant: dict and object configs agree, the
    default ffn is 4·d, and bench.py's gpt_flops_per_step IS this
    function (one MFU denominator for bench and the live gauge)."""
    cfg = {"hidden_size": 64, "num_layers": 4, "vocab_size": 256}
    d, L, v, ffn = 64, 4, 256, 256
    per_layer = 4 * d * d + 2 * d * ffn
    p_matmul = L * per_layer + v * d
    tokens = 8 * 32
    want = 6 * p_matmul * tokens + L * 8 * (4 * 32 * 32 * d) * 3 * 0.5
    assert steptrace.model_flops(cfg, 8, 32) == want

    class C:
        hidden_size, num_layers, vocab_size = 64, 4, 256

    assert steptrace.model_flops(C(), 8, 32) == want
    assert steptrace.model_flops(dict(cfg, ffn_size=128), 8, 32) != want

    import bench

    assert bench.gpt_flops_per_step(C(), 8, 32) == want


# ------------------------------------------------ straggler attribution

def test_straggler_of_names_rank_and_phase():
    base = {"start": 0.0, "data_wait": 0.01, "h2d": 0.02,
            "dispatch": 0.05, "opt_publish": 0.06}
    slow = dict(base, dispatch=0.15, opt_publish=0.16)
    out = steptrace.straggler_of([{"rank": 0, "phases": base},
                                  {"rank": 1, "phases": slow},
                                  {"rank": 2, "phases": base}])
    assert out["rank"] == 1
    assert out["phase"] == "dispatch"
    assert out["lag_s"] == pytest.approx(0.10)
    assert set(out["per_rank"]) == {0, 1, 2}
    # timeline-form views (ring records); None entries are skipped
    tl = lambda dt: [{"phase": "start", "t": 0.0, "dt_s": 0.0},  # noqa: E731
                     {"phase": "h2d", "t": dt, "dt_s": dt}]
    out2 = steptrace.straggler_of(
        [None,
         {"rank": 3, "timeline": tl(0.02), "total_s": 0.02},
         {"rank": 4, "timeline": tl(0.30), "total_s": 0.30}])
    assert out2["rank"] == 4 and out2["phase"] == "h2d"
    assert steptrace.straggler_of([]) is None


def test_collective_bytes_per_second():
    out = steptrace.collective_bytes_per_second(
        {"dp": 100, "mp": 500}, 0.10, {"dp": 600, "mp": 500}, 0.20)
    assert out["dp"]["bytes_per_s"] == pytest.approx(500 / 0.10)
    assert out["dp"]["delta_bytes"] == 500
    assert out["mp"]["bytes_per_s"] is None     # bytes don't differ
    # non-positive time delta: noise swamped the signal — no rate
    neg = steptrace.collective_bytes_per_second(
        {"dp": 0}, 0.30, {"dp": 100}, 0.20)
    assert neg["dp"]["bytes_per_s"] is None


# -------------------------------------------------- chrome train lanes

def test_full_mode_chrome_events_feed_train_report(mode):
    """Full mode: every non-quiet segment becomes a step.<phase>
    chrome event whose args carry the step join key, and
    trace_merge.train_report rebuilds per-step per-rank lanes."""
    obs.set_mode("full")
    obs_tracing.reset()
    steptrace.reset()
    _, _, step, x, y = _tiny_step()
    for _ in range(3):
        step(x, y)
    evs = [e for e in obs.chrome_events()
           if e["name"].startswith("step.")]
    assert {"step." + p for p in EMITTING_FULL} <= {e["name"]
                                                    for e in evs}
    assert all("step" in e["args"] and "family" in e["args"]
               for e in evs)
    report = _load_trace_merge().train_report(evs)
    assert [r["step"] for r in report] == [1, 2]
    for r in report:
        assert set(r["ranks"]) == {0}
        assert r["ranks"][0]["family"] == "train"
        assert r["ranks"][0]["total_ms"] >= 0


def test_train_report_cli_names_seeded_straggler(tmp_path):
    """Synthetic 2-rank streams with a 50 ms delay folded into rank
    1's dispatch: the CLI's --train-report names that rank AND that
    phase for every step."""

    def ev(rank, step_i, phase, ts_us, dur_us):
        return {"name": f"step.{phase}", "ph": "X", "ts": ts_us,
                "dur": dur_us, "pid": rank, "tid": 0,
                "args": {"step": step_i, "family": "dist"}}

    for rank in (0, 1):
        with open(tmp_path / f"trace.rank{rank}.jsonl", "w") as f:
            t = 1_000_000
            for step_i in (1, 2):
                for phase, dur in (
                        ("data_wait", 1000), ("h2d", 2000),
                        ("dispatch",
                         5000 + (50_000 if rank == 1 else 0)),
                        ("opt_publish", 1500)):
                    f.write(json.dumps(ev(rank, step_i, phase, t,
                                          dur)) + "\n")
                    t += dur
    tm = _load_trace_merge()
    out = tmp_path / "report.json"
    assert tm.main([str(tmp_path), "-o", str(tmp_path / "trace.json"),
                    "--train-report", str(out)]) == 0
    report = json.loads(out.read_text())
    assert [r["step"] for r in report] == [1, 2]
    for r in report:
        assert r["slowest_rank"] == 1
        assert r["slow_phase"] == "dispatch"
        assert r["lag_ms"] == pytest.approx(50.0)
        assert set(r["ranks"]) == {"0", "1"}


# ----------------------------------------------------- meter routing

def test_steptimer_records_explicit_dt(mode):
    from paddle_tpu import profiler

    obs.set_mode("metrics")
    bm = profiler.benchmark()
    bm.enable()
    try:
        bm.auto_step(num_samples=8, dt=0.25)
        bm.auto_step(num_samples=8, dt=0.35)
        assert bm.step_times == [0.25, 0.35]
        assert bm.stats()["avg_batch_cost_s"] == pytest.approx(0.30)
        assert bm.auto_fed
    finally:
        bm.disable()


def test_trainstep_feeds_meter_with_steptrace_wall(mode):
    """With the phase plane on, the instrumented step hands the meter
    its measured wall (anchor→opt_publish) — the shared meter and
    pt_train_phase_seconds cannot disagree about step cost."""
    from paddle_tpu import profiler

    obs.set_mode("metrics")
    steptrace.reset()
    bm = profiler.benchmark()
    bm.enable()
    try:
        _, _, step, x, y = _tiny_step()
        for _ in range(3):
            step(x, y)
        recs = steptrace.recent_steps()
        # compile step self-clocks (first tick records nothing); the
        # two non-quiet steps record exactly their traced totals
        assert bm.step_times == [r["total_s"] for r in recs]
    finally:
        bm.disable()


# --------------------------------------------- 2-proc chaos acceptance

@pytest.mark.slow
@pytest.mark.chaos
def test_two_proc_straggler_attribution(tmp_path):
    """ISSUE-18 acceptance: a 2-proc run with a seeded 50 ms delay on
    rank 1's step.dispatch scope → the live cross-rank exchange AND
    the merged trace's train report both name rank 1 / dispatch."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
        "PT_TELEMETRY": "1",
        "PT_TELEMETRY_DIR": str(tmp_path / "telemetry"),
        "PT_CHAOS_PLAN": json.dumps({"seed": 0, "injectors": [
            {"scope": "step.dispatch", "kind": "delay", "ranks": [1],
             "p": 1.0, "delay_s": 0.05}]}),
    })
    r = subprocess.run(
        [_sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", f"--log_dir={tmp_path}/log",
         os.path.join(ROOT, "tests", "steptrace_worker.py"),
         str(tmp_path)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, f"stdout:{r.stdout}\nstderr:{r.stderr}"

    out = json.load(open(tmp_path / "steptrace_out_0.json"))
    assert out["straggler"]["rank"] == 1
    assert out["straggler"]["phase"] == "dispatch"
    assert out["straggler"]["lag_s"] >= 0.03
    # identity holds on every rank's records (acceptance: unrounded)
    for rank in (0, 1):
        o = json.load(open(tmp_path / f"steptrace_out_{rank}.json"))
        assert o["recent"], "no non-quiet steps recorded"
        for rec in o["recent"]:
            assert abs(sum(e["dt_s"] for e in rec["timeline"])
                       - rec["total_s"]) < 1e-6

    tm = _load_trace_merge()
    events, bad = tm.collect(sorted(glob.glob(
        str(tmp_path / "telemetry" / "trace.rank*.jsonl"))))
    report = tm.train_report(events)
    assert report, "no train lanes in the merged trace"
    votes = [(r["slowest_rank"], r["slow_phase"]) for r in report]
    # every post-warm-up step should name the seeded rank and phase
    assert votes.count((1, "dispatch")) >= len(votes) - 1, votes
