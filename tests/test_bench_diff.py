"""Perf-regression sentinel (tools/bench_diff.py) on synthetic stamp
pairs: direction inference, tolerance bands, the honesty rules (never
compare across backends; a parsed=null driver shell is "no data", not
"no regression"), and the latest-vs-previous directory workflow.
"""
import importlib.util
import json
import os

import pytest

pytestmark = pytest.mark.observability

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bd():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(ROOT, "tools", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _stamp(backend="cpu", **detail):
    return {"metric": "ms_per_step", "value": 1.0, "unit": "ms",
            "backend": backend, "detail": detail}


def _write(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


# ------------------------------------------------------------ direction

def test_direction_inference():
    bd = _bd()
    assert bd.direction_of("detail.ms_per_step") == "lower"
    assert bd.direction_of("detail.ttft_p99") == "lower"
    assert bd.direction_of("detail.dp.bytes") == "lower"
    assert bd.direction_of("detail.compile_s") == "lower"
    assert bd.direction_of("detail.final_loss_delta") == "lower"
    assert bd.direction_of("detail.overhead_ratio") == "lower"
    assert bd.direction_of("detail.tokens_per_s") == "higher"
    assert bd.direction_of("detail.mfu") == "higher"
    assert bd.direction_of("detail.dp.bytes_per_s") == "higher"
    assert bd.direction_of("detail.affinity_hit_rate") == "higher"
    assert bd.direction_of("detail.vs_baseline") == "higher"
    # identity/config leaves are never gated
    assert bd.direction_of("detail.model") is None
    assert bd.direction_of("detail.n_devices") is None


def test_flatten_skips_bools_and_strings():
    bd = _bd()
    flat = bd.flatten({"a": {"b": 1.5, "name": "gpt", "ok": True},
                       "xs": [1, 2]})
    assert flat == {"a.b": 1.5, "xs.0": 1.0, "xs.1": 2.0}


# ----------------------------------------------------------------- diff

def test_regression_detected_both_directions():
    bd = _bd()
    rep = bd.diff(_stamp(ms_per_step=100.0, tokens_per_s=1000.0),
                  _stamp(ms_per_step=120.0, tokens_per_s=1000.0))
    assert rep["comparable"]
    assert [r["metric"] for r in rep["regressions"]] == \
        ["detail.ms_per_step"]
    rep = bd.diff(_stamp(tokens_per_s=1000.0),
                  _stamp(tokens_per_s=800.0))
    assert [r["metric"] for r in rep["regressions"]] == \
        ["detail.tokens_per_s"]


def test_within_tolerance_and_improvement():
    bd = _bd()
    rep = bd.diff(_stamp(ms_per_step=100.0),
                  _stamp(ms_per_step=105.0))     # +5% < 10% band
    assert not rep["regressions"]
    rep = bd.diff(_stamp(ms_per_step=100.0),
                  _stamp(ms_per_step=50.0))
    assert not rep["regressions"]
    assert [r["metric"] for r in rep["improvements"]] == \
        ["detail.ms_per_step"]
    # absolute floor: micro-noise near zero never trips
    rep = bd.diff(_stamp(stall_s=0.0), _stamp(stall_s=1e-12),
                  abs_tol=1e-9)
    assert not rep["regressions"]


def test_backend_mismatch_never_compares():
    bd = _bd()
    rep = bd.diff(_stamp(backend="cpu_fallback", ms_per_step=100.0),
                  _stamp(backend="accelerator", ms_per_step=1.0))
    assert not rep["comparable"]
    assert "backend mismatch" in rep["reason"]
    assert not rep["rows"]
    # the device record bench.py stamps since PR 21: a one-chip and a
    # four-chip capture do not compare either, equal records do
    one = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    four = dict(one, count=4)
    a, b = _stamp(ms_per_step=100.0), _stamp(ms_per_step=100.0)
    a["device"], b["device"] = one, four
    assert not bd.diff(a, b)["comparable"]
    b["device"] = dict(one)
    assert bd.diff(a, b)["comparable"]


# -------------------------------------------------------- stamps on disk

def test_driver_shell_unwrap_and_parsed_null(tmp_path):
    bd = _bd()
    inner = _stamp(ms_per_step=100.0)
    shell = {"n": 4, "cmd": "python bench.py", "rc": 0, "tail": "",
             "parsed": inner}
    doc, why = bd.load_stamp(_write(tmp_path / "ok.json", shell))
    assert doc == inner and why is None
    dead = {"n": 5, "cmd": "python bench.py", "rc": 124, "tail": "",
            "parsed": None}
    doc, why = bd.load_stamp(_write(tmp_path / "dead.json", dead))
    assert doc is None and "parsed=null" in why


def test_cli_exit_codes(tmp_path):
    bd = _bd()
    a = _write(tmp_path / "BENCH_r01.json", _stamp(ms_per_step=100.0))
    b = _write(tmp_path / "BENCH_r02.json", _stamp(ms_per_step=101.0))
    c = _write(tmp_path / "BENCH_r03.json", _stamp(ms_per_step=200.0))
    assert bd.main([a, b]) == 0                       # within band
    assert bd.main([a, c]) == 1                       # regression
    assert bd.main([a, c, "--tol", "1.5"]) == 0       # band widened
    d = _write(tmp_path / "other.json",
               _stamp(backend="accelerator", ms_per_step=1.0))
    assert bd.main([a, d]) == 2                       # not comparable
    shell = _write(tmp_path / "shell.json",
                   {"n": 1, "cmd": "x", "rc": 124, "parsed": None})
    assert bd.main([a, shell]) == 2                   # no data
    # directory mode: latest vs previous by name (r02 -> r03)
    assert bd.pick_pair(str(tmp_path / "nope")) is None
    assert bd.main([str(tmp_path)]) == 1
    out = tmp_path / "report.json"
    assert bd.main([a, c, "--json", str(out)]) == 1
    rep = json.loads(out.read_text())
    assert rep["old"] == "BENCH_r01.json"
    assert rep["regressions"][0]["metric"] == "detail.ms_per_step"
    assert rep["regressions"][0]["rel"] == pytest.approx(1.0)


def test_pick_pair_orders_by_capture_number(tmp_path):
    bd = _bd()
    for n in ("r01", "r02", "r10"):
        _write(tmp_path / f"BENCH_{n}.json", _stamp())
    old, new = bd.pick_pair(str(tmp_path))
    assert os.path.basename(old) == "BENCH_r02.json"
    assert os.path.basename(new) == "BENCH_r10.json"
