"""What keeps a run honest about the device it ran on (ISSUE 21): the
entry points that report chip numbers fail without a chip, the peaks
table has no default row, the compile cache is placed from outside, and
a device index is never clamped. The on-chip half is `chip_smoke.py`
itself, run through the chip tool."""
import json
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, script), *args], cwd=ROOT,
        env=env, capture_output=True, text=True, timeout=timeout)


def _json_lines(text):
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return out


def test_chip_smoke_fails_without_a_chip_and_names_what_it_found():
    p = _run("chip_smoke.py")
    assert p.returncode != 0
    assert "platform=cpu" in p.stdout + p.stderr
    # no result line of any kind: the contract's last-line JSON is
    # reserved for a run that passed on a TPU
    assert not any("ok" in d for d in _json_lines(p.stdout))


def test_bench_fails_without_a_chip_and_prints_no_metric():
    p = _run("bench.py")
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any("metric" in d or "value" in d
                   for d in _json_lines(p.stdout))


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from paddle_tpu.core import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # set in the environment: that directory, and nothing is set
        # in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        # not set: the checkout's own fixed directory
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(ROOT, ".jax_cache")
        assert compile_cache.cache_dir() == want
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_peaks_table_has_no_default_row():
    from paddle_tpu.device.peaks import (DEVICE_PEAKS, UnknownDeviceKind,
                                         peaks_for)

    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["int8_ops"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert all("source" in row for row in DEVICE_PEAKS.values())
    for kind in ("cpu", "TPU v9000", ""):
        with pytest.raises(UnknownDeviceKind):
            peaks_for(kind)


def test_mfu_gauge_is_not_published_without_a_published_peak():
    """On the CPU there is no peak, so arming goodput without one
    publishes tokens/s and leaves pt_train_mfu alone."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import steptrace

    prev = obs.set_mode("metrics")
    try:
        steptrace.reset()
        reg = obs.registry()
        steptrace.arm_goodput(flops_per_step=1e12, tokens_per_step=4096,
                              peak_flops=1e14)
        tr = steptrace.begin_step("train", 1, prev_end=10.0, t_entry=10.1)
        tr.stamp("opt_publish", 10.5)
        steptrace.end_step(tr)
        armed = reg.get("pt_train_mfu").value
        assert armed == pytest.approx(1e12 / 0.5 / 1e14)
        steptrace.arm_goodput(flops_per_step=1e12, tokens_per_step=4096)
        tr = steptrace.begin_step("train", 2, prev_end=20.0, t_entry=20.1)
        tr.stamp("opt_publish", 20.25)
        steptrace.end_step(tr)
        assert reg.get("pt_train_mfu").value == armed       # untouched
        assert reg.get("pt_train_tokens_per_second").value == \
            pytest.approx(4096 / 0.25)
    finally:
        steptrace.reset()
        obs.set_mode(prev)


def test_set_device_index_past_the_last_device_is_an_error():
    import jax

    n = len(jax.devices())
    before = jax.config.jax_default_device
    try:
        with pytest.raises(ValueError, match="out of range"):
            paddle.device.set_device(f"tpu:{n + 1}")
        with pytest.raises(ValueError, match="out of range"):
            paddle.device.set_device("tpu:9")
        assert paddle.device.set_device(f"tpu:{n - 1}") is jax.devices()[-1]
    finally:
        jax.config.update("jax_default_device", before)
        paddle.device._current = None


def test_launcher_refuses_to_share_chips_between_local_ranks():
    from paddle_tpu.distributed import launch

    with pytest.raises(SystemExit) as e:
        launch.launch(["--devices", "0,1", "--nproc_per_node", "2",
                       "train.py"])
    assert "one process" in str(e.value)


def test_spawn_children_inherit_the_platform(monkeypatch):
    """`spawn` used to default its children to JAX_PLATFORMS=cpu when
    the variable was unset; they inherit the parent's environment."""
    import multiprocessing as mp

    from paddle_tpu.distributed import api_extra

    seen = []

    class _Proc:
        exitcode = 0

        def __init__(self, target, args, daemon):
            seen.append(args[2])

        def start(self):
            pass

        def join(self):
            pass

    class _Ctx:
        Process = _Proc

    monkeypatch.setattr(mp, "get_context", lambda kind: _Ctx())
    api_extra.spawn(lambda: None, nprocs=2)
    assert len(seen) == 2
    assert all("JAX_PLATFORMS" not in env for env in seen)
