"""The trace reduction (.xplane.pb → busy and idle time, per-operation
self time, step programs, idle gaps by host span), checked on a small
trace recorded on the chip during PR 24 (data/README.txt) and on
hand-made intervals; and the per-layer readers on that reduction."""
import gzip
import importlib.util
import os
import shutil

import pytest

from harness import metric_lib, peaks, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with gzip.open(os.path.join(
            HERE, "data", "decode_v5e_260ms.xplane.pb.gz")) as src, \
            open(d / "host.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    path = tr.find_xplane(str(d.parent.parent.parent))
    return tr.reduce_trace(path)


def test_busy_and_idle_of_the_recorded_window(reduction):
    r = reduction
    assert r["window_s"] == pytest.approx(0.26)
    assert list(r["devices"]) == [0]
    assert r["busy_s"] == pytest.approx(0.254093043, rel=1e-6)
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=0.02)
    # the longest idle gap is the host reading the window's tokens
    assert r["idle_gaps"][0][0] == "np.asarray_jax.Array_"
    assert all(tr._UNSAFE.search(n) is None for n, _s in r["idle_gaps"])


def test_operations_are_self_time_and_kernels_are_named(reduction):
    ops = dict(reduction["device_ops"])
    assert reduction["device_ops"][0][0] == "custom-call:tpu_custom_call"
    assert "while" not in ops or ops["while"] < 0.001   # a container
    dev = reduction["devices"][0]
    # self times add up to the busy time: nothing is counted twice
    assert sum(dev["ops"].values()) == pytest.approx(
        reduction["busy_s"], rel=1e-3)
    kernel = tr.matching_seconds(dev, KERNEL)
    assert kernel == pytest.approx(0.222876118, rel=1e-6)
    assert kernel == pytest.approx(ops["custom-call:tpu_custom_call"])
    assert tr.matching_seconds(dev, "no_such_operation") is None
    # one fused window and one single tick of the engine's step program
    assert tr.module_durations_ms(dev, r"jit_pure") == pytest.approx(
        [148.379228, 67.318428])
    assert tr.module_durations_ms(dev, r"jit_step") == []


def test_hand_made_intervals():
    merged, total = tr._union([(0, 10), (5, 12), (20, 25)])
    assert merged == [[0, 12], [20, 25]] and total == 17
    # a while [0,100) around two ops and a nested call around a third
    ev = [(0, 100), (10, 20), (40, 30), (45, 10)]
    assert tr.self_times(ev) == [50, 20, 20, 10]
    assert tr.op_name('%closed_call.259 = bf16[24,1,16,128] custom-call('
                      'x), custom_call_target="tpu_custom_call", a={}') \
        == "custom-call:tpu_custom_call"
    assert tr.op_name("%convert_reduce_fusion.286 = (f32[24]) fusion(y)") \
        == "convert_reduce_fusion"
    assert tr.op_name("%fusion.3712.remat2 = f32[2] fusion(z)") == "fusion"
    assert tr.op_name("%all-reduce.5 = f32[8] all-reduce(q)") \
        == "all-reduce"
    host = [(0, 50, "PjitFunction(pure)"), (10, 30, "np.asarray(x)"),
            (200, 300, "sleep")]
    got = dict(tr.attribute_gaps([(12, 10), (60, 5), (210, 40)], host))
    assert got == {"np.asarray_x_": 10 / 1e9, "unattributed": 5 / 1e9,
                   "sleep": 40 / 1e9}
    many = [(i * 10, 1) for i in range(50)]
    got = dict(tr.attribute_gaps(many, [], max_gaps=10))
    assert got["shorter_gaps"] == pytest.approx(40 / 1e9)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_on_the_recorded_window(reduction):
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "cerebras-gpt-1.3b.json")) as f:
        cfg = json.load(f)
    # the work as a driver would hand it over: 24 rows through one
    # fused window of 8 and 128 rows through one single tick, at
    # contexts of about 400 positions (the segments attend 320 * 400
    # positions in all)
    from references import gpt2

    segments = [(419, 8)] * 23 + [(423, 8), (300, 128)]
    traced = {"processed": 24 * 8 + 128, "segments": segments,
              "iterations": 9, "steps": 2, "fused_steps": 1}
    assert gpt2.attended(traced) == (24 * 8 + 128) * 400
    ctx = {"obs": {"traced": traced, "decode_k": 8, "kv_dtype": "bfloat16",
                   "weight_dtype": "bfloat16", "window": {}},
           "trace": reduction, "cfg": cfg, "chips": 1, "ref": gpt2,
           "peaks": peaks.peaks_for("TPU v5 lite")}
    share = _reader("paged_attn_time_share.decode")(ctx)
    assert share == pytest.approx(100 * 0.222876118 / 0.254093043)
    roof = _reader("paged_attn_roofline.decode")(ctx)
    want = 100 * (320 * 400 * 196608 / 0.222876118) / 819e9
    assert roof == pytest.approx(want) and 0 < roof < 100
    assert _reader("step_device_ms_p50.decode")(ctx) == pytest.approx(
        (148.379228 + 67.318428) / 2)
    hbm = _reader("step_hbm_share.decode")(ctx)
    need = 9 * 1315723264 * 2 + 320 * 400 * 196608
    assert hbm == pytest.approx(100 * need / 0.254093043 / 819e9)
    mfu = _reader("step_mfu.decode")(ctx)
    assert 0 < mfu < 100
    # nothing to read: the reader returns nothing, never 0
    empty = dict(ctx, trace=None)
    for name in ("paged_attn_roofline.decode", "step_mfu.decode",
                 "paged_attn_time_share.tpot", "flash_attn_roofline",
                 "step_device_ms_p50.train", "step_hbm_share.decode"):
        assert _reader(name)(empty) is None
    assert _reader("flash_attn_roofline")(dict(
        ctx, obs={"window": {}})) is None
    assert _reader("slot_occupancy.decode")(ctx) is None
    assert _reader("queue_wait_p50_s")(ctx) is None


def test_kernel_seconds_are_a_mean_over_devices():
    def dev(ns):
        return {"events": [('%k = f32[1] custom-call(), '
                            'custom_call_target="tpu_custom_call"', "",
                            0, ns, ns)]}

    trace = {"devices": {0: dev(2e9), 1: dev(4e9), 2: {"events": []},
                         3: dev(2e9)}, "busy_s": 4.0}
    assert metric_lib.kernel_seconds({"trace": trace}, KERNEL) == 2.0
    assert metric_lib.kernel_time_share({"trace": trace}, KERNEL) == 50.0


def test_peaks_have_no_default_row():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9000", ""):
        with pytest.raises(LookupError):
            peaks.peaks_for(kind)
    # the copy agrees with the program's table
    from paddle_tpu.device.peaks import DEVICE_PEAKS

    assert peaks.DEVICE_PEAKS == DEVICE_PEAKS
