"""Every serving mix is a fixed grid that the seed only orders."""
import collections
import json
import os

import numpy as np
import pytest

import references
from harness import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRAFFIC = os.path.join(ROOT, "benchmarks", "traffic")
SEEDS = (0, 7, 2 ** 31 + 5, 2 ** 32 + 99)


def mixes(kind):
    out = []
    for f in sorted(os.listdir(TRAFFIC)):
        assert f.endswith(traffic.EXTENSIONS), f
        mix = traffic.load_mix(TRAFFIC, f[:-len(".json")])
        if mix["kind"] == kind:
            out.append(pytest.param(mix, id=f))
    return out


@pytest.mark.parametrize("mix", mixes("open_loop"))
@pytest.mark.parametrize("seconds", (10, 40, 51))
def test_open_loop_offers_the_same_requests_and_gaps(mix, seconds):
    want = None
    for seed in SEEDS:
        sched = traffic.open_schedule(mix, seed, seconds)
        due = np.array([t for t, _p, _o in sched])
        assert len(sched) == round(mix["rate_per_s"] * seconds)
        assert due[0] == 0.0 and np.all(np.diff(due) > 0)
        assert due[-1] < seconds
        gaps = np.append(np.diff(due), seconds - due[-1])
        assert gaps.sum() == pytest.approx(seconds)
        got = (collections.Counter((p, o) for _t, p, o in sched),
               np.sort(gaps))
        if want is None:
            want = got
        else:
            assert got[0] == want[0]
            np.testing.assert_allclose(got[1], want[1], rtol=1e-9)
    a = traffic.open_schedule(mix, 1, seconds)
    b = traffic.open_schedule(mix, 2, seconds)
    assert a != b and a == traffic.open_schedule(mix, 1, seconds)
    pairs = np.array(traffic.open_pairs(mix, seconds))
    for col, key in ((0, "prompt_len"), (1, "output_len")):
        assert pairs[:, col].min() >= mix[key]["min"]
        assert pairs[:, col].max() <= mix[key]["max"]
    # the exponential's burstiness is kept: gaps spread like 1/rate
    gaps = traffic.open_gaps(mix, seconds)
    assert np.std(gaps) / np.mean(gaps) > 0.8


@pytest.mark.parametrize("mix", mixes("closed_loop"))
def test_closed_loop_walks_the_same_grid(mix):
    grid = traffic.closed_grid(mix)
    assert len(grid) == mix["grid"][0] * mix["grid"][1] == 64
    assert len(set(grid)) == len(grid)
    firsts = []
    for seed in SEEDS:
        walk = traffic.closed_walk(mix, seed)
        laps = [[next(walk) for _ in grid] for _ in range(3)]
        for lap in laps:
            assert sorted(lap) == sorted(grid)
        assert laps[0] != laps[1]                # a fresh permutation
        firsts.append(laps[0])
    assert firsts[0] != firsts[1]
    # balanced: every block of 8 holds each prompt and each output
    # length once, so the first wave (24 slots = 3 blocks) offers the
    # same lengths for every seed, paired differently
    n = mix["grid"][0]
    for lap in firsts:
        for b in range(0, len(lap), n):
            ps, os_ = zip(*lap[b:b + n])
            assert len(set(ps)) == n and len(set(os_)) == n
    waves = [sorted(p for p, _o in lap[:24]) for lap in firsts]
    assert all(w == waves[0] for w in waves)
    p, o = np.array(grid).T
    assert mix["prompt_len"]["min"] <= p.min() and \
        p.max() <= mix["prompt_len"]["max"]
    assert mix["output_len"]["min"] <= o.min() and \
        o.max() <= mix["output_len"]["max"]


def test_length_quantiles():
    d = {"min": 64, "median": 128, "max": 512}
    q = traffic.length_at(d, [0.0, 0.25, 0.5, 0.75, 1.0])
    assert list(q) == [64, 91, 128, 256, 512]
    mid = traffic.length_at(d, traffic.quantile_midpoints(1000))
    assert np.all(np.diff(mid) >= 0) and np.median(mid) == 128


@pytest.mark.parametrize("mix", mixes("train_steps"))
def test_training_mix_rows_all_differ_and_follow_the_seed(mix):
    small = dict(mix, batch=4, seq=32)
    a = traffic.train_batches(small, 2 ** 31 + 1, 50257, 3)
    assert a.shape == (3, 4, 32) and a.dtype == np.int32
    assert len({r.tobytes() for r in a.reshape(-1, 32)}) == 12
    assert np.array_equal(a, traffic.train_batches(small, 2 ** 31 + 1,
                                                   50257, 3))
    assert not np.array_equal(a, traffic.train_batches(small, 5, 50257, 3))
    assert a.min() >= 0 and a.max() < 50257


def test_the_serving_traffic_fits_the_engine():
    """No operation may fail: every request fits the model's context,
    and a closed loop's worst case in flight fits the page pool."""
    from perfbench_pending import manifest_with_pending

    m = manifest_with_pending()
    files = {c["name"]: c["file"] for c in m["configs"]}
    for w in m["workloads"]:
        mix = traffic.load_mix(TRAFFIC, w["traffic"])
        if mix["kind"] == "train_steps":
            continue
        with open(os.path.join(ROOT, files[w["config"]])) as f:
            cfg = json.load(f)
        e = cfg["engine"]
        pairs = (traffic.closed_grid(mix) if mix["kind"] == "closed_loop"
                 else traffic.open_pairs(mix, m["run_seconds"]))
        longest = max(p + o for p, o in pairs)
        ref = references.load(cfg["reference"])
        assert longest <= e["max_model_len"] <= ref.positions(cfg)
        per_token = ref.kv_bytes_per_token(cfg, e["kv_dtype"])
        pool_tokens = e["pool_budget_bytes"] // per_token
        if mix["kind"] == "closed_loop":
            assert e["num_slots"] * (longest + e["page_size"]) \
                <= pool_tokens
