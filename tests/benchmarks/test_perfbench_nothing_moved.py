"""Nothing moved when the GPT-2 files went behind the reference
contract: `data/frozen_gpt2.json` holds what the arithmetic, the
weights and the served and trained comparisons read at PR 26's tree
(`harness/arith.py`, `weights.py`, `reference.py`, `check.py`), at the
published and the `rehearse` sizes of both configurations, and every
number is read again here through the route a run takes:
`references.load(cfg["reference"])`. The old serving arithmetic took a
difference of two sums over all records; the frozen `serve_flops` and
`kv_bytes_attended` were computed that way from the snapshots in the
file, and must equal, integer for integer, what the segments give."""
import hashlib
import json
import os

import numpy as np
import pytest

import references
from harness import check

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(HERE, "data", "frozen_gpt2.json")) as _f:
    FROZEN = json.load(_f)
SEEDS = (7, 2 ** 31 + 7)


def _overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _cfg(case):
    name, size = case.rsplit(".", 1)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        cfg = json.load(f)
    return _overlay(cfg, cfg["rehearse"]) if size == "rehearse" else cfg


def _work():
    """The traced window between the two frozen snapshots, as
    `drivers/_serving.py: work_between` hands it over."""
    a, b = FROZEN["snapshots"]["a"], FROZEN["snapshots"]["b"]
    segments = [(x, y - x) for x, y in zip(a, b) if y != x]
    return {"segments": segments, "processed": sum(b) - sum(a),
            "iterations": 9}


ARITHMETIC = {
    "param_count": lambda r, c: r.param_count(c),
    "matmul_params": lambda r, c: r.matmul_params(c),
    "serve_flops": lambda r, c: r.serve_flops(c, _work()),
    "train_step_flops": lambda r, c: r.train_step_flops(c, 16, 1024),
    "flash_attn_flops": lambda r, c: r.flash_attn_flops(c, 16, 1024),
    "kv_bytes_per_token.bfloat16":
        lambda r, c: r.kv_bytes_per_token(c, "bfloat16"),
    "kv_bytes_per_token.int8": lambda r, c: r.kv_bytes_per_token(c, "int8"),
    "kv_bytes_attended.bfloat16":
        lambda r, c: r.kv_bytes_attended(c, _work(), "bfloat16"),
    "weight_bytes.bfloat16": lambda r, c: r.weight_bytes(c, "bfloat16"),
    "weight_bytes.float32": lambda r, c: r.weight_bytes(c, "float32"),
    "weight_bytes_9_iterations.bfloat16":
        lambda r, c: r.weight_bytes(c, "bfloat16", _work()),
    "positions": lambda r, c: r.positions(c),
}


@pytest.mark.parametrize("key", sorted(ARITHMETIC))
@pytest.mark.parametrize("case", sorted(FROZEN["cases"]))
def test_arithmetic_reads_what_it_read(case, key):
    cfg = _cfg(case)
    got = ARITHMETIC[key](references.load(cfg["reference"]), cfg)
    want = FROZEN["cases"][case][key]
    assert got == want and type(got) is type(want)


def _sha(tree):
    h = hashlib.sha256()
    flat = {k: v for k, v in tree.items() if k != "layers"}
    flat.update({"layers/" + k: v for k, v in tree["layers"].items()})
    for k in sorted(flat):
        a = np.asarray(flat[k].astype("float32"))
        h.update(k.encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


REHEARSE = sorted(c for c in FROZEN["cases"] if c.endswith(".rehearse"))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("case", REHEARSE)
def test_weights_are_the_same_bits(case, seed):
    cfg, frozen = _cfg(case), FROZEN["cases"][case]
    ref = references.load(cfg["reference"])
    tree = ref.make_weights(cfg, seed, frozen["weights_dtype"])
    assert _sha(tree) == frozen[f"weights_sha256.{seed}"]


@pytest.mark.parametrize("quant", (None, "int8"))
@pytest.mark.parametrize("case", REHEARSE)
def test_served_numbers_on_a_fixed_sample(case, quant):
    cfg = dict(_cfg(case), serve={"weight_dtype": "bfloat16"})
    rng = np.random.default_rng(27)
    sample = [(rng.integers(0, cfg["vocab_size"], (n,)).astype(np.int32),
               p) for n, p in ((60, 9), (41, 20), (33, 4))]
    got = check.served_numbers(references.load(cfg["reference"]), cfg,
                               SEEDS[1], sample, 40, quant=quant)
    want = FROZEN["cases"][case][f"served_numbers.{quant}"]
    assert set(got) == set(want)
    assert got["tokens_compared"] == want["tokens_compared"] == 101
    assert got["tokens_off_argmax"] == want["tokens_off_argmax"]
    for k, v in want.items():
        assert got[k] == pytest.approx(v, rel=1e-5, abs=1e-7), k


def test_three_reference_steps_on_fixed_batches():
    case = "gpt2-medium.rehearse"
    cfg, want = _cfg(case), FROZEN["cases"][case]["trained"]
    batches = list(np.random.default_rng(28).integers(
        0, cfg["vocab_size"], (3, 4, 32)).astype(np.int32))
    got = check.reference_training(references.load(cfg["reference"]), cfg,
                                   SEEDS[1], batches)
    assert got["losses"] == pytest.approx(want["losses"], rel=1e-6)
    for part in ("grad1", "change"):
        assert set(got[part]) == set(want[part])
        for k, v in want[part].items():
            np.testing.assert_allclose(got[part][k], np.asarray(v),
                                       rtol=1e-4, atol=1e-9, err_msg=k)


def test_segments_equal_the_old_difference_of_sums():
    """Σ context_sum(start, n) over the segments is, integer for
    integer, what PR 26's snapshots gave: Σ context_sum(0, b) −
    Σ context_sum(0, a) — for a request that ran on, one that began
    inside, one that did not move, and one preempted back to 0."""
    from harness import arith
    from references import gpt2

    a = [0, 431, 100, 700, 0, 300]
    b = [70, 439, 108, 708, 0, 12]
    old = sum(arith.context_sum(0, y) for y in b) - sum(
        arith.context_sum(0, x) for x in a)
    segments = [(x, y - x) for x, y in zip(a, b) if y != x]
    assert len(segments) == 5
    assert gpt2.attended({"segments": segments}) == old


def test_work_between_two_snapshots():
    """The driver's `work`: deltas of the plain counters and of every
    numeric entry of the program's `metrics` and `stats`, one segment a
    request that moved, and the model's iterations."""
    from drivers import _serving as sv

    a = {"t": 10.0, "generated": 5, "processed": 531, "steps": 7,
         "fused_steps": 4, "occupancy_sum": 3.5, "prefill_tokens": 500,
         "decode_tokens": 31, "dispatches": 7, "preemptions": 0,
         "boundaries": 7, "per_record": {0: 431, 1: 100, 2: 0},
         "metrics": {"dispatches": 7, "experts_touched": 40},
         "stats": {"steps": 7, "stage_hits": 1},
         "compile_stats": {"paged": 1}}
    b = dict(a, t=14.0, generated=40, processed=640, steps=12,
             fused_steps=8, occupancy_sum=7.0, decode_tokens=66,
             prefill_tokens=574, dispatches=12, boundaries=12,
             per_record={0: 439, 1: 108, 2: 0, 3: 93},
             metrics={"dispatches": 12, "experts_touched": 71,
                      "new_gauge": 2},
             stats={"steps": 12, "stage_hits": 1})
    w = sv.work_between(a, b, decode_k=8)
    assert w["segments"] == [(431, 8), (100, 8), (0, 93)]
    assert w["processed"] == 109 == sum(n for _s, n in w["segments"])
    assert w["iterations"] == 4 * 8 + 1
    assert w["t"] == 4.0 and w["generated"] == 35 and w["steps"] == 5
    assert w["metrics"] == {"dispatches": 5, "experts_touched": 31}
    assert w["stats"] == {"steps": 5, "stage_hits": 0}
    assert "compile_stats" not in w and "per_record" not in w
    assert sv.numeric({"a": 1, "b": 2.5, "c": True, "d": "x", "e": None,
                       "f": {}}) == {"a": 1, "b": 2.5}
