"""The plain reference against the program at a tiny size on the CPU
(logits, loss, gradients), the two configurations' parameter counts,
and the controls: the reference in a lower precision reads differently
from the reference."""
import json
import os

import numpy as np
import pytest

from harness import arith, check
from references import gpt2

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"n_embd": 64, "n_layer": 3, "n_head": 4, "n_inner": 256,
        "n_positions": 128, "vocab_size": 384}


def _cfg(name):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,millions", [("gpt2-medium", 354.8),
                                           ("cerebras-gpt-1.3b", 1315.7)])
def test_parameter_counts_from_the_configuration_files(name, millions):
    cfg = _cfg(name)
    n = gpt2.param_count(cfg)
    assert n == cfg["parameters"]
    assert round(n / 1e6, 1) == millions
    top, layers = gpt2.shapes(cfg)
    assert sum(int(np.prod(s)) for s in list(top.values())
               + list(layers.values())) == n
    assert cfg["reduced"] == []


def test_kv_and_flops_arithmetic():
    cfg = _cfg("cerebras-gpt-1.3b")
    assert gpt2.kv_bytes_per_token(cfg, "bfloat16") == 196608
    assert gpt2.kv_bytes_per_page(cfg, 16, "bfloat16") == 16 * 196608
    # the copies agree with the program's originals
    from paddle_tpu.inference import LLMEngineConfig
    from paddle_tpu.observability.steptrace import model_flops

    from builders import gpt as builder

    mcfg = builder.gpt_config(cfg)
    assert gpt2.kv_bytes_per_page(cfg, 16, "bfloat16") == \
        LLMEngineConfig.kv_bytes_per_page(mcfg, 16, "bfloat16")
    med = _cfg("gpt2-medium")
    assert gpt2.train_step_flops(med, 16, 1024) == pytest.approx(
        model_flops(builder.gpt_config(med), 16, 1024))
    assert gpt2.train_step_flops(med, 16, 1024) / 16384 == \
        pytest.approx(2.2717e9, rel=1e-4)
    assert arith.context_sum(10, 3) == 11 + 12 + 13


def test_weights_follow_the_seed_and_take_large_seeds():
    a = gpt2.make_weights(TINY, 2 ** 31 + 7, "float32")
    b = gpt2.make_weights(TINY, 2 ** 31 + 7, "float32")
    c = gpt2.make_weights(TINY, 2 ** 31 + 8, "float32")
    assert np.array_equal(a["wte"], b["wte"])
    assert not np.array_equal(a["wte"], c["wte"])
    assert not np.array_equal(
        a["wte"], gpt2.make_weights(TINY, 7, "float32")["wte"])
    assert a["layers"]["qkv_w"].shape == (3, 64, 192)
    assert str(gpt2.make_weights(TINY, 1, "bfloat16")["wpe"].dtype) \
        == "bfloat16"
    assert abs(float(np.std(a["layers"]["fc1_w"])) - 0.02) < 2e-3
    assert abs(float(np.mean(a["layers"]["ln1_w"])) - 1.0) < 5e-3


@pytest.fixture(scope="module")
def program():
    """GPTForCausalLM with the benchmark's weights, and one batch."""
    from builders import gpt as builder

    model = builder.build_model(TINY, 11, "float32")
    ids = np.random.default_rng(0).integers(
        0, TINY["vocab_size"], (4, 32)).astype(np.int32)
    return model, ids


def test_logits_match_the_program(program):
    import paddle_tpu as paddle

    model, ids = program
    model.eval()
    got = np.asarray(model(paddle.to_tensor(ids)).numpy())
    w = gpt2.make_weights(TINY, 11, "float32")
    want = np.asarray(gpt2.logits_fn(w, ids, TINY["n_head"]))
    assert got.shape == want.shape == (4, 32, TINY["vocab_size"])
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_loss_and_gradients_match_the_program(program):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTPretrainingCriterion

    from builders import gpt as builder

    model, ids = program
    model.train()
    t = paddle.to_tensor(ids)
    loss = GPTPretrainingCriterion()(model(t), t)
    loss.backward()
    w = gpt2.make_weights(TINY, 11, "float32")
    ls, g = jax.value_and_grad(gpt2.loss_sum)(w, ids, TINY["n_head"])
    count = ids.shape[0] * (ids.shape[1] - 1)
    assert float(loss.numpy()) == pytest.approx(float(ls) / count,
                                                rel=1e-6)
    seen = 0
    for name, p in model.state_dict().items():
        key, layer = builder.tree_position(name)
        want = g[key] if layer is None else \
            g["layers"][key.split("/")[1]][layer]
        got = np.asarray(p.grad.numpy())
        np.testing.assert_allclose(got, np.asarray(want) / count,
                                   atol=1e-6, err_msg=name)
        seen += 1
    assert seen == 4 + 12 * TINY["n_layer"]


def test_three_steps_and_the_numbers_compared():
    w0 = gpt2.make_weights(TINY, 3, "float32")
    batches = list(np.random.default_rng(1).integers(
        0, TINY["vocab_size"], (3, 4, 32)).astype(np.int32))
    ref = gpt2.train_three_steps(TINY, w0, batches, rows_per_block=2)
    again = gpt2.train_three_steps(TINY, w0, batches, rows_per_block=4)
    assert ref["losses"] == pytest.approx(again["losses"], rel=1e-6)
    assert ref["grad1"]["layers/qkv_b"].shape == (TINY["n_layer"], 3)
    same = check.trained_numbers(ref, again)
    assert same["grad1_gap_max"] < 1e-4 and same["loss1_gap"] < 1e-6
    # a key's bias has no gradient under softmax: left out by the rule
    # on the reference's gradient, not by name
    assert same["leaves_left_out"] == TINY["n_layer"]
    assert all("qkv_b" in n and n.endswith("[1]")
               for n in same["_worst"]["left_out"])
    # the faults and the control read differently from the reference
    half = gpt2.train_three_steps(TINY, w0, batches, keep_rows=[0, 1])
    fault = check.trained_numbers(ref, half)
    assert fault["grad1_gap_max"] > 10 * max(same["grad1_gap_max"], 1e-3)
    unchanged = dict(again, change={k: np.zeros_like(v) for k, v in
                                    again["change"].items()})
    assert check.trained_numbers(ref, unchanged)["change_gap_max"] == 1.0
    fp8 = gpt2.train_three_steps(TINY, w0, batches, quant="fp8")
    ctl = check.trained_numbers(ref, fp8)
    assert ctl["grad1_gap_max"] > 3 * max(same["grad1_gap_max"], 1e-4)


def test_served_gaps_and_the_int8_control():
    cfg = dict(TINY, serve={"weight_dtype": "bfloat16"})
    w = gpt2.make_weights(cfg, 5, "bfloat16")
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg["vocab_size"], (9,)).astype(np.int32)
    toks = list(prompt)
    for _ in range(12):          # greedy decoding by the reference
        lg = gpt2.logits_fn(w, np.asarray([toks], np.int32),
                            cfg["n_head"])
        toks.append(int(np.argmax(np.asarray(lg)[0, -1])))
    sample = [(np.asarray(toks, np.int32), len(prompt))]
    good = check.served_numbers(gpt2, cfg, 5, sample, 12)
    assert good["tokens_compared"] == 12
    assert good["served_gap_max"] == pytest.approx(0.0, abs=1e-5)
    wrong = list(toks)
    wrong[len(prompt) + 4] = (wrong[len(prompt) + 4] + 1) \
        % cfg["vocab_size"]
    bad = check.served_numbers(
        gpt2, cfg, 5, [(np.asarray(wrong, np.int32), len(prompt))], 12)
    assert bad["served_gap_max"] > 0.05 and bad["tokens_off_argmax"] >= 1
    ctl = check.served_numbers(gpt2, cfg, 5, sample, 12, quant="int8")
    assert ctl["tokens_compared"] == 12 and ctl["served_gap_max"] >= 0.0
    # the yardstick of served_noise_power: no margin reads sd·φ(0), a
    # wide one nothing, and noise at the yardstick's own sd reads 1
    eg = check.expected_gap(np.array([0.0, 0.03, 3.0]), sd=0.03)
    assert eg[0] == pytest.approx(0.03 * 0.3989422804)
    assert eg[0] > eg[1] > eg[2] >= 0 and eg[2] < 1e-12
    draw = np.random.default_rng(0)
    margin = draw.uniform(0, 0.3, 200000)
    gaps = np.maximum(0.0, draw.normal(0, 0.03, margin.size) - margin)
    assert gaps.sum() / check.expected_gap(margin, 0.03).sum() == \
        pytest.approx(1.0, rel=0.03)
    assert good["served_noise_power"] == pytest.approx(0.0, abs=1e-3)
    assert bad["served_noise_power"] > 0.5
    rows = check.judge({"served_gap_max": 0.3, "x": float("nan")},
                       {"served_gap_max": 0.2, "x": 1.0, "missing": 1.0})
    assert [ok for *_r, ok in rows] == [False, False, False]


@pytest.mark.parametrize("seed", (5, 6, 2 ** 31 + 7))
def test_the_int8_control_reads_above_the_served_path_at_test_size(seed):
    """The control of the serving cells (the reference in int8 put in
    the program's place: at every position of the same prompts and
    tokens, the gap of the token the low precision puts first) at a
    size a test can hold: a vocabulary wide enough that some margins
    are small. A float32 greedy path reads 0 there (the test above);
    the control flips positions and reads above it. On the chip, at the
    cells' own size, it reads eight times the served path or more
    (PERF.md §6)."""
    cfg = dict(TINY, vocab_size=4096, serve={"weight_dtype": "bfloat16"})
    rng = np.random.default_rng(seed)
    sample = [(rng.integers(0, 4096, (100,)).astype(np.int32), 4)
              for _ in range(4)]
    control = check.served_numbers(gpt2, cfg, seed, sample, 96,
                                   quant="int8")
    assert control["tokens_compared"] == 384
    assert control["tokens_off_argmax"] >= 1
    assert control["served_noise_power"] > 0
    assert 0 < control["served_gap_max"] < 0.1       # a rounding, no more
