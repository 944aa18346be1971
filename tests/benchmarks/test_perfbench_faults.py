"""`correct` has been shown to fail: the rest of a run is driven (the
harness's look for a chip skipped by its rehearsal flag, tiny sizes on
the CPU) with the timed path broken underneath the harness, once for
each fault a cell can have, and `correct` comes out false. Unbroken,
the same run comes out true."""
import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def bench_run(root_with_pending):
    """run.py, loaded from a checkout in which the pending cells
    (benchmarks/pending/) are admitted, so that their path is driven
    too."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_run", os.path.join(root_with_pending, "benchmarks",
                                      "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _result(bench_run, capsys, cell, seed):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "1", "--trace", "0",
                         "--rehearse-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_unbroken_runs_are_correct(bench_run, capsys):
    for cell in ("gpt2m_train", "cgpt1p3b_decode", "cgpt1p3b_chat"):
        out = _result(bench_run, capsys, cell, 2 ** 31 + 21)
        assert out["correct"] is True, out["compared"]
        assert out["failed"] == 0 and out["attempted"] > 0
        assert out["metrics"] == {}     # a CPU run prints no device metric


def test_a_step_that_returns_its_state_unchanged(bench_run, capsys,
                                                 monkeypatch):
    from paddle_tpu.optimizer import optimizer as opt_mod

    inner = opt_mod.Adam._update

    def update(self, pv, gv, state, lr, wd=0.0, param=None):
        _new_p, new_state = inner(self, pv, gv, state, lr, wd=wd,
                                  param=param)
        return pv, new_state

    monkeypatch.setattr(opt_mod.Adam, "_update", update)
    out = _result(bench_run, capsys, "gpt2m_train", 31)
    assert out["correct"] is False
    assert out["compared"]["change_gap_max"]["value"] == 1.0
    assert not out["compared"]["change_gap_max"]["ok"]


def test_half_of_the_batch_left_out(bench_run, capsys, monkeypatch):
    from paddle_tpu.ops import manipulation as manip
    from paddle_tpu.text.models import gpt as gpt_mod

    inner = gpt_mod.GPTPretrainingCriterion.forward

    def forward(self, logits, labels):
        half = logits.shape[0] // 2     # the mean is over the rest
        return inner(self, manip.slice(logits, [0], [0], [half]),
                     manip.slice(labels, [0], [0], [half]))

    monkeypatch.setattr(gpt_mod.GPTPretrainingCriterion, "forward",
                        forward)
    out = _result(bench_run, capsys, "gpt2m_train", 32)
    assert out["correct"] is False
    assert not out["compared"]["grad1_gap_max"]["ok"]


@pytest.mark.parametrize("cell", ["cgpt1p3b_decode", "cgpt1p3b_chat"])
def test_a_token_altered_where_it_is_produced(bench_run, capsys,
                                              monkeypatch, cell):
    from paddle_tpu.inference import llm_engine

    inner = llm_engine._Request.result_array

    def result_array(self):
        out = np.array(inner(self))
        out[self.prompt_len:] = (out[self.prompt_len:] + 1) % 512
        return out

    monkeypatch.setattr(llm_engine._Request, "result_array",
                        result_array)
    out = _result(bench_run, capsys, cell, 33)
    assert out["correct"] is False
    assert not out["compared"]["served_noise_power"]["ok"]


def test_an_answer_cut_short(bench_run, capsys, monkeypatch):
    from paddle_tpu.inference import llm_engine

    inner = llm_engine._Request.result_array

    def result_array(self):
        return inner(self)[:-1]

    monkeypatch.setattr(llm_engine._Request, "result_array",
                        result_array)
    out = _result(bench_run, capsys, "cgpt1p3b_chat", 34)
    assert out["correct"] is False
    assert out["compared"]["malformed_answers"]["value"] > 0
