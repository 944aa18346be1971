"""GPT flagship model: eager/compiled parity and TP parity on the 8-device
mesh (SURVEY.md §4 implication (c))."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.text.models import (
    GPTForCausalLM,
    GPTPretrainingCriterion,
    gpt_tiny,
)


def _batch(cfg, b=2, s=64, seed=0):
    rng = np.random.default_rng(seed)
    return paddle.to_tensor(rng.integers(0, cfg.vocab_size, (b, s)))


class TestGPT:
    @pytest.mark.slow
    def test_forward_shapes_and_grads(self):
        mesh_mod.reset_mesh()
        paddle.seed(0)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        ids = _batch(cfg)
        logits = model(ids)
        assert logits.shape == [2, 64, cfg.vocab_size]
        crit = GPTPretrainingCriterion()
        loss = crit(logits, ids)
        loss.backward()
        assert model.gpt.wte.weight.grad is not None
        assert model.gpt.layers[0].qkv.weight.grad is not None
        assert model.gpt.layers[-1].fc2.weight.grad is not None

    @pytest.mark.slow
    def test_trainstep_matches_eager_step(self):
        mesh_mod.reset_mesh()
        paddle.seed(1)
        cfg = gpt_tiny()
        m_e = GPTForCausalLM(cfg)
        paddle.seed(1)
        m_j = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        ids = _batch(cfg, seed=3)

        opt_e = paddle.optimizer.SGD(0.1, parameters=m_e.parameters())
        opt_j = paddle.optimizer.SGD(0.1, parameters=m_j.parameters())

        def loss_fn(m, ids):
            return crit(m(ids), ids)

        l_e = loss_fn(m_e, ids)
        l_e.backward()
        opt_e.step()
        step = paddle.jit.TrainStep(m_j, loss_fn, opt_j)
        l_j = step(ids)
        np.testing.assert_allclose(float(l_e.numpy()), float(l_j.numpy()),
                                   rtol=1e-5)
        np.testing.assert_allclose(
            m_e.gpt.layers[0].qkv.weight.numpy(),
            m_j.gpt.layers[0].qkv.weight.numpy(), rtol=1e-4, atol=1e-5)

    def test_tp_matches_serial(self):
        cfg = gpt_tiny()
        ids = _batch(cfg, seed=5)
        mesh_mod.reset_mesh()
        paddle.seed(2)
        serial = GPTForCausalLM(cfg)
        out_serial = serial(ids).numpy()

        mesh_mod.init_mesh(mp=8)
        paddle.seed(2)
        tp = GPTForCausalLM(cfg)
        out_tp = tp(ids).numpy()
        mesh_mod.reset_mesh()
        np.testing.assert_allclose(out_serial, out_tp, rtol=1e-4, atol=1e-4)

    def test_train_loss_decreases_hybrid(self):
        mesh_mod.init_mesh(dp=2, sharding=2, mp=2)
        paddle.seed(3)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        crit = GPTPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

        def loss_fn(m, ids):
            return crit(m(ids), ids)

        step = dist.DistributedTrainStep(model, loss_fn, opt,
                                         zero_level="os_g")
        ids = _batch(cfg, b=4, s=64, seed=7)
        l0 = float(step(ids).numpy())
        for _ in range(5):
            l = float(step(ids).numpy())
        mesh_mod.reset_mesh()
        assert l < l0


class TestBert:
    def _mlm_batch(self, cfg, b=2, s=32, seed=0, mask_frac=0.15):
        rng = np.random.default_rng(seed)
        ids = rng.integers(0, cfg.vocab_size, (b, s))
        labels = np.full((b, s), -100, np.int64)
        mask = rng.random((b, s)) < mask_frac
        mask[:, 0] = True  # ensure at least one target
        labels[mask] = ids[mask]
        masked = ids.copy()
        masked[mask] = 0  # [MASK] id
        nsp = rng.integers(0, 2, (b,))
        return (paddle.to_tensor(masked), paddle.to_tensor(labels),
                paddle.to_tensor(nsp))

    @pytest.mark.slow
    def test_forward_shapes_and_grads(self):
        from paddle_tpu.text.models import (
            BertForPretraining, BertPretrainingCriterion, bert_tiny)

        mesh_mod.reset_mesh()
        paddle.seed(0)
        cfg = bert_tiny()
        model = BertForPretraining(cfg)
        ids, labels, nsp = self._mlm_batch(cfg)
        mlm_logits, nsp_logits = model(ids)
        assert mlm_logits.shape == [2, 32, cfg.vocab_size]
        assert nsp_logits.shape == [2, 2]
        crit = BertPretrainingCriterion()
        loss = crit(mlm_logits, labels, nsp_logits, nsp)
        loss.backward()
        assert model.bert.embeddings.word.weight.grad is not None
        assert model.bert.layers[-1].fc2.weight.grad is not None

    @pytest.mark.slow
    def test_attention_mask_blocks_padding(self):
        from paddle_tpu.text.models import BertModel, bert_tiny

        paddle.seed(1)
        cfg = bert_tiny()
        model = BertModel(cfg)
        model.eval()
        rng = np.random.default_rng(2)
        real = rng.integers(1, cfg.vocab_size, (1, 16))
        # same prefix, garbage tail, tail masked out
        padded = np.concatenate(
            [real, rng.integers(1, cfg.vocab_size, (1, 8))], axis=1)
        attn = np.concatenate([np.ones((1, 16)), np.zeros((1, 8))], axis=1)
        out_short, _ = model(paddle.to_tensor(real))
        out_masked, _ = model(paddle.to_tensor(padded),
                              attention_mask=paddle.to_tensor(attn))
        np.testing.assert_allclose(out_masked.numpy()[:, :16],
                                   out_short.numpy(), rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_tp_matches_serial(self):
        from paddle_tpu.text.models import BertForPretraining, bert_tiny

        cfg = bert_tiny()
        rng = np.random.default_rng(3)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 32)))
        mesh_mod.reset_mesh()
        paddle.seed(2)
        serial = BertForPretraining(cfg)
        serial.eval()
        out_serial, _ = serial(ids)

        mesh_mod.init_mesh(mp=8)
        paddle.seed(2)
        tp = BertForPretraining(cfg)
        tp.eval()
        out_tp, _ = tp(ids)
        mesh_mod.reset_mesh()
        np.testing.assert_allclose(out_serial.numpy(), out_tp.numpy(),
                                   rtol=1e-4, atol=1e-4)

    def test_pretraining_loss_decreases_distributed(self):
        from paddle_tpu.text.models import (
            BertForPretraining, BertPretrainingCriterion, bert_tiny)

        mesh_mod.init_mesh(dp=2, sharding=2, mp=2)
        paddle.seed(3)
        cfg = bert_tiny()
        model = BertForPretraining(cfg)
        crit = BertPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        ids, labels, nsp = self._mlm_batch(cfg, b=4, seed=5)

        def loss_fn(m, ids, labels, nsp):
            mlm, nsp_logits = m(ids)
            return crit(mlm, labels, nsp_logits, nsp)

        step = dist.DistributedTrainStep(model, loss_fn, opt,
                                         zero_level="os_g")
        l0 = float(step(ids, labels, nsp).numpy())
        for _ in range(5):
            l = float(step(ids, labels, nsp).numpy())
        mesh_mod.reset_mesh()
        assert l < l0

    @pytest.mark.slow
    def test_sequence_classification_finetune(self):
        from paddle_tpu.text.models import (
            BertForSequenceClassification, bert_tiny)

        mesh_mod.reset_mesh()
        paddle.seed(4)
        cfg = bert_tiny()
        model = BertForSequenceClassification(cfg, num_classes=3)
        opt = paddle.optimizer.AdamW(5e-4, parameters=model.parameters())
        rng = np.random.default_rng(6)
        ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (8, 16)))
        y = paddle.to_tensor(rng.integers(0, 3, (8,)))
        step = paddle.jit.TrainStep(
            model, lambda m, a, b: nn.functional.cross_entropy(m(a), b),
            opt)
        l0 = float(step(ids, y).numpy())
        for _ in range(10):
            l = float(step(ids, y).numpy())
        assert l < l0


class TestGeneration:
    @pytest.mark.slow
    def test_greedy_matches_full_forward(self):
        mesh_mod.reset_mesh()
        paddle.seed(20)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        model.eval()
        rng = np.random.default_rng(9)
        prompt = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (2, 8)))
        out = model.generate(prompt, max_new_tokens=6).numpy()
        assert out.shape == (2, 14)
        np.testing.assert_array_equal(out[:, :8], prompt.numpy())
        # KV-cache greedy decode == argmax over the FULL forward each step
        ref = prompt.numpy()
        for _ in range(6):
            logits = model(paddle.to_tensor(ref)).numpy()
            nxt = logits[:, -1].argmax(-1)
            ref = np.concatenate([ref, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(out, ref)

    def test_sampling_modes(self):
        paddle.seed(21)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        model.eval()
        prompt = paddle.to_tensor(
            np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 4)))
        s1 = model.generate(prompt, max_new_tokens=8, do_sample=True,
                            temperature=1.0, top_k=5).numpy()
        assert s1.shape == (1, 12)
        assert ((0 <= s1) & (s1 < cfg.vocab_size)).all()
        # respects max_seq_len cap
        long_prompt = paddle.to_tensor(np.zeros(
            (1, cfg.max_seq_len - 2), np.int64))
        capped = model.generate(long_prompt, max_new_tokens=50).numpy()
        assert capped.shape[1] == cfg.max_seq_len

    def test_generate_edge_cases(self):
        paddle.seed(22)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        model.eval()
        prompt = paddle.to_tensor(np.zeros((1, 4), np.int64))
        # zero budget → prompt unchanged
        assert model.generate(prompt, max_new_tokens=0).shape == [1, 4]
        # prompt at the cap → nothing to generate
        full = paddle.to_tensor(np.zeros((1, cfg.max_seq_len), np.int64))
        assert model.generate(full, max_new_tokens=5).shape == \
            [1, cfg.max_seq_len]
        # over-long prompt raises instead of silently clamping
        import pytest as _pytest

        over = paddle.to_tensor(np.zeros((1, cfg.max_seq_len + 1),
                                         np.int64))
        with _pytest.raises(ValueError, match="max_seq_len"):
            model.generate(over)
        # top_k > vocab clamps instead of crashing
        out = model.generate(prompt, max_new_tokens=3, do_sample=True,
                             top_k=10 ** 6)
        assert out.shape == [1, 7]

    def test_generate_eos_early_stop(self):
        """The stop-semantics contract shared with the serving engine
        (inference/llm_engine.py): a row that GENERATES eos keeps the
        eos, emits pad afterwards, and the loop exits once every row is
        finished."""
        paddle.seed(26)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        model.eval()
        rng = np.random.default_rng(12)
        prompt = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (2, 5)))
        base = model.generate(prompt, max_new_tokens=8).numpy()
        # pick row 0's 2nd generated token as eos; row 1 may finish later
        eos = int(base[0, 5 + 1])
        out = model.generate(prompt, max_new_tokens=8, eos_token_id=eos,
                             pad_token_id=0).numpy()
        assert out.shape[1] <= base.shape[1]
        for r in range(2):
            row = out[r, 5:]
            hits = np.where(row == eos)[0]
            if hits.size:  # tokens up to+incl eos match, then pad
                k = hits[0]
                np.testing.assert_array_equal(row[:k + 1],
                                              base[r, 5:5 + k + 1])
                assert (row[k + 1:] == 0).all()
            else:  # unfinished rows are untouched
                np.testing.assert_array_equal(row,
                                              base[r, 5:5 + row.size])
        # single finished row ends the whole loop early
        solo = model.generate(prompt[0:1], max_new_tokens=8,
                              eos_token_id=eos).numpy()
        assert solo.shape[1] == 5 + 2
        np.testing.assert_array_equal(solo[0], base[0, :7])

    def test_generate_reuses_compiled_step(self):
        paddle.seed(23)
        cfg = gpt_tiny()
        model = GPTForCausalLM(cfg)
        model.eval()
        prompt = paddle.to_tensor(np.zeros((1, 4), np.int64))
        model.generate(prompt, max_new_tokens=4)
        step_static = model.__dict__["_decode_step_static"]
        n_after_first = len(step_static._cache)
        model.generate(prompt, max_new_tokens=8)  # same 128 bucket
        assert len(step_static._cache) == n_after_first, \
            "second generate() re-traced despite identical shapes"
        # the compiled step is instance-owned: a dropped model must not
        # stay pinned by a class-level cache
        assert "_decode_step_static" not in type(model).__dict__


@pytest.mark.slow
def test_bert_fused_mlm_loss_matches_criterion():
    import numpy as np

    from paddle_tpu.text.models import (BertForPretraining,
                                        BertPretrainingCriterion)
    from paddle_tpu.text.models.bert import BertConfig

    paddle.seed(5)
    cfg = BertConfig(vocab_size=96, hidden_size=16, num_layers=1,
                     num_heads=2, intermediate_size=32, max_position=32)
    model = BertForPretraining(cfg)
    crit = BertPretrainingCriterion()
    rng = np.random.default_rng(3)
    ids = paddle.to_tensor(rng.integers(0, 96, (2, 11)).astype(np.int32))
    labels = np.full((2, 11), -100, np.int64)
    m = rng.random((2, 11)) < 0.3
    labels[m] = rng.integers(0, 96, m.sum())
    labels_t = paddle.to_tensor(labels)
    nsp = paddle.to_tensor(rng.integers(0, 2, (2,)))

    mlm, nsp_logits = model(ids)
    ref = crit(mlm, labels_t, nsp_logits, nsp)
    got = model.fused_mlm_loss(ids, labels_t, nsp_labels=nsp)
    np.testing.assert_allclose(got.numpy(), ref.numpy(), rtol=1e-5,
                               atol=1e-6)


def test_bert_length_mask_matches_dense_mask():
    """A 1-D attention_mask (per-example valid lengths — the flash-eligible
    form) must produce the same outputs as the equivalent [b, s] keep
    mask on the valid positions."""
    import numpy as np

    from paddle_tpu.text.models import BertModel
    from paddle_tpu.text.models.bert import BertConfig

    paddle.seed(9)
    cfg = BertConfig(vocab_size=64, hidden_size=16, num_layers=2,
                     num_heads=2, intermediate_size=32, max_position=32)
    model = BertModel(cfg)
    rng = np.random.default_rng(6)
    ids = paddle.to_tensor(rng.integers(0, 64, (3, 12)).astype(np.int32))
    lens = np.array([12, 7, 3])
    keep = (np.arange(12)[None, :] < lens[:, None]).astype(np.float32)

    seq_l, pooled_l = model(ids, attention_mask=paddle.to_tensor(lens))
    seq_m, pooled_m = model(ids, attention_mask=paddle.to_tensor(keep))
    # compare only valid positions: pad rows are garbage either way
    for b, n in enumerate(lens):
        np.testing.assert_allclose(seq_l.numpy()[b, :n],
                                   seq_m.numpy()[b, :n],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pooled_l.numpy(), pooled_m.numpy(),
                               rtol=1e-5, atol=1e-5)


def test_generate_ragged_left_padded_matches_per_example():
    """Batched generation with LEFT-padded ragged prompts must equal
    each example generated alone (greedy decoding: deterministic)."""
    import numpy as np

    from paddle_tpu.text.models import GPTForCausalLM
    from paddle_tpu.text.models.gpt import GPTConfig

    paddle.seed(17)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32)
    model = GPTForCausalLM(cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 64, 5), rng.integers(1, 64, 3)]
    width = 5
    ids = np.zeros((2, width), np.int32)
    mask = np.zeros((2, width), np.int64)
    for i, p in enumerate(prompts):
        ids[i, width - len(p):] = p
        mask[i, width - len(p):] = 1

    batched = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                             attention_mask=paddle.to_tensor(mask))
    for i, p in enumerate(prompts):
        solo = model.generate(
            paddle.to_tensor(p[None, :].astype(np.int32)),
            max_new_tokens=6)
        np.testing.assert_array_equal(
            batched.numpy()[i, width - len(p):],
            solo.numpy()[0])

    # non-left-contiguous mask rejected
    bad = mask.copy()
    bad[1] = [1, 0, 1, 1, 1]
    import pytest as _pytest
    with _pytest.raises(ValueError):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                       attention_mask=paddle.to_tensor(bad))
    # all-zero row (empty prompt) rejected, not silently garbage
    empty = mask.copy()
    empty[1] = 0
    with _pytest.raises(ValueError):
        model.generate(paddle.to_tensor(ids), max_new_tokens=2,
                       attention_mask=paddle.to_tensor(empty))


def test_gpt_config_recompute_loss_parity():
    """GPTConfig(recompute=...) — per-layer activation recompute on the
    serial path — must not change the math (loss sequence identical)."""
    import numpy as np

    from paddle_tpu.text.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.text.models.gpt import GPTConfig

    crit = GPTPretrainingCriterion()
    ids = paddle.to_tensor(
        np.random.default_rng(1).integers(0, 64, (2, 9)).astype(np.int32))
    losses = {}
    for rc in (False, True, "dots_saveable"):
        paddle.seed(23)
        cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                        num_heads=2, max_seq_len=32, recompute=rc)
        model = GPTForCausalLM(cfg)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        step = paddle.jit.TrainStep(model, lambda m, i: crit(m(i), i), opt)
        losses[rc] = [float(step(ids).numpy()) for _ in range(3)]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-5)
    np.testing.assert_allclose(losses[False], losses["dots_saveable"],
                               rtol=1e-5)


def test_split_fused_qkv_is_the_q_k_v_thirds_split_into_heads():
    """[b, s, 3·d] → three [b, s, nh, hd]: the same values a
    [b, s, 3, nh, hd] view gives, taken as lane-aligned slices of the
    last dim (which XLA does not answer with a relayout)."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.meta_parallel.mp_layers import (
        split_fused_qkv)

    b, s, nh, hd = 2, 8, 4, 16
    x = np.random.default_rng(0).standard_normal(
        (b, s, 3 * nh * hd)).astype(np.float32)
    got = split_fused_qkv(paddle.to_tensor(x), b, s, nh, hd)
    want = x.reshape(b, s, 3, nh, hd)
    assert len(got) == 3
    for i, t in enumerate(got):
        assert list(t.shape) == [b, s, nh, hd]
        np.testing.assert_array_equal(t.numpy(), want[:, :, i])
