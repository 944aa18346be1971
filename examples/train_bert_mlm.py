"""BERT/ERNIE masked-LM pretraining step with the fused vocab head
(BASELINE config 3). Shows the two loss paths side by side:

  * materialized: model() -> [b, s, vocab] logits -> criterion
    (required under vocab-sharded TP — ParallelCrossEntropy), and
  * fused: model.fused_mlm_loss() — head matmul + softmax-CE computed
    in token blocks, the logits never reach HBM (docs/PERF_NOTES.md).

Run: JAX_PLATFORMS=cpu python examples/train_bert_mlm.py  (or on TPU as-is)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import amp
from paddle_tpu.text.models import BertForPretraining
from paddle_tpu.text.models.bert import BertConfig


def make_batch(rng, vocab, batch, seq, mask_rate=0.15):
    ids = rng.integers(4, vocab, (batch, seq))
    labels = np.full((batch, seq), -100, np.int64)
    mask = rng.random((batch, seq)) < mask_rate
    labels[mask] = ids[mask]          # predict the original token
    ids_in = ids.copy()
    ids_in[mask] = 3                  # [MASK]
    nsp = rng.integers(0, 2, (batch,))
    return (paddle.to_tensor(ids_in.astype(np.int32)),
            paddle.to_tensor(labels), paddle.to_tensor(nsp))


def main():
    cfg = BertConfig(vocab_size=2048, hidden_size=128, num_layers=2,
                     num_heads=4, max_position=128)
    paddle.seed(0)
    model = BertForPretraining(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def loss_fn(m, ids, labels, nsp):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return m.fused_mlm_loss(ids, labels, nsp_labels=nsp)

    step = paddle.jit.TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    for i in range(20):
        ids, labels, nsp = make_batch(rng, cfg.vocab_size, 8, 64)
        loss = step(ids, labels, nsp)
        if i % 5 == 0:
            print(f"step {i}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
