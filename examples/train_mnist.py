"""MNIST end-to-end with the high-level Model API (BASELINE config 1).

Run: JAX_PLATFORMS=cpu python examples/train_mnist.py  (or on TPU as-is)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.vision.models import LeNet


class SyntheticMNIST(paddle.io.Dataset):
    """Deterministic stand-in so the example runs hermetically; swap for
    paddle.vision.datasets.MNIST(mode="train") with local archives."""

    def __init__(self, n=512):
        rng = np.random.default_rng(0)
        self.x = rng.standard_normal((n, 1, 28, 28)).astype(np.float32)
        self.y = rng.integers(0, 10, (n, 1))

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def main():
    model = paddle.Model(LeNet())
    model.prepare(
        paddle.optimizer.Adam(1e-3, parameters=model.parameters()),
        nn.CrossEntropyLoss(),
        paddle.metric.Accuracy())
    loader = paddle.io.DataLoader(SyntheticMNIST(), batch_size=64,
                                  shuffle=True)
    model.fit(loader, epochs=2, verbose=1)
    result = model.evaluate(loader, verbose=0)
    print("eval:", result)


if __name__ == "__main__":
    main()
