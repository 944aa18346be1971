"""Hybrid-parallel GPT training: dp × mp (tensor) over ONE mesh.

Runs on a virtual 8-device CPU mesh (or a real TPU slice unchanged):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_gpt_hybrid.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _bootstrap import virtual_cpu_devices

virtual_cpu_devices(8)

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import init_mesh
from paddle_tpu.distributed.parallel_step import DistributedTrainStep
from paddle_tpu.text.models import (GPTConfig, GPTForCausalLM,
                                    GPTPretrainingCriterion)


def main():
    # one mesh, every parallelism form is a placement over it
    init_mesh(dp=4, mp=2)

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, ffn_size=128, max_seq_len=64)
    model = GPTForCausalLM(cfg)
    crit = GPTPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())

    def loss_fn(m, ids):
        return crit(m(ids), ids)

    # ZeRO-2 opt-state sharding + remat with the MXU-friendly policy;
    # grad all-reduce over dp and TP collectives are compiler-emitted
    step = DistributedTrainStep(model, loss_fn, opt, zero_level=2,
                                remat="dots_saveable")

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (8, 64)).astype(np.int32))
    for i in range(5):
        loss = step(ids)
        print(f"step {i}: loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
