"""Switch-MoE GPT through the pipeline: experts sharded over 'ep'
INSIDE 1F1B stages (reference: incubate MoE + fleet pipeline, composed
here as one compiled SPMD program — dispatch needs no all-to-all since
tokens replicate across ep while experts shard).

Runs on a virtual 8-device CPU mesh (or a real TPU slice unchanged):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_gpt_moe_pipeline.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _bootstrap import virtual_cpu_devices

virtual_cpu_devices(8)

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import init_mesh
from paddle_tpu.text.models.gpt import GPTConfig
from paddle_tpu.text.models.gpt_pipeline import PipelinedGPTForCausalLM


def main():
    init_mesh(pp=2, ep=4)  # 2 pipeline stages x 4 expert shards

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                    num_heads=4, max_seq_len=64)
    model = PipelinedGPTForCausalLM(cfg, n_micro=4,
                                    moe_experts=8, moe_hidden=128)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda m, ids: m.loss(ids), opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (8, 64)))
    for i in range(10):
        loss = step(ids)
        if i % 2 == 0:
            print(f"step {i}: loss {float(loss.numpy()):.4f}")
    print("MoE pipeline GPT trained (8 experts over ep=4, pp=2).")


if __name__ == "__main__":
    main()
