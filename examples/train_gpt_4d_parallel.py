"""4D-parallel GPT training: dp × pp × mp × sp in ONE compiled program.

The flagship composition (reference hybrid configs run TP inside
pipeline stages; sequence parallelism is a capability the reference
lacks): the 1F1B pipeline schedule, Megatron tensor parallelism inside
every stage, ring attention over the sequence shards, and data
parallelism — all axes of one `jax.sharding.Mesh`, one XLA program per
train step.

Runs on a virtual 16-device CPU mesh (or a real TPU slice unchanged):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python examples/train_gpt_4d_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _bootstrap import virtual_cpu_devices

virtual_cpu_devices(16)

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.distributed import init_mesh
from paddle_tpu.text.models.gpt import GPTConfig
from paddle_tpu.text.models.gpt_pipeline import PipelinedGPTForCausalLM


def main():
    # one mesh; the pipelined model reads every axis it finds:
    #   pp → 1F1B stages, mp → Megatron shards inside each stage,
    #   sp → ring attention over sequence shards, dp → batch shards
    init_mesh(dp=2, pp=2, mp=2, sp=2)

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                    num_heads=4, ffn_size=128, max_seq_len=64)
    model = PipelinedGPTForCausalLM(cfg, n_micro=4, remat="layer")
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda m, ids: m.loss(ids), opt)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, cfg.vocab_size, (8, 64)))
    for i in range(10):
        loss = step(ids)
        if i % 2 == 0:
            print(f"step {i}: loss {float(loss.numpy()):.4f}")
    print("4D-parallel GPT trained (dp/pp/mp/sp in one program).")


if __name__ == "__main__":
    main()
