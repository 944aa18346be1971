"""Long-context training with ring-attention sequence parallelism.

The sequence axis is sharded over the mesh's ``sp`` axis; each device
holds seq/sp tokens and K/V shards rotate around the ring
(`lax.ppermute` over ICI) with streaming-logsumexp merging — memory per
chip stays O(seq/sp) while attention stays exact. A capability the
reference lacks (its long-sequence levers are recompute + fused kernels).

Run on a virtual 8-device mesh (or a real TPU slice unchanged):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_long_context_sp.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from _bootstrap import virtual_cpu_devices

virtual_cpu_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from paddle_tpu.distributed import init_mesh
from paddle_tpu.distributed.sequence_parallel import ring_attention


def main():
    mesh = init_mesh(sp=8)
    b, seq, h, d = 2, 1024, 4, 32  # 128 tokens per device

    def attention_block(params, q, k, v):
        out = ring_attention(q, k, v, causal=True)
        return out.reshape(b, q.shape[1], h * d) @ params

    def loss_fn(params, q, k, v, y):
        return jnp.mean((attention_block(params, q, k, v) - y) ** 2)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, q, k, v, y, lr):
        loss, g = grad_fn(params, q, k, v, y)
        # grads of replicated params need the mean over the ring
        g = jax.lax.pmean(g, "sp")
        return params - lr * g, jax.lax.pmean(loss, "sp")

    smapped = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp"), P(None, "sp"),
                  P(None, "sp"), P()),
        out_specs=(P(), P()), check_vma=False))

    rng = np.random.default_rng(0)
    params = jnp.asarray(rng.normal(0, 0.05, (h * d, 16)), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(b, seq, h, d)), jnp.float32)
               for _ in range(3))
    # learnable target: a fixed linear readout of the attention output,
    # so gradient descent can actually close the gap
    w_true = jnp.asarray(rng.normal(0, 0.5, (h * d, 16)), jnp.float32)
    y = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True).reshape(
            b, -1, h * d) @ w_true,
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False))(q, k, v)

    for i in range(8):
        params, loss = smapped(params, q, k, v, y, jnp.float32(2.0))
        print(f"step {i}: loss {float(loss):.5f}")


if __name__ == "__main__":
    main()
