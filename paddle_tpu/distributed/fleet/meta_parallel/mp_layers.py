"""Megatron-style tensor-parallel layers, GSPMD-first.

TPU-native re-design of the reference mpu layers
(reference: python/paddle/distributed/fleet/layers/mpu/mp_layers.py —
VocabParallelEmbedding:39, ColumnParallelLinear:155, RowParallelLinear:293,
ParallelCrossEntropy:438; comm primitives mp_ops.py _c_identity/_c_concat/
_mp_allreduce; CUDA ops c_embedding_op, c_softmax_with_cross_entropy_op).

Design difference, by intent: the reference manually slices weights
per-rank and inserts collective ops. Here every layer holds the FULL
logical weight annotated with a PartitionSpec on the 'mp' mesh axis; the
XLA SPMD partitioner materializes per-device shards and inserts the same
all-reduces/all-gathers (over ICI) that Megatron does by hand — and fuses
them with the matmuls. The layer API (gather_output, input_is_parallel)
is preserved so reference model code ports unchanged. Under shard_map
(explicit mode) the same layers lower to lax collectives via the
paddle_tpu.distributed.collective API.
"""
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .... import nn
from ....nn import functional as F
from ....ops._helpers import apply_jfn, ensure_tensor
from ....tensor_core import Tensor
from ... import collective as coll
from ... import mesh as mesh_mod

__all__ = [
    "VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
    "ParallelCrossEntropy", "mark_sharding", "shard_activation",
]


def mark_sharding(param, *spec):
    """Attach a PartitionSpec to a parameter and (eagerly) place it.

    On a one-device mesh there is nothing to place, and placing anyway
    is not free: jax (>= 0.7, sharding in types) types an array by the
    mesh it lives on, a jit fed some mesh-placed inputs returns ALL its
    outputs mesh-placed, and the leaves this function never touched
    (LayerNorm, biases, optimizer state) then change type between step
    0 and step 1 — `jit.TrainStep` traced and compiled the GPT step
    twice on jax 0.9.0 (chip_smoke.py, PR 21). Same rule as
    `shard_activation` below."""
    param._pspec = P(*spec)
    mesh = mesh_mod.global_mesh()
    if all(n == 1 for n in mesh.shape.values()):
        return param
    if any(s is not None for s in param._pspec) and not isinstance(
            param._value, jax.core.Tracer):
        try:
            param._value = jax.device_put(
                param._value, jax.sharding.NamedSharding(mesh, param._pspec))
        except Exception:  # ptlint: disable=PTL804 (placement is advisory; spec kept for jit)
            pass  # single-device or incompatible mesh: spec kept for jit
    return param


def shard_activation(x, *spec):
    """with_sharding_constraint on an activation (no-op on 1-device mesh).

    Axis names whose mesh size does not divide the annotated dim are
    dropped — the spec is a layout hint, and e.g. a 4-head model on an
    mp=8 mesh should fall back to replicating heads, not error."""
    x = ensure_tensor(x)
    mesh = mesh_mod.global_mesh()
    if all(n == 1 for n in mesh.shape.values()):
        return x
    spec = tuple(
        s if (s is None or d % mesh.shape[s] == 0) else None
        for s, d in zip(spec, x.shape)
    )
    sh = jax.sharding.NamedSharding(mesh, P(*spec))

    def jfn(v):
        return jax.lax.with_sharding_constraint(v, sh)

    return apply_jfn("shard_activation", jfn, x)


def split_fused_qkv(qkv, batch, seq, num_heads, head_dim):
    """[b, s, 3·d] fused-qkv (mp-sharded last dim) → (q, k, v) each
    [b, s, nh, hd] with heads riding 'mp' and sequence free to ride
    'sp' — the one attention input layout every transformer here uses."""
    from ....ops import manipulation as manip

    # each a lane-aligned slice of the LAST dim, then split into heads: a
    # [b, s, 3, nh, 64] view makes XLA lay the whole activation out with
    # the sequence minor and copy it back for the attention kernel
    d = num_heads * head_dim
    out = []
    for i in range(3):
        t = manip.reshape(manip.slice(qkv, [2], [i * d], [(i + 1) * d]),
                          [batch, seq, num_heads, head_dim])
        out.append(shard_activation(t, "dp", "sp", "mp", None))
    return tuple(out)


class VocabParallelEmbedding(nn.Layer):
    """Embedding with the vocab dimension sharded over 'mp'
    (reference mp_layers.py:39: per-rank vocab range + masked lookup +
    allreduce; here: row-sharded weight, XLA partitions the gather)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self._num = num_embeddings
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=nn.initializer.Normal(0.0, 0.02))
        mark_sharding(self.weight, "mp", None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return shard_activation(out, *(["dp"] + [None] * (out.ndim - 1)))


class ColumnParallelLinear(nn.Layer):
    """Linear with the OUTPUT dim sharded over 'mp'
    (reference mp_layers.py:155). gather_output=False leaves activations
    mp-sharded for a following RowParallelLinear."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.gather_output = gather_output
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr)
        mark_sharding(self.weight, None, "mp")
        if has_bias is None or has_bias:
            self.bias = self.create_parameter(
                shape=[out_features], is_bias=True)
            mark_sharding(self.bias, "mp")
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            return shard_activation(out, *(["dp"] + [None] * (out.ndim - 1)))
        # keep last dim sharded on mp for the following RowParallelLinear
        spec = ["dp"] + [None] * (out.ndim - 2) + ["mp"]
        return shard_activation(out, *spec)


class RowParallelLinear(nn.Layer):
    """Linear with the INPUT dim sharded over 'mp'
    (reference mp_layers.py:293: partial matmul + allreduce — XLA inserts
    exactly that reduce when input activations are mp-sharded)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features], attr=weight_attr)
        mark_sharding(self.weight, "mp", None)
        self.bias = self.create_parameter(
            shape=[out_features], is_bias=True) if has_bias else None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        return shard_activation(out, *(["dp"] + [None] * (out.ndim - 1)))


class ParallelCrossEntropy(nn.Layer):
    """Vocab-parallel softmax CE (reference mp_layers.py:438 →
    c_softmax_with_cross_entropy_op). GSPMD: plain CE over mp-sharded
    logits; the partitioner reduces max/sum over the vocab shards."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return F.cross_entropy(input, label, reduction="none",
                               ignore_index=self.ignore_index)
