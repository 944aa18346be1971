"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

NEW capability (SURVEY.md §5.7: the reference has NO sequence parallelism —
its long-context levers are recompute + fused attention). Designed
TPU-first per SURVEY.md §7: the sequence axis is sharded over the 'sp'
mesh axis; ring attention rotates K/V blocks around the ring with
`lax.ppermute` (neighbor exchange rides ICI) while each step's partial
attention merges via streaming log-sum-exp (the flash-attention recurrence
across devices). Ulysses instead all-to-alls heads↔sequence so each device
runs full-sequence attention on a head slice.

Both functions are pure jax, written to run INSIDE an SPMD program
(shard_map over 'sp', e.g. from DistributedTrainStep with a seq-sharded
batch spec) — collectives compile into the step.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "ring_flash_attention", "ulysses_attention",
           "RingAttention"]


def _online_merge(acc, m, l, scores, v_blk):
    """Streaming-softmax block merge (flash recurrence).

    acc: [b,h,sq,d] weighted value accumulator
    m:   [b,h,sq]  running max
    l:   [b,h,sq]  running sum of exp
    scores: [b,h,sq,sk] this block's logits
    """
    blk_max = scores.max(axis=-1)
    new_m = jnp.maximum(m, blk_max)
    correction = jnp.exp(m - new_m)
    p = jnp.exp(scores - new_m[..., None])
    new_l = l * correction + p.sum(axis=-1)
    new_acc = acc * correction[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk)
    return new_acc, new_m, new_l


def ring_attention(q, k, v, causal=False, axis_name="sp"):
    """Attention over a sequence sharded along `axis_name`.

    q, k, v: [batch, seq_local, heads, head_dim] (local shard).
    Returns [batch, seq_local, heads, head_dim].
    """
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    qt = jnp.swapaxes(q, 1, 2)  # b,h,sq,d
    acc = jnp.zeros((b, h, s_loc, d), jnp.float32)
    # running max starts at the finite mask floor, NOT -inf: -inf
    # intermediates make exp(m - new_m) an inf-minus-inf shape that
    # XLA's algebraic simplifier can rewrite into 0·inf NaNs under some
    # fusion layouts (observed on XLA:CPU with traced label operands —
    # the de-optimized program was NaN-free while the jitted one NaN'd).
    # The ring starts on the diagonal block, where every causal row has
    # at least one valid key, so the -1e30 floor never wins a max it
    # shouldn't.
    m = jnp.full((b, h, s_loc), -1e30, jnp.float32)
    l = jnp.zeros((b, h, s_loc), jnp.float32)

    q_pos = idx * s_loc + jnp.arange(s_loc)

    k_blk, v_blk = k, v
    # static ring loop (sp is a compile-time mesh size)
    for r in range(sp):
        src = (idx - r) % sp  # whose K/V block we currently hold
        scores = jnp.einsum("bhqd,bkhd->bhqk", qt, k_blk).astype(
            jnp.float32) * scale
        if causal:
            # mask directly to the finite floor (never -inf; see the
            # running-max init note above): exp underflows to 0 for
            # masked keys once any valid key sets the row max
            k_pos = src * s_loc + jnp.arange(s_loc)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, -1e30)
        acc, m, l = _online_merge(acc, m, l, scores, v_blk)
        if r != sp - 1:
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_flash_attention(q, k, v, causal=False, axis_name="sp",
                         block_q=None, block_k=None, interpret=False):
    """Ring attention with the Pallas flash kernel per K/V block.

    Unlike `ring_attention` (dense per-block scores in HBM, all blocks
    computed then masked), each ring step runs the flash kernel on the
    resident K/V shard — scores never touch HBM — and returns
    (out, lse); blocks are merged by streaming-softmax over lse. Under
    causal masking, blocks strictly above the diagonal are SKIPPED via
    lax.cond (the dense version burned ~half the FLOPs computing them):
    src == idx runs the kernel causal, src < idx runs it full, src > idx
    contributes nothing. Differentiable end-to-end: the kernel's lse
    output carries a custom-vjp cotangent (flash_attention_lse_bhd), the
    merge is plain jnp.

    q, k, v: [batch, seq_local, heads, head_dim]. Same contract as
    ring_attention. `interpret=True` runs the kernel in the Pallas
    interpreter; the caller chooses it (the CPU tests do), nothing here
    looks at the backend.
    """
    from ..ops.pallas_kernels.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_lse_bhd)

    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    sp = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape

    def to_bhd(t):
        return jnp.swapaxes(t, 1, 2).reshape(b * h, s_loc, d)

    qb = to_bhd(q)
    k_blk, v_blk = to_bhd(k), to_bhd(v)
    m = jnp.full((b * h, s_loc), -1e30, jnp.float32)   # running lse max
    num = jnp.zeros((b * h, s_loc, d), jnp.float32)
    den = jnp.zeros((b * h, s_loc), jnp.float32)

    def _blk(is_causal):
        def run(qq, kk, vv):
            o, l = flash_attention_lse_bhd(qq, kk, vv, is_causal,
                                           block_q, block_k, interpret)
            return o.astype(jnp.float32), l[:, 0, :]

        return run

    def _skip(qq, kk, vv):
        return (jnp.zeros((b * h, s_loc, d), jnp.float32),
                jnp.full((b * h, s_loc), -1e30, jnp.float32))

    for r in range(sp):
        src = (idx - r) % sp   # whose K/V block we currently hold
        if causal:
            o_blk, lse_blk = lax.cond(
                src == idx, _blk(True),
                lambda qq, kk, vv: lax.cond(
                    src < idx, _blk(False), _skip, qq, kk, vv),
                qb, k_blk, v_blk)
        else:
            o_blk, lse_blk = _blk(False)(qb, k_blk, v_blk)
        m_new = jnp.maximum(m, lse_blk)
        scale_old = jnp.exp(m - m_new)
        scale_blk = jnp.exp(lse_blk - m_new)
        num = num * scale_old[..., None] + o_blk * scale_blk[..., None]
        den = den * scale_old + scale_blk
        m = m_new
        if r != sp - 1:
            perm = [(i, (i + 1) % sp) for i in range(sp)]
            k_blk = lax.ppermute(k_blk, axis_name, perm)
            v_blk = lax.ppermute(v_blk, axis_name, perm)

    out = num / jnp.maximum(den, 1e-30)[..., None]
    return jnp.swapaxes(out.reshape(b, h, s_loc, d), 1, 2).astype(q.dtype)


def ulysses_attention(q, k, v, causal=False, axis_name="sp"):
    """DeepSpeed-Ulysses style: all-to-all so each device holds ALL the
    sequence for heads/sp heads, runs dense attention, then scatters back.
    Requires heads % sp == 0."""
    sp = lax.axis_size(axis_name)
    b, s_loc, h, d = q.shape
    if h % sp != 0:
        raise ValueError(f"heads {h} not divisible by sp degree {sp}")

    def seq2head(x):
        # [b, s_loc, h, d] -> [b, s_loc*sp, h/sp, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def head2seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    s_full = qg.shape[1]
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qg, kg).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s_full, s_full), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w.astype(vg.dtype), vg)
    return head2seq(out)


class RingAttention:
    """Layer-ish wrapper selecting ring vs ulysses (API surface for model
    code; call inside SPMD programs)."""

    def __init__(self, mode="ring", causal=True, axis_name="sp"):
        self.mode = mode
        self.causal = causal
        self.axis_name = axis_name

    def __call__(self, q, k, v):
        fn = {"ring": ring_attention,
              "ring_flash": ring_flash_attention,
              "ulysses": ulysses_attention}[self.mode]
        return fn(q, k, v, causal=self.causal, axis_name=self.axis_name)
